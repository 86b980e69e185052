"""Random weights drawn on the device, directly in their served form.

A deployment loads weights that are already quantized, so the benchmark
draws them that way: the target's W4A8 layers as packed int4 codes with
per-column f32 scales, the draft's BVQ layers as int4 codebooks with int32
indices.  Each model is one jitted call from its key: no dense weights, no
folding, no k-means.  Both the program and the plain reference read these
arrays; neither makes them.

Layout of the W4A8 target (what ``serving/quantized_lm.apply_quantized_lm``
reads):  ``embed (V, d)`` in the serving dtype; ``head`` and every layer
linear ``{"packed": (K/2, N) int8, "sw": (1, N) f32}`` with two int4 codes
per byte along K (element 2i in the low nibble, 2i+1 in the high one);
``qk_extra`` holds the per-head q/k RMSNorm gains where the model has them.
Layer leaves carry a leading layer axis.  The BVQ draft's embedding and
head are tied where its configuration says so: the head is drawn as the
embedding's transpose.

Codes are uniform over -7..7 with 0 drawn twice as often (a random byte
whose nibble reads -8 is set to 0), so their mean is 0 and their RMS is
sqrt(17.5).  Scales make each linear's output about unit RMS for unit-RMS
inputs, times a per-column factor exp(0.1 z): the logits of both models
then spread by about 1, as a trained model's do at temperature 1.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

__all__ = ["w4a8_target", "bvq_draft", "dims"]

CODE_RMS = math.sqrt(17.5)  # int4 codes -7..7, 0 twice as likely
CB_RMS = math.sqrt(2 * 140 / 15)  # codebook entries uniform over -7..7


def dims(m: dict) -> dict:
    """Model sizes under short names, from a configuration's HF keys."""
    h = m["num_attention_heads"]
    return dict(
        L=m["num_hidden_layers"], d=m["hidden_size"], f=m["intermediate_size"],
        h=h, kv=m["num_key_value_heads"],
        hd=m.get("head_dim") or m["hidden_size"] // h, V=m["vocab_size"],
        qk_norm=bool(m.get("qk_norm", False)),
    )


def _codes(key, shape):
    """Packed int4 pairs: random bytes, a nibble reading -8 set to 0."""
    b = jax.random.bits(key, shape, jnp.uint8)
    lo, hi = b & 0x0F, b >> 4
    lo = jnp.where(lo == 8, 0, lo)
    hi = jnp.where(hi == 8, 0, hi)
    return jax.lax.bitcast_convert_type((hi << 4) | lo, jnp.int8)


def _w4(key, lead, k, n):
    kc, ks = jax.random.split(key)
    scale = jnp.exp(0.1 * jax.random.normal(ks, lead + (1, n), jnp.float32))
    return {"packed": _codes(kc, lead + (k // 2, n)),
            "sw": scale / (CODE_RMS * math.sqrt(k))}


def _gain(key, shape, dtype):
    return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


@partial(jax.jit, static_argnames=("m", "dtype"))
def _target(key, m, dtype):
    m = dict(m)
    L, d, f, h, kv, hd, V = (m[x] for x in ("L", "d", "f", "h", "kv", "hd", "V"))
    ks = iter(jax.random.split(key, 16))
    lead = (L,)
    layers = {
        "wq": _w4(next(ks), lead, d, h * hd),
        "wk": _w4(next(ks), lead, d, kv * hd),
        "wv": _w4(next(ks), lead, d, kv * hd),
        "wo": _w4(next(ks), lead, h * hd, d),
        "w_gate": _w4(next(ks), lead, d, f),
        "w_up": _w4(next(ks), lead, d, f),
        "w_down": _w4(next(ks), lead, f, d),
        "qk_extra": ({"q_norm": _gain(next(ks), (L, hd), dtype),
                      "k_norm": _gain(next(ks), (L, hd), dtype)}
                     if m["qk_norm"] else {}),
    }
    return {
        "embed": jax.random.normal(next(ks), (V, d), dtype),
        "head": _w4(next(ks), (), d, V),
        "layers": layers,
    }


def w4a8_target(key, model: dict, dtype: str = "bfloat16") -> dict:
    """The W4A8 target's served params, drawn in one program."""
    m = tuple(sorted(dims(model).items()))
    return _target(key, m, jnp.dtype(dtype))


@partial(jax.jit, static_argnames=("m", "bvq", "dtype"))
def _draft(key, m, bvq, dtype):
    from repro.core.bvq import BVQWeight

    m, bvq = dict(m), dict(bvq)
    L, d, f, h, kv, hd, V = (m[x] for x in ("L", "d", "f", "h", "kv", "hd", "V"))
    v, c, bc = bvq["vec_dim"], bvq["codebook_size"], bvq["block_cols"]
    ks = iter(jax.random.split(key, 32))

    def lin(k, n):
        k1, k2, k3 = jax.random.split(next(ks), 3)
        nb = n // bc
        cb = jax.random.randint(k1, (L, nb, c, v), -7, 8, jnp.int32)
        scale = jnp.exp(0.1 * jax.random.normal(k2, (L, nb, 1, 1), jnp.float32))
        return BVQWeight(
            codebooks=cb.astype(jnp.int8),
            scales=scale / (CB_RMS * math.sqrt(k)),
            indices=jax.random.randint(k3, (L, nb, k // v, bc), 0, c, jnp.int32),
            shape=(k, n), vec_dim=v,
        )

    layers = {
        "ln1": {"g": _gain(next(ks), (L, d), dtype)},
        "ln2": {"g": _gain(next(ks), (L, d), dtype)},
        "wq": lin(d, h * hd), "wk": lin(d, kv * hd), "wv": lin(d, kv * hd),
        "wo": lin(h * hd, d),
        "w_gate": lin(d, f), "w_up": lin(d, f), "w_down": lin(f, d),
        "qk_extra": ({"q_norm": _gain(next(ks), (L, hd), dtype),
                      "k_norm": _gain(next(ks), (L, hd), dtype)}
                     if m["qk_norm"] else {}),
    }
    # tied, as the configuration states: the head is the embedding's
    # transpose (the program holds it as a second array); entries of RMS
    # 1/sqrt(d) give logits that spread by about 1
    embed = (jax.random.normal(next(ks), (V, d), jnp.float32) / math.sqrt(d)).astype(dtype)
    return {
        "embed": embed,
        "head": embed.T,
        "final_norm": {"g": _gain(next(ks), (d,), dtype)},
        "layers": layers,
    }


def bvq_draft(key, model: dict, bvq: dict, dtype: str = "bfloat16") -> dict:
    """The BVQ draft's served params, drawn in one program."""
    if not model.get("tie_word_embeddings", False):
        raise ValueError("the BVQ draft is drawn with a tied embedding and head")
    m = tuple(sorted(dims(model).items()))
    b = tuple(sorted((k, bvq[k]) for k in ("vec_dim", "codebook_size", "block_cols")))
    return _draft(key, m, b, jnp.dtype(dtype))
