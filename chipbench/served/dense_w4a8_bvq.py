"""Served form: a dense decoder in W4A8 beside a BVQ draft.

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
Layer leaves carry a leading layer axis.  The BVQ draft's head is the
embedding's transpose where its configuration ties them, and an array drawn
on its own where it does not, in the serving dtype either way.

Codes are uniform over -7..7 with 0 drawn twice as often (a random byte
whose nibble reads -8 is set to 0), so their mean is 0 and their RMS is
sqrt(17.5).  Scales make each linear's output about unit RMS for unit-RMS
inputs, times a per-column factor exp(0.1 z): the logits of both models
then spread by about 1, as a trained model's do at temperature 1.

The configuration's keys are Hugging Face's (``num_hidden_layers``,
``num_key_value_heads``, ...), the draft's under ``draft``; ``serving``
gives the dtype, the BVQ sizes and the target's online rotation.  A
``rope_scaling`` of type ``linear`` is passed to the program as its factor;
any other type is refused.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from chipbench.spec import SpecError

__all__ = ["draw", "program_config", "model_ops", "smoke", "matmul_params"]

CODE_RMS = math.sqrt(17.5)  # int4 codes -7..7, 0 twice as likely
CB_RMS = math.sqrt(2 * 140 / 15)  # codebook entries uniform over -7..7

# CPU copy: the widths of both models cut to these, the heads' grouping and
# every flag (qk-norm, tied head, RoPE) kept from the configuration
SMOKE = dict(
    target=dict(num_hidden_layers=2, hidden_size=128, intermediate_size=384,
                num_attention_heads=4, head_dim=32, vocab_size=512),
    draft=dict(num_hidden_layers=2, hidden_size=64, intermediate_size=192,
               num_attention_heads=4, head_dim=32, vocab_size=512),
    bvq=dict(vec_dim=8, codebook_size=64, block_cols=32),
    rotation=dict(n=384, m=12, h_m="paley1", k=5, kind="exact"),
)


def _dims(m: dict) -> dict:
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


@partial(jax.jit, static_argnames=("m", "bvq", "tied", "dtype"))
def _draft(key, m, bvq, tied, dtype):
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
    # entries of RMS 1/sqrt(d) give logits that spread by about 1; a tied
    # head is the embedding's transpose (the program holds it as a second
    # array), an untied one is drawn from the last key
    embed = (jax.random.normal(next(ks), (V, d), jnp.float32) / math.sqrt(d)).astype(dtype)
    final_norm = {"g": _gain(next(ks), (d,), dtype)}
    head = (embed.T if tied else
            (jax.random.normal(next(ks), (d, V), jnp.float32) / math.sqrt(d)).astype(dtype))
    return {"embed": embed, "head": head, "final_norm": final_norm, "layers": layers}


def draw(key, config: dict):
    """(target params, draft params), each drawn on the device in one
    program, in their served form."""
    sv = config["serving"]
    dtype = jnp.dtype(sv["dtype"])
    kt, kd = jax.random.split(key)
    target = _target(kt, tuple(sorted(_dims(config).items())), dtype)
    dm = config["draft"]
    bvq = tuple(sorted((k, sv["bvq"][k]) for k in ("vec_dim", "codebook_size", "block_cols")))
    draft = _draft(kd, tuple(sorted(_dims(dm).items())), bvq,
                   bool(dm.get("tie_word_embeddings", False)), dtype)
    return target, draft


def _rope_factor(m: dict):
    rs = m.get("rope_scaling")
    if rs is None:
        return None
    kind = rs.get("type", rs.get("rope_type"))
    if kind != "linear":
        raise SpecError(f"rope_scaling of type {kind!r}: only 'linear' is served")
    return float(rs["factor"])


def program_config(config: dict, role: str):
    """The program's ``ModelConfig`` of the target (``role="target"``) or
    the draft (``role="draft"``)."""
    import dataclasses

    from repro.models.common import Family, ModelConfig

    m = config if role == "target" else config["draft"]
    name = config["name"] if role == "target" else config["name"] + "-draft"
    kw = dict(
        name=name, family=Family.DENSE, n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        vocab=m["vocab_size"], head_dim=m.get("head_dim"),
        qk_norm=bool(m.get("qk_norm", False)),
        rope_theta=float(m["rope_theta"]), dtype=config["serving"]["dtype"],
    )
    factor = _rope_factor(m)
    if factor is not None:
        if "rope_scaling" not in {f.name for f in dataclasses.fields(ModelConfig)}:
            raise SpecError(f"{name}: the program's ModelConfig takes no RoPE scaling factor")
        kw["rope_scaling"] = factor
    return ModelConfig(**kw)


def matmul_params(m: dict) -> int:
    """The target's matmul parameters, the head included."""
    L, d, f = m["num_hidden_layers"], m["hidden_size"], m["intermediate_size"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m.get("head_dim") or d // h
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return L * per_layer + d * m["vocab_size"]


def model_ops(config: dict, positions) -> float:
    """The target's model operations for tokens at the given 0-based
    positions of their sequences: 2 x its matmul parameters per token, plus
    attention's 4 x layers x query heads x head_dim x context, where the
    context is the number of keys the token attends to (its position + 1).
    The draft model and rejected drafts do not count: they are the cost of
    speculation, not the model's work."""
    L, h = config["num_hidden_layers"], config["num_attention_heads"]
    hd = config.get("head_dim") or config["hidden_size"] // h
    positions = list(positions)
    dense = 2.0 * matmul_params(config) * len(positions)
    attn = 4.0 * L * h * hd * sum(p + 1 for p in positions)
    return dense + attn


def smoke(config: dict) -> dict:
    """The configuration's CPU copy at ``SMOKE``'s sizes, with as many
    query heads per K/V head as the configuration has."""
    def cut(m, sizes):
        out = dict(m, **sizes)
        group = m["num_attention_heads"] // m["num_key_value_heads"]
        out["num_key_value_heads"] = max(1, sizes["num_attention_heads"] // group)
        return out

    cfg = cut(config, SMOKE["target"])
    cfg["draft"] = cut(config["draft"], SMOKE["draft"])
    cfg["serving"] = dict(config["serving"], bvq=SMOKE["bvq"], online_rotation=SMOKE["rotation"])
    cfg["reduced"] = sorted(set(SMOKE["target"]) | {"num_key_value_heads", "draft"})
    return cfg
