"""Plain reference of a dense decoder served in W4A8 with an online rotation.

What the configuration states, written out in float32 with every matrix
product at ``Precision.HIGHEST`` and no cache, paging, batching or kernel:

- pre-norm blocks whose RMSNorm gains are folded into the next linear (the
  served form), so each norm is ``x / sqrt(mean(x^2) + eps)``;
- every linear is W4A8: the input is quantized per row to signed
  ``act_bits``-bit integers by its absmax, multiplied by the int4 weight
  codes (exact integer sums), and scaled back by the row scale and the
  weight's column scale;
- attention: optional per-head q/k RMSNorm with gains, rotary position
  embedding on the two halves of each head (angle ``p * theta^(-i/half)``,
  with ``p / factor`` in place of ``p`` under a ``rope_scaling`` of type
  ``linear``), causal softmax over grouped K/V heads (query head j reads
  K/V head ``j // (heads / kv_heads)``);
- feed-forward: ``silu(gate) * up``, then the online rotation of the
  configuration's ``online_rotation`` (orthonormal Hadamard blocks of
  ``m * 2^k`` channels, applied twice with the second pass shifted by half a
  block for a "tiled" plan), then the down projection;
- a final norm and the W4A8 head.

It reads the served weights only as arrays in the layout
``served/dense_w4a8_bvq.py`` draws, and imports nothing of the program.
``act_bits=4`` gives the control: the same model with int4 activations.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

__all__ = ["hadamard", "rotation_block", "logits_rows"]


def _sylvester(n: int) -> np.ndarray:
    h = np.ones((1, 1), np.int64)
    while h.shape[0] < n:
        h = np.block([[h, h], [h, -h]])
    return h


def _paley1(q: int) -> np.ndarray:
    """Paley's first construction, order q + 1 (q prime, q = 3 mod 4)."""
    residues = {(i * i) % q for i in range(1, q)}
    chi = np.array([0] + [1 if i in residues else -1 for i in range(1, q)])
    jac = chi[(np.arange(q)[None, :] - np.arange(q)[:, None]) % q]
    s = np.zeros((q + 1, q + 1), np.int64)
    s[0, 1:] = 1
    s[1:, 0] = -1
    s[1:, 1:] = jac
    return s + np.eye(q + 1, dtype=np.int64)


def hadamard(m: int, construction: str) -> np.ndarray:
    if construction == "sylvester":
        h = _sylvester(m)
    elif construction == "paley1":
        h = _paley1(m - 1)
    else:
        raise ValueError(f"unknown Hadamard construction {construction!r}")
    assert np.array_equal(h @ h.T, m * np.eye(m, dtype=np.int64)), m
    return h


def rotation_block(rot: dict) -> np.ndarray:
    """kron(H_m, H_{2^k}) / sqrt(m 2^k), float32."""
    hm = hadamard(rot["m"], rot["h_m"])
    h2 = _sylvester(1 << rot["k"])
    b = np.kron(hm, h2).astype(np.float64) / math.sqrt(rot["m"] << rot["k"])
    return b.astype(np.float32)


def _blocks(x, hb):
    t, n = x.shape
    b = hb.shape[0]
    y = jnp.matmul(x.reshape(t, n // b, b), hb, precision=HIGHEST)
    return y.reshape(t, n)


def _rotate(x, hb, kind: str):
    x = jnp.asarray(x)
    b = hb.shape[0]
    if kind == "exact":
        return _blocks(x, hb)
    if kind == "tiled":
        y = jnp.roll(_blocks(x, hb), -(b // 2), axis=-1)
        return jnp.roll(_blocks(y, hb), b // 2, axis=-1)
    if kind == "two_block":
        n = x.shape[-1]
        x = x.at[:, :b].set(_blocks(x[:, :b], hb))
        return x.at[:, n - b:].set(_blocks(x[:, n - b:], hb))
    raise ValueError(f"unknown rotation kind {kind!r}")


def _unpack(packed):
    """(K/2, N) bytes -> (K, N) int4 codes; byte row i holds rows 2i (low
    nibble) and 2i+1 (high nibble), each a signed 4-bit value."""
    p = packed.astype(jnp.int32) & 0xFF
    lo = ((p & 0xF) ^ 8) - 8
    hi = (((p >> 4) & 0xF) ^ 8) - 8
    k2, n = packed.shape
    return jnp.stack([lo, hi], axis=1).reshape(2 * k2, n)


def _linear(x, w, act_bits: int):
    qmax = (1 << (act_bits - 1)) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / qmax, 1e-8)
    xq = jnp.clip(jnp.round(x / s), -qmax, qmax)
    acc = jnp.matmul(xq, _unpack(w["packed"]).astype(jnp.float32),
                     precision=HIGHEST)
    return acc * s * w["sw"].reshape(1, -1)


def _norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta: float, factor: float = 1.0):
    t, _, hd = x.shape
    half = hd // 2
    inv = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    pos = jnp.arange(t, dtype=jnp.float32)
    if factor != 1.0:
        pos = pos / factor
    ang = pos[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v):
    t, h, hd = q.shape
    kv = k.shape[1]
    q = q.reshape(t, kv, h // kv, hd) / math.sqrt(hd)
    s = jnp.einsum("tkgd,ukd->kgtu", q, k, precision=HIGHEST)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("kgtu,ukd->tkgd", p, v, precision=HIGHEST)
    return o.reshape(t, h * hd)


@partial(jax.jit, static_argnames=("m", "kind", "act_bits"))
def _hidden(params, tokens, hb, m, kind, act_bits):
    m = dict(m)
    h, kv, hd, eps = m["h"], m["kv"], m["hd"], m["eps"]
    t = tokens.shape[0]
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        z = _norm(x, eps)
        q = _linear(z, p["wq"], act_bits).reshape(t, h, hd)
        k = _linear(z, p["wk"], act_bits).reshape(t, kv, hd)
        v = _linear(z, p["wv"], act_bits).reshape(t, kv, hd)
        if m["qk_norm"]:
            q = _norm(q, eps) * p["qk_extra"]["q_norm"].astype(jnp.float32)
            k = _norm(k, eps) * p["qk_extra"]["k_norm"].astype(jnp.float32)
        q, k = _rope(q, m["theta"], m["rope_factor"]), _rope(k, m["theta"], m["rope_factor"])
        x = x + _linear(_attention(q, k, v), p["wo"], act_bits)
        z = _norm(x, eps)
        hid = (jax.nn.silu(_linear(z, p["w_gate"], act_bits))
               * _linear(z, p["w_up"], act_bits))
        hid = _rotate(hid, hb, kind)
        return x + _linear(hid, p["w_down"], act_bits), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _norm(x, eps)


@partial(jax.jit, static_argnames=("n", "act_bits"))
def _head(head, hidden, start, n, act_bits):
    rows = jax.lax.dynamic_slice_in_dim(hidden, start, n, axis=0)
    return _linear(rows, head, act_bits)


def _rope_factor(config: dict) -> float:
    rs = config.get("rope_scaling")
    if rs is None:
        return 1.0
    kind = rs.get("type", rs.get("rope_type"))
    if kind != "linear":
        raise ValueError(f"rope_scaling of type {kind!r} is not written out here")
    return float(rs["factor"])


def _bucket(x: int, step: int) -> int:
    return -(-x // step) * step


def logits_rows(params: dict, config: dict, tokens, start: int, n: int,
                act_bits: int = 8):
    """float32 logits at positions ``start .. start+n-1`` of ``tokens``
    (each predicts the token after it), as a device array of at least ``n``
    rows.  Lengths are padded to buckets so few programs compile; causal
    attention keeps the padding from reaching the rows asked for."""
    rows = _bucket(n, 128)
    t_pad = _bucket(max(len(tokens), start + rows), 1024)
    tok = np.zeros(t_pad, np.int32)
    tok[: len(tokens)] = tokens
    rot = config["serving"]["online_rotation"]
    m = dict(h=config["num_attention_heads"], kv=config["num_key_value_heads"],
             hd=config.get("head_dim")
             or config["hidden_size"] // config["num_attention_heads"],
             eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]),
             rope_factor=_rope_factor(config), qk_norm=bool(config.get("qk_norm", False)))
    hidden = _hidden(params, jnp.asarray(tok), jnp.asarray(rotation_block(rot)),
                     tuple(sorted(m.items())), rot["kind"], act_bits)
    return _head(params["head"], hidden, start, rows, act_bits)
