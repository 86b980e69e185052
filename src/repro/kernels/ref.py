"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each function mirrors the corresponding kernel's contract exactly; kernel
tests sweep shapes/dtypes and assert_allclose against these.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hadamard
from repro.core.bvq import BVQWeight, bvq_reconstruct
from repro.core.quantization import unpack_int4
from repro.core.rotation import _apply_blocks

__all__ = [
    "block_rotate_ref",
    "w4a8_matmul_ref2",
    "bvq_matmul_ref2",
    "gather_pages_ref",
    "paged_attn_ref",
]


def block_rotate_ref(x: jnp.ndarray, m: int, k: int, transpose: bool = False):
    """Oracle for kernels.fwht.block_rotate_pallas."""
    return _apply_blocks(x, m, k, transpose=transpose)


def w4a8_matmul_ref2(xq, wp, sx, sw):
    """Oracle for kernels.w4a8_matmul.w4a8_matmul_pallas (packed input):
    int8 x int8 with an int32 accumulator, the MXU's integer dot."""
    acc = jax.lax.dot_general(
        xq.astype(jnp.int8),
        unpack_int4(wp, axis=0),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return acc.astype(jnp.float32) * sx * sw


def bvq_matmul_ref2(x: jnp.ndarray, bw: BVQWeight):
    """Oracle for kernels.bvq_matmul.bvq_matmul_pallas."""
    return (x.astype(jnp.float32) @ bvq_reconstruct(bw)).astype(jnp.float32)


def gather_pages_ref(pool: jnp.ndarray, page_table: jnp.ndarray) -> jnp.ndarray:
    """(P, KVS, ps, hd) head-major pool + (B, max_pages) table -> (B,
    max_pages*ps, KVS, hd) contiguous per-request K/V (the dense view of a
    paged cache)."""
    b, mp = page_table.shape
    _, kvs, ps, hd = pool.shape
    pages = pool[page_table]  # (B, mp, KVS, ps, hd)
    return pages.transpose(0, 1, 3, 2, 4).reshape(b, mp * ps, kvs, hd)


def paged_attn_ref(
    q: jnp.ndarray,  # (B, KVS, G, hd) f32, or (B, W, KVS, G, hd) for a window
    k_pool: jnp.ndarray,  # (P, KVS, page_size, hd)
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,  # (B, max_pages) int32 (unused slots: any valid id)
    lengths: jnp.ndarray,  # (B,) int32 valid tokens (incl. the window when 5-D)
    k_scale: jnp.ndarray = None,  # (P, KVS, page_size, 1) f32 (int8 pools)
    v_scale: jnp.ndarray = None,
    tree_mask: jnp.ndarray = None,  # (B, W, W) visibility among window slots
) -> jnp.ndarray:
    """Oracle for kernels.paged_attn.paged_decode_attention_pallas: gather
    the pages into a dense cache, then masked softmax attention per row.
    With scales the pools are int8 and dequantize after the gather — the
    reference semantics of the kernel's in-page dequant epilogue.

    A 5-D q is a W-token causally-masked window whose last query sits at
    absolute position ``lengths - 1`` (the speculative verify span).

    ``tree_mask`` generalizes the causal window to a speculation TREE: the
    window occupies absolute kv slots ``lengths - W .. lengths - 1`` and
    query slot w sees kv window slot j iff ``tree_mask[b, w, j]`` (the
    ancestor relation), while every query still sees the whole committed
    prefix (positions < lengths - W).  ``tree_mask=None`` is the bit-exact
    causal-window path above (chain speculation)."""
    windowed = q.ndim == 5
    if not windowed:
        q = q[:, None]  # (B, 1, KVS, G, hd); lengths = prefix == window end
    b, w, kvs, g, hd = q.shape
    k = gather_pages_ref(k_pool, page_table).astype(jnp.float32)  # (B, S, KVS, hd)
    v = gather_pages_ref(v_pool, page_table).astype(jnp.float32)
    if k_scale is not None:
        k = k * gather_pages_ref(k_scale, page_table).astype(jnp.float32)
        v = v * gather_pages_ref(v_scale, page_table).astype(jnp.float32)
    s = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum(
        "bwkgh,bskh->bwkgs", q.astype(jnp.float32) * scale, k,
        preferred_element_type=jnp.float32,
    )
    if tree_mask is None:
        # query w attends kv positions <= lengths - W + w
        horizon = lengths[:, None] - w + jnp.arange(w)[None, :]  # (B, W)
        valid = jnp.arange(s)[None, None] <= horizon[..., None]  # (B, W, S)
    else:
        # window slot of each kv position (clipped; gated by in_window)
        rel = jnp.arange(s)[None, :] - (lengths[:, None] - w)  # (B, S)
        in_window = (rel >= 0) & (rel < w)
        idx = jnp.broadcast_to(jnp.clip(rel, 0, w - 1)[:, None, :], (b, w, s))
        win_vis = jnp.take_along_axis(tree_mask.astype(bool), idx, axis=2)
        prefix = jnp.arange(s)[None, None, :] < (lengths[:, None, None] - w)
        valid = prefix | (in_window[:, None, :] & win_vis)  # (B, W, S)
    scores = jnp.where(valid[:, :, None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bwkgs,bskh->bwkgh", p, v, preferred_element_type=jnp.float32)
    if not windowed:
        out = out[:, 0]
    return out.astype(jnp.float32)
