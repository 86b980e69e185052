"""Pallas TPU kernel for the W4A8 GEMM (the paper's 4K INT8 MAC array with
fused dynamic dequantization).

Weights arrive nibble-packed (two INT4 values per int8 along K); activations
are dynamic per-token INT8 with their scales bypassed into the epilogue —
exactly the paper's TFTE dataflow: INT32 accumulation on the MXU, one
FP multiply per output element at the end.

Grid: (M tiles, N tiles, K tiles), K innermost so a VMEM scratch accumulator
carries partial sums; the unpack (shift/mask) runs on the VPU right before
the MXU dot.  Tile defaults (128, 128, 256-packed) keep the working set
under ~0.5 MB VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["w4a8_matmul_pallas"]


def _unpack_nibbles(wp: jnp.ndarray) -> jnp.ndarray:
    """(bk2, bn) int8 packed -> (2*bk2, bn) int8 sign-extended int4.

    Element 2i of K is the low nibble, 2i+1 the high nibble (matches
    core.quantization.pack_int4 with axis=0).  The shifts run in int32;
    the result narrows to int8 because the MXU takes int8 x int8 with an
    int32 accumulator (Mosaic refuses an int32 x int32 dot)."""
    p = wp.astype(jnp.int32)
    lo = (p << 28) >> 28
    hi = p >> 4
    bk2, bn = wp.shape
    return jnp.stack([lo, hi], axis=1).reshape(2 * bk2, bn).astype(jnp.int8)


def _w4a8_kernel(xq_ref, wp_ref, sx_ref, sw_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xq = xq_ref[...]  # (bm, bk) int8
    w = _unpack_nibbles(wp_ref[...])  # (bk, bn) int8
    acc_ref[...] += jax.lax.dot_general(
        xq,
        w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        o_ref[...] = (acc * sx_ref[...] * sw_ref[...]).astype(o_ref.dtype)


def _tile(dim: int, want: int) -> int:
    t = min(want, dim)
    while dim % t:
        t -= 1
    return t


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret", "out_dtype")
)
def w4a8_matmul_pallas(
    xq: jnp.ndarray,  # (M, K) int8
    wp: jnp.ndarray,  # (K // 2, N) int8, nibble-packed along K
    sx: jnp.ndarray,  # (M, 1) f32 per-token activation scales
    sw: jnp.ndarray,  # (1, N) f32 per-channel weight scales
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: Optional[bool] = None,
    out_dtype=jnp.float32,
) -> jnp.ndarray:
    """y = (xq @ unpack_int4(wp)) * sx * sw with INT32 accumulation."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m, k = xq.shape
    k2, n = wp.shape
    assert k == 2 * k2, (k, k2)
    assert sx.shape == (m, 1) and sw.shape == (1, n)
    bm = _tile(m, bm)
    bn = _tile(n, bn)
    bk = _tile(k, bk)
    assert bk % 2 == 0, "K tile must cover whole packed bytes"
    k_steps = k // bk
    grid = (m // bm, n // bn, k_steps)
    return pl.pallas_call(
        functools.partial(_w4a8_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu_vmem((bm, bn), jnp.int32)],
        interpret=interpret,
    )(xq, wp, sx, sw)


def pltpu_vmem(shape, dtype):
    """VMEM scratch allocation, tolerant of the CPU interpreter."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)
