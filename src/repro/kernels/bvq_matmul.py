"""Pallas TPU kernel for the BVQ matmul (the paper's RS-PNM + tile fusion).

Layout mirrors the chip: each block of ``block_cols`` output channels owns a
small codebook (the stacked-ReRAM resident data -> here: VMEM resident); the
int32 indices stream in per block; the weight tile is DECODED ONCE per block
into a VMEM scratch and reused by every token tile — that grid ordering IS
the tile-fusion unit: one codebook fetch serves the whole token batch, and
blocks are independent (intra-/inter-layer parallelism).

Grid: (N blocks, M tiles), N outer.  At the first M tile of a block the
kernel decodes the block's ``(K, bc)`` tile into the scratch; every M tile
then takes one dot against it.  K is kept whole per step (draft-scale
layers); the M tile is chosen from K and the call's M (``tile_rows``) so
that the whole step fits ``VMEM_BUDGET``.

The decode is a lane gather, which Mosaic lowers to ``tpu.dynamic_gather``:
the codebook is handed in as a table of ``bc``-lane rows, row ``t*H + h``
holding entry ``h*bc + l`` of component ``t`` in lane ``l`` (``H = ceil(C /
bc)`` chunks; C=256 over 128 lanes is two).  For a chunk of index rows, each
component ``t`` is ``take_along_axis`` of its broadcast table row by the
indices, one gather per chunk ``h`` and a select on the index's chunk.  The
decoded tile is laid out component-major (row ``t*rows + r`` holds weight row
``r*v + t``); the wrapper permutes x's K axis to match, so the sum over K is
unchanged.

Math (what ``ref.bvq_matmul_ref2`` states in float32): codebook entries are
INT4 integers, exact in bfloat16, so the tile is held in bf16.  x is split
into bfloat16 parts whose sum is x exactly (one part for a bf16 x, three for
f32: 3 x 8 mantissa bits cover f32's 24), each part is dotted with the tile
with f32 accumulation, and the ``(bm, bc)`` sum is multiplied by the block's
f32 scale.  Every product is exact and every sum is f32.

VMEM at the served draft shapes (v 8, C 256, bc 128; ``vmem_bytes``): at
K 3072 the decoded tile (K x 128 bf16) takes 768 KiB, the index block (K/8
x 128 int32) 192 KiB and the codebook table 8 KiB, each double-buffered,
and an M tile of x 48 KiB for a draft step (16 rows) and 3 MiB for a
prefill tile (256 f32 rows), double-buffered: 7.4 MiB at most.  At K 5504
(deepseek-coder-1.3b's down projection) a 256-row tile of x alone takes
11 MiB double-buffered, so a prefill runs 224-row tiles (11.7 MiB) and a
draft step of 8-16 rows takes 2.4-2.7 MiB.  The budget stays under the
16 MiB a kernel may take by default on a v5e.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bvq import BVQWeight

__all__ = ["bvq_matmul_pallas", "vmem_bytes", "tile_rows", "VMEM_BUDGET"]

_BM = 256  # most token rows per M tile
# VMEM the kernel may plan for: well under the 16 MiB a kernel may take by
# default on a v5e, so a compile never meets the limit
VMEM_BUDGET = 12 * 2**20
_CHUNK_ROWS = 64  # index rows decoded per loop step (8 int32 vregs at bc 128)


def _split_bf16(x: jnp.ndarray, parts: int):
    """bf16 parts whose f32 sum is x (exactly, for parts=3 and f32 x)."""
    out = []
    rest = x.astype(jnp.float32)
    for _ in range(parts):
        p = rest.astype(jnp.bfloat16)
        out.append(p)
        rest = rest - p.astype(jnp.float32)
    return out


def _bvq_kernel(x_ref, tab_ref, idx_ref, s_ref, o_ref, w_ref, *,
                v: int, chunks: int, rows: int, chunk_rows: int, parts: int):
    bc = idx_ref.shape[-1]

    @pl.when(pl.program_id(1) == 0)
    def _decode():
        def body(c, carry):
            r0 = pl.multiple_of(c * chunk_rows, chunk_rows)
            idx = idx_ref[0, pl.ds(r0, chunk_rows), :]  # (R, bc) int32
            for t in range(v):
                val = None
                for h in range(chunks):
                    row = tab_ref[0, pl.ds(t * chunks + h, 1), :]  # (1, bc)
                    src = jnp.broadcast_to(row, idx.shape)
                    lane = jnp.clip(idx - h * bc, 0, bc - 1)
                    g = jnp.take_along_axis(src, lane, axis=1,
                                            mode="promise_in_bounds")
                    val = g if val is None else jnp.where(idx >= h * bc, g, val)
                w_ref[pl.ds(t * rows + r0, chunk_rows), :] = val.astype(w_ref.dtype)
            return carry

        jax.lax.fori_loop(0, rows // chunk_rows, body, 0)

    w = w_ref[...]
    acc = None
    for p in _split_bf16(x_ref[...], parts):
        d = jax.lax.dot_general(p, w, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        acc = d if acc is None else acc + d
    o_ref[...] = (acc * s_ref[0]).astype(o_ref.dtype)


def _codebook_table(bw: BVQWeight) -> jnp.ndarray:
    """(nb, v * H, bc) f32 lane table of the raw INT4 codebook entries:
    row ``t*H + h``, lane ``l`` holds entry ``h*bc + l``'s component t."""
    nb, c, v = bw.codebooks.shape
    bc = bw.indices.shape[-1]
    chunks = -(-c // bc)
    cb = bw.codebooks.astype(jnp.float32)
    cb = jnp.pad(cb, ((0, 0), (0, chunks * bc - c), (0, 0)))
    return cb.transpose(0, 2, 1).reshape(nb, v * chunks, bc)


def vmem_bytes(k: int, bc: int, v: int, c: int, bm: int = _BM) -> int:
    """VMEM the kernel takes for a (K, N) weight in ``bc``-column blocks at
    an M tile of ``bm`` f32 rows: the decoded bf16 tile, and the
    double-buffered index block, codebook table, scale, x tile and output."""
    table = v * -(-c // bc) * bc * 4
    per_step = (k // v) * bc * 4 + table + 8 * bc * 4 + bm * k * 4 + bm * bc * 4
    return k * bc * 2 + 2 * per_step


def tile_rows(m: int, k: int, bc: int, v: int, c: int) -> int:
    """The M tile for ``m`` rows against a (K, N) weight: the largest
    multiple of 8, at most ``_BM`` and at most ``m`` rounded up to 8, whose
    ``vmem_bytes`` fits ``VMEM_BUDGET``; 0 where not even 8 rows fit."""
    for bm in range(min(_BM, -(-m // 8) * 8), 0, -8):
        if vmem_bytes(k, bc, v, c, bm) <= VMEM_BUDGET:
            return bm
    return 0


def _chunk_rows(rows: int) -> int:
    for r in (_CHUNK_ROWS, 32, 16, 8):
        if rows % r == 0:
            return r
    return rows


@functools.partial(jax.jit, static_argnames=("interpret", "out_dtype"))
def bvq_matmul_pallas(
    x: jnp.ndarray,  # (M, K)
    bw: BVQWeight,
    interpret: Optional[bool] = None,
    out_dtype=jnp.float32,
) -> jnp.ndarray:
    """y = x @ reconstruct(bw); each block's tile decoded once per call,
    in M tiles of ``tile_rows`` rows."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m, k = x.shape
    k_w, n = bw.shape
    assert k == k_w, (k, k_w)
    nb, rows, bc = bw.indices.shape
    v = bw.vec_dim
    assert rows * v == k
    parts = 1 if x.dtype == jnp.bfloat16 else 3
    # K in component-major order, to match the decoded tile's rows
    xp = x.reshape(m, rows, v).swapaxes(1, 2).reshape(m, k)
    bm = tile_rows(m, k, bc, v, bw.codebooks.shape[-2])
    if bm == 0:
        raise ValueError(f"no M tile of a ({k}, {n}) BVQ weight fits in VMEM")
    mp = -(-m // bm) * bm
    if mp != m:
        xp = jnp.pad(xp, ((0, mp - m), (0, 0)))
    tab = _codebook_table(bw)
    chunks = tab.shape[1] // v
    scale = jnp.broadcast_to(bw.scales.astype(jnp.float32).reshape(nb, 1, 1), (nb, 1, bc))
    kernel = functools.partial(_bvq_kernel, v=v, chunks=chunks, rows=rows,
                               chunk_rows=_chunk_rows(rows), parts=parts)
    y = pl.pallas_call(
        kernel,
        grid=(nb, mp // bm),
        in_specs=[
            pl.BlockSpec((bm, k), lambda j, i: (i, 0)),
            pl.BlockSpec((1, v * chunks, bc), lambda j, i: (j, 0, 0)),
            pl.BlockSpec((1, rows, bc), lambda j, i: (j, 0, 0)),
            pl.BlockSpec((1, 1, bc), lambda j, i: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bc), lambda j, i: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((k, bc), jnp.bfloat16)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="bvq_matmul",
    )(xp, tab, bw.indices, scale)
    return y[:m] if mp != m else y
