"""Paged decode-attention Pallas kernel: attend THROUGH a page table.

The continuous-batching runtime (serving/engine.serve_batch) keeps every
request's KV in a shared block-granular pool (serving/paged_cache.py) that
is DEVICE-RESIDENT: the model forward scatters new tokens straight into
pool pages and this kernel attends in place through the page table, with
zero gather materialization.

The kernel generalizes to the speculative VERIFY window: q may carry W > 1
query tokens per request (the round's [last_tok, drafts...] span), causally
masked inside the window — query w attends to absolute positions
<= length - W + w.

The walk.  One grid step is one request (batch row).  It reads the row's
length and page-table row from scalar-prefetched SMEM and walks ONLY the
row's live pages, ``cdiv(length, page_size)`` of them, in blocks of
``pages_per_block`` pages.  A page of the head-major pool,
``(KVS, page_size, hd)``, is one contiguous run of bytes, so each page of
a block is one DMA that brings every KV head at once; the blocks are
double-buffered (the next block's copies are in flight while the current
one is attended).  Within a block every KV head runs one online-softmax
update over the block's ``pages_per_block * page_size`` tokens.  The last
block of a row is ragged: its pages past the row's live pages are neither
copied nor read as data — their VMEM slots keep whatever they held, and the
length mask (scores to -1e30, V rows to 0) makes that content irrelevant,
NaN included.  ``pages_per_block`` comes from the shapes alone
(:func:`pages_per_block`): as many pages as cover ``BLOCK_TOKENS`` tokens,
no more than the table holds, and no more than fit ``BUFFER_BUDGET``.

Layout:
  page_table (B, max_pages) int32  — scalar-prefetched; slots past a row's
                                     live pages are never read
  lengths    (B,)           int32  — valid tokens per request INCLUDING the
                                     W window tokens just written
  q          (B, KVS, G, hd)       — single decode token (W = 1), or
             (B, W, KVS, G, hd)    — multi-token verify window
  k_pool     (P, KVS, page_size, hd)   — head-major pages, left in HBM
  v_pool     (P, KVS, page_size, hd)
  k_scale    (P, KVS, page_size, 1) f32, optional — per-slot-per-head
  v_scale    (P, KVS, page_size, 1) f32, optional   dequant scales
  out        same shape as q, f32

Compressed pools (``kv_quant="int8"``): pass int8 k/v pools plus the scale
pools.  The block's int8 bytes cross HBM->VMEM as they are and expand to
f32 right before the dots.  The per-slot scales of each row's table are
gathered outside the kernel into lane-dense ``(KVS, tokens)`` tiles, one
per block (a one-wide minor dim cannot be sliced for a DMA), and multiply
their slots' score columns (K) and probability columns (V): the products
of dequantizing the rows first, up to f32 rounding.  The dots take f32
operands and accumulate in f32 (q·scale, probabilities, outputs) in every
variant.

TPU note: compiled (``interpret=False``, the default off the CPU), the
pool's minor dims must be multiples of (8, 128) or whole array dims:
page_size a multiple of 8, hd a multiple of 128 or the whole head dim.
Every variant (decode, verify, int8 scales, tree mask) compiles for a v5e
at the served models' shapes; tests/test_tpu_compile.py keeps those
compiles.  Interpret mode (CPU tests) takes any shape.

Invariants (the contract with the serving engine):

* **Only live table slots are read** — a row's first
  ``cdiv(length, page_size)`` table slots must hold its pages (the engine
  backs them at admission and never moves them); every later slot is
  ignored, whatever page id it holds.
* **Length 0** — a row of length 0 copies nothing and writes zeros, which
  the caller ignores.
* **Causal padding** — the fused PAR path always calls with the engine-wide
  fixed window W = max_dl + 1 and per-row lengths counting exactly the
  tokens written; rows whose real window is shorter arrive zero-padded.
  Query w's horizon is ``length - W + w``, so padded tail queries only ever
  produce garbage in their OWN output rows — earlier positions' outputs are
  bitwise independent of the padding, which is what makes fixed-width
  compilation safe.
* **Role-masked rows** — rows excluded from a fused dispatch arrive with
  an all-scratch table row and a length that counts only the padded window;
  the finite garbage they produce is ignored by the caller.  The kernel
  itself never needs a role input.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_decode_attention_pallas", "pages_per_block"]

BLOCK_TOKENS = 256  # tokens one block of pages covers at most
BUFFER_BUDGET = 8 * 2**20  # VMEM for the double-buffered K and V blocks


def pages_per_block(page_size: int, kvs: int, hd: int, dtype,
                    max_pages: int) -> int:
    """Pages the kernel copies and attends per block: enough to cover
    ``BLOCK_TOKENS`` tokens, at most ``max_pages``, and as many as let two
    buffers each of K and V fit ``BUFFER_BUDGET``."""
    page = kvs * page_size * hd * jnp.dtype(dtype).itemsize
    ppb = max(1, min(BLOCK_TOKENS // page_size, max_pages))
    while ppb > 1 and 2 * 2 * ppb * page > BUFFER_BUDGET:
        ppb -= 1
    return ppb


def _kernel(pt_ref, len_ref, q_ref, k_hbm, v_hbm, *refs, ppb: int,
            page_size: int, max_pages: int, window: int, group: int,
            scale: float, quantized: bool, treed: bool):
    """One batch row: walk its live pages block by block.

    ``refs``: [k_scale, v_scale] (int8) and [tree_mask] (tree) inputs, the
    output, then the scratch — m, l, acc, the K and V block buffers and the
    DMA semaphores (one per buffer slot)."""
    refs = list(refs)
    ks_ref, vs_ref = (refs.pop(0), refs.pop(0)) if quantized else (None, None)
    tm_ref = refs.pop(0) if treed else None
    o_ref, m_ref, l_ref, acc_ref, k_buf, v_buf, sem = refs
    _, kvs, rows, hd = q_ref.shape
    tokens = ppb * page_size
    b = pl.program_id(0)
    length = len_ref[b]
    n_live = jnp.minimum(pl.cdiv(length, page_size), max_pages)
    n_blocks = pl.cdiv(n_live, ppb)
    pairs = ((k_hbm, k_buf), (v_hbm, v_buf))

    def copy_block(blk, slot, wait: bool):
        # one DMA per live page and pool: (KVS, page_size, hd), every head
        for j in range(ppb):
            p = blk * ppb + j

            @pl.when(p < n_live)
            def _():
                page = pt_ref[b * max_pages + p]
                for src, dst in pairs:
                    c = pltpu.make_async_copy(
                        src.at[page], dst.at[slot, j], sem.at[slot]
                    )
                    if wait:
                        c.wait()
                    else:
                        c.start()

    if treed:
        # Tree mask, gather-free (TPU wants matmuls, not dynamic indexing):
        # expand tm to query rows with a row-onehot (W*G, W); each block
        # projects it onto its kv slots with a col-onehot (W, tokens).
        r_iota = jax.lax.broadcasted_iota(jnp.int32, (rows, window), 0)
        j_iota = jax.lax.broadcasted_iota(jnp.int32, (rows, window), 1)
        row_onehot = (r_iota // group == j_iota).astype(jnp.float32)
        mask_rows = jax.lax.dot_general(
            row_onehot, tm_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (W*G, W)

    def visible_in(base):
        """(W*G, tokens) bool: which of the block's kv slots each query row
        sees.  Row (w, g) at absolute position length - W + w sees kv
        positions <= itself (reducing to `pos < length` when W == 1); the
        tree mask instead shows each query the committed prefix
        (pos < length - W) and its window ancestors."""
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (rows, tokens), 1)
        if not treed:
            w = jax.lax.broadcasted_iota(jnp.int32, (rows, tokens), 0) // group
            return pos <= length - window + w
        slot_iota = jax.lax.broadcasted_iota(jnp.int32, (window, tokens), 0)
        col_iota = jax.lax.broadcasted_iota(jnp.int32, (window, tokens), 1)
        rel = base + col_iota - (length - window)
        col_onehot = (rel == slot_iota).astype(jnp.float32)
        win_vis = jax.lax.dot_general(
            mask_rows, col_onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (W*G, tokens)
        return (pos < length - window) | (win_vis > 0.5)

    def attend_block(blk, slot):
        base = blk * tokens
        visible = visible_in(base)
        # V rows past the length are zeroed: stale or never-copied slots of
        # a ragged block must not reach the output, not even as 0 * NaN
        live = base + jax.lax.broadcasted_iota(jnp.int32, (tokens, 1), 0) < length
        live_cols = base + jax.lax.broadcasted_iota(jnp.int32, (1, tokens), 1) < length

        def head(h, carry):
            # (ppb, page_size, hd) -> (tokens, hd), in f32
            k = k_buf[slot, :, h].astype(jnp.float32).reshape(tokens, hd)
            v = v_buf[slot, :, h].astype(jnp.float32).reshape(tokens, hd)
            v = jnp.where(live, v, 0.0)
            q = q_ref[0, h].astype(jnp.float32) * scale  # (W*G, hd)
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (W*G, tokens)
            if quantized:
                # a slot's scale multiplies its whole K (V) row, so it
                # multiplies that slot's score (probability) column
                scores = scores * ks_ref[0, blk, pl.ds(h, 1), :]
            scores = jnp.where(visible, scores, -1e30)
            m_prev = m_ref[h]  # (W*G, 1)
            m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
            prob = jnp.exp(scores - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + prob.sum(axis=-1, keepdims=True)
            if quantized:
                v_rows = jnp.where(live_cols, vs_ref[0, blk, pl.ds(h, 1), :], 0.0)
                prob = prob * v_rows
            pv = jax.lax.dot_general(
                prob, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (W*G, hd)
            acc_ref[h] = acc_ref[h] * corr + pv
            m_ref[h] = m_new
            return carry

        jax.lax.fori_loop(0, kvs, head, 0)

    m_ref[...] = jnp.full_like(m_ref, -1e30)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n_blocks > 0)
    def _():
        copy_block(0, 0, wait=False)

    def block(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _():
            copy_block(blk + 1, 1 - slot, wait=False)

        copy_block(blk, slot, wait=True)
        attend_block(blk, slot)
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(
    q: jnp.ndarray,  # (B, KVS, G, hd) or (B, W, KVS, G, hd)
    k_pool: jnp.ndarray,  # (P, KVS, page_size, hd)
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,  # (B, max_pages) int32
    lengths: jnp.ndarray,  # (B,) int32 — valid tokens incl. the window
    interpret: Optional[bool] = None,
    k_scale: Optional[jnp.ndarray] = None,  # (P, KVS, page_size, 1) f32
    v_scale: Optional[jnp.ndarray] = None,
    tree_mask: Optional[jnp.ndarray] = None,  # (B, W, W) window visibility
) -> jnp.ndarray:
    """Attention through the page table (no dense cache copy), f32 out.

    4-D q decodes one token per request (``lengths`` = valid prefix, the
    original contract); 5-D q scores a W-token window causally (``lengths``
    counts the window's tokens too — the dense verify-path convention).

    With ``k_scale``/``v_scale`` (both or neither) the pools are int8 and
    each block is dequantized inside the kernel (``value * scale`` per slot
    per kv-head) — the compressed-at-rest path.

    ``tree_mask`` (5-D q only) replaces the intra-window causal mask with a
    per-row (W, W) visibility relation — query slot w sees window slot j iff
    ``tree_mask[b, w, j]`` — turning the verify window into a speculation
    tree; every query still sees the committed prefix."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    windowed = q.ndim == 5
    if windowed:
        b, w, kvs, g, hd = q.shape
        # (B, W, KVS, G, hd) -> (B, KVS, W*G, hd), rows (w, g) W-major
        qk = q.transpose(0, 2, 1, 3, 4).reshape(b, kvs, w * g, hd)
    else:
        b, kvs, g, hd = q.shape
        w = 1
        qk = q
    _, pool_kvs, page_size, pool_hd = k_pool.shape
    assert (pool_kvs, pool_hd) == (kvs, hd), (k_pool.shape, q.shape)
    quantized = k_scale is not None
    assert quantized == (v_scale is not None), "pass both scales or neither"
    max_pages = page_table.shape[1]
    rows = w * g
    ppb = pages_per_block(page_size, kvs, hd, k_pool.dtype, max_pages)
    row_spec = pl.BlockSpec((1, kvs, rows, hd), lambda i, pt, ln: (i, 0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [row_spec, hbm, hbm]
    inputs = [qk, k_pool, v_pool]
    buffers = [
        pltpu.VMEM((2, ppb, kvs, page_size, hd), k_pool.dtype),
        pltpu.VMEM((2, ppb, kvs, page_size, hd), v_pool.dtype),
    ]
    if quantized:
        # the row's per-slot scales, gathered through its table and laid
        # out lane-dense, one (KVS, tokens) tile per block:
        # (B, blocks, KVS, ppb * page_size)
        n_blocks = -(-max_pages // ppb)
        table = jnp.pad(page_table, ((0, 0), (0, n_blocks * ppb - max_pages)))

        def by_block(sc):
            sc = sc[table].astype(jnp.float32)  # (B, n_blocks*ppb, KVS, ps, 1)
            sc = sc.reshape(b, n_blocks, ppb, kvs, page_size)
            return sc.transpose(0, 1, 3, 2, 4).reshape(
                b, n_blocks, kvs, ppb * page_size
            )

        in_specs += [pl.BlockSpec((1, n_blocks, kvs, ppb * page_size),
                                  lambda i, pt, ln: (i, 0, 0, 0))] * 2
        inputs += [by_block(k_scale), by_block(v_scale)]
    treed = tree_mask is not None
    if treed:
        assert windowed, "tree_mask requires a 5-D window q"
        assert tree_mask.shape == (b, w, w), (tree_mask.shape, (b, w, w))
        in_specs += [pl.BlockSpec((1, w, w), lambda i, pt, ln: (i, 0, 0))]
        inputs += [tree_mask.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # page_table (flattened), lengths
        grid=(b,),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((kvs, rows, 1), jnp.float32),
            pltpu.VMEM((kvs, rows, 1), jnp.float32),
            pltpu.VMEM((kvs, rows, hd), jnp.float32),
            *buffers,
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, ppb=ppb, page_size=page_size, max_pages=max_pages,
            window=w, group=g, scale=1.0 / math.sqrt(hd),
            quantized=quantized, treed=treed,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvs, rows, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention_pallas",
    )(page_table.astype(jnp.int32).reshape(-1), lengths.astype(jnp.int32),
      *inputs)
    if windowed:
        out = out.reshape(b, kvs, w, g, hd).transpose(0, 2, 1, 3, 4)
    return out
