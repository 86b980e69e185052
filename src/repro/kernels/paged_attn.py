"""Paged decode-attention Pallas kernel: attend THROUGH a page table.

The continuous-batching runtime (serving/engine.serve_batch) keeps every
request's KV in a shared block-granular pool (serving/paged_cache.py) that
is now DEVICE-RESIDENT: the model forward scatters new tokens straight into
pool pages and this kernel attends in place through the page table.  The
grid's innermost dimension walks a request's page table and the BlockSpec
index_map reads the page id from a scalar-prefetched table, so each
(request, kv-head) pair streams exactly its own pages pool->VMEM once and
runs online softmax in VREGs — decode attention over the paged pool with
zero gather materialization (the same trick the dense int8 kernel in
decode_attn.py plays on a contiguous cache, plus scalar-prefetch
indirection).

The kernel generalizes to the speculative VERIFY window: q may carry W > 1
query tokens per request (the round's [last_tok, drafts...] span), causally
masked inside the window — query w attends to absolute positions
<= length - W + w.

Layout (one grid step = one (request, kv-head) pair x one page):
  page_table (B, max_pages) int32  — scalar-prefetched; unused slots must
                                     hold any in-range id (masked by length)
  lengths    (B,)           int32  — valid tokens per request INCLUDING the
                                     W window tokens just written
  q          (B, KVS, G, hd)       — single decode token (W = 1), or
             (B, W, KVS, G, hd)    — multi-token verify window
  k_pool     (P, KVS, page_size, hd)   — head-major pages
  v_pool     (P, KVS, page_size, hd)
  k_scale    (P, KVS, page_size, 1) f32, optional — per-slot-per-head
  v_scale    (P, KVS, page_size, 1) f32, optional   dequant scales
  out        same shape as q, f32

The pools are head-major so one grid step's block, ``(1, 1, page_size,
hd)``, has the page's slots and the head dim as its two minor dimensions:
Mosaic requires those to be multiples of the (8, 128) tile or the whole
array dimension, which a token-major ``(P, page_size, KVS, hd)`` pool
cannot give (its block would carry one of KVS heads as the second-minor
dimension).

Compressed pools (``kv_quant="int8"``): pass int8 k/v pools plus the scale
pools and the kernel dequantizes INSIDE the page loop — each page's int8
bytes stream pool->VMEM compressed (≈4x less traffic than f32) and expand
to f32 only in registers, right before the QK^T dot.  Both the 4-D decode
and 5-D verify-window paths share the epilogue.

TPU note: compiled (``interpret=False``, the default off the CPU), the
block's minor dims must be multiples of (8, 128) or whole array dims:
page_size a multiple of 8, hd a multiple of 128 or the whole head dim.
Every variant (decode, verify, int8 scales, tree mask) compiles for a v5e
at KVS 32, hd 128 and page_size 16 or 32; tests/test_tpu_compile.py keeps
the page-16 compiles.  Interpret mode (CPU tests) takes any shape.

Invariants (the contract with the serving engine):

* **Page-table lifetime stability** — the scalar-prefetched table is read
  fresh every call but the engine uploads each request's row exactly once
  per lifetime (pages are backed at admission and never move); unused table
  slots must hold ANY in-range page id (the engine points them at its
  scratch page) because the grid dereferences every slot and relies on the
  length mask, not the table, for validity.
* **Causal padding** — the fused PAR path always calls with the engine-wide
  fixed window W = max_dl + 1 and per-row lengths counting exactly the
  tokens written; rows whose real window is shorter arrive zero-padded.
  Query w's horizon is ``length - W + w``, so padded tail queries only ever
  produce garbage in their OWN output rows — earlier positions' outputs are
  bitwise independent of the padding, which is what makes fixed-width
  compilation safe.
* **Role-masked rows** — rows excluded from a fused dispatch arrive with
  an all-scratch table row and length 0: every kv position masks out, the
  softmax degenerates to uniform over -1e30 scores, and the finite garbage
  output is ignored by the caller.  The kernel itself never needs a role
  input.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_decode_attention_pallas"]


def _attend_page(k, v, len_ref, q_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 n_pages: int, page_size: int, window: int, group: int,
                 scale: float, tm=None):
    """One online-softmax step over one (already dequantized, f32) page.

    ``tm`` (optional, (W, W) f32 for this batch row) replaces the causal
    window mask with an arbitrary intra-window visibility relation — the
    speculation-tree ancestor mask.  ``tm=None`` keeps the historical
    causal path bit-exact."""
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # (W*G, hd)
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (W*G, page_size)
    # mask token slots beyond each query's causal horizon: row (w, g) at
    # absolute position length - W + w sees kv positions <= itself.  This
    # also covers page-table slots past the request's page count (every
    # slot is masked) and reduces to `pos < length` when W == 1.
    pos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    w = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0) // group
    if tm is None:
        scores = jnp.where(pos <= len_ref[b] - window + w, scores, -1e30)
    else:
        # Tree mask, gather-free (TPU wants matmuls, not dynamic indexing):
        # expand tm to query rows with a row-onehot (W*G, W), then project
        # onto this page's kv slots with a col-onehot (W, page_size) built
        # from each slot's window-relative index.  Committed-prefix slots
        # (pos < length - W) stay visible to every query.
        r_iota = jax.lax.broadcasted_iota(jnp.int32, (window * group, window), 0)
        j_iota = jax.lax.broadcasted_iota(jnp.int32, (window * group, window), 1)
        row_onehot = (r_iota // group == j_iota).astype(jnp.float32)
        mask_rows = jax.lax.dot_general(
            row_onehot, tm, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (W*G, W)
        slot_iota = jax.lax.broadcasted_iota(jnp.int32, (window, page_size), 0)
        col_iota = jax.lax.broadcasted_iota(jnp.int32, (window, page_size), 1)
        rel = p * page_size + col_iota - (len_ref[b] - window)
        col_onehot = (rel == slot_iota).astype(jnp.float32)
        win_vis = jax.lax.dot_general(
            mask_rows, col_onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (W*G, page_size)
        visible = (pos < len_ref[b] - window) | (win_vis > 0.5)
        scores = jnp.where(visible, scores, -1e30)

    m_prev = m_ref[...]  # (W*G, 1)
    m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
    prob = jnp.exp(scores - m_new)  # (W*G, page_size)
    corr = jnp.exp(m_prev - m_new)  # (W*G, 1)
    l_ref[...] = l_ref[...] * corr + prob.sum(axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        prob, v,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )  # (W*G, hd)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = m_new

    @pl.when(p == n_pages - 1)
    def _epilogue():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype
        )


def _kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, **kw):
    k = k_ref[0, 0].astype(jnp.float32)  # (page_size, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    _attend_page(k, v, len_ref, q_ref, o_ref, m_ref, l_ref, acc_ref, **kw)


def _kernel_quant(pt_ref, len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                  o_ref, m_ref, l_ref, acc_ref, **kw):
    """int8 pools: dequantize this page in VREGs (per-slot scale broadcast
    over hd) right before the dots — the page crossed HBM->VMEM as int8."""
    k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]
    v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]
    _attend_page(k, v, len_ref, q_ref, o_ref, m_ref, l_ref, acc_ref, **kw)


def _kernel_tree(pt_ref, len_ref, q_ref, k_ref, v_ref, tm_ref, o_ref,
                 m_ref, l_ref, acc_ref, **kw):
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    _attend_page(k, v, len_ref, q_ref, o_ref, m_ref, l_ref, acc_ref,
                 tm=tm_ref[0], **kw)


def _kernel_quant_tree(pt_ref, len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                       tm_ref, o_ref, m_ref, l_ref, acc_ref, **kw):
    k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]
    v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]
    _attend_page(k, v, len_ref, q_ref, o_ref, m_ref, l_ref, acc_ref,
                 tm=tm_ref[0], **kw)


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
    each page is dequantized inside the kernel (``value * scale`` per slot
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
    n_pages = page_table.shape[1]
    rows = w * g
    scale = 1.0 / math.sqrt(hd)
    grid = (b, kvs, n_pages)
    page_spec = lambda width: pl.BlockSpec(
        (1, 1, page_size, width), lambda i, j, p, pt, ln: (pt[i, p], j, 0, 0)
    )
    in_specs = [
        pl.BlockSpec((1, 1, rows, hd), lambda i, j, p, pt, ln: (i, j, 0, 0)),
        page_spec(hd),
        page_spec(hd),
    ]
    inputs = [qk, k_pool, v_pool]
    if quantized:
        in_specs += [page_spec(1), page_spec(1)]
        inputs += [k_scale, v_scale]
    treed = tree_mask is not None
    if treed:
        assert windowed, "tree_mask requires a 5-D window q"
        assert tree_mask.shape == (b, w, w), (tree_mask.shape, (b, w, w))
        in_specs += [
            pl.BlockSpec((1, w, w), lambda i, j, p, pt, ln: (i, 0, 0))
        ]
        inputs += [tree_mask.astype(jnp.float32)]
    kernels = {
        (False, False): _kernel,
        (True, False): _kernel_quant,
        (False, True): _kernel_tree,
        (True, True): _kernel_quant_tree,
    }
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # page_table, lengths
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, rows, hd), lambda i, j, p, pt, ln: (i, j, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            kernels[(quantized, treed)],
            n_pages=n_pages, page_size=page_size,
            window=w, group=g, scale=scale,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvs, rows, hd), jnp.float32),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32), *inputs)
    if windowed:
        out = out.reshape(b, kvs, w, g, hd).transpose(0, 2, 1, 3, 4)
    return out
