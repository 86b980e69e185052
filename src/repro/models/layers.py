"""Layer library: norms, rotary, GQA attention (flash + decode paths),
MLP variants, MoE (dense reference + all-to-all expert parallel).

Conventions
-----------
* params are plain dict pytrees; every ``init_*`` returns ``(params, specs)``
  where ``specs`` mirrors params with ``PartitionSpec`` leaves.
* linear weights are (in, out); attention projections keep an explicit
  (heads, head_dim) split so head sharding is a named axis.
* TP ("model" axis) shards: q heads, FFN inner dim, expert dim, vocab.
  GQA with n_kv < TP replicates kv heads to ``n_kv_store = n_kv * rep``
  "virtual" heads (rep = tp // gcd(n_kv, tp)) so the KV cache shards evenly
  and attention needs NO cross-shard collectives (vLLM-style).
* archs whose head count does not divide TP (internvl 14H, whisper 20H) run
  attention data-parallel only: weights replicated over "model", FFN still
  TP-sharded (documented in DESIGN.md §Arch-applicability).
* fsdp=True additionally shards the non-TP weight axis over "data"
  (ZeRO-3); XLA inserts the all-gathers.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.common import ModelConfig

__all__ = [
    "rmsnorm_init",
    "rmsnorm",
    "rope",
    "attn_tp_enabled",
    "attention_init",
    "attention_apply",
    "mlp_init",
    "mlp_apply",
    "moe_init",
    "moe_apply",
    "embed_init",
    "Cache",
    "PagedCache",
    "paged_attention_update",
    "forward_cache_ctx",
]

Params = Dict[str, Any]


def _gcd(a: int, b: int) -> int:
    while b:
        a, b = b, a % b
    return a


def pick_batch_axes(mesh, batch: int):
    # Largest prefix of ('pod','data') whose size product divides `batch`;
    # long-context decode (batch 1) replicates over the data axis.
    axes = ("pod", "data") if "pod" in mesh.shape else ("data",)
    chosen = []
    prod = 1
    for a in axes:
        if batch % (prod * mesh.shape[a]) == 0:
            chosen.append(a)
            prod *= mesh.shape[a]
    if not chosen:
        return None
    return chosen[0] if len(chosen) == 1 else tuple(chosen)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int, dtype) -> Tuple[Params, Params]:
    return {"g": jnp.ones((d,), dtype)}, {"g": P(None)}


def rmsnorm(params: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["g"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary embedding
# ---------------------------------------------------------------------------


def rope(
    x: jnp.ndarray, positions: jnp.ndarray, theta: float, scaling: float = 1.0
) -> jnp.ndarray:
    """x: (B, S, H, hd); positions: (B, S) int32.  ``scaling`` is the
    linear RoPE factor: each position is divided by it before the angles
    are taken, the frequencies unchanged (Hugging Face's ``linear``
    ``rope_scaling``).  A factor of 1 traces no division."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    pos = positions[..., None].astype(jnp.float32)  # (B, S, 1)
    if scaling != 1.0:
        pos = pos / scaling
    ang = pos * freqs  # (B, S, half)
    cos = jnp.cos(ang)[..., None, :]  # (B, S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cache:
    """Functional KV cache: fixed buffers + explicit length.

    With INT8 KV quantization (cfg.kv_quant) the buffers are int8 and
    ``k_scale``/``v_scale`` hold per-token-per-head absmax scales — the
    paper-aligned A8 cache that halves decode HBM traffic."""

    k: jnp.ndarray  # (B, S_max, n_kv_store, hd)
    v: jnp.ndarray
    length: jnp.ndarray  # () int32
    k_scale: Optional[jnp.ndarray] = None  # (B, S_max, n_kv_store, 1) f32
    v_scale: Optional[jnp.ndarray] = None


@dataclasses.dataclass
class PagedCache:
    """One layer's view of the device-resident paged KV pool.

    Unlike ``Cache`` (per-request dense buffers), the pool is SHARED across
    the whole batch: each request owns the pages its ``page_table`` row
    names, and ``length`` is per row.  Built inside the traced forward from
    the paged cache pytree plus static serving config — not itself a pytree.

    ``impl`` picks the attention path:
      * "gather" — gather pages into a dense per-request view ON DEVICE
        (width = the table span, max_pages * page_size) and run the exact
        dense decode/flash math (bit-identical floats to the
        single-request path; the default serving path);
      * "pallas" — attend in place through the page table with
        ``kernels/paged_attn.paged_decode_attention_pallas`` (interpret mode
        on CPU), zero gather materialization.

    Rows excluded from a fused PAR dispatch arrive here already diverted:
    ``forward_cache_ctx`` applies the per-row role mask upstream by
    rewriting the masked rows' table entries to the scratch page and their
    lengths to 0, so this type never needs to know about roles.

    With ``k_scale``/``v_scale`` set the pool is COMPRESSED: k/v hold int8
    and the scale pools (same page layout, trailing dim 1) hold the
    per-slot-per-head dequant factors.  New tokens quantize on scatter
    (value + scale written in the same dispatch) and both impls dequantize
    at the consumer — the Pallas kernel inside its page loop, the gather
    path right after the gather — so pages stay int8 at rest.
    """

    k: jnp.ndarray  # (P(+scratch), kvh, page_size, hd) — head-major
    v: jnp.ndarray
    page_table: jnp.ndarray  # (B, max_pages) int32
    length: jnp.ndarray  # (B,) int32 — tokens already written per request
    impl: str = "gather"  # "gather" | "pallas"
    k_scale: Optional[jnp.ndarray] = None  # (P(+scratch), kvh, page_size, 1)
    v_scale: Optional[jnp.ndarray] = None
    # (B, S, S) intra-window visibility (speculation-tree ancestor mask);
    # None keeps the causal window semantics bit-exact (chain mode)
    tree_mask: Optional[jnp.ndarray] = None


def forward_cache_ctx(cache, b: int, s: int, paged_impl: str):
    """Shared forward preamble for every model path (bf16 / W4A8 / BVQ):
    ``(offset, positions (B, S), paged_ctx)`` for any cache form.

    A cache carrying ``page_table`` is the device-resident paged pool
    (``{"lengths" (B,), "page_table" (B, mp), "attn": {"k": (L, P, kvh,
    ps, hd), ...}}``): offset is the per-row length vector and paged_ctx
    the ``(page_table, impl, tree_mask)`` triple the per-layer attention
    needs (``tree_mask``/``win_pos`` cache keys are the speculation-tree
    extras — see ``PagedCache``).  A dense cache (or None) yields the
    scalar offset and ``paged_ctx = None``.

    Role-mask semantics (fused cross-request PAR dispatches): an optional
    ``"role_mask"`` (B,) bool entry selects which rows PARTICIPATE in this
    forward.  Masked-out rows are routed entirely to the pool's scratch
    page (their page-table row is replaced by the scratch id and their
    length by 0), so their KV writes land where no request reads and their
    attention output is garbage the caller ignores.  This is what lets the
    serving engine run the draft model and the target model over the SAME
    batch in ONE fused program — each row's role mask decides which of the
    two forwards actually touches its pages — without any row ever
    polluting the pool of a model it is not using this slot."""
    if cache is not None and "page_table" in cache:
        offset = cache["lengths"]  # (B,)
        table = cache["page_table"]
        mask = cache.get("role_mask")
        if mask is not None:
            # pool device arrays carry one trailing scratch page the
            # allocator never hands out — divert masked rows' table + length
            # there so their scatter/attend is inert (dup writes harmless)
            scratch = cache["attn"]["k"].shape[1] - 1
            offset = jnp.where(mask, offset, 0)
            table = jnp.where(mask[:, None], table, scratch)
        win_pos = cache.get("win_pos")  # (B, S) tree depths, optional
        if win_pos is None:
            positions = jnp.broadcast_to(
                offset[:, None] + jnp.arange(s)[None, :], (b, s)
            )
        else:
            # speculation tree: slot order in the window is BFS (stable pool
            # slots), but RoPE positions follow tree DEPTH — node i sits at
            # absolute position offset + depth(i)
            positions = offset[:, None] + win_pos
        return offset, positions, (table, paged_impl, cache.get("tree_mask"))
    offset = cache["length"] if cache is not None else jnp.zeros((), jnp.int32)
    positions = jnp.broadcast_to(offset + jnp.arange(s)[None, :], (b, s))
    return offset, positions, None


def paged_attention_update(
    q: jnp.ndarray,  # (B, S, H, hd) — post-rope queries
    k_new: jnp.ndarray,  # (B, S, kvh_store, hd) — post-rope, post-repeat
    v_new: jnp.ndarray,
    pc: PagedCache,
) -> Tuple[jnp.ndarray, dict]:
    """Scatter the S new tokens into their pool pages, then attend over the
    valid per-request prefix (+ the causally-masked window when S > 1).

    Returns ``(out (B, S, H, hd), new_pools)`` where ``new_pools`` is the
    updated storage dict — ``{"k", "v"}`` plus ``{"k_scale", "v_scale"}``
    for compressed pools.  The scatter is one ``.at[page, :, slot].set``
    per pool array — rows write disjoint pages by construction (inactive
    rows all target the scratch page, where duplicate writes are
    harmless); for compressed pools the span quantizes first and values +
    scales land in the SAME dispatch, so a readable slot always carries
    its own scale."""
    b, s, h, hd = q.shape
    n_pages, kvh, ps, _ = pc.k.shape  # head-major pages
    mp = pc.page_table.shape[1]
    quantized = pc.k_scale is not None
    pos = pc.length[:, None] + jnp.arange(s)[None, :]  # (B, S) absolute slots
    page = jnp.take_along_axis(
        pc.page_table, jnp.minimum(pos // ps, mp - 1), axis=1
    )  # (B, S) physical page per token
    # positions past the table span (an engine sizing bug — admission
    # reserves peak+window, so it cannot happen from serve_batch) divert to
    # the pool's last page (the engine's scratch) rather than silently
    # overwriting the request's own committed KV in its last page
    page = jnp.where(pos >= mp * ps, n_pages - 1, page).reshape(-1)
    slot = (pos % ps).reshape(-1)  # (B*S,) in-page slot per token

    def scatter(pool, span):
        # pool[page, :, slot] indexes (B*S, kvh, width): one row per token
        return pool.at[page, :, slot].set(
            span.astype(pool.dtype).reshape(b * s, kvh, pool.shape[-1])
        )

    if quantized:
        kq, ksc = _kv_quantize(k_new)
        vq, vsc = _kv_quantize(v_new)
        new_k = scatter(pc.k, kq)
        new_v = scatter(pc.v, vq)
        new_ks = scatter(pc.k_scale, ksc)
        new_vs = scatter(pc.v_scale, vsc)
        new_pools = {"k": new_k, "v": new_v, "k_scale": new_ks, "v_scale": new_vs}
    else:
        new_k = scatter(pc.k, k_new)
        new_v = scatter(pc.v, v_new)
        new_ks = new_vs = None
        new_pools = {"k": new_k, "v": new_v}
    new_len = pc.length + s  # valid tokens incl. this span, per row
    if pc.impl == "pallas":
        from repro.kernels.paged_attn import paged_decode_attention_pallas

        g = h // kvh
        q5 = q.reshape(b, s, kvh, g, hd)  # H is (kv-head, group)-major
        out = paged_decode_attention_pallas(
            q5, new_k, new_v, pc.page_table, new_len,
            k_scale=new_ks, v_scale=new_vs, tree_mask=pc.tree_mask,
        )
        return out.reshape(b, s, h, hd).astype(q.dtype), new_pools
    if pc.impl != "gather":
        raise ValueError(f"unknown paged attention impl {pc.impl!r}")
    # device-side gather to the table-span width (>= every valid length by
    # the allocator's reservation invariant), then the identical dense
    # math — bit-identical to the host-dense path: masked columns
    # contribute exact zeros, so the width difference never shows
    kd = gather_pages(new_k, pc.page_table)
    vd = gather_pages(new_v, pc.page_table)
    if quantized:
        # explicit f32 dequant, then the UNCHANGED fp attention math — this
        # is what keeps the gather path numerically equivalent (same dots,
        # small f32 tolerance) to the kernel's in-page dequant epilogue
        ksd = gather_pages(new_ks, pc.page_table)
        vsd = gather_pages(new_vs, pc.page_table)
        kd = (kd.astype(jnp.float32) * ksd).astype(q.dtype)
        vd = (vd.astype(jnp.float32) * vsd).astype(q.dtype)
    if pc.tree_mask is not None:
        out = _tree_window_attention(q, kd, vd, new_len, pc.tree_mask)
    elif s == 1:
        out = _decode_attention(q, kd, vd, new_len)
    else:
        out = flash_attention(q, kd, vd, causal=True, q_offset=pc.length)
    return out, new_pools


def gather_pages(pool: jnp.ndarray, page_table: jnp.ndarray) -> jnp.ndarray:
    """Dense token-major view of a head-major pool: ``(P, kvh, ps, w)``
    gathered through a ``(B, mp)`` table -> ``(B, mp * ps, kvh, w)``."""
    b, mp = page_table.shape
    _, kvh, ps, w = pool.shape
    pages = pool[page_table.reshape(-1)].reshape(b, mp, kvh, ps, w)
    return pages.transpose(0, 1, 3, 2, 4).reshape(b, mp * ps, kvh, w)


def _tree_window_attention(
    q: jnp.ndarray,  # (B, W, H, hd) — the full speculation-tree window
    kd: jnp.ndarray,  # (B, T, kvh, hd) dense gathered (dequantized) K
    vd: jnp.ndarray,
    lengths: jnp.ndarray,  # (B,) valid tokens INCLUDING the window
    tree_mask: jnp.ndarray,  # (B, W, W) intra-window visibility
) -> jnp.ndarray:
    """Gather-path tree attention: window query w sees the committed prefix
    (positions < lengths - W) plus window slot j iff ``tree_mask[b, w, j]``.
    Same masked-softmax math as the causal gather path, generalized mask —
    the dense mirror of the Pallas kernel's tree branch."""
    b, w, h, hd = q.shape
    t, kvh = kd.shape[1], kd.shape[2]
    g = h // kvh
    q5 = q.reshape(b, w, kvh, g, hd).astype(jnp.float32)
    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum(
        "bwkgh,btkh->bwkgt", q5 * scale, kd.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    rel = jnp.arange(t)[None, :] - (lengths[:, None] - w)  # (B, T)
    in_window = (rel >= 0) & (rel < w)
    idx = jnp.broadcast_to(jnp.clip(rel, 0, w - 1)[:, None, :], (b, w, t))
    win_vis = jnp.take_along_axis(tree_mask.astype(bool), idx, axis=2)
    prefix = jnp.arange(t)[None, None, :] < (lengths[:, None, None] - w)
    valid = prefix | (in_window[:, None, :] & win_vis)  # (B, W, T)
    scores = jnp.where(valid[:, :, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bwkgt,btkh->bwkgh", p, vd.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, w, h, hd).astype(q.dtype)


def _kv_quantize(k: jnp.ndarray):
    """(B,S,H,hd) -> (int8 values, (B,S,H,1) f32 scales)."""
    s = jnp.maximum(
        jnp.max(jnp.abs(k.astype(jnp.float32)), axis=-1, keepdims=True), 1e-8
    ) / 127.0
    q = jnp.clip(jnp.round(k.astype(jnp.float32) / s), -127, 127).astype(jnp.int8)
    return q, s.astype(jnp.float32)


def _kv_dequant(q: jnp.ndarray, s, dtype) -> jnp.ndarray:
    if s is None:
        return q.astype(dtype)
    return (q.astype(jnp.float32) * s).astype(dtype)


def attn_tp_enabled(cfg: ModelConfig, tp: int) -> bool:
    return cfg.n_heads % tp == 0 and tp > 1


def kv_store_heads(cfg: ModelConfig, tp: int) -> int:
    if not attn_tp_enabled(cfg, tp):
        return cfg.n_kv
    rep = tp // _gcd(cfg.n_kv, tp)
    return cfg.n_kv * rep


def attention_init(
    key: jax.Array, cfg: ModelConfig, tp: int, cross: bool = False
) -> Tuple[Params, Params]:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.hd
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    dt = cfg.jdtype
    params = {
        "wq": jax.random.normal(k1, (d, h, hd), dt) * s,
        "wk": jax.random.normal(k2, (d, kv, hd), dt) * s,
        "wv": jax.random.normal(k3, (d, kv, hd), dt) * s,
        "wo": jax.random.normal(k4, (h, hd, d), dt) * (s / math.sqrt(h / 1.0)),
    }
    tp_on = attn_tp_enabled(cfg, tp)
    hspec = "model" if tp_on else None
    fs = "data" if cfg.fsdp else None
    specs = {
        "wq": P(fs, hspec, None),
        "wk": P(fs, None, None),  # kv heads may not divide tp; see kv repeat
        "wv": P(fs, None, None),
        "wo": P(hspec, None, fs),
    }
    if cfg.qk_norm:
        params["q_norm"] = jnp.ones((hd,), dt)
        params["k_norm"] = jnp.ones((hd,), dt)
        specs["q_norm"] = P(None)
        specs["k_norm"] = P(None)
    return params, specs


def _qk_head_norm(x: jnp.ndarray, g: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)).astype(x.dtype)


def _repeat_kv(k: jnp.ndarray, store: int) -> jnp.ndarray:
    """(B, S, n_kv, hd) -> (B, S, store, hd), repeating heads contiguously so
    virtual head v serves q-heads [v * H/store : (v+1) * H/store)."""
    b, s, kv, hd = k.shape
    if store == kv:
        return k
    rep = store // kv
    return jnp.repeat(k, rep, axis=2)


def flash_attention(
    q: jnp.ndarray,  # (B, Sq, H, hd)
    k: jnp.ndarray,  # (B, Skv, Hkv_store, hd)
    v: jnp.ndarray,
    causal: bool,
    q_offset: jnp.ndarray | int = 0,  # absolute position of q[0]: () or (B,)
    kv_chunk: int = 1024,
) -> jnp.ndarray:
    """Streaming-softmax attention, lax.scan over KV chunks (bounds memory
    at ~Sq x kv_chunk scores per step — the 32k cells need this).  Widths
    that don't split evenly fall back to the largest divisor <= the target
    chunk count; a prime Skv > kv_chunk therefore runs unchunked — callers
    with such widths (none of the shipped paths: caches, paged spans, and
    training lengths are all highly composite) should pad K/V instead."""
    b, sq, h, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    scale = 1.0 / math.sqrt(hd)
    # dots run in the model dtype (bf16 on TPU -> MXU rate, half the bytes);
    # softmax statistics and the accumulator stay f32 (standard flash)
    dot_dt = q.dtype
    qf = (q.reshape(b, sq, hkv, g, hd).astype(jnp.float32) * scale).astype(dot_dt)
    # largest chunk count <= skv/kv_chunk that divides skv evenly — keeps
    # the score-tensor memory bound for widths (e.g. paged spans sized in
    # pages, not powers of two) that a fixed chunk count cannot split
    n_chunks = max(skv // kv_chunk, 1)
    while skv % n_chunks:
        n_chunks -= 1
    kc = k.reshape(b, n_chunks, skv // n_chunks, hkv, hd).astype(dot_dt)
    vc = v.reshape(b, n_chunks, skv // n_chunks, hkv, hd).astype(dot_dt)
    # scalar offset -> (1, Sq) broadcast row; per-request (B,) -> (B, Sq)
    q_pos = jnp.arange(sq)[None, :] + jnp.reshape(jnp.asarray(q_offset), (-1, 1))

    def step(carry, inputs):
        m, l, acc = carry
        kb, vb, c_idx = inputs  # (B, C, hkv, hd) x2, ()
        ck = kb.shape[1]
        scores = jnp.einsum(
            "bqkgh,bckh->bkgqc", qf, kb, preferred_element_type=jnp.float32
        )  # (B,hkv,g,Sq,C) f32
        if causal:
            kv_pos = c_idx * ck + jnp.arange(ck)
            mask = q_pos[:, :, None] >= kv_pos[None, None, :]  # (B|1, Sq, C)
            scores = jnp.where(mask[:, None, None], scores, -1e30)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum(
            "bkgqc,bckh->bkgqh", p.astype(dot_dt), vb,
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, hd), jnp.float32)
    kc_t = jnp.moveaxis(kc, 1, 0)  # (n_chunks, B, C, hkv, hd)
    vc_t = jnp.moveaxis(vc, 1, 0)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (kc_t, vc_t, jnp.arange(n_chunks))
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd)
    return out.astype(q.dtype)


def _decode_attention(
    q: jnp.ndarray,  # (B, 1, H, hd)
    cache_k: jnp.ndarray,  # (B, S_max, hkv, hd) — model dtype or int8
    cache_v: jnp.ndarray,
    length: jnp.ndarray,  # () or (B,) — valid prefix INCLUDING the new token
    k_scale=None,  # (B, S_max, hkv, 1) f32 when the cache is int8
    v_scale=None,
) -> jnp.ndarray:
    b, sq, h, hd = q.shape
    s_max, hkv = cache_k.shape[1], cache_k.shape[2]
    g = h // hkv
    scale = 1.0 / math.sqrt(hd)
    # low-precision dots with f32 accumulation avoid materializing a 4-byte
    # copy of the (huge) cache operand; f32 models keep f32 math (tests)
    dot_dt = (
        jnp.bfloat16
        if (k_scale is not None or cache_k.dtype == jnp.bfloat16)
        else jnp.float32
    )
    qf = (q.reshape(b, sq, hkv, g, hd).astype(jnp.float32) * scale).astype(dot_dt)
    scores = jnp.einsum(
        "bqkgh,bskh->bkgqs", qf, cache_k.astype(dot_dt),
        preferred_element_type=jnp.float32,
    )  # (B,hkv,g,1,S) f32
    if k_scale is not None:
        # per-token scales factor OUT of the contraction (exact)
        ks = jnp.moveaxis(k_scale[..., 0], 1, -1)[:, :, None, None, :]
        scores = scores * ks
    valid = jnp.arange(s_max)[None, :] < jnp.reshape(length, (-1, 1))  # (B|1, S)
    scores = jnp.where(valid[:, None, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    if v_scale is not None:
        vs = jnp.moveaxis(v_scale[..., 0], 1, -1)[:, :, None, None, :]
        p = p * vs  # fold the per-token V scale into the weights (exact)
    out = jnp.einsum(
        "bkgqs,bskh->bkgqh", p.astype(dot_dt),
        cache_v.astype(dot_dt), preferred_element_type=jnp.float32,
    )
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd)
    return out.astype(q.dtype)


def attention_apply(
    params: Params,
    x: jnp.ndarray,  # (B, S, d)
    cfg: ModelConfig,
    tp: int,
    cache: Optional[Cache] = None,
    positions: Optional[jnp.ndarray] = None,
    causal: bool = True,
    kv_override: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,  # cross-attn
    use_rope: bool = True,
) -> Tuple[jnp.ndarray, Optional[Cache]]:
    b, s, d = x.shape
    store = kv_store_heads(cfg, tp)
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if cfg.qk_norm:
        q = _qk_head_norm(q, params["q_norm"])
    if kv_override is not None:
        k, v = kv_override  # already (B, T, store, hd)
        new_cache = cache
        if use_rope and positions is not None:
            q = rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
        out = flash_attention(q, k, v, causal=False)
    else:
        k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
        if cfg.qk_norm:
            k = _qk_head_norm(k, params["k_norm"])
        if positions is None:
            if isinstance(cache, PagedCache):
                positions = cache.length[:, None] + jnp.arange(s)[None, :]
            else:
                positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        if use_rope:
            q = rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
            k = rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
        k = _repeat_kv(k, store)
        v = _repeat_kv(v, store)
        if isinstance(cache, PagedCache):
            # device-resident paged pool: scatter the new span into its
            # pages and attend through the page table (per-row lengths)
            out, np_ = paged_attention_update(q, k, v, cache)
            y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
            return y, dataclasses.replace(
                cache, k=np_["k"], v=np_["v"],
                k_scale=np_.get("k_scale"), v_scale=np_.get("v_scale"),
                length=cache.length + s,
            )
        quant = cache is not None and cache.k_scale is not None
        if cache is None:
            out = flash_attention(q, k, v, causal=causal)
            new_cache = None
        elif s == 1:
            # decode: append then attend over the valid prefix
            if quant:
                kq, ksc = _kv_quantize(k)
                vq, vsc = _kv_quantize(v)
                ck = jax.lax.dynamic_update_slice_in_dim(cache.k, kq, cache.length, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(cache.v, vq, cache.length, axis=1)
                cks = jax.lax.dynamic_update_slice_in_dim(cache.k_scale, ksc, cache.length, axis=1)
                cvs = jax.lax.dynamic_update_slice_in_dim(cache.v_scale, vsc, cache.length, axis=1)
                new_len = cache.length + 1
                out = _decode_attention(q, ck, cv, new_len, k_scale=cks, v_scale=cvs)
                new_cache = Cache(k=ck, v=cv, length=new_len, k_scale=cks, v_scale=cvs)
            else:
                ck = jax.lax.dynamic_update_slice_in_dim(cache.k, k, cache.length, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(cache.v, v, cache.length, axis=1)
                new_len = cache.length + 1
                out = _decode_attention(q, ck, cv, new_len)
                new_cache = Cache(k=ck, v=cv, length=new_len)
        else:
            # prefill/extend into the cache, then flash over the FULL buffer:
            # the causal mask (q_pos = offset + i vs absolute kv positions)
            # attends the cached prefix and masks unwritten tail slots.
            if quant:
                kq, ksc = _kv_quantize(k)
                vq, vsc = _kv_quantize(v)
                ck = jax.lax.dynamic_update_slice_in_dim(cache.k, kq, cache.length, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(cache.v, vq, cache.length, axis=1)
                cks = jax.lax.dynamic_update_slice_in_dim(cache.k_scale, ksc, cache.length, axis=1)
                cvs = jax.lax.dynamic_update_slice_in_dim(cache.v_scale, vsc, cache.length, axis=1)
                kf = _kv_dequant(ck, cks, x.dtype)
                vf = _kv_dequant(cv, cvs, x.dtype)
                out = flash_attention(q, kf, vf, causal=True, q_offset=cache.length)
                new_cache = Cache(k=ck, v=cv, length=cache.length + s,
                                  k_scale=cks, v_scale=cvs)
            else:
                ck = jax.lax.dynamic_update_slice_in_dim(cache.k, k, cache.length, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(cache.v, v, cache.length, axis=1)
                out = flash_attention(q, ck, cv, causal=True, q_offset=cache.length)
                new_cache = Cache(k=ck, v=cv, length=cache.length + s)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, new_cache


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_init(key: jax.Array, cfg: ModelConfig) -> Tuple[Params, Params]:
    d, f = cfg.d_model, cfg.d_ff
    dt = cfg.jdtype
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(f)
    fs = "data" if cfg.fsdp else None
    if cfg.act == "swiglu":
        k1, k2, k3 = jax.random.split(key, 3)
        params = {
            "w_gate": jax.random.normal(k1, (d, f), dt) * s_in,
            "w_up": jax.random.normal(k2, (d, f), dt) * s_in,
            "w_down": jax.random.normal(k3, (f, d), dt) * s_out,
        }
        specs = {
            "w_gate": P(fs, "model"),
            "w_up": P(fs, "model"),
            "w_down": P("model", fs),
        }
    else:
        k1, k2 = jax.random.split(key)
        params = {
            "w_up": jax.random.normal(k1, (d, f), dt) * s_in,
            "w_down": jax.random.normal(k2, (f, d), dt) * s_out,
        }
        specs = {"w_up": P(fs, "model"), "w_down": P("model", fs)}
    return params, specs


def mlp_apply(params: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.act == "swiglu":
        g = x @ params["w_gate"]
        u = x @ params["w_up"]
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    elif cfg.act == "squared_relu":
        u = x @ params["w_up"]
        r = jax.nn.relu(u.astype(jnp.float32))
        h = (r * r).astype(x.dtype)
    else:  # gelu
        u = x @ params["w_up"]
        h = jax.nn.gelu(u.astype(jnp.float32)).astype(x.dtype)
    return h @ params["w_down"]


# ---------------------------------------------------------------------------
# MoE — dense reference + GShard-style all-to-all expert parallelism
# ---------------------------------------------------------------------------


def moe_ff_split(cfg: ModelConfig, tp: int) -> int:
    """When n_experts < tp, each expert's FFN columns split across
    tp // n_experts shards so the (expert x slice) grid covers the model
    axis exactly (grok-1: 8 experts x 2 slices on tp=16)."""
    e = cfg.n_experts
    if tp <= e:
        assert e % tp == 0, (e, tp)
        return 1
    assert tp % e == 0, (e, tp)
    return tp // e


def moe_init(key: jax.Array, cfg: ModelConfig, tp: int = 1) -> Tuple[Params, Params]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    split = moe_ff_split(cfg, tp)
    fs_ = f // split
    dt = cfg.jdtype
    kr, k1, k2, k3 = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(f)
    fs = "data" if cfg.fsdp else None
    # storage: (e * split, d, f / split) — total element count == e * d * f
    params = {
        "router": jax.random.normal(kr, (d, e), jnp.float32) * s_in,
        "w_gate": jax.random.normal(k1, (e * split, d, fs_), dt) * s_in,
        "w_up": jax.random.normal(k2, (e * split, d, fs_), dt) * s_in,
        "w_down": jax.random.normal(k3, (e * split, fs_, d), dt) * s_out,
    }
    specs = {
        "router": P(None, None),
        "w_gate": P("model", fs, None),
        "w_up": P("model", fs, None),
        "w_down": P("model", None, fs),
    }
    return params, specs


def _topk_gates(logits: jnp.ndarray, k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """-> (gate values (T, k) normalized, expert ids (T, k))."""
    vals, ids = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(vals, axis=-1)
    return gates, ids


def moe_apply_dense(params: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Reference: every token through every expert, gated combine.
    Exact math, x E/k compute — smoke tests and tiny configs only.
    Handles the (e * split, d, f / split) storage layout."""
    b, s, d = x.shape
    e = cfg.n_experts
    es, _, fs_ = params["w_gate"].shape
    split = es // e
    t = x.reshape(-1, d)
    logits = (t.astype(jnp.float32) @ params["router"]).astype(jnp.float32)
    gates, ids = _topk_gates(logits, cfg.top_k)
    combine = jnp.zeros((t.shape[0], e), jnp.float32)
    combine = jax.vmap(lambda c, i, g: c.at[i].add(g))(combine, ids, gates)
    # (e*split, d, f/split) -> (e, d, f)
    wg = params["w_gate"].reshape(e, split, d, fs_).transpose(0, 2, 1, 3).reshape(e, d, split * fs_)
    wu = params["w_up"].reshape(e, split, d, fs_).transpose(0, 2, 1, 3).reshape(e, d, split * fs_)
    wd = params["w_down"].reshape(e, split, fs_, d).reshape(e, split * fs_, d)
    g_out = jnp.einsum("td,edf->tef", t, wg)
    u_out = jnp.einsum("td,edf->tef", t, wu)
    h = jax.nn.silu(g_out.astype(jnp.float32)).astype(x.dtype) * u_out
    y = jnp.einsum("tef,efd->ted", h, wd)
    out = jnp.einsum("ted,te->td", y.astype(jnp.float32), combine)
    return out.astype(x.dtype).reshape(b, s, d)


def moe_apply_a2a(
    params: Params,
    x: jnp.ndarray,  # (B, S, d) — sharded (data, model) over (B, S)
    cfg: ModelConfig,
    mesh,
    seq_sharded: bool = True,
) -> jnp.ndarray:
    """GShard-style EP: tokens route to capacity-bounded per-expert slots,
    all-to-all over the 'model' axis ships slots to their (expert x
    ff-slice) owners, expert GEMMs run batched, a second all-to-all ships
    partial results back (summed over ff slices when experts < tp).

    Inside shard_map each device sees a (B/data, S/model, d) token slab, so
    capacity is per (device, expert); over-capacity tokens drop to the
    residual path (GShard semantics).
    """
    tp = mesh.shape["model"]
    e = cfg.n_experts
    split = moe_ff_split(cfg, tp)
    e_loc = max(e // tp, 1)
    batch_axes = pick_batch_axes(mesh, x.shape[0])

    def local(x_loc, router, w_gate, w_up, w_down):
        b_loc, s_loc, d = x_loc.shape
        t = x_loc.reshape(-1, d)
        n_tok = t.shape[0]
        cap = max(int(cfg.capacity_factor * n_tok * cfg.top_k / e), 4)
        logits = t.astype(jnp.float32) @ router
        gates, ids = _topk_gates(logits, cfg.top_k)  # (T, k)
        flat_ids = ids.reshape(-1)
        flat_gates = gates.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(n_tok), cfg.top_k)
        onehot = jax.nn.one_hot(flat_ids, e, dtype=jnp.int32)  # (T*k, E)
        pos = (jnp.cumsum(onehot, axis=0) - 1) * onehot  # 0-based rank
        slot = jnp.sum(pos, axis=-1)
        keep = (slot >= 0) & (slot < cap)
        slot_c = jnp.clip(slot, 0, cap - 1)
        buf = jnp.zeros((e, cap, d), x_loc.dtype)
        buf = buf.at[flat_ids, slot_c].add(
            jnp.where(keep[:, None], t[flat_tok], 0.0).astype(x_loc.dtype)
        )
        if split > 1:
            # duplicate each expert's slots to all of its ff-slice owners
            buf = jnp.repeat(buf, split, axis=0)  # (E*split == tp, cap, d)
        buf = buf.reshape(tp, e_loc, cap, d)
        recv = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=0)
        # recv: (tp, e_loc, cap, d) — every peer's slots for MY experts
        recv = recv.reshape(e_loc, tp * cap, d)
        g_out = jnp.einsum("ecd,edf->ecf", recv, w_gate)
        u_out = jnp.einsum("ecd,edf->ecf", recv, w_up)
        h = jax.nn.silu(g_out.astype(jnp.float32)).astype(recv.dtype) * u_out
        y = jnp.einsum("ecf,efd->ecd", h, w_down)  # partial over ff slice
        y = y.reshape(e_loc, tp, cap, d).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(y, "model", split_axis=0, concat_axis=0)
        back = back.reshape(e, split, cap, d).sum(axis=1)  # sum ff slices
        picked = back[flat_ids, slot_c]  # (T*k, d)
        picked = jnp.where(keep[:, None], picked, 0.0)
        contrib = picked.astype(jnp.float32) * flat_gates[:, None]
        out = jnp.zeros((n_tok, d), jnp.float32).at[flat_tok].add(contrib)
        return out.astype(x_loc.dtype).reshape(b_loc, s_loc, d)

    tok_spec = (
        P(batch_axes, "model", None) if seq_sharded else P(batch_axes, None, None)
    )
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            tok_spec,
            P(None, None),
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=tok_spec,
        check_vma=False,
    )
    return fn(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])


def moe_apply(
    params: Params, x: jnp.ndarray, cfg: ModelConfig, mesh=None
) -> jnp.ndarray:
    if cfg.moe_impl == "a2a" and mesh is not None:
        return moe_apply_a2a(params, x, cfg, mesh)
    return moe_apply_dense(params, x, cfg)


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------


def embed_init(key: jax.Array, cfg: ModelConfig) -> Tuple[Params, Params]:
    k1, k2 = jax.random.split(key)
    dt = cfg.jdtype
    fs = "data" if cfg.fsdp else None
    params = {
        "tok": jax.random.normal(k1, (cfg.vocab_padded, cfg.d_model), dt) * 0.02,
        "head": jax.random.normal(k2, (cfg.d_model, cfg.vocab_padded), dt)
        * (1.0 / math.sqrt(cfg.d_model)),
    }
    specs = {"tok": P("model", fs), "head": P(fs, "model")}
    return params, specs
