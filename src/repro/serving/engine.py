"""Serving engine: the stepwise continuous-batching runtime.

``Engine`` is the serving surface: requests are admitted at ANY time
(``add_request``), each ``step()`` runs one WDOS-scheduled draft/verify
round over whatever is active and streams incremental ``RequestOutput``s,
and ``abort()`` frees a request's pool pages immediately.  Nothing drains:
a request submitted after round k is prefilled and scheduled in round k+1
while the rest of the batch keeps decoding — the continuous arrival/retire
pattern the paper's out-of-order WDOS scheduler (Fig. 31.1.5) exploits to
overlap different requests' draft (RERAM) and verify (EMAC) pipelines.

Two execution modes (``EngineConfig.par_mode``, outputs bit-identical):

* ``"off"`` — two-phase rounds: every active row drafts its window in
  lockstep micro-steps, then ONE batched verify pass scores everyone.
* ``"wdos"`` — fused cross-request PAR: each step executes a horizon of
  mixed phase plans emitted by the WDOS planner
  (core/scheduler.plan_mixed_slot).  Per slot, window-full rows VERIFY
  (target model, full window) while the other rows DRAFT their next
  proposal (draft model, one token) — in one fused XLA dispatch whose
  per-row role masks keep each model's pool writes confined to the rows
  that actually use it.  Rows cycle out of phase, so a fast-accepting
  request commits several windows inside one engine round while a
  long-window neighbour is still drafting; requests carry mid-window
  phase state across steps (serving/request.py).

KV lives in DEVICE-RESIDENT block-granular paged pools
(serving/paged_cache.py allocator + JAX pool arrays): prefill scatters
straight into pool pages, each batched draft/verify step scatters new
tokens in place and attends through per-row page tables, and accept/rewind
is a per-row length update — no per-round host gather/scatter of K/V.

Invariants the hot loop relies on (see docs/ARCHITECTURE.md for the map):

* page-table lifetime stability — a request's pages are reserved AND
  backed at admission, so its table row uploads once and stays valid from
  prefill to retirement; only lengths change per round;
* rewind bounds — a round writes at most ``max_dl + 1`` positions past the
  committed prefix and always rewinds back to ``committed - 1`` tokens, so
  the admission-time reservation (prompt + max_tokens + max_dl) is never
  exceeded and stale tail slots are masked-then-overwritten, never read;
* role-mask semantics — in fused dispatches a row participates in a model's
  forward iff its mask bit is set; masked rows are diverted to the pool's
  scratch page inside the traced forward (models/layers.forward_cache_ctx),
  so a drafting row can never pollute the target pool and vice versa;
* per-request determinism — draft/accept PRNG keys are indexed by
  (request seed, round, position) and rounds count COMMITS, so scheduling
  (batch composition, two-phase vs fused) never shifts a request's tokens.

Sampling is per request (``api.SamplingParams``): ``temperature == 0`` is
greedy and bit-identical per request to the single-request reference
drivers (batching, paging, and residency change scheduling, never
sampling); ``temperature > 0`` runs lossless speculative rejection sampling
from a per-request key stream, so a request's sampled tokens are identical
at batch 1 and batch N (tests/test_engine_api.py).

The pre-redesign entry points — ``serve_sd``, ``serve_apsd``,
``serve_batch``, ``serve_batch_host`` — survive as thin DEPRECATED wrappers
over ``Engine`` (each warns once); the legacy host-gather loop itself stays
frozen in serving/host_gather.py as the benchmark baseline.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scheduler as sch
from repro.core.apsd import PAR, APSDConfig, APSDStats, RoundRecord
from repro.core.speculative import (
    LMInterface,
    SDConfig,
    SDStats,
    sample_token_host,
    speculative_accept_greedy_host,
    speculative_sample_host,
    speculative_tree_accept_greedy_host,
    speculative_tree_sample_host,
    topk_tokens_host,
    tree_ancestor_mask,
    tree_depths,
)
from repro.models import layers as L
from repro.models import lm
from repro.models.common import ModelConfig
from repro.serving import quantized_lm as qlm
from repro.serving.api import (
    CompletionOutput,
    EngineConfig,
    RequestOutput,
    SamplingParams,
    default_detokenize,
    resolve_paged_attn_impl,
    warn_deprecated_once,
)
from repro.serving.batcher import BatchConfig, ContinuousBatcher
from repro.serving.flight_recorder import FlightRecorder
from repro.serving.observability import (
    LATENCY_BUCKETS,
    RATIO_BUCKETS,
    MetricsRegistry,
)
from repro.serving.paged_cache import (
    PagedKVPool,
    device_pool_store,
    num_pages_for_bytes,
    pages_for,
)
from repro.serving.prefix_cache import PrefixCache
from repro.serving.request import Request, RequestState
from repro.serving.tracing import NULL_TRACER, Tracer

__all__ = [
    "Engine",
    "EngineConfig",
    "SamplingParams",
    "RequestOutput",
    "CompletionOutput",
    "make_interface",
    "ServingModel",
    "serve_sd",
    "serve_apsd",
    "serve_batch",
    "serve_batch_host",
    "BatchConfig",
]


@dataclasses.dataclass
class ServingModel:
    cfg: ModelConfig
    params: Any
    mode: str = "bf16"  # bf16 | w4a8 | bvq
    mesh: Any = None
    s_max: int = 512
    # Pallas kernels for the quantized linears.  None is each mode's
    # default, and the two differ on purpose: the BVQ linears take the
    # kernel where it compiles (a TPU at a supported shape;
    # kernels/ops.resolve_bvq_impl), the W4A8 linears keep their jnp
    # reference on every backend.  True or False forces the kernels, or
    # the references, in both modes.
    use_pallas: Optional[bool] = None
    # paged decode attention path: "auto" resolves per backend (the Pallas
    # paged kernel where its TPU dialect lowers, the exact device gather
    # everywhere else); "gather" replays the exact dense math over a
    # device-side page gather (bit-identical to the dense cache path);
    # "pallas" attends in place through the page table with
    # kernels/paged_attn.py (interpret mode on CPU).
    paged_attn_impl: str = "auto"

    def _apply(self, params, tokens, cache):
        paged_kw = {}
        if cache is not None and "page_table" in cache:
            paged_kw = dict(paged_impl=resolve_paged_attn_impl(self.paged_attn_impl))
        if self.mode == "w4a8":
            return qlm.apply_quantized_lm(
                params, self.cfg, self.mesh, tokens, cache=cache,
                use_pallas=bool(self.use_pallas), **paged_kw,
            )
        if self.mode == "bvq":
            return qlm.apply_bvq_lm(
                params, self.cfg, self.mesh, tokens, cache=cache,
                use_pallas=self.use_pallas, **paged_kw,
            )
        return lm.apply_lm(
            params, self.cfg, self.mesh, tokens, cache=cache, **paged_kw
        )


def make_interface(model: ServingModel) -> LMInterface:
    cfg, mesh, s_max = model.cfg, model.mesh, model.s_max

    def fresh_cache(batch):
        if model.mode in ("w4a8", "bvq"):
            # quantized paths use the dense attn cache layout
            c = lm.init_cache(
                dataclasses.replace(cfg),  # same shapes
                batch, s_max, tp=mesh.shape["model"] if mesh else 1,
            )
            return c
        return lm.init_cache(cfg, batch, s_max, tp=mesh.shape["model"] if mesh else 1)

    @jax.jit
    def _prefill(params, tokens, cache):
        return model._apply(params, tokens, cache)

    @jax.jit
    def _extend(params, tokens, cache):
        return model._apply(params, tokens, cache)

    def prefill(params, tokens):
        cache = fresh_cache(tokens.shape[0])
        return _prefill(params, tokens, cache)

    def extend(params, tokens, cache):
        return _extend(params, tokens, cache)

    def rewind(cache, n):
        if n < 0:
            raise ValueError(f"rewind expects n >= 0, got {n}")
        length = cache["length"]
        try:
            if int(length) - n < 0:
                raise ValueError(
                    f"over-rewind: cache length {int(length)} < rewind {n}"
                )
        except jax.errors.ConcretizationTypeError:
            pass  # traced length: fall through to the clamp below
        c = dict(cache)
        c["length"] = jnp.maximum(length - n, 0)
        return c

    return LMInterface(prefill=prefill, extend=extend, rewind=rewind)


# ---------------------------------------------------------------------------
# Shared helpers (the frozen host_gather baseline also imports these)
# ---------------------------------------------------------------------------


def _np_dtype(cfg: ModelConfig):
    return np.asarray(jnp.zeros((), cfg.jdtype)).dtype


def _wdos_costs(mcfg: ModelConfig) -> Tuple[float, float]:
    load = 12.0 * mcfg.d_model * mcfg.d_model * 1e-6  # ~per-layer weight bytes
    return load, 0.25 * load


def _empty_summary(cfg) -> dict:
    return {
        "requests": 0, "rounds": 0, "steps": 0, "emitted": 0,
        "acceptance_rate": 0.0, "target_pool": None, "draft_pool": None,
        "wdos_modeled_speedup": 1.0,
        "wdos_utilization": {},
        "par_mode": getattr(cfg, "par_mode", "off"),
        "kv_path": getattr(cfg, "kv_path", "paged"),
        "kv_copy_s": 0.0,
        "table_upload_s": 0.0,
    }


def _pool_for(
    model: ServingModel, cfg, peaks: Sequence[int],
    alloc_storage: bool = True,
):
    """Page pool sized to hold `max_batch` worst-case requests (or the
    explicit cfg.num_pages budget).  alloc_storage=False builds the pure
    allocator for the device-resident path (KV bytes live in JAX arrays)."""
    mcfg = model.cfg
    if mcfg.kv_quant:
        # the MODEL's dense-cache kv_quant knob (contiguous int8 cache) —
        # distinct from EngineConfig.kv_quant, which compresses the PAGED
        # pools and dequantizes inside the paged-attention consumers
        raise NotImplementedError("paged pools hold dense-dtype KV (kv_quant=False)")
    if model.mesh is not None:
        raise NotImplementedError("the Engine runs the single-host path (mesh=None)")
    if getattr(cfg, "pool_bytes", None) is not None:
        # byte-budget sizing: admission is then effectively on COMPRESSED
        # bytes — an int8 pool gets ~3.5x the pages (and thus resident
        # requests) of a dense pool under the same budget
        num_pages = num_pages_for_bytes(
            cfg.pool_bytes,
            n_layers=mcfg.n_layers,
            kv_heads=L.kv_store_heads(mcfg, 1),
            head_dim=mcfg.hd,
            page_size=cfg.page_size,
            dtype=_np_dtype(mcfg),
            kv_quant=getattr(cfg, "kv_quant", "none"),
        )
    elif cfg.num_pages is not None:
        num_pages = cfg.num_pages
    else:
        worst = sorted((pages_for(p, cfg.page_size) for p in peaks), reverse=True)
        num_pages = sum(worst[: cfg.max_batch])
    return PagedKVPool(
        n_layers=mcfg.n_layers,
        kv_heads=L.kv_store_heads(mcfg, 1),
        head_dim=mcfg.hd,
        num_pages=num_pages,
        page_size=cfg.page_size,
        dtype=_np_dtype(mcfg),
        alloc_storage=alloc_storage,
        kv_quant=getattr(cfg, "kv_quant", "none"),
    )


# host_gather.py (frozen baseline) keeps calling the accept rule through
# this name; the shared implementation lives in core/speculative.py now.
_greedy_accept_host = speculative_accept_greedy_host


def _make_paged_step(model: ServingModel):
    """jit of one batched paged forward: every active request is a batch row
    with its OWN page-table row and length (positions, causal masking, and
    the pool write slots are per-row).  The K/V store is carried as a device
    dict pytree (``{"k", "v"}`` dense, ``+{"k_scale", "v_scale"}`` for int8
    pools — see paged_cache.device_pool_store): the step scatters new tokens
    (and, quantized, their page scales — same dispatch, so value/scale can
    never go stale independently) in place and returns the updated store, so
    NO K/V bytes ever cross the host boundary.  The store is DONATED: the
    caller always rebinds it to the step's output, so XLA may alias the
    scatter in place instead of copying the pool."""

    @partial(jax.jit, donate_argnums=(2,))
    def step(params, tokens, store, page_table, lengths):
        # tokens (B, W) int32; store arrays (L, P+1, kvh, ps, hd|1)
        cache = {
            "lengths": lengths,
            "page_table": page_table,
            "attn": dict(store),
        }
        logits, nc = model._apply(params, tokens, cache)
        return logits, {name: nc["attn"][name] for name in store}

    return step


def _make_tree_step(model: ServingModel):
    """jit of one batched TREE-window forward (spec_mode="tree"): the window
    holds a draft tree in BFS order, ``win_pos`` gives each slot its RoPE
    depth offset, and ``tree_mask`` (B, W, W) restricts window-internal
    attention to each slot's own root-path (models/layers.forward_cache_ctx
    threads both through the paged attention consumers; the Pallas kernel
    applies the mask in place, the gather fallback through
    ``_tree_window_attention``).  Same donation/store contract as
    ``_make_paged_step``."""

    @partial(jax.jit, donate_argnums=(2,))
    def step(params, tokens, store, page_table, lengths, win_pos, tree_mask):
        cache = {
            "lengths": lengths,
            "page_table": page_table,
            "win_pos": win_pos,
            "tree_mask": tree_mask,
            "attn": dict(store),
        }
        logits, nc = model._apply(params, tokens, cache)
        return logits, {name: nc["attn"][name] for name in store}

    return step


def _make_fused_step(target: ServingModel, draft: ServingModel):
    """jit of ONE fused PAR dispatch: the target model's verify pass (width
    ``max_dl + 1``, rows selected by `v_mask`) and the draft model's
    micro-step (width 1, rows selected by `d_mask`) in a SINGLE XLA
    program.  The two subgraphs share no values, so the compiler is free to
    overlap them — the TPU analogue of the chip issuing TLM work to the
    EMAC queue while DLM work streams from RERAM.  Masked rows are diverted
    to each pool's scratch page inside the traced forward
    (models/layers.forward_cache_ctx role-mask semantics), so a drafting
    row never writes the target pool and a verifying row's target writes
    never leak into its neighbour's pages.  Widths are FIXED per engine
    (verify always max_dl + 1, causally padded), so the program compiles
    once, not per round shape."""

    @partial(jax.jit, donate_argnums=(4, 5))
    def step(t_params, d_params, v_tokens, d_tokens,
             t_store, d_store,
             t_table, t_len, d_table, d_len, v_mask, d_mask):
        t_cache = {
            "lengths": t_len,
            "page_table": t_table,
            "role_mask": v_mask,
            "attn": dict(t_store),
        }
        v_logits, t_nc = target._apply(t_params, v_tokens, t_cache)
        d_cache = {
            "lengths": d_len,
            "page_table": d_table,
            "role_mask": d_mask,
            "attn": dict(d_store),
        }
        d_logits, d_nc = draft._apply(d_params, d_tokens, d_cache)
        return (v_logits, d_logits,
                {name: t_nc["attn"][name] for name in t_store},
                {name: d_nc["attn"][name] for name in d_store})

    return step


def _make_masked_draft_step(draft: ServingModel):
    """jit of a draft-only PAR slot (no row is window-full): one draft
    micro-step with the per-row role mask, so rows retired mid-step stay
    inert without re-uploading the page table."""

    @partial(jax.jit, donate_argnums=(2,))
    def step(params, tokens, store, page_table, lengths, mask):
        cache = {
            "lengths": lengths,
            "page_table": page_table,
            "role_mask": mask,
            "attn": dict(store),
        }
        logits, nc = draft._apply(params, tokens, cache)
        return logits, {name: nc["attn"][name] for name in store}

    return step


def _make_fused_tree_step(target: ServingModel, draft: ServingModel):
    """jit of ONE fused tree-PAR dispatch: both sides run the FULL
    fixed-width tree window (``tree_budget + 1``) — the target verifies
    complete trees on rows selected by `v_mask` while the draft side
    re-feeds every active row's partial tree (for verifying rows that is
    the straggler dispatch landing the leaf KV).  Tree masks and depth
    positions ride per side; widths are fixed so the program compiles
    once."""

    @partial(jax.jit, donate_argnums=(4, 5))
    def step(t_params, d_params, v_tokens, d_tokens,
             t_store, d_store,
             t_table, t_len, d_table, d_len, v_mask, d_mask,
             t_win_pos, t_tree_mask, d_win_pos, d_tree_mask):
        t_cache = {
            "lengths": t_len,
            "page_table": t_table,
            "role_mask": v_mask,
            "win_pos": t_win_pos,
            "tree_mask": t_tree_mask,
            "attn": dict(t_store),
        }
        v_logits, t_nc = target._apply(t_params, v_tokens, t_cache)
        d_cache = {
            "lengths": d_len,
            "page_table": d_table,
            "role_mask": d_mask,
            "win_pos": d_win_pos,
            "tree_mask": d_tree_mask,
            "attn": dict(d_store),
        }
        d_logits, d_nc = draft._apply(d_params, d_tokens, d_cache)
        return (v_logits, d_logits,
                {name: t_nc["attn"][name] for name in t_store},
                {name: d_nc["attn"][name] for name in d_store})

    return step


def _make_masked_tree_draft_step(draft: ServingModel):
    """jit of a draft-only tree-PAR slot (no row is tree-full): one
    full-width tree re-feed with the per-row role mask."""

    @partial(jax.jit, donate_argnums=(2,))
    def step(params, tokens, store, page_table, lengths, mask,
             win_pos, tree_mask):
        cache = {
            "lengths": lengths,
            "page_table": page_table,
            "role_mask": mask,
            "win_pos": win_pos,
            "tree_mask": tree_mask,
            "attn": dict(store),
        }
        logits, nc = draft._apply(params, tokens, cache)
        return logits, {name: nc["attn"][name] for name in store}

    return step


@partial(jax.jit, donate_argnums=(0,))
def _scatter_prefill(store, k_dense, v_dense, pages, n, start=0):
    """Scatter a freshly prefilled request's cache rows [start, n) straight
    into its pool pages — device to device, no host round-trip.
    store: device store dict (paged_cache.device_pool_store);
    k_dense/v_dense: (L, s_max, kvh, hd); pages: (mp,) physical page ids,
    unowned slots holding the scratch page.  `n`/`start` are traced (one
    compile per model, not per prompt length): the fixed-width scatter
    covers the whole table span and routes slots outside [start, n) to the
    scratch page.  A prefix-cache hit passes start = tokens_matched so the
    shared prefix pages — whose rows are already resident — are never
    touched (rows below `start` may even map COW-protected shared pages).

    For an int8 store the dense prefix is quantized here (the same
    per-slot-per-head rule the decode steps apply in
    models/layers.paged_attention_update) and values + scales land in one
    dispatch, so a page's scale can never be stale relative to its bytes."""
    p1, ps = store["k"].shape[1], store["k"].shape[3]  # (L, P+1, kvh, ps, hd)
    s_max = k_dense.shape[1]
    cap = pages.shape[0] * ps  # table span; may overhang s_max by < ps
    pos = jnp.arange(cap)
    # rows outside [start, n) land on the scratch page: harmless dup writes
    page = jnp.where((pos >= start) & (pos < n), pages[pos // ps], p1 - 1)
    slot = pos % ps
    src_k = k_dense[:, jnp.minimum(pos, s_max - 1)]
    src_v = v_dense[:, jnp.minimum(pos, s_max - 1)]
    if "k_scale" in store:
        qk, sk = L._kv_quantize(src_k)
        qv, sv = L._kv_quantize(src_v)
        writes = {"k": qk, "v": qv, "k_scale": sk, "v_scale": sv}
    else:
        writes = {"k": src_k, "v": src_v}
    out = {}
    for name, src in writes.items():
        pool = store[name]
        # pool[:, page, :, slot] indexes (cap, L, kvh, width)
        out[name] = pool.at[:, page, :, slot].set(
            jnp.swapaxes(src, 0, 1).astype(pool.dtype)
        )
    return out


@partial(jax.jit, donate_argnums=(0,))
def _copy_page(store, src, dst):
    """Copy one physical page (every array of the store: values and, for
    int8, scales) device-side — the copy-on-write step that privatizes a
    partially-shared prefix page before its holder's first scatter.
    `src`/`dst` are traced so the one compiled program serves every COW."""
    return {name: a.at[:, dst].set(a[:, src]) for name, a in store.items()}


@partial(jax.jit, donate_argnums=(0,))
def _compact_slots(store, src, dst):
    """Batched flat-slot copy over every array of a device store — the
    tree-verify COMPACTION step: after acceptance walks a non-leftmost
    root path, the accepted nodes' KV rows (scattered at their BFS window
    slots) are copied down to the chain positions the committed sequence
    expects, so rewind-to-committed leaves the pool bit-identical to a
    chain round that drafted the same tokens.  `src`/`dst` are fixed-width
    (padded with the scratch page's flat slots: harmless self-copies), so
    one compiled program serves every round; the gather reads the donated
    input before the scatter writes, so overlapping src/dst spans are
    safe."""
    out = {}
    for name, a in store.items():
        ps = a.shape[3]  # (L, P+1, kvh, ps, width)
        rows = a[:, src // ps, :, src % ps]  # (N, L, kvh, width)
        out[name] = a.at[:, dst // ps, :, dst % ps].set(rows)
    return out


def _sample_tree_level(req, cfg, logits: np.ndarray) -> None:
    """Grow one request's draft tree by ONE level from a window logits
    matrix (W, V) — row 0 is the distribution after the committed tip, row
    1+i after drafted node i.  Frontier nodes (deepest fully-grown level)
    each fan out to ``spec_branches`` children when the draft's top-1
    probability is below ``branch_threshold`` (a low-confidence position —
    the paper's adaptive parallel-speculation cue) and the per-round node
    budget still covers the fan-out; otherwise one child.  Greedy requests
    take the top-k distinct tokens (child 0 is the argmax, so the chain
    path is always a subtree and greedy tree output is token-identical to
    greedy chain); sampled requests draw i.i.d. children from the
    request's draft key stream indexed by ``tree_draws`` (the with-
    replacement draws the tree rejection rule in core/speculative.py is
    exact for) and stash the logits row for the accept rule.  Mutates the
    request's tree phase state in place; when the budget is exhausted
    before any child lands, stamps ``tree_depth`` to ``tree_dl`` so the
    tree reads as full."""
    parents = req.tree_parents
    depths = tree_depths(parents, len(parents) + 1)
    d = req.tree_depth
    if d == 0:
        frontier = [0]
    else:
        frontier = [1 + i for i in range(len(parents)) if depths[1 + i] == d]
    sp = req.sampling
    grew = False
    for slot in frontier:
        budget = cfg.tree_budget - len(req.tree_nodes)
        if budget <= 0:
            break
        row = logits[slot]
        # draft top-1 probability (softmax max) — the branch cue
        conf = 1.0 / float(
            np.exp(row.astype(np.float64) - float(row.max())).sum()
        )
        k = (
            cfg.spec_branches
            if conf < cfg.branch_threshold and budget >= cfg.spec_branches
            else 1
        )
        if sp.greedy:
            toks = topk_tokens_host(row, k)
        else:
            toks = [
                int(sample_token_host(
                    req.draft_key(req.tree_draws + i), row,
                    sp.temperature, sp.top_k, sp.top_p,
                ))
                for i in range(k)
            ]
            req.tree_draws += k
            req.tree_q[slot] = row.copy()
        for t in toks:
            req.tree_parents.append(slot - 1)
            req.tree_nodes.append(int(t))
        grew = True
    req.tree_depth = d + 1 if grew else req.tree_dl


def _tree_window_rows(req, width: int):
    """(tokens, positions, mask) window rows for one request's tree: slot 0
    re-feeds the committed tip at depth 0, slot 1+i holds drafted node i at
    its tree depth; the ancestor mask keeps padded slots self-visible so
    their (overwritten-later) softmax stays finite."""
    toks = np.zeros((width,), np.int32)
    toks[0] = req.last_tok
    n = len(req.tree_nodes)
    if n:
        toks[1: 1 + n] = req.tree_nodes
    return (
        toks,
        tree_depths(req.tree_parents, width),
        tree_ancestor_mask(req.tree_parents, width),
    )


class _TableSet:
    """Host mirror of one pool's per-slot page tables / lengths.

    Page tables only change at admission/retirement (pages are backed
    eagerly, so a request's table is stable for its whole lifetime);
    lengths change every round.  Both are O(B) int32 uploads — the point of
    the device-resident design is that these tiny tables are ALL that
    crosses the host boundary per round.  `cap_tokens` (the engine's
    max_model_len, NOT s_max) sizes the table width, which in turn bounds
    the attention span the paged forward touches."""

    def __init__(self, max_batch: int, pool: PagedKVPool, cap_tokens: int):
        self.max_pages = pages_for(cap_tokens, pool.page_size)
        self.scratch = pool.num_pages  # device arrays have one extra page
        self.table = np.full((max_batch, self.max_pages), self.scratch, np.int32)
        self.lengths = np.zeros((max_batch,), np.int32)
        self._table_dev = None

    def set_row(self, slot: int, seq) -> None:
        row = self.table[slot]
        row[:] = self.scratch
        row[: len(seq.pages)] = seq.pages
        self._table_dev = None

    def clear_row(self, slot: int) -> None:
        self.table[slot] = self.scratch
        self._table_dev = None

    def load(self, rows):
        """rows: iterable of (slot, PagedSequence) -> (table, lengths) dev.
        Blocks until the uploads land so the caller's timing is comparable
        to the host baseline's blocking kv_copy_s."""
        self.lengths[:] = 0
        for slot, seq in rows:
            self.lengths[slot] = seq.length
        return self.table_dev(), jax.block_until_ready(jnp.asarray(self.lengths))

    def table_dev(self):
        """The cached device page table alone (fused PAR slots build their
        per-slot lengths/masks themselves; the table row for every active
        request is lifetime-stable, so one upload serves the whole step)."""
        if self._table_dev is None:
            self._table_dev = jax.block_until_ready(jnp.asarray(self.table))
        return self._table_dev


# ---------------------------------------------------------------------------
# The stepwise Engine
# ---------------------------------------------------------------------------


class Engine:
    """Continuous-batching speculative-decoding engine over device-resident
    paged KV pools.

    Lifecycle::

        eng = Engine(target, draft, EngineConfig(max_batch=4))
        rid = eng.add_request(prompt, SamplingParams(max_tokens=32))
        while eng.has_unfinished():
            for out in eng.step():      # one batched SD round
                stream(out.new_token_ids)
        tokens = eng.output_tokens(rid)

    ``add_request`` is admissible at any time — between ``step()`` calls a
    new request joins the queue and is prefilled/scheduled on the next step
    without draining the active batch.  ``abort`` retires a request
    immediately and returns its pool pages.  ``run`` is the convenience
    drain loop the deprecated ``serve_batch`` wrapper uses.

    Greedy requests are bit-identical per request to the single-request
    dense-cache reference; sampled requests (``temperature > 0``) follow
    the lossless rejection-sampling rule with per-request key streams.
    """

    def __init__(
        self,
        target: ServingModel,
        draft: ServingModel,
        config: Optional[EngineConfig] = None,
        detokenize: Optional[Callable[[int], str]] = None,
        metrics: Optional[MetricsRegistry] = None,
        trace: Optional[Tracer] = None,
    ):
        cfg = config if config is not None else EngineConfig()
        if cfg.paged_attn_impl is not None:
            impl = resolve_paged_attn_impl(cfg.paged_attn_impl)
            target = dataclasses.replace(target, paged_attn_impl=impl)
            draft = dataclasses.replace(draft, paged_attn_impl=impl)
        self.cfg = cfg
        self.target = target
        self.draft = draft
        self.max_model_len = (
            cfg.max_model_len
            if cfg.max_model_len is not None
            else min(target.s_max, draft.s_max)
        )
        for model in (target, draft):
            if self.max_model_len > model.s_max:
                raise ValueError(
                    f"max_model_len {self.max_model_len} exceeds "
                    f"s_max={model.s_max} of {model.cfg.name}"
                )

        # host pools are pure allocators; the KV bytes live in device arrays.
        # One allocator serves every storage KIND: under kv_quant="mixed"
        # both a dense and an int8 device store back the SAME page ids, a
        # request reads/writes only the store of its resolved kind, and the
        # wrong-kind storage of its pages simply holds unread garbage — so
        # admission, page tables, and rewind bookkeeping stay kind-agnostic.
        worst = [self.max_model_len] * cfg.max_batch
        self._t_pool = _pool_for(target, cfg, worst, alloc_storage=False)
        self._d_pool = _pool_for(draft, cfg, worst, alloc_storage=False)
        self._kinds: Tuple[str, ...] = cfg.kv_kinds
        self._t_store = {
            k: device_pool_store(self._t_pool, kv_quant=k) for k in self._kinds
        }
        self._d_store = {
            k: device_pool_store(self._d_pool, kv_quant=k) for k in self._kinds
        }

        # copy-on-write prefix cache: a refcounted radix tree over prompt
        # blocks that maps cache hits as read-only shared pages in BOTH
        # pools, so the shared span's prefill is skipped entirely
        # (serving/prefix_cache.py; admission integration lives in the
        # batcher, the hit-path prefill in _prefill_into)
        self._prefix: Optional[PrefixCache] = None
        if cfg.prefix_cache:
            self._prefix = PrefixCache(
                {"target": self._t_pool, "draft": self._d_pool},
                cfg.page_size,
            )

        # observability: one shared registry — the batcher's fused/finish
        # counters, the engine's latency histograms, and the server's
        # GET /metrics all read and write the same families.  The tracer
        # defaults to the no-op NULL_TRACER; when a real one is passed the
        # engine adopts its clock so request timestamps and spans share a
        # timebase.  All instrumentation wraps dispatch boundaries the hot
        # loop already synchronizes at — no block_until_ready is added.
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = trace if trace is not None else NULL_TRACER
        if self.tracer.enabled:
            self._now = self.tracer.now
        else:
            _t0 = time.perf_counter()
            self._now = lambda: time.perf_counter() - _t0
        self._init_metrics()

        # flight recorder: bounded ring of per-round records with anomaly
        # triggers (serving/flight_recorder.py); fed at every round-wall
        # site INCLUDING empty rounds (pool exhaustion shows up as rounds
        # that admit and run nothing)
        self.flight = FlightRecorder(
            cfg.flight_ring, metrics=self.metrics, tracer=self.tracer,
            dump_dir=cfg.flight_dump_dir,
        )

        self._batcher = ContinuousBatcher(
            cfg, self._t_pool, self._d_pool,
            t_layers=target.cfg.n_layers, d_layers=draft.cfg.n_layers,
            t_costs=_wdos_costs(target.cfg), d_costs=_wdos_costs(draft.cfg),
            metrics=self.metrics,
            prefix_cache=self._prefix,
        )
        self._t_iface, self._d_iface = make_interface(target), make_interface(draft)
        self._t_step, self._d_step = _make_paged_step(target), _make_paged_step(draft)
        if cfg.par_mode == "wdos":
            self._fused_step = _make_fused_step(target, draft)
            self._draft_slot_step = _make_masked_draft_step(draft)
        if cfg.spec_mode == "tree":
            self._t_tree_step = _make_tree_step(target)
            self._d_tree_step = _make_tree_step(draft)
            if cfg.par_mode == "wdos":
                self._fused_tree_step = _make_fused_tree_step(target, draft)
                self._draft_tree_slot_step = _make_masked_tree_draft_step(draft)
        self._t_tables = _TableSet(cfg.max_batch, self._t_pool, self.max_model_len)
        self._d_tables = _TableSet(cfg.max_batch, self._d_pool, self.max_model_len)
        self._requests: Dict[int, Request] = {}
        self._next_id = 0
        # token -> text for SamplingParams.stop matching (and the HTTP
        # server's text fields); defaults to the toy decimal renderer
        self._detokenize = (
            detokenize if detokenize is not None else default_detokenize
        )

    # -- observability -------------------------------------------------------

    def _init_metrics(self) -> None:
        """Register the engine's metric families (docs/OBSERVABILITY.md is
        the catalog).  Registration is idempotent, so sharing one registry
        across engines is safe."""
        m = self.metrics
        self._m_submitted = m.counter(
            "requests_submitted_total", "Requests accepted by add_request"
        )
        self._m_steps = m.counter("steps_total", "Engine steps executed")
        self._m_emitted = m.counter(
            "tokens_emitted_total", "Tokens delivered to consumers"
        )
        self._m_drafted = m.counter(
            "tokens_drafted_total", "Draft tokens proposed"
        )
        self._m_accepted = m.counter(
            "tokens_accepted_total", "Draft tokens accepted by verification"
        )
        self._m_table_upload = m.counter(
            "table_upload_seconds_total",
            "Host seconds uploading page tables / lengths (the only "
            "per-round host->device traffic on the paged path)",
        )
        self._m_accept_rate = m.gauge(
            "acceptance_rate", "Cumulative accepted/drafted fraction"
        )
        self._m_queue_depth = m.gauge(
            "queue_depth", "Requests waiting for admission (QUEUED)"
        )
        self._m_active = m.gauge(
            "active_requests", "Requests holding a decode slot"
        )
        self._m_pool_pages = m.gauge(
            "pool_pages", "Paged-KV pool residency", ("pool", "state")
        )
        self._m_kv_bytes = m.gauge(
            "kv_bytes_total",
            "Bytes resident in allocated paged-KV pages (per storage "
            "dtype; int8 includes its f32 per-slot scales)",
            ("pool", "dtype"),
        )
        self._m_kv_bytes_per_token = m.gauge(
            "kv_bytes_per_token",
            "K+V bytes one cached token occupies (per storage dtype)",
            ("pool", "dtype"),
        )
        self._m_ttft = m.histogram(
            "ttft_seconds", "Submit -> first delivered token",
            buckets=LATENCY_BUCKETS,
        )
        self._m_itl = m.histogram(
            "itl_seconds",
            "Gap between successive token deliveries of one request "
            "(round granularity: one observation per non-empty delta)",
            buckets=LATENCY_BUCKETS,
        )
        self._m_round_wall = m.histogram(
            "round_wall_seconds", "Wall time of one engine step",
            buckets=LATENCY_BUCKETS,
        )
        self._m_admission_wait = m.histogram(
            "admission_wait_seconds", "Submit -> admission into a decode slot",
            buckets=LATENCY_BUCKETS,
        )
        self._m_round_accept = m.histogram(
            "round_acceptance", "Per-round accepted/drafted fraction",
            buckets=RATIO_BUCKETS,
        )
        # tree-speculation families (live under spec_mode="tree";
        # registered unconditionally — and materialized at zero — so the
        # catalog and the /metrics scrape are stable on chain engines)
        self._m_tree_nodes = m.counter(
            "tree_nodes_total",
            "Draft-tree nodes proposed for verification (tree rounds)",
        )
        self._m_tree_branches = m.counter(
            "tree_branches_total",
            "Extra branches forked beyond a chain: fan-out minus one, "
            "summed over branching nodes",
        )
        self._m_tree_depth = m.histogram(
            "tree_accept_depth",
            "Depth of the accepted root path per tree round (committed "
            "draft tokens; the bonus token is not counted)",
            buckets=(0.0, 1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0),
        )
        self._m_tree_compactions = m.counter(
            "tree_compactions_total",
            "Device compaction dispatches relocating an accepted "
            "non-leftmost tree path's KV into chain order",
        )
        for fam in (self._m_tree_nodes, self._m_tree_branches,
                    self._m_tree_compactions):
            fam.inc(0)
        # prefix-cache families (registered unconditionally so the catalog
        # is stable; they stay at zero when EngineConfig.prefix_cache=False)
        self._m_prefix_hit_rate = m.gauge(
            "prefix_hit_rate",
            "Prefix-cache hit fraction over admission lookups",
        )
        self._m_shared_pages = m.gauge(
            "shared_pages",
            "Prefix-cache page residency: state='shared' counts pool pages "
            "mapped by more than one holder, state='cached' the pages "
            "pinned by the radix tree",
            ("pool", "state"),
        )
        self._m_tokens_saved = m.counter(
            "prefill_tokens_saved_total",
            "Prompt rows whose prefill was skipped via shared prefix pages",
        )
        self._m_prefix_cow = m.counter(
            "prefix_cow_total",
            "Copy-on-write privatizations of a partially-shared prefix page",
        )
        # the path each BVQ model's linears resolved to (fixed per engine)
        bvq_impl = m.gauge(
            "bvq_linear_impl",
            "BVQ linears per layer on each path of ops.bvq_linear: the "
            "Pallas kernel or the jnp reference",
            ("model", "impl"),
        )
        # each model's linear RoPE factor (ModelConfig.rope_scaling; 1 = none)
        rope_factor = m.gauge(
            "rope_scaling_factor",
            "Linear RoPE scaling factor each model divides its positions by",
            ("model",),
        )
        for role, model in (("target", self.target), ("draft", self.draft)):
            rope_factor.labels(model=role).set(model.cfg.rope_scaling)
            if model.mode == "bvq" and model.params is not None:
                counts = qlm.bvq_impl_counts(model.params, model.use_pallas)
                for impl, n in counts.items():
                    bvq_impl.labels(model=role, impl=impl).set(n)

    def _refresh_gauges(self) -> None:
        """Republish the level-style series (queue depth, active slots,
        pool residency, cumulative acceptance) — called at step boundaries
        and before a stats snapshot, never inside a dispatch."""
        self._m_queue_depth.set(self.queue_depth())
        self._m_active.set(self.num_active())
        for name, pool in (("target", self._t_pool), ("draft", self._d_pool)):
            st = pool.stats()
            g = self._m_pool_pages
            g.labels(pool=name, state="used").set(st.used_pages)
            g.labels(pool=name, state="reserved").set(st.reserved_pages)
            g.labels(pool=name, state="free").set(st.free_pages)
            used_tokens = st.used_pages * pool.page_size
            for dt, bpt in pool.bytes_per_token_by_kind().items():
                self._m_kv_bytes_per_token.labels(pool=name, dtype=dt).set(bpt)
                self._m_kv_bytes.labels(pool=name, dtype=dt).set(
                    bpt * used_tokens
                )
        drafted = self._m_drafted.value()
        if drafted:
            self._m_accept_rate.set(self._m_accepted.value() / drafted)
        if self._prefix is not None:
            self._m_prefix_hit_rate.set(self._prefix.hit_rate)
            for name, pool in (
                ("target", self._t_pool), ("draft", self._d_pool)
            ):
                g = self._m_shared_pages
                g.labels(pool=name, state="shared").set(pool.shared_page_count)
                g.labels(pool=name, state="cached").set(self._prefix.node_count)

    def stats_snapshot(self) -> dict:
        """One consistent, JSON-safe stats view, built in a single pass on
        the calling thread.  The AsyncEngine worker publishes this object
        atomically after each step, so ``/stats`` reports queue depth,
        active-vs-queued counts, and pool residency from the SAME moment
        instead of separately-raced reads."""
        self._refresh_gauges()
        t_stats, d_stats = self.pool_stats()
        b = self._batcher
        snap = {
            "queued": self.queue_depth(),
            "active": self.num_active(),
            "max_batch": self.cfg.max_batch,
            "par_mode": self.cfg.par_mode,
            "kv_quant": self.cfg.kv_quant,
            "steps": b.step_count,
            "rounds": b.rounds,
            "finished_requests": b.finished_count,
            "emitted_tokens": b.finished_emitted,
            "acceptance_rate": b.finished_accepted / max(b.finished_drafted, 1),
            "target_pool": dataclasses.asdict(t_stats),
            "draft_pool": dataclasses.asdict(d_stats),
        }
        fused = b.fused_summary()
        if fused is not None:
            snap["fused"] = fused
        if self._prefix is not None:
            snap["prefix_cache"] = self._prefix.stats()
        return snap

    # -- request lifecycle ---------------------------------------------------

    def add_request(
        self,
        prompt,
        sampling_params: Optional[SamplingParams] = None,
        sink: Optional[Callable[[int], None]] = None,
    ) -> int:
        """Submit a prompt; returns its request id.  Admissible at any time
        — the batcher prefills it on the next ``step()`` once a slot and
        pages are free, without draining the active batch."""
        sp = sampling_params if sampling_params is not None else SamplingParams()
        req = Request(
            rid=self._next_id,
            prompt=np.asarray(prompt).reshape(-1),
            max_new_tokens=sp.max_tokens,
            sink=sink,
            sampling=sp,
            detokenize=self._detokenize,
            # raises ValueError when the request pins a storage this engine
            # did not allocate (e.g. kv_quant="int8" on a "none" engine)
            kv_kind=self.cfg.resolve_kv_quant(sp.kv_quant),
        )
        peak = req.peak_cache_len(self.cfg.spec_window)
        if peak > self.max_model_len:
            raise ValueError(
                f"request peak cache length {peak} (prompt {req.prompt.shape[0]} "
                f"+ max_tokens {sp.max_tokens} + speculation window "
                f"{self.cfg.spec_window}) exceeds max_model_len={self.max_model_len}"
            )
        self._next_id += 1
        self._requests[req.rid] = req
        self._batcher.submit(req)
        req.submit_ts = self._now()
        self._m_submitted.inc()
        self.tracer.instant("engine", "submit", cat="lifecycle", rid=req.rid)
        return req.rid

    def abort(self, request_id: int) -> bool:
        """Cancel a request: a queued one is dropped, an active one retires
        immediately and its pool pages return to the free list (un-blocking
        queued admissions on the next step).  Returns False if the id is
        unknown or already finished."""
        req = self._requests.get(request_id)
        if req is None or req.state is RequestState.FINISHED:
            return False
        if req.state is RequestState.QUEUED:
            return self._batcher.cancel_queued(request_id) is not None
        slot = self._batcher.slot_of(request_id)
        assert slot is not None, "active request without a slot"
        self._t_tables.clear_row(slot)
        self._d_tables.clear_row(slot)
        self._batcher.retire(slot, reason="abort")
        return True

    def release_request(self, request_id: int) -> bool:
        """Drop a FINISHED request's bookkeeping (its ``Request`` object,
        including the output buffer).  A run-to-drain caller never needs
        this — ``output_tokens``/``request`` stay valid until released —
        but a long-lived server must release retired requests or the
        engine's request map grows without bound (the batcher's summary
        counters are aggregates and survive the release)."""
        req = self._requests.get(request_id)
        if req is None or req.state is not RequestState.FINISHED:
            return False
        del self._requests[request_id]
        return True

    def has_unfinished(self) -> bool:
        return not self._batcher.all_done()

    def queue_depth(self) -> int:
        """Requests waiting for admission (QUEUED, not yet in a batch slot)."""
        return len(self._batcher.queue)

    def num_active(self) -> int:
        """Requests currently holding a decode slot."""
        return sum(1 for r in self._batcher.slots if r is not None)

    def request(self, request_id: int) -> Request:
        return self._requests[request_id]

    def output_tokens(self, request_id: int) -> jnp.ndarray:
        req = self._requests[request_id]
        return jnp.asarray(req.out[: req.max_new_tokens], jnp.int32)

    def pool_stats(self):
        """(target PoolStats, draft PoolStats) — page residency right now."""
        return self._t_pool.stats(), self._d_pool.stats()

    # -- flight-recorder feed ------------------------------------------------

    def _flight_base(self) -> Tuple[float, float, int]:
        """Counter values at round start, so the round record carries
        per-round DELTAS (the emitted counter increments after the round
        wall is taken, so drafted/accepted/admitted are the honest
        per-round signals)."""
        return (
            self._m_drafted.value(),
            self._m_accepted.value(),
            self._batcher.admitted,
        )

    def _flight_round(self, base, t_step: float, t_end: float, rows: int,
                      mode: str) -> None:
        """Append one round record to the flight recorder (including empty
        rounds: pool exhaustion and admission stalls MANIFEST as rounds
        with queued work and zero rows)."""
        if not self.flight.enabled:
            return
        d0, a0, adm0 = base
        self.flight.record({
            "round": self._batcher.step_count,
            "mode": mode,
            "rows": rows,
            "wall_s": t_end - t_step,
            "drafted": self._m_drafted.value() - d0,
            "accepted": self._m_accepted.value() - a0,
            "admitted": self._batcher.admitted - adm0,
            "queued": self.queue_depth(),
            "active": self.num_active(),
            "free_pages": {
                "target": self._t_pool.free_pages,
                "draft": self._d_pool.free_pages,
            },
            "t": t_end,
        })

    def flight_snapshot(self, dump: bool = False) -> dict:
        """The flight recorder's JSON-safe view (``GET /debug/flight``);
        ``dump=True`` additionally captures the trace tail and writes a
        postmortem file when a dump dir is configured."""
        return self.flight.dump() if dump else self.flight.snapshot()

    # -- the stepwise round --------------------------------------------------

    def _kvq_mask(self, active):
        """(B,) bool device mask — True where the row's KV is int8 — or
        ``None`` on single-kind engines (which then dispatch exactly the
        pre-compression program: no mask, no merge, bit-identical)."""
        if len(self._kinds) == 1:
            return None
        m = np.zeros((self.cfg.max_batch,), bool)
        for slot, req in active:
            m[slot] = req.kv_kind == "int8"
        return jnp.asarray(m)

    def _dispatch(self, step_fn, params, tokens, stores, table,
                  lengths, kvq_dev, *extra):
        """One logical batched forward over every storage kind.

        Single-kind engines run one dispatch.  Mixed engines run the step
        once per store and merge logits row-wise by kind: a row's writes
        land only in its OWN pages of each store (the page table confines
        them), and a row only ever READS the store of its kind, so the
        wrong-kind dispatch leaves unread garbage — never corruption.
        ``extra`` forwards step-specific trailing operands (the tree
        steps' win_pos / tree_mask)."""
        if kvq_dev is None:
            k0 = self._kinds[0]
            logits, stores[k0] = step_fn(
                params, tokens, stores[k0], table, lengths, *extra,
            )
            return logits
        outs = {}
        for k in self._kinds:
            outs[k], stores[k] = step_fn(
                params, tokens, stores[k], table, lengths, *extra,
            )
        return jnp.where(kvq_dev[:, None, None], outs["int8"], outs["none"])

    def _prefill_into(self, req: Request, model: ServingModel,
                      iface: LMInterface, seq, store, tables, slot,
                      role: str):
        """Prefill one request into one pool (target or draft).

        Miss path: the same jitted prefill program as the single-request
        path => bitwise identical prefix KV; the cache rows scatter
        device->device into the request's (eagerly backed, lifetime-stable)
        pages — only the store of the request's resolved kind (int8 rows
        quantize inside the scatter; the wrong-kind storage of these pages
        is never read).

        Prefix-cache hit path (req.prefix_match covers ``m`` tokens): the
        shared pages are already in the page table (mapped at admission) and
        hold exactly the KV a full prefill would have written (prefix rows
        are bitwise invariant to what follows them).  A partially-shared
        last page is copy-on-written FIRST — value and scale arrays of the
        request's store, device-side — so the shared original is never
        written; then the unshared tail [m, plen-1) runs as a dense
        ``extend`` over a cache seeded with the node mirrors' FP prefix
        (bitwise equal to full-prefill tail KV) and scatters with
        ``start=m``, leaving the shared rows untouched.  The request's
        first write lands at ``plen-1 >= m``, always in a private page, so
        speculative rewind (bounded below by committed-1) can never touch a
        shared page.

        Returns ``(store, dense_kv)`` where dense_kv = (k, v) host arrays
        covering rows [0, plen-1) for radix-tree donation, or None when the
        forward was skipped entirely (full hit — every block is cached)."""
        plen = req.prompt.shape[0]
        match = req.prefix_match
        m = match.tokens_matched if match is not None else 0
        seq.ensure_backed(seq.capacity_pages * seq.pool.page_size)
        if seq.needs_cow:
            src, dst = seq.cow_last_shared()
            store = _copy_page(store, src, dst)
            if self._prefix is not None:
                self._prefix.cow_copies += 1
            self._m_prefix_cow.inc()
        tables.set_row(slot, seq)
        if m >= plen - 1:
            # full hit: rows [0, plen-1) are all resident in shared pages
            # (the COW above privatized the write frontier); no forward runs
            return store, None
        if m > 0:
            k_pre, v_pre = match.prefix_kv(role)
            cache = lm.init_cache(
                model.cfg, 1, model.s_max,
                tp=model.mesh.shape["model"] if model.mesh else 1,
            )
            attn = dict(cache["attn"])
            attn["k"] = attn["k"].at[:, 0, :m].set(
                jnp.asarray(k_pre, attn["k"].dtype)
            )
            attn["v"] = attn["v"].at[:, 0, :m].set(
                jnp.asarray(v_pre, attn["v"].dtype)
            )
            cache = dict(cache)
            cache["attn"] = attn
            cache["length"] = jnp.asarray(m, jnp.int32)
            # pad the unshared tail to a power-of-two bucket so the extend
            # compiles once per bucket, not once per tail length (causal
            # attention: pad rows sit AFTER the tail, so tail rows are
            # bitwise unaffected; the scatter's [start, n) bound and the
            # mirror slice below both ignore the pad rows)
            tail = req.prompt[m:-1]
            width = 1 << (len(tail) - 1).bit_length()
            # steady-state hits leave tails shorter than one page (only full
            # blocks are cached); floor the bucket at page_size so they all
            # share ONE compiled extend instead of one per {1, 2, 4, ...}
            width = min(max(width, seq.pool.page_size), model.s_max - m)
            padded = np.zeros(width, np.int32)
            padded[: len(tail)] = tail
            _, cache = iface.extend(
                model.params, jnp.asarray(padded[None]), cache
            )
        else:
            _, cache = iface.prefill(
                model.params, jnp.asarray(req.prompt[None, :-1])
            )
        store = _scatter_prefill(
            store,
            cache["attn"]["k"][:, 0], cache["attn"]["v"][:, 0],
            jnp.asarray(tables.table[slot]), plen - 1, m,
        )
        seq.advance(plen - 1 - m)
        dense = None
        if self._prefix is not None:
            upto = plen - 1
            ps = seq.pool.page_size
            # the full-block walk guarantees nodes for blocks [0, m // ps);
            # when that covers every full block of the prompt, insert()
            # would be a no-op — skip the device->host KV pull entirely
            # (the steady-state hit path: only the sub-page tail ran)
            if m // ps < upto // ps:
                dense = (
                    np.asarray(cache["attn"]["k"][:, 0, :upto]),
                    np.asarray(cache["attn"]["v"][:, 0, :upto]),
                )
        return store, dense

    def _admit(self, rnd: int) -> None:
        """Admit whatever fits and prefill it into both pools.  Profiler
        spans (round ``rnd``): ``engine.admit`` over the batcher's
        admission and page allocation, then per request one
        ``engine.prefill`` per model and an ``engine.admit`` over the
        request's own bookkeeping (prefix-cache donation)."""
        span = self.tracer.span
        with span("engine", "admit", round=rnd):
            admitted = self._batcher.admit()
        for slot, req in admitted:
            t_adm = self._now()
            req.admit_ts = t_adm
            if req.submit_ts is not None:
                self._m_admission_wait.observe(t_adm - req.submit_ts)
            self.tracer.instant(
                f"row{slot}", "admit", cat="lifecycle", rid=req.rid
            )
            kind = req.kv_kind
            m = req.prefix_match.tokens_matched if req.prefix_match else 0
            tokens = max(req.prompt.shape[0] - 1 - m, 0)
            with span("engine", "prefill", round=rnd, model="target",
                      rid=req.rid, tokens=tokens):
                self._t_store[kind], t_kv = self._prefill_into(
                    req, self.target, self._t_iface, req.t_seq,
                    self._t_store[kind], self._t_tables, slot, "target",
                )
            with span("engine", "prefill", round=rnd, model="draft",
                      rid=req.rid, tokens=tokens):
                self._d_store[kind], d_kv = self._prefill_into(
                    req, self.draft, self._d_iface, req.d_seq,
                    self._d_store[kind], self._d_tables, slot, "draft",
                )
            with span("engine", "admit", round=rnd, row=slot, rid=req.rid):
                if self._prefix is not None:
                    if req.prefix_match is not None:
                        self._m_tokens_saved.inc(m)
                    if t_kv is not None and d_kv is not None:
                        # donate the freshly prefilled FULL blocks: the tree
                        # pins the pages (pool incref) and mirrors the dense
                        # FP rows for future hits' seeded tail prefills
                        self._prefix.insert(
                            req.prompt, kind,
                            {"target": req.t_seq.pages, "draft": req.d_seq.pages},
                            {"target": t_kv, "draft": d_kv},
                            upto=req.prompt.shape[0] - 1,
                        )
                req.state = RequestState.DECODE
            self.tracer.rec(
                f"row{slot}", "prefill", t_adm, self._now(),
                cat="prefill", rid=req.rid,
            )

    def step(self) -> List[RequestOutput]:
        """Admit what fits, then run ONE engine round over every active
        request — a two-phase draft-all-then-verify-all round
        (``par_mode="off"``) or a horizon of WDOS-planned fused PAR
        dispatches (``par_mode="wdos"``).  Returns a ``RequestOutput`` per
        request that progressed, with the incrementally verified tokens.
        The two modes emit bit-identical tokens; "wdos" may commit more
        than one window per request per round.

        Under ``spec_mode="tree"`` the same two schedulers run the
        tree-speculation round instead: top-k branch drafting into a
        fixed-width window, one causally-tree-masked verify dispatch, and
        the lossless multi-branch accept walk (core/speculative.py)."""
        if self.cfg.spec_mode == "tree":
            if self.cfg.par_mode == "wdos":
                return self._step_fused_tree()
            return self._step_two_phase_tree()
        if self.cfg.par_mode == "wdos":
            return self._step_fused()
        return self._step_two_phase()

    def _step_two_phase(self) -> List[RequestOutput]:
        """Two-phase chain round.  The engine thread's time is split into
        non-overlapping profiler leaf spans, each with ``round``: admit,
        prefill, table_upload, then per draft step ``draft_dispatch`` /
        ``draft_pull`` / ``draft_sample``, then ``verify_dispatch``,
        ``verify_pull``, ``accept`` and ``emit``."""
        cfg = self.cfg
        span = self.tracer.span
        rnd = self._batcher.step_count + 1
        t_step = self._now()
        fb = self._flight_base()
        self._admit(rnd)
        active = self._batcher.active()
        if not active:
            self._batcher.step_count += 1
            self._m_steps.inc()
            self._refresh_gauges()
            self._flight_round(fb, t_step, self._now(), 0, "two_phase")
            return []

        dls = {slot: req.controller.draft_len() for slot, req in active}
        modes = {slot: req.controller.mode for slot, req in active}
        round_dl = max(dls.values())
        any_sampled = any(not req.sampling.greedy for _, req in active)
        kvq_dev = self._kvq_mask(active)

        t0 = self._now()
        with span("engine", "table_upload", round=rnd):
            d_table, d_len0 = self._d_tables.load((s, r.d_seq) for s, r in active)
            t_table, t_len0 = self._t_tables.load((s, r.t_seq) for s, r in active)
            t_draft0 = self._now()
            cur = np.zeros((cfg.max_batch,), np.int32)
            for slot, req in active:
                cur[slot] = req.last_tok
            cur_dev = jnp.asarray(cur)
        self._m_table_upload.inc(t_draft0 - t0)

        # ---- draft phase: round_dl proposal steps + 1 straggler step, all
        # batched; the draft pool stays on device across the loop.  Greedy
        # batches keep the next-token argmax on device; once any active row
        # samples, each proposal hops through the host so every sampled row
        # can draw from its own (temperature/top-k, per-request-key) draft
        # distribution — greedy rows still take the argmax (np and jnp share
        # the first-max tie rule, so the round stays bit-identical for them).
        draft_cols: List[Any] = []
        q_cols: List[np.ndarray] = []  # per-position draft logits (sampled rounds)
        for j in range(round_dl + 1):
            with span("engine", "draft_dispatch", round=rnd, j=j):
                logits = self._dispatch(
                    self._d_step, self.draft.params, cur_dev[:, None],
                    self._d_store, d_table, d_len0 + j, kvq_dev,
                )
                if j < round_dl and not any_sampled:
                    cur_dev = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
                    draft_cols.append(cur_dev)
            if j < round_dl and any_sampled:
                with span("engine", "draft_pull", round=rnd, j=j,
                          bytes=logits.nbytes // logits.shape[1]):
                    last = np.asarray(logits[:, -1, :])
                with span("engine", "draft_sample", round=rnd, j=j,
                          rows=len(active)):
                    q_cols.append(last)
                    nxt = np.argmax(last, axis=-1).astype(np.int32)
                    for slot, req in active:
                        sp = req.sampling
                        if not sp.greedy:
                            nxt[slot] = sample_token_host(
                                req.draft_key(j), last[slot],
                                sp.temperature, sp.top_k, sp.top_p,
                            )
                    draft_cols.append(nxt)
                    cur_dev = jnp.asarray(nxt)
            # j == round_dl: straggler — feeds d_{round_dl-1}, completing the
            # cache for fully-accepted rows; over-written rows rewind it away
        if any_sampled:
            drafts = np.stack(draft_cols, axis=1)  # (B, round_dl)
        else:
            with span("engine", "draft_pull", round=rnd, j=round_dl,
                      bytes=4 * cfg.max_batch * round_dl):
                drafts = np.asarray(jnp.stack(draft_cols, axis=1))
        t_verify0 = self._now()
        self.tracer.rec(
            "engine", "draft_phase", t_draft0, t_verify0,
            cat="phase", rows=len(active), dl=round_dl,
        )

        # ---- verify phase: one batched pass scoring [last_tok, drafts...]
        with span("engine", "verify_dispatch", round=rnd):
            window = np.zeros((cfg.max_batch, round_dl + 1), np.int32)
            window[:, 0] = cur
            window[:, 1:] = drafts
            v_logits = self._dispatch(
                self._t_step, self.target.params, jnp.asarray(window),
                self._t_store, t_table, t_len0, kvq_dev,
            )
        with span("engine", "verify_pull", round=rnd, bytes=v_logits.nbytes):
            p_logits = np.asarray(v_logits)  # (B, round_dl+1, V)
        self.tracer.rec(
            "engine", "verify_phase", t_verify0, self._now(),
            cat="phase", rows=len(active),
        )

        # ---- per-request accept / commit: a pure length update per row —
        # the KV was written in place by the steps above, and rewind just
        # drops the tail (stale pool slots are masked, then overwritten)
        with span("engine", "accept", round=rnd, rows=len(active)):
            work = []
            progressed: List[Request] = []
            for slot, req in active:
                dl = dls[slot]
                sp = req.sampling
                if sp.greedy:
                    new, n_acc = speculative_accept_greedy_host(
                        drafts[slot], p_logits[slot], dl
                    )
                else:
                    q_logits = np.stack([q_cols[j][slot] for j in range(dl)])
                    new, n_acc = speculative_sample_host(
                        req.accept_key(), drafts[slot], p_logits[slot],
                        q_logits, dl, sp.temperature, sp.top_k, sp.top_p,
                    )
                req.commit(new)
                req.record_round(modes[slot], dl, n_acc, len(new))
                req.rounds += 1
                req.drafted += dl
                req.accepted += n_acc
                req.controller.observe(n_acc, dl)
                self._m_drafted.inc(dl)
                self._m_accepted.inc(n_acc)
                self._m_round_accept.observe(n_acc / dl if dl else 0.0)
                if self.tracer.enabled:
                    self.tracer.instant(
                        f"row{slot}", "commit", cat="commit",
                        rid=req.rid, drafted=dl, accepted=n_acc,
                    )
                work.append((req, dl))
                progressed.append(req)
                # both models wrote round_dl+1 positions; keep n_acc + 1
                # (draft invariant: cache == committed[:-1], incl. straggler)
                for seq in (req.t_seq, req.d_seq):
                    seq.advance(round_dl + 1)
                    seq.rewind(round_dl - n_acc, release_pages=False)
            self._batcher.model_round(work)
            self._retire_done(active)
            t_end = self._end_round(t_step, len(active), "off")
            self._flight_round(fb, t_step, t_end, len(active), "two_phase")
        return self._emit(rnd, progressed, t_end)

    def _retire_done(self, active) -> None:
        """Retire every finished request of ``active`` (end of a round)."""
        for slot, req in active:
            if req.done:
                self._t_tables.clear_row(slot)
                self._d_tables.clear_row(slot)
                if self.tracer.enabled:
                    self.tracer.instant(
                        f"row{slot}", "finish", cat="lifecycle",
                        rid=req.rid, reason=req.finish_reason or "length",
                    )
                self._batcher.retire(slot)

    def _end_round(self, t_step: float, rows: int, par_mode: str) -> float:
        """Close a round: count it, observe its wall (``round_wall_seconds``)
        and record its in-memory ``step#k`` span.  Returns the end time."""
        self._batcher.step_count += 1
        self._m_steps.inc()
        t_end = self._now()
        self._m_round_wall.observe(t_end - t_step)
        self.tracer.rec(
            "engine", f"step#{self._batcher.step_count}", t_step, t_end,
            cat="step", par_mode=par_mode, rows=rows,
        )
        self._refresh_gauges()
        return t_end

    def _emit(self, rnd: int, reqs, t_end: float) -> List[RequestOutput]:
        """The round's ``RequestOutput``s, under the ``engine.emit`` span;
        each carries the round's end on ``time.perf_counter``."""
        with self.tracer.span("engine", "emit", round=rnd):
            round_end = time.perf_counter()
            return [self._output_for(req, t_end, round_end) for req in reqs]

    # -- tree speculation (spec_mode="tree") ---------------------------------

    def _tree_verify_commit(self, slot, req, p_win, mode, dl, moves_t,
                            moves_d, work) -> int:
        """Accept/commit one verified tree row: walk the lossless
        multi-branch accept rule over the window logits (W, V), commit the
        accepted root path (+ the residual/bonus token), queue the KV
        compaction moves that relocate the path's BFS slots to the chain
        positions the committed sequence expects, and advance/rewind both
        sequences back to committed-1.  Returns the accepted count."""
        w = self.cfg.tree_budget + 1
        sp = req.sampling
        nodes, parents = req.tree_nodes, req.tree_parents
        if sp.greedy:
            new, path, n_acc = speculative_tree_accept_greedy_host(
                nodes, parents, p_win
            )
        else:
            q_win = np.zeros((w, p_win.shape[-1]), np.float32)
            for qslot, row in req.tree_q.items():
                q_win[qslot] = row
            new, path, n_acc = speculative_tree_sample_host(
                req.accept_key(), nodes, parents, p_win, q_win,
                sp.temperature, sp.top_k, sp.top_p,
            )
        drafted_n = len(nodes)
        req.commit(new)
        req.record_round(mode, dl, n_acc, len(new))
        req.rounds += 1
        req.drafted += drafted_n
        req.accepted += n_acc
        req.controller.observe(n_acc, dl)
        self._m_drafted.inc(drafted_n)
        self._m_accepted.inc(n_acc)
        self._m_round_accept.observe(n_acc / dl if dl else 0.0)
        self._m_tree_nodes.inc(drafted_n)
        # a chain of drafted_n nodes has drafted_n DISTINCT parents (each
        # node its own); every duplicate parent is one extra forked branch
        self._m_tree_branches.inc(drafted_n - len(set(parents)))
        self._m_tree_depth.observe(n_acc)
        if self.tracer.enabled:
            self.tracer.instant(
                f"row{slot}", "commit", cat="commit",
                rid=req.rid, drafted=drafted_n, accepted=n_acc,
            )
        work.append((req, dl))
        # the accepted path sits at BFS window slots base+1+path[i]; the
        # committed sequence needs its KV at the chain slots base+1+i.  A
        # leftmost path (always, at fan-out 1) is already in place.  RoPE
        # agrees by construction: path[i] is a depth-(i+1) node, encoded at
        # position base+1+i — exactly its destination slot.
        if path != list(range(n_acc)):
            for seq, mv in (
                (req.t_seq, moves_t[req.kv_kind]),
                (req.d_seq, moves_d[req.kv_kind]),
            ):
                base = seq.length
                src = seq.flat_slots(base + 1 + np.asarray(path, np.int64))
                dst = seq.flat_slots(
                    base + 1 + np.arange(n_acc, dtype=np.int64)
                )
                mv[0].extend(int(x) for x in src)
                mv[1].extend(int(x) for x in dst)
        # both models wrote the full W-wide window; keep n_acc + 1
        # (draft invariant: cache == committed[:-1], incl. straggler)
        for seq in (req.t_seq, req.d_seq):
            seq.advance(w)
            seq.rewind(w - 1 - n_acc, release_pages=False)
        req.clear_tree()
        return n_acc

    def _compact_pools(self, moves_t, moves_d) -> None:
        """Flush queued tree-compaction moves: one fixed-width
        ``_compact_slots`` dispatch per (pool, kind) that has any, padded
        with scratch-page self-copies so each compiles once.  Each
        dispatch counts in ``tree_compactions_total`` and the flush is the
        ``engine.compaction`` span (a tree round otherwise hides its KV
        relocation cost in the step gap)."""
        jobs = [
            (stores, pool, k, src, dst)
            for moves, stores, pool in (
                (moves_t, self._t_store, self._t_pool),
                (moves_d, self._d_store, self._d_pool),
            )
            for k, (src, dst) in moves.items() if src
        ]
        if not jobs:
            return
        cap = self.cfg.max_batch * self.cfg.tree_budget
        rnd = self._batcher.step_count + 1  # the round still running
        with self.tracer.span("engine", "compaction", cat="phase", round=rnd,
                              dispatches=len(jobs)):
            for stores, pool, k, src, dst in jobs:
                scratch = pool.num_pages * pool.page_size  # extra page's 1st slot
                s = np.full((cap,), scratch, np.int64)
                d = np.full((cap,), scratch, np.int64)
                s[: len(src)] = src
                d[: len(dst)] = dst
                stores[k] = _compact_slots(
                    stores[k], jnp.asarray(s), jnp.asarray(d)
                )
                self._m_tree_compactions.inc()

    def _step_two_phase_tree(self) -> List[RequestOutput]:
        """Tree-speculation round, two-phase schedule: grow every active
        request's draft tree one LEVEL per draft dispatch — the whole
        fixed-width window re-feeds at the SAME base length each time, so
        each level's frontier attends its ancestors through the tree mask
        and pad slots hold not-yet-read garbage — then verify every
        complete tree in ONE tree-masked target dispatch and walk the
        multi-branch accept rule per row.  Dispatch count matches a chain
        round of the same depth (round_depth + 1 draft + 1 verify).  The
        profiler leaf spans are the chain round's, plus
        ``engine.compaction``."""
        cfg = self.cfg
        span = self.tracer.span
        rnd = self._batcher.step_count + 1
        t_step = self._now()
        fb = self._flight_base()
        self._admit(rnd)
        active = self._batcher.active()
        if not active:
            self._batcher.step_count += 1
            self._m_steps.inc()
            self._refresh_gauges()
            self._flight_round(fb, t_step, self._now(), 0, "two_phase_tree")
            return []

        w = cfg.tree_budget + 1
        b = cfg.max_batch
        # the controller's draft length is the tree DEPTH target; the node
        # budget (tree_budget) caps how much width the fan-out rule may
        # spend along the way
        dls = {
            slot: min(req.controller.draft_len(), cfg.tree_budget)
            for slot, req in active
        }
        modes = {slot: req.controller.mode for slot, req in active}
        round_depth = max(dls.values())
        kvq_dev = self._kvq_mask(active)

        t0 = self._now()
        with span("engine", "table_upload", round=rnd):
            d_table, d_len0 = self._d_tables.load((s, r.d_seq) for s, r in active)
            t_table, t_len0 = self._t_tables.load((s, r.t_seq) for s, r in active)
        t_draft0 = self._now()
        self._m_table_upload.inc(t_draft0 - t0)

        for slot, req in active:
            req.begin_tree(dls[slot])

        diag = np.arange(w)

        def window_inputs():
            tok = np.zeros((b, w), np.int32)
            pos = np.zeros((b, w), np.int32)
            tm = np.zeros((b, w, w), np.float32)
            tm[:, diag, diag] = 1.0  # inactive rows: self-only, finite softmax
            for slot, req in active:
                tok[slot], pos[slot], tm[slot] = _tree_window_rows(req, w)
            return jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(tm)

        # ---- draft phase: round_depth level-growing dispatches + 1
        # straggler feeding the complete tree (lands the leaf KV for
        # fully-accepted paths; rewind drops the rest)
        for j in range(round_depth + 1):
            with span("engine", "draft_dispatch", round=rnd, j=j):
                tok_dev, pos_dev, tm_dev = window_inputs()
                logits = self._dispatch(
                    self._d_tree_step, self.draft.params, tok_dev,
                    self._d_store, d_table, d_len0, kvq_dev, pos_dev, tm_dev,
                )
            if j < round_depth:
                with span("engine", "draft_pull", round=rnd, j=j,
                          bytes=logits.nbytes):
                    l_np = np.asarray(logits)
                with span("engine", "draft_sample", round=rnd, j=j,
                          rows=len(active)):
                    for slot, req in active:
                        if not req.tree_full:
                            _sample_tree_level(req, cfg, l_np[slot])
        t_verify0 = self._now()
        self.tracer.rec(
            "engine", "tree_draft", t_draft0, t_verify0,
            cat="phase", rows=len(active), depth=round_depth, spec="tree",
        )

        # ---- verify phase: one tree-masked batched pass over full trees
        with span("engine", "verify_dispatch", round=rnd):
            tok_dev, pos_dev, tm_dev = window_inputs()
            v_logits = self._dispatch(
                self._t_tree_step, self.target.params, tok_dev,
                self._t_store, t_table, t_len0, kvq_dev, pos_dev, tm_dev,
            )
        with span("engine", "verify_pull", round=rnd, bytes=v_logits.nbytes):
            p_logits = np.asarray(v_logits)  # (B, W, V)
        self.tracer.rec(
            "engine", "tree_verify", t_verify0, self._now(),
            cat="phase", rows=len(active), spec="tree",
        )

        # ---- per-request accept / commit / compaction
        work: List[Tuple[Request, int]] = []
        progressed: List[Request] = []
        moves_t = {k: ([], []) for k in self._kinds}
        moves_d = {k: ([], []) for k in self._kinds}
        with span("engine", "accept", round=rnd, rows=len(active)):
            for slot, req in active:
                self._tree_verify_commit(
                    slot, req, p_logits[slot], modes[slot], dls[slot],
                    moves_t, moves_d, work,
                )
                progressed.append(req)
        self._compact_pools(moves_t, moves_d)
        with span("engine", "accept", round=rnd, rows=len(active)):
            self._batcher.model_round(work)
            self._retire_done(active)
            t_end = self._end_round(t_step, len(active), "off")
            self._flight_round(fb, t_step, t_end, len(active), "two_phase_tree")
        return self._emit(rnd, progressed, t_end)

    def _output_for(self, req: Request, now: Optional[float] = None,
                    round_end: Optional[float] = None) -> RequestOutput:
        """One streaming RequestOutput: the newly deliverable tokens since
        the last step (``Request.take_delta`` — stop-string holdback may
        defer tokens, never retract them) plus the cumulative deliverable
        completion.

        This is also the delivery point, so TTFT/ITL are accounted here: a
        non-empty delta is one delivery — the first observes TTFT (submit
        to first token), each later one the inter-delivery gap (ITL at
        round granularity)."""
        delta = req.take_delta()
        if delta:
            t = self._now() if now is None else now
            self._m_emitted.inc(len(delta))
            if req.first_emit_ts is None:
                req.first_emit_ts = t
                if req.submit_ts is not None:
                    self._m_ttft.observe(t - req.submit_ts)
            elif req.last_emit_ts is not None:
                self._m_itl.observe(t - req.last_emit_ts)
            req.last_emit_ts = t
        return RequestOutput(
            request_id=req.rid,
            prompt_token_ids=[int(t) for t in req.prompt],
            new_token_ids=delta,
            finished=req.state is RequestState.FINISHED,
            outputs=[CompletionOutput(
                index=0,
                token_ids=[int(t) for t in req.out[: req.emittable_len()]],
                finish_reason=req.finish_reason,
            )],
            round_end=round_end,
        )

    # -- the fused cross-request PAR round (par_mode="wdos") -----------------

    def _step_fused(self) -> List[RequestOutput]:
        """One engine round as a horizon of FUSED dispatches.

        The horizon is ``max_dl + 2`` slots — the same dispatch budget a
        two-phase round spends (``round_dl + 1`` draft micro-steps plus one
        verify pass) — but rows are no longer in lockstep: each slot the
        WDOS planner (core/scheduler.plan_mixed_slot) sends window-full
        rows to VERIFY and everyone else to DRAFT, both executed in one
        fused XLA program.  A short-window row therefore verifies, commits,
        opens its next window and keeps drafting while a long-window
        neighbour is still proposing — which is exactly how the fused mode
        drains staggered/heterogeneous workloads in fewer rounds than the
        two-phase scheduler (tests/test_par_mode.py asserts both the
        round count and bit-identical tokens).  Mid-window phase state
        carries across steps; every active row completes at least one
        verify per round (its remaining cycle is at most ``max_dl + 1``
        slots), so each round streams tokens for every active request.

        Profiler leaf spans: the chain round's admit, prefill,
        table_upload, then per slot ``engine.slot_dispatch`` (planning,
        inputs and the fused or draft-only dispatch), the pulls
        (``verify_pull``, ``draft_pull`` with ``j`` the slot), the draft
        rows' ``draft_sample`` and the verify rows' ``accept``; then
        ``accept`` (round bookkeeping) and ``emit``."""
        cfg = self.cfg
        span = self.tracer.span
        rnd = self._batcher.step_count + 1
        t_step = self._now()
        fb = self._flight_base()
        self._admit(rnd)
        if not self._batcher.active():
            self._batcher.step_count += 1
            self._m_steps.inc()
            self._refresh_gauges()
            self._flight_round(fb, t_step, self._now(), 0, "fused")
            return []
        wv = cfg.max_dl + 1  # fixed verify width: one compiled program
        horizon = cfg.max_dl + 2
        b = cfg.max_batch
        touched: Dict[int, Request] = {
            req.rid: req for _, req in self._batcher.active()
        }
        # kind mask over the step's initial actives covers every later slot
        # too (the active set only shrinks mid-step; retired rows' merged
        # logits are never read)
        kvq_dev = self._kvq_mask(self._batcher.active())
        work: List[Tuple[Request, int]] = []

        # page tables are lifetime-stable: one cached upload serves every
        # slot of the step (rows retired mid-step are inert via the masks)
        t0 = self._now()
        with span("engine", "table_upload", round=rnd):
            d_table = self._d_tables.table_dev()
            t_table = self._t_tables.table_dev()
        self._m_table_upload.inc(self._now() - t0)

        for i_slot in range(horizon):
            active = self._batcher.active()
            if not active:
                break
            by_slot = dict(active)
            slot_t0 = self._now()
            with span("engine", "slot_dispatch", round=rnd, slot=i_slot):
                for _, req in active:
                    if req.pending_dl is None:
                        req.begin_window(req.controller.draft_len())
                plan = sch.plan_mixed_slot([
                    sch.RowPhase(slot=s, window=r.pending_dl,
                                 drafted=len(r.pending))
                    for s, r in active
                ])

                # assemble the slot's per-row inputs (O(B) int32 host work)
                d_tok = np.zeros((b, 1), np.int32)
                d_len = np.zeros((b,), np.int32)
                d_mask = np.zeros((b,), bool)
                for slot in plan.draft_rows:
                    req = by_slot[slot]
                    d_tok[slot, 0] = req.draft_tip
                    d_len[slot] = req.d_seq.length + len(req.pending)
                    d_mask[slot] = True
                for slot in plan.verify_rows:
                    # the window's straggler: the draft side feeds the final
                    # proposal WHILE the target verifies — intra-request
                    # overlap riding along in the same fused program
                    req = by_slot[slot]
                    d_tok[slot, 0] = int(req.pending[-1])
                    d_len[slot] = req.d_seq.length + req.pending_dl
                    d_mask[slot] = True

                v_logits = None
                if plan.verify_rows:
                    v_tok = np.zeros((b, wv), np.int32)
                    t_len = np.zeros((b,), np.int32)
                    v_mask = np.zeros((b,), bool)
                    for slot in plan.verify_rows:
                        req = by_slot[slot]
                        v_tok[slot, 0] = req.last_tok
                        v_tok[slot, 1: 1 + req.pending_dl] = req.pending
                        t_len[slot] = req.t_seq.length
                        v_mask[slot] = True
                    v_tok_dev, d_tok_dev = jnp.asarray(v_tok), jnp.asarray(d_tok)
                    t_len_dev, d_len_dev = jnp.asarray(t_len), jnp.asarray(d_len)
                    vm_dev, dm_dev = jnp.asarray(v_mask), jnp.asarray(d_mask)
                    vs, ds = {}, {}
                    for k in self._kinds:
                        (vs[k], ds[k], self._t_store[k],
                         self._d_store[k]) = self._fused_step(
                            self.target.params, self.draft.params,
                            v_tok_dev, d_tok_dev,
                            self._t_store[k], self._d_store[k],
                            t_table, t_len_dev, d_table, d_len_dev,
                            vm_dev, dm_dev,
                        )
                    if kvq_dev is None:
                        v_logits, d_logits = vs[self._kinds[0]], ds[self._kinds[0]]
                    else:
                        sel = kvq_dev[:, None, None]
                        v_logits = jnp.where(sel, vs["int8"], vs["none"])
                        d_logits = jnp.where(sel, ds["int8"], ds["none"])
                else:
                    d_tok_dev = jnp.asarray(d_tok)
                    d_len_dev, dm_dev = jnp.asarray(d_len), jnp.asarray(d_mask)
                    ds = {}
                    for k in self._kinds:
                        ds[k], self._d_store[k] = self._draft_slot_step(
                            self.draft.params, d_tok_dev, self._d_store[k],
                            d_table, d_len_dev, dm_dev,
                        )
                    if kvq_dev is None:
                        d_logits = ds[self._kinds[0]]
                    else:
                        d_logits = jnp.where(
                            kvq_dev[:, None, None], ds["int8"], ds["none"]
                        )
            v_np = None
            if v_logits is not None:
                with span("engine", "verify_pull", round=rnd,
                          bytes=v_logits.nbytes):
                    v_np = np.asarray(v_logits)
            # only drafting rows consume draft logits; skip the (B, V)
            # device->host pull on all-verify slots
            d_np = None
            if plan.draft_rows:
                with span("engine", "draft_pull", round=rnd, j=i_slot,
                          bytes=d_logits.nbytes // d_logits.shape[1]):
                    d_np = np.asarray(d_logits[:, -1, :])
            slot_t1 = self._now()
            self._batcher.record_fused_slot(plan, slot_t1 - slot_t0, wv)
            if self.tracer.enabled:
                # one engine-track span per fused dispatch plus a span on
                # every participating row's track — the per-row staggering
                # IS the wdos schedule made visible
                kind = (
                    "fused" if plan.fused
                    else "verify_only" if plan.verify_rows
                    else "draft_only"
                )
                self.tracer.rec(
                    "engine", "fused_slot", slot_t0, slot_t1, cat="fused",
                    kind=kind, draft_rows=len(plan.draft_rows),
                    verify_rows=len(plan.verify_rows),
                )
                for slot in plan.draft_rows:
                    self.tracer.rec(
                        f"row{slot}", "draft", slot_t0, slot_t1,
                        cat="draft", rid=by_slot[slot].rid,
                    )
                for slot in plan.verify_rows:
                    self.tracer.rec(
                        f"row{slot}", "verify", slot_t0, slot_t1,
                        cat="verify", rid=by_slot[slot].rid,
                    )

            # draft rows: append the next proposal (same argmax/sampling
            # rule and the same (round, position) key indices as the
            # two-phase path, so tokens are bit-identical across modes)
            if plan.draft_rows:
                with span("engine", "draft_sample", round=rnd, j=i_slot,
                          rows=len(plan.draft_rows)):
                    for slot in plan.draft_rows:
                        req = by_slot[slot]
                        sp = req.sampling
                        row = d_np[slot]
                        if sp.greedy:
                            nxt = int(np.argmax(row))
                        else:
                            nxt = sample_token_host(
                                req.draft_key(len(req.pending)), row,
                                sp.temperature, sp.top_k, sp.top_p,
                            )
                            req.pending_q.append(row.copy())
                        req.pending.append(nxt)

            # verify rows: per-row accept/commit, then advance/rewind both
            # sequences back to committed-1 (the rewind-bounds invariant)
            if plan.verify_rows:
                with span("engine", "accept", round=rnd,
                          rows=len(plan.verify_rows)):
                    for slot in plan.verify_rows:
                        self._fused_verify_commit(
                            slot, by_slot[slot], v_np[slot], wv, work
                        )

        with span("engine", "accept", round=rnd, rows=len(touched)):
            self._batcher.model_round(work)
            t_end = self._end_round(t_step, len(touched), "wdos")
            self._flight_round(fb, t_step, t_end, len(touched), "fused")
        return self._emit(rnd, touched.values(), t_end)

    def _fused_verify_commit(self, slot: int, req: Request, p_win, wv: int,
                             work) -> None:
        """Accept/commit one verify row of a fused slot; retire it
        mid-step when it finishes."""
        dl = req.pending_dl
        sp = req.sampling
        mode = req.controller.mode
        drafts = np.asarray(req.pending, np.int64)
        if sp.greedy:
            new, n_acc = speculative_accept_greedy_host(drafts, p_win, dl)
        else:
            new, n_acc = speculative_sample_host(
                req.accept_key(), drafts, p_win, np.stack(req.pending_q), dl,
                sp.temperature, sp.top_k, sp.top_p,
            )
        req.commit(new)
        req.record_round(mode, dl, n_acc, len(new))
        req.rounds += 1
        req.drafted += dl
        req.accepted += n_acc
        req.controller.observe(n_acc, dl)
        self._m_drafted.inc(dl)
        self._m_accepted.inc(n_acc)
        self._m_round_accept.observe(n_acc / dl if dl else 0.0)
        if self.tracer.enabled:
            self.tracer.instant(
                f"row{slot}", "commit", cat="commit",
                rid=req.rid, drafted=dl, accepted=n_acc,
            )
        work.append((req, dl))
        # target wrote wv positions, draft dl + 1 (incl. straggler); both
        # keep exactly n_acc + 1
        req.t_seq.advance(wv)
        req.t_seq.rewind(wv - 1 - n_acc, release_pages=False)
        req.d_seq.advance(dl + 1)
        req.d_seq.rewind(dl - n_acc, release_pages=False)
        req.clear_window()
        if req.done:
            # retire MID-STEP: the freed slot's mask bits go False for the
            # remaining slots (its stale table rows are never
            # dereferenced), and its pages are free for the next step's
            # admissions
            self._retire_done([(slot, req)])

    def _step_fused_tree(self) -> List[RequestOutput]:
        """One tree-speculation round as a horizon of fused dispatches
        (spec_mode="tree", par_mode="wdos"): every slot the WDOS planner
        sends tree-full rows to VERIFY — the tree-masked target window
        fused with the draft side's straggler re-feed of the same complete
        tree — while everyone else grows its tree one level from the same
        fused program's draft logits.  Phase state (the partial tree)
        carries across engine steps exactly like the chain window, and
        ``rounds`` increments only at commit, so a request's trees and
        tokens are identical to the two-phase tree scheduler's.  Profiler
        leaf spans as in ``_step_fused``, plus ``engine.compaction``."""
        cfg = self.cfg
        span = self.tracer.span
        rnd = self._batcher.step_count + 1
        t_step = self._now()
        fb = self._flight_base()
        self._admit(rnd)
        if not self._batcher.active():
            self._batcher.step_count += 1
            self._m_steps.inc()
            self._refresh_gauges()
            self._flight_round(fb, t_step, self._now(), 0, "fused_tree")
            return []
        w = cfg.tree_budget + 1  # fixed window width, BOTH sides
        horizon = min(cfg.max_dl, cfg.tree_budget) + 2
        b = cfg.max_batch
        diag = np.arange(w)
        touched: Dict[int, Request] = {
            req.rid: req for _, req in self._batcher.active()
        }
        kvq_dev = self._kvq_mask(self._batcher.active())
        work: List[Tuple[Request, int]] = []

        t0 = self._now()
        with span("engine", "table_upload", round=rnd):
            d_table = self._d_tables.table_dev()
            t_table = self._t_tables.table_dev()
        self._m_table_upload.inc(self._now() - t0)

        for i_slot in range(horizon):
            active = self._batcher.active()
            if not active:
                break
            by_slot = dict(active)
            slot_t0 = self._now()
            with span("engine", "slot_dispatch", round=rnd, slot=i_slot):
                for _, req in active:
                    if req.tree_dl is None:
                        req.begin_tree(
                            min(req.controller.draft_len(), cfg.tree_budget)
                        )
                plan = sch.plan_mixed_slot([
                    sch.RowPhase(slot=s, window=r.tree_dl, drafted=r.tree_depth)
                    for s, r in active
                ])

                # every active row re-feeds its current tree on the draft
                # side at its BASE length (verify rows feed the complete
                # tree — the straggler landing the leaf KV inside the
                # verify slot)
                d_tok = np.zeros((b, w), np.int32)
                d_pos = np.zeros((b, w), np.int32)
                d_tm = np.zeros((b, w, w), np.float32)
                d_tm[:, diag, diag] = 1.0
                d_len = np.zeros((b,), np.int32)
                d_mask = np.zeros((b,), bool)
                for slot, req in active:
                    d_tok[slot], d_pos[slot], d_tm[slot] = _tree_window_rows(req, w)
                    d_len[slot] = req.d_seq.length
                    d_mask[slot] = True

                v_logits = None
                if plan.verify_rows:
                    v_tok = np.zeros((b, w), np.int32)
                    t_pos = np.zeros((b, w), np.int32)
                    t_tm = np.zeros((b, w, w), np.float32)
                    t_tm[:, diag, diag] = 1.0
                    t_len = np.zeros((b,), np.int32)
                    v_mask = np.zeros((b,), bool)
                    for slot in plan.verify_rows:
                        req = by_slot[slot]
                        v_tok[slot], t_pos[slot], t_tm[slot] = _tree_window_rows(
                            req, w
                        )
                        t_len[slot] = req.t_seq.length
                        v_mask[slot] = True
                    heads = (jnp.asarray(v_tok), jnp.asarray(d_tok))
                    tails = (
                        t_table, jnp.asarray(t_len), d_table, jnp.asarray(d_len),
                        jnp.asarray(v_mask), jnp.asarray(d_mask),
                        jnp.asarray(t_pos), jnp.asarray(t_tm),
                        jnp.asarray(d_pos), jnp.asarray(d_tm),
                    )
                    vs, ds = {}, {}
                    for k in self._kinds:
                        (vs[k], ds[k], self._t_store[k],
                         self._d_store[k]) = self._fused_tree_step(
                            self.target.params, self.draft.params, *heads,
                            self._t_store[k], self._d_store[k], *tails,
                        )
                    if kvq_dev is None:
                        v_logits, d_logits = vs[self._kinds[0]], ds[self._kinds[0]]
                    else:
                        sel = kvq_dev[:, None, None]
                        v_logits = jnp.where(sel, vs["int8"], vs["none"])
                        d_logits = jnp.where(sel, ds["int8"], ds["none"])
                else:
                    d_tok_dev = jnp.asarray(d_tok)
                    tails = (
                        d_table, jnp.asarray(d_len), jnp.asarray(d_mask),
                        jnp.asarray(d_pos), jnp.asarray(d_tm),
                    )
                    ds = {}
                    for k in self._kinds:
                        ds[k], self._d_store[k] = self._draft_tree_slot_step(
                            self.draft.params, d_tok_dev, self._d_store[k],
                            *tails,
                        )
                    if kvq_dev is None:
                        d_logits = ds[self._kinds[0]]
                    else:
                        d_logits = jnp.where(
                            kvq_dev[:, None, None], ds["int8"], ds["none"]
                        )
            v_np = None
            if v_logits is not None:
                with span("engine", "verify_pull", round=rnd,
                          bytes=v_logits.nbytes):
                    v_np = np.asarray(v_logits)
            # tree growth consumes the WHOLE window's logits (one row per
            # frontier node), not just the last column
            d_np = None
            if plan.draft_rows:
                with span("engine", "draft_pull", round=rnd, j=i_slot,
                          bytes=d_logits.nbytes):
                    d_np = np.asarray(d_logits)
            slot_t1 = self._now()
            self._batcher.record_fused_slot(
                plan, slot_t1 - slot_t0, w, draft_width=w
            )
            if self.tracer.enabled:
                kind = (
                    "fused" if plan.fused
                    else "verify_only" if plan.verify_rows
                    else "draft_only"
                )
                self.tracer.rec(
                    "engine", "fused_slot", slot_t0, slot_t1, cat="fused",
                    kind=kind, draft_rows=len(plan.draft_rows),
                    verify_rows=len(plan.verify_rows), spec="tree",
                )
                for slot in plan.draft_rows:
                    self.tracer.rec(
                        f"row{slot}", "tree_draft", slot_t0, slot_t1,
                        cat="draft", rid=by_slot[slot].rid,
                        depth=by_slot[slot].tree_depth,
                    )
                for slot in plan.verify_rows:
                    self.tracer.rec(
                        f"row{slot}", "tree_verify", slot_t0, slot_t1,
                        cat="verify", rid=by_slot[slot].rid,
                    )

            # draft rows: one more tree level (same fan-out rule and the
            # same (round, draw-index) key stream as the two-phase path)
            if plan.draft_rows:
                with span("engine", "draft_sample", round=rnd, j=i_slot,
                          rows=len(plan.draft_rows)):
                    for slot in plan.draft_rows:
                        _sample_tree_level(by_slot[slot], cfg, d_np[slot])

            # verify rows: accept/commit + queue compaction, retire done
            moves_t = {k: ([], []) for k in self._kinds}
            moves_d = {k: ([], []) for k in self._kinds}
            if plan.verify_rows:
                with span("engine", "accept", round=rnd,
                          rows=len(plan.verify_rows)):
                    for slot in plan.verify_rows:
                        req = by_slot[slot]
                        self._tree_verify_commit(
                            slot, req, v_np[slot], req.controller.mode,
                            req.tree_dl, moves_t, moves_d, work,
                        )
                        if req.done:
                            self._retire_done([(slot, req)])
            # flush compaction BEFORE the next fused dispatch: a committed
            # row's next window overlaps its old BFS slots
            self._compact_pools(moves_t, moves_d)

        with span("engine", "accept", round=rnd, rows=len(touched)):
            self._batcher.model_round(work)
            t_end = self._end_round(t_step, len(touched), "wdos")
            self._flight_round(fb, t_step, t_end, len(touched), "fused_tree")
        return self._emit(rnd, touched.values(), t_end)

    # -- drain / reporting ---------------------------------------------------

    def run(
        self,
        prompts: Optional[Sequence[Any]] = None,
        sampling_params=None,
        sinks: Optional[Sequence[Optional[Callable[[int], None]]]] = None,
    ) -> Tuple[List[jnp.ndarray], dict]:
        """Convenience drain loop: optionally add `prompts` (with one shared
        or per-prompt ``SamplingParams``), then ``step()`` until nothing is
        queued or active.  Returns (outputs for the added prompts — or every
        request this engine has seen — in submission order, summary)."""
        rids = None
        if prompts is not None:
            n = len(prompts)
            if sampling_params is None:
                sps = [None] * n
            elif isinstance(sampling_params, SamplingParams):
                sps = [sampling_params] * n
            else:
                sps = list(sampling_params)
                if len(sps) != n:
                    raise ValueError(
                        f"{len(sps)} sampling_params for {n} prompts"
                    )
            rids = [
                self.add_request(p, sps[i], sink=sinks[i] if sinks else None)
                for i, p in enumerate(prompts)
            ]
        while self.has_unfinished():
            self.step()
        ids = rids if rids is not None else sorted(self._requests)
        return [self.output_tokens(r) for r in ids], self.summary()

    def summary(self) -> dict:
        s = self._batcher.summary()
        s["kv_path"] = "paged"
        s["par_mode"] = self.cfg.par_mode
        s["kv_quant"] = self.cfg.kv_quant
        s["kv_bytes_per_token"] = {
            "target": float(self._t_pool.bytes_per_token()),
            "draft": float(self._d_pool.bytes_per_token()),
        }
        s["kv_copy_s"] = 0.0  # no host K/V copies exist on this path
        s["table_upload_s"] = self._m_table_upload.value()
        if self._prefix is not None:
            s["prefix_cache"] = self._prefix.stats()
        return s


# ---------------------------------------------------------------------------
# Deprecated run-to-drain wrappers (kept bit-identical for greedy decoding)
# ---------------------------------------------------------------------------


def _seed_from_key(key) -> int:
    """Fold a jax PRNG key into a per-request integer seed (the wrappers'
    bridge from the old key-threading API to per-request key streams)."""
    return int(np.asarray(jax.random.key_data(key)).ravel()[-1])


def serve_sd(
    key: jax.Array,
    target: ServingModel,
    draft: ServingModel,
    prompt: jnp.ndarray,
    cfg: SDConfig,
):
    """DEPRECATED: single-request speculative decoding via the Engine.

    Greedy outputs are bit-identical to the historical ``sd_generate``
    driver.  For ``temperature > 0`` the engine's per-request key stream
    (seeded from `key`) replaces the old shared key threading, so sampled
    outputs are equally-distributed but not draw-for-draw identical."""
    warn_deprecated_once("serve_sd", "Engine.add_request(...) + Engine.step()")
    prompt_np = np.asarray(prompt).reshape(-1)
    ecfg = EngineConfig(
        max_batch=1,
        draft_len=cfg.draft_len,
        model_wdos=False,
        max_model_len=prompt_np.shape[0] + cfg.max_tokens + cfg.draft_len,
    )
    eng = Engine(target, draft, ecfg)
    sp = SamplingParams(
        temperature=max(cfg.temperature, 0.0),
        max_tokens=cfg.max_tokens,
        seed=_seed_from_key(key),
    )
    outs, _ = eng.run([prompt_np], sp)
    req = eng.request(0)
    stats = SDStats(
        emitted=jnp.asarray(req.emitted_total),
        rounds=jnp.asarray(req.rounds),
        drafted=jnp.asarray(req.drafted),
        accepted=jnp.asarray(req.accepted),
    )
    return outs[0], stats


def serve_apsd(
    key: jax.Array,
    target: ServingModel,
    draft: ServingModel,
    prompt: jnp.ndarray,
    cfg: APSDConfig,
):
    """DEPRECATED: single-request APSD via the Engine's adaptive mode.

    The engine's per-request ``DraftController`` drives the same
    ``APSDPolicy`` mode machine (short windows while the TLM rejects, long
    while it accepts), so greedy outputs stay bit-identical (lossless);
    round stats are rebuilt from the request's round log.  The engine
    schedules PAR rounds as longer windows rather than the reference
    driver's draft-during-verify pipelining — the cross-request overlap the
    batcher's WDOS model prices replaces intra-request pipelining here (the
    full pipelined reference survives as ``core/apsd.apsd_generate``)."""
    warn_deprecated_once(
        "serve_apsd", "Engine with EngineConfig(adaptive=True)"
    )
    prompt_np = np.asarray(prompt).reshape(-1)
    ecfg = EngineConfig(
        max_batch=1,
        adaptive=True,
        short_dl=cfg.short_dl,
        long_dl=cfg.long_dl,
        model_wdos=False,
        max_model_len=prompt_np.shape[0] + cfg.max_tokens + cfg.long_dl,
    )
    eng = Engine(target, draft, ecfg)
    sp = SamplingParams(
        temperature=max(cfg.temperature, 0.0),
        max_tokens=cfg.max_tokens,
        seed=_seed_from_key(key),
    )
    outs, _ = eng.run([prompt_np], sp)
    req = eng.request(0)
    records = tuple(
        RoundRecord(mode=m, drafted=d, accepted=a, emitted=e, discarded=0)
        for m, d, a, e in req.history
    )
    stats = APSDStats(
        emitted=sum(r.emitted for r in records),
        rounds=len(records),
        drafted=sum(r.drafted for r in records),
        accepted=sum(r.accepted for r in records),
        discarded=0,
        par_rounds=sum(1 for r in records if r.mode == PAR),
        records=records,
    )
    return outs[0], stats


def serve_batch(
    key: jax.Array,
    target: ServingModel,
    draft: ServingModel,
    prompts: Sequence[Any],  # each (S,) or (1, S) int32, S >= 2
    cfg: BatchConfig,
    sinks: Optional[Sequence[Optional[Callable[[int], None]]]] = None,
) -> Tuple[List[jnp.ndarray], dict]:
    """DEPRECATED: run-to-drain batch decoding; use ``Engine`` directly.

    Thin wrapper: builds an ``Engine`` sized exactly like the historical
    closed-batch runtime (pool fits the ``max_batch`` largest submitted
    requests; table width = the batch's worst-case peak), adds every prompt,
    and drains.  Greedy outputs are bit-identical per request to the
    pre-redesign loop (and to ``serve_sd``).  ``cfg.kv_path == "host"``
    still selects the frozen legacy host-gather loop
    (serving/host_gather.py) kept as the benchmark baseline."""
    warn_deprecated_once("serve_batch", "Engine.run(...)")
    if cfg.kv_path == "host":
        from repro.serving.host_gather import serve_batch_host as _host_impl

        return _host_impl(key, target, draft, prompts, cfg, sinks=sinks)
    if cfg.kv_path != "paged":
        raise ValueError(f"kv_path must be 'paged' or 'host', got {cfg.kv_path!r}")
    if cfg.temperature != 0.0:
        raise NotImplementedError(
            "the deprecated serve_batch wrapper keeps its historical "
            "greedy-only contract; pass SamplingParams(temperature=...) "
            "to Engine.add_request for sampled decoding"
        )
    del key  # greedy path is deterministic; kept for API symmetry
    if not len(prompts):
        return [], _empty_summary(cfg)
    prompts_np = [np.asarray(p).reshape(-1) for p in prompts]
    peaks = [p.shape[0] + cfg.max_tokens + cfg.max_dl for p in prompts_np]
    for model in (target, draft):
        if max(peaks) > model.s_max:
            raise ValueError(
                f"peak cache length {max(peaks)} exceeds s_max={model.s_max} "
                f"of {model.cfg.name}"
            )
    if cfg.num_pages is not None:
        num_pages = cfg.num_pages
    else:
        worst = sorted((pages_for(p, cfg.page_size) for p in peaks), reverse=True)
        num_pages = sum(worst[: cfg.max_batch])
    ecfg = EngineConfig(
        max_batch=cfg.max_batch,
        page_size=cfg.page_size,
        draft_len=cfg.draft_len,
        adaptive=cfg.adaptive,
        short_dl=cfg.short_dl,
        long_dl=cfg.long_dl,
        num_pages=num_pages,
        max_model_len=max(peaks),
        model_wdos=cfg.model_wdos,
    )
    eng = Engine(target, draft, ecfg)
    sp = SamplingParams(max_tokens=cfg.max_tokens)
    return eng.run(prompts_np, sp, sinks=sinks)


def serve_batch_host(
    key: jax.Array,
    target: ServingModel,
    draft: ServingModel,
    prompts: Sequence[Any],
    cfg: BatchConfig,
    sinks: Optional[Sequence[Optional[Callable[[int], None]]]] = None,
) -> Tuple[List[jnp.ndarray], dict]:
    """DEPRECATED: the legacy host-gather loop, kept only as the benchmark
    baseline (``bench_serving --kv-path host``)."""
    warn_deprecated_once(
        "serve_batch_host", "Engine.run(...) (device-resident paged KV)"
    )
    from repro.serving.host_gather import serve_batch_host as _host_impl

    return _host_impl(key, target, draft, prompts, cfg, sinks=sinks)
