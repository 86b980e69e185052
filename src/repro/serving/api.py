"""Public serving API types: the stepwise ``Engine`` surface.

The serving entry points used to be closed-batch functions (``serve_sd``,
``serve_batch``, ...) that took a fixed prompt list and ran to drain.  The
``Engine`` (serving/engine.py) replaces them with the continuous surface the
paper's out-of-order WDOS scheduler actually wants — work arrives and
retires at any time:

    eng = Engine(target, draft, EngineConfig(max_batch=4))
    rid = eng.add_request(prompt, SamplingParams(max_tokens=32))
    while eng.has_unfinished():
        for out in eng.step():          # one WDOS-scheduled SD round
            consume(out.new_token_ids)  # streams as tokens verify

This module holds the request/response types shared by every path:

* ``SamplingParams`` — frozen per-request decode knobs.  ``temperature > 0``
  selects lossless speculative *rejection sampling* with a per-request PRNG
  key stream (seeded by ``seed``), so a request's sampled tokens are
  deterministic regardless of which batch composition it happens to run in.
* ``RequestOutput`` / ``CompletionOutput`` — the single streaming result
  type: each ``Engine.step()`` emits one ``RequestOutput`` per request that
  made progress, carrying the incremental ``new_token_ids`` plus the
  cumulative completion and finish reason.
* ``EngineConfig`` — engine-wide scheduling/residency knobs (the per-request
  knobs moved into ``SamplingParams``).

``resolve_paged_attn_impl`` centralizes the backend auto-selection: the
Pallas paged-attention kernel is the default on TPU (the backend its
dialect lowers on), the bit-exact device gather everywhere else.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import List, Optional, Tuple, Union

__all__ = [
    "SamplingParams",
    "CompletionOutput",
    "RequestOutput",
    "EngineConfig",
    "resolve_paged_attn_impl",
    "default_detokenize",
]


def default_detokenize(token_id: int) -> str:
    """The repo's toy LMs decode over an untextured integer vocab, so the
    default "detokenizer" renders each token as its decimal id plus a
    trailing space (``[5, 17] -> "5 17 "``).  Stop-string matching
    (``SamplingParams.stop``) and the HTTP server's ``text`` fields run on
    this stream; pass a real detokenizer to ``Engine``/``AsyncEngine`` when
    serving a real vocabulary."""
    return f"{token_id} "

_PAGED_ATTN_IMPLS = ("gather", "pallas")
# backends where the paged kernel LOWERS: kernels/paged_attn.py is written
# against the TPU Pallas dialect (pltpu.PrefetchScalarGridSpec / VMEM
# scratch) and only interprets elsewhere, so auto-selection must not hand
# it to GPU — the gather path is the correct default there until a
# Triton-dialect port lands
_PALLAS_BACKENDS = ("tpu",)


def resolve_paged_attn_impl(
    impl: Optional[str] = "auto", backend: Optional[str] = None
) -> str:
    """Resolve a paged-attention impl choice to ``"gather"`` or ``"pallas"``.

    ``impl`` of ``None``/``"auto"`` auto-selects by accelerator backend:
    ``"pallas"`` where the kernel compiles (TPU), ``"gather"`` (the
    bit-exact dense replay) everywhere else.  An explicit
    ``"gather"``/``"pallas"`` always wins.  ``backend`` overrides
    ``jax.default_backend()`` (tests)."""
    if impl in _PAGED_ATTN_IMPLS:
        return impl
    if impl not in (None, "auto"):
        raise ValueError(
            f"paged_attn_impl must be one of {_PAGED_ATTN_IMPLS + ('auto',)}, "
            f"got {impl!r}"
        )
    if backend is None:
        import jax

        backend = jax.default_backend()
    return "pallas" if backend in _PALLAS_BACKENDS else "gather"


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decode parameters (frozen — safe to share across requests).

    ``temperature == 0`` is greedy decoding: deterministic, bit-identical to
    the single-request reference drivers.  ``temperature > 0`` runs lossless
    speculative rejection sampling: the draft proposes from its own
    (temperature/top-k/top-p filtered) distribution and the target accepts
    with the Leviathan rule, so emitted tokens are distributed exactly as
    autoregressive sampling from the target — including nucleus (``top_p``)
    truncation, which filters BOTH distributions identically so the rule
    stays lossless.  All randomness derives from a per-request key stream
    seeded by ``seed`` and indexed by (round, position), never from shared
    state — the same (prompt, params) pair yields the same tokens at batch 1
    and batch N.

    ``stop`` holds stop strings matched against the request's detokenized
    output stream (the engine's ``detokenize`` callable renders tokens to
    text): generation ends with ``finish_reason="stop"`` at the first match,
    and the final output is truncated so the stop string itself is excluded
    (tokens whose text overlaps the match are dropped)."""

    temperature: float = 0.0
    top_k: int = 0  # 0: no truncation; k > 0: sample from the top-k logits
    top_p: float = 1.0  # nucleus mass; 1.0: no truncation
    seed: int = 0
    max_tokens: int = 64
    stop: Tuple[str, ...] = ()  # stop strings over the detokenized stream
    # per-request KV storage opt-in: None defers to the engine's
    # EngineConfig.kv_quant default; "none" pins full-precision pages;
    # "int8" opts into compressed pages (relaxed determinism — see
    # docs/SERVING.md).  An explicit value that the engine mode cannot
    # honour is rejected at add_request time.
    kv_quant: Optional[str] = None

    def __post_init__(self):
        if self.kv_quant not in (None, "none", "int8"):
            raise ValueError(
                f"kv_quant must be None, 'none' or 'int8', got {self.kv_quant!r}"
            )
        if self.temperature < 0.0:
            raise ValueError(f"temperature must be >= 0, got {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.max_tokens <= 0:
            raise ValueError(f"max_tokens must be > 0, got {self.max_tokens}")
        stop: Union[str, Tuple[str, ...]] = self.stop
        if isinstance(stop, str):
            stop = (stop,)
        stop = tuple(stop)
        for s in stop:
            if not isinstance(s, str) or not s:
                raise ValueError(f"stop entries must be non-empty strings, got {s!r}")
        object.__setattr__(self, "stop", stop)

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


@dataclasses.dataclass
class CompletionOutput:
    """One completion of a request (the engine produces exactly one)."""

    index: int
    token_ids: List[int]  # cumulative generated tokens, trimmed to the budget
    finish_reason: Optional[str] = None  # None | "length" | "stop" | "abort"

    @property
    def finished(self) -> bool:
        return self.finish_reason is not None


@dataclasses.dataclass
class RequestOutput:
    """Streaming per-request result emitted by ``Engine.step()``.

    ``new_token_ids`` holds only the tokens verified *this* step (what a
    server would flush to the client); ``outputs[0].token_ids`` is the
    cumulative completion so far."""

    request_id: int
    prompt_token_ids: List[int]
    new_token_ids: List[int]
    finished: bool
    outputs: List[CompletionOutput]
    # end of the engine round that produced this output, on
    # time.perf_counter (the server's stream_lag_seconds reads it)
    round_end: Optional[float] = None

    @property
    def token_ids(self) -> List[int]:
        return self.outputs[0].token_ids


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine-wide knobs: scheduling, paging, and attention residency.

    Per-request knobs (``max_tokens``, ``temperature``) live in
    ``SamplingParams``; this config is fixed for the engine's lifetime."""

    max_batch: int = 8  # concurrent DECODE slots (batched model rows)
    page_size: int = 16  # tokens per KV page
    draft_len: int = 3  # fixed draft window (adaptive=False)
    adaptive: bool = False  # per-request APSD draft-length adaptation
    short_dl: int = 2
    long_dl: int = 6
    num_pages: Optional[int] = None  # page budget per pool (None: fit
    # max_batch worst-case requests of max_model_len tokens)
    max_model_len: Optional[int] = None  # peak cache length a request may
    # reach (prompt + max_tokens + draft window).  None defaults to the
    # models' s_max — the honest bound when future requests are unknown,
    # but it sizes the page tables (and the "gather" impl's attention
    # span) to the worst case; set it to your real peak when known, as
    # the deprecated serve_batch wrapper does with its closed prompt list.
    model_wdos: bool = True  # build the per-round WDOS DAG (stats)
    # paged decode-attention impl: "gather" | "pallas" | None ("auto").
    # None resolves per ServingModel (each model's own paged_attn_impl,
    # itself "auto" by default => pallas where it lowers [TPU], gather
    # elsewhere); an explicit value here overrides both models.
    paged_attn_impl: Optional[str] = None
    # cross-request PAR execution:
    #   "off"  — two-phase rounds: every active row drafts in lockstep,
    #            then one batched verify pass scores everyone (the
    #            pre-PAR behaviour, kept bit-identical);
    #   "wdos" — fused rounds: each engine step runs a horizon of FUSED
    #            dispatches in which the WDOS phase planner
    #            (core/scheduler.plan_mixed_slot) picks, per slot, which
    #            rows run a draft micro-step and which verify their full
    #            window — request A verifies while request B drafts in
    #            ONE XLA program, so rows cycle out of phase and a
    #            fast-accepting row commits multiple windows per step.
    # Greedy AND sampled outputs are bit-identical across the two modes
    # (per-row math and key streams are unchanged; only the grouping of
    # work into dispatches differs) — tests/test_par_mode.py.
    par_mode: str = "off"
    # paged-KV storage precision:
    #   "none"  — full-precision pools (the model's cache dtype); every
    #             request bit-identical to the pre-compression engine;
    #   "int8"  — ALL requests store K/V as int8 pages with per-slot
    #             per-kv-head f32 scales (~3.7x fewer pool bytes/token for
    #             f32 models; scales ride their own page-indexed pools).
    #             Dequantization happens inside the attention consumers
    #             (kernels/paged_attn.py epilogue / the device gather), so
    #             pages stay compressed at rest and in flight;
    #   "mixed" — both storages are allocated and each request picks via
    #             SamplingParams.kv_quant (default "none"): fp and int8
    #             rows batch together in the same engine step.
    # A request's explicit SamplingParams.kv_quant must be compatible:
    # "none"/"int8" engines reject requests pinning the other storage.
    kv_quant: str = "none"
    # Copy-on-write prefix cache (serving/prefix_cache.py): cache prompt
    # prefixes at page granularity in a refcounted radix tree and map hits
    # as read-only shared pages, skipping their prefill.  Tokens are
    # bit-identical to prefix_cache=False for every (impl, par_mode,
    # kv_quant) combination — tests/test_prefix_cache.py.
    prefix_cache: bool = False
    # Pool sizing by BYTE budget instead of page count: when set, each
    # pool gets `num_pages_for_bytes(pool_bytes, ...)` pages under its own
    # storage kind, so compressed (int8) pools admit ~3.5x the resident
    # requests of dense pools at the SAME budget.  Mutually exclusive with
    # num_pages.
    pool_bytes: Optional[int] = None
    # Speculation topology:
    #   "chain" — single-branch drafting (one candidate continuation per
    #             round; the historical APSD behaviour, bit-identical);
    #   "tree"  — TREE drafting: a frontier node fans out to
    #             ``spec_branches`` top-k candidate children whenever its
    #             draft top-1 probability falls below ``branch_threshold``
    #             (and the ``tree_budget`` node budget allows), and the
    #             target verifies the WHOLE tree in one ancestor-masked
    #             dispatch.  Accepted tokens stay distribution-exact
    #             (lossless tree rejection sampling,
    #             core/speculative.speculative_tree_sample_host); expected
    #             accepted tokens/round rises precisely on low-acceptance
    #             requests.
    spec_mode: str = "chain"
    spec_branches: int = 2  # fan-out at a branching position (tree mode)
    tree_budget: int = 8  # max drafted nodes per tree round (tree mode)
    # branch when the draft's top-1 probability < branch_threshold: 0.0
    # never branches (a chain-shaped tree), 1.0 branches at every frontier
    # position the node budget allows
    branch_threshold: float = 0.6
    # Flight recorder (serving/flight_recorder.py): bounded ring of
    # per-round records with anomaly triggers (slow round, acceptance
    # collapse, pool exhaustion, admission stall).  flight_ring=0 disables
    # recording entirely; flight_dump_dir writes postmortem JSON files
    # there when an anomaly fires (None: postmortems stay in memory,
    # readable at GET /debug/flight).
    flight_ring: int = 256
    flight_dump_dir: Optional[str] = None

    def __post_init__(self):
        if self.par_mode not in ("off", "wdos"):
            raise ValueError(
                f"par_mode must be 'off' or 'wdos', got {self.par_mode!r}"
            )
        if self.spec_mode not in ("chain", "tree"):
            raise ValueError(
                f"spec_mode must be 'chain' or 'tree', got {self.spec_mode!r}"
            )
        if self.spec_mode == "tree":
            if self.spec_branches < 2:
                raise ValueError(
                    f"spec_branches must be >= 2, got {self.spec_branches}"
                )
            if self.tree_budget < 1:
                raise ValueError(
                    f"tree_budget must be >= 1, got {self.tree_budget}"
                )
            if not (0.0 <= self.branch_threshold <= 1.0):
                raise ValueError(
                    f"branch_threshold must be in [0, 1], got "
                    f"{self.branch_threshold}"
                )
        if self.kv_quant not in ("none", "int8", "mixed"):
            raise ValueError(
                f"kv_quant must be 'none', 'int8' or 'mixed', got "
                f"{self.kv_quant!r}"
            )
        if self.pool_bytes is not None:
            if self.num_pages is not None:
                raise ValueError("set num_pages or pool_bytes, not both")
            if self.pool_bytes <= 0:
                raise ValueError(f"pool_bytes must be > 0, got {self.pool_bytes}")
        if self.flight_ring < 0:
            raise ValueError(
                f"flight_ring must be >= 0, got {self.flight_ring}"
            )

    @property
    def max_dl(self) -> int:
        return self.long_dl if self.adaptive else self.draft_len

    @property
    def spec_window(self) -> int:
        """Worst-case speculative tokens resident in a request's cache at
        once — what admission must reserve beyond prompt + max_tokens.  A
        chain round writes at most ``max_dl`` uncommitted drafts; a tree
        round writes the whole padded window (``tree_budget`` nodes)."""
        return self.tree_budget if self.spec_mode == "tree" else self.max_dl

    @property
    def kv_kinds(self) -> Tuple[str, ...]:
        """The KV storage kinds this engine allocates pools for."""
        return ("none", "int8") if self.kv_quant == "mixed" else (self.kv_quant,)

    def resolve_kv_quant(self, requested: Optional[str]) -> str:
        """Resolve a request's ``SamplingParams.kv_quant`` against the engine
        mode: ``None`` takes the engine default ("none" under "mixed"); an
        explicit choice must name a storage the engine allocated."""
        if requested is None:
            return "none" if self.kv_quant == "mixed" else self.kv_quant
        if requested not in self.kv_kinds:
            raise ValueError(
                f"request kv_quant={requested!r} is incompatible with engine "
                f"kv_quant={self.kv_quant!r} (allocated kinds: {self.kv_kinds})"
            )
        return requested


# ---------------------------------------------------------------------------
# Deprecation bookkeeping for the legacy serve_* wrappers
# ---------------------------------------------------------------------------

_DEPRECATION_EMITTED: set = set()


def warn_deprecated_once(name: str, replacement: str) -> None:
    """Emit a DeprecationWarning for `name` at most once per process, so a
    server's log is not flooded by per-request wrapper calls.  (Whether the
    one emission is *displayed* still follows the active warning filters,
    as with any ``warnings.warn``.)"""
    if name in _DEPRECATION_EMITTED:
        return
    _DEPRECATION_EMITTED.add(name)
    warnings.warn(
        f"{name}() is deprecated; use {replacement}",
        DeprecationWarning,
        stacklevel=3,
    )
