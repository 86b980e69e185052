"""Serving runtime: the stepwise continuous-batching ``Engine`` over
device-resident paged KV pools, plus the deprecated run-to-drain shims.

The public surface::

    from repro.serving import Engine, EngineConfig, SamplingParams

    eng = Engine(target, draft, EngineConfig(max_batch=4))
    rid = eng.add_request(prompt, SamplingParams(max_tokens=32))
    while eng.has_unfinished():
        for out in eng.step():          # one WDOS-scheduled SD round
            stream(out.new_token_ids)   # RequestOutput, incremental

``EngineConfig(par_mode="wdos")`` switches the rounds from two-phase
(draft-all-then-verify-all) to FUSED cross-request PAR: the WDOS phase
planner co-schedules one request's verify with its neighbours' draft
micro-steps in single fused dispatches — bit-identical tokens, fewer
rounds on heterogeneous workloads.  docs/SERVING.md is the API reference;
docs/ARCHITECTURE.md maps the stack.

The ASYNC front-end turns the engine into a service::

    async with AsyncEngine(eng, max_queued=32) as aeng:
        async for out in aeng.generate(prompt, sp):   # one iterator per
            send(out.new_token_ids)                   # request, tokens
                                                      # bit-identical to
                                                      # Engine.run()

``async_engine.AsyncEngine`` runs the step loop on a worker thread with
per-request streams, cancellation -> ``Engine.abort`` (pages freed
immediately), and a bounded admission queue (``QueueFullError`` on
fail-fast overflow); ``server.CompletionServer`` serves it over HTTP
(``POST /v1/completions`` with SSE streaming, ``/healthz``, ``/stats``,
``/metrics``) on stdlib asyncio streams — no framework dependency.

OBSERVABILITY (docs/OBSERVABILITY.md is the reference): every engine
carries a ``MetricsRegistry`` (``observability.py`` — zero-dependency
counters/gauges/histograms, Prometheus-text ``render()``) that the
batcher, the async front-end, the HTTP server, and the benchmarks all
share.  The engine's and server's spans (``tracing.py``) always go to
``jax.profiler``, on the same clock as the device's programs, so a
profiler session (``jax.profiler.start_trace``) attributes each idle gap
of the chip to the host work under way; pass ``Engine(...,
trace=Tracer())`` to also record per-request span timelines exportable as
Chrome-trace/Perfetto JSON.  Instrumentation never adds host syncs —
bit-identity is unaffected with tracing and the profiler on.  Every
engine carries a ``flight_recorder.FlightRecorder`` — a
bounded ring of per-round records with anomaly postmortems, served at
``GET /debug/flight``.

Internals (engine-owned, import from their modules if you must):
  paged_cache.PagedKVPool  — block-granular KV pages, free list, reservations
  request.Request          — lifecycle + per-request sampling key streams
  batcher.ContinuousBatcher— page-budget admission + WDOS round model
  host_gather              — frozen legacy gather/scatter loop (bench baseline)

Deprecated shims (each warns once): ``serve_sd``, ``serve_apsd``,
``serve_batch``, ``serve_batch_host`` — thin wrappers over ``Engine``,
bit-identical for greedy decoding.
"""
from repro.serving.api import (
    CompletionOutput,
    EngineConfig,
    RequestOutput,
    SamplingParams,
    default_detokenize,
    resolve_paged_attn_impl,
)
from repro.serving.async_engine import AsyncEngine, QueueFullError
from repro.serving.flight_recorder import ANOMALY_KINDS, FlightRecorder
from repro.serving.engine import (
    BatchConfig,
    Engine,
    ServingModel,
    make_interface,
    serve_apsd,
    serve_batch,
    serve_batch_host,
    serve_sd,
)
from repro.serving.observability import (
    LATENCY_BUCKETS,
    RATIO_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from repro.serving.server import CompletionServer
from repro.serving.tracing import (
    NULL_TRACER,
    NullTracer,
    Tracer,
    validate_chrome_trace,
)

__all__ = [
    # the Engine API
    "Engine",
    "EngineConfig",
    "SamplingParams",
    "RequestOutput",
    "CompletionOutput",
    "ServingModel",
    "make_interface",
    "resolve_paged_attn_impl",
    "default_detokenize",
    # the async front-end
    "AsyncEngine",
    "QueueFullError",
    "CompletionServer",
    # observability: metrics registry + span tracer
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "LATENCY_BUCKETS",
    "RATIO_BUCKETS",
    "Tracer",
    "NullTracer",
    "NULL_TRACER",
    "validate_chrome_trace",
    "FlightRecorder",
    "ANOMALY_KINDS",
    # deprecated run-to-drain shims (+ their config type)
    "serve_sd",
    "serve_apsd",
    "serve_batch",
    "serve_batch_host",
    "BatchConfig",
]
