"""One run of one cell: set up, serve a timed window over HTTP, check.

The system under test is the serving stack as a deployment runs it:
``CompletionServer`` over ``AsyncEngine(Engine(target, draft, ...))`` on a
localhost port, in this process, which holds the chip.  Every request is a
streamed ``POST /v1/completions``, sent by an open-loop Poisson generator
(``loadgen.py``) at the rate the cell's traffic file fixes, and timed from
the moment it was due.

Phases of a run, in order:

1. set-up (``setup_s``): served-form weights drawn on the device (the
   configuration's ``served/<name>.py``), the engine and server built, one
   short request per prompt length and decoding kind sent so every program
   the window uses is compiled or loaded from the persistent cache, then
   arrivals start ``ramp_s`` before the window so it opens at steady state;
2. the measured window of ``--seconds``; with ``--trace 1`` the profiler
   records it;
3. at the close every request still in flight is dropped (the client hangs
   up, the engine aborts it): the metrics count only what the window saw;
4. the program's state is freed and a sample of the greedy requests, each
   scored on what it had served, is compared with the plain reference
   (``check.py``).
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

import numpy as np

from chipbench import check, loadgen
from chipbench import trace as tr
from chipbench.peaks import peaks_for
from chipbench.spec import ROOT, Bench

SPAN_SEND = "chipbench.send"
SPAN_RECV = "chipbench.recv"


@dataclasses.dataclass
class Record:
    """One request as the client saw it; times on ``time.perf_counter``."""

    req: loadgen.Request
    due: float
    sent: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    finish: Optional[str] = None
    status: Optional[int] = None
    error: Optional[str] = None
    done: bool = False

    @property
    def ok(self) -> bool:
        return (self.done and self.status == 200 and self.error is None
                and self.finish == "length"
                and len(self.tokens) == self.req.max_tokens)

    @property
    def failed(self) -> bool:
        """Answered wrongly (a request still running when dropped is not)."""
        return (self.error is not None
                or (self.done and not self.ok))


@dataclasses.dataclass
class Ctx:
    """What a per-layer metric reader sees of one run."""

    t_open: float
    t_close: float
    records: List[Record]
    counters_open: Dict[str, float]
    counters_close: Dict[str, float]
    model: dict  # the target's sizes (configuration keys)
    peaks: dict
    trace: Optional[tr.DeviceTrace] = None
    served: Optional[ModuleType] = None  # the configuration's served form

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def delta(self, name: str) -> float:
        return self.counters_close[name] - self.counters_open[name]

    def due_in_window(self) -> List[Record]:
        return [r for r in self.records if self.t_open <= r.due < self.t_close]

    def first_token_in_window(self) -> List[Record]:
        return [r for r in self.records
                if r.times and self.t_open <= r.times[0] < self.t_close]

    def finished_in_window(self) -> List[Record]:
        return [r for r in self.records
                if r.ok and self.t_open <= r.times[-1] < self.t_close]

    def output_positions_in_window(self) -> List[int]:
        out = []
        for r in self.records:
            p = len(r.req.prompt)
            out.extend(p + i for i, t in enumerate(r.times)
                       if self.t_open <= t < self.t_close)
        return out


def _counters(registry) -> Dict[str, float]:
    def hist(name):
        fam = registry.get(name)
        return (fam.sum_value(), fam.value()) if fam is not None else (0.0, 0.0)

    rw = hist("round_wall_seconds")
    return {
        "drafted": registry.value("tokens_drafted_total"),
        "accepted": registry.value("tokens_accepted_total"),
        "round_wall_sum": rw[0], "round_wall_count": rw[1],
    }


class _CompileCounter:
    """Times of every backend compile (or persistent-cache load) in the
    process, from JAX's monitoring events; one listener per process."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    _instance = None

    def __new__(cls):
        if cls._instance is None:
            import jax

            cls._instance = super().__new__(cls)
            cls._instance.times = []
            jax.monitoring.register_event_duration_secs_listener(cls._instance._on)
        return cls._instance

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.times.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t < t1 for t in self.times)


def max_model_len(config: dict, traffic: dict) -> int:
    return (max(traffic["prompt_len"]["grid"]) + traffic["max_tokens"]["max"]
            + config["engine"].get("draft_len", 3))


def build(config: dict, traffic: dict, seed: int, served: ModuleType):
    """(target params, Engine) for one cell, its models drawn and shaped by
    the configuration's served form."""
    import jax

    from repro.serving import Engine, EngineConfig
    from repro.serving.engine import ServingModel

    sv = config["serving"]
    key = jax.random.PRNGKey(int(np.random.default_rng([seed, 0]).integers(2**31 - 1)))
    tparams, dparams = served.draw(key, config)
    jax.block_until_ready((tparams, dparams))
    s_max = max_model_len(config, traffic)
    eng = dict(config["engine"])
    page = eng.get("page_size", 16)
    eng.update(max_batch=traffic["engine"]["max_batch"],
               num_pages=-(-traffic["engine"]["pool_tokens"] // page),
               max_model_len=s_max)
    target = ServingModel(cfg=served.program_config(config, "target"),
                          params=tparams, mode=sv["target"], s_max=s_max)
    draft = ServingModel(cfg=served.program_config(config, "draft"),
                         params=dparams, mode=sv["draft"], s_max=s_max)
    return tparams, Engine(target, draft, EngineConfig(**eng))


async def _request(port: int, rec: Record) -> None:
    import jax

    from repro.serving import http_client as hc

    delay = rec.due - time.perf_counter()
    if delay > 0:
        await asyncio.sleep(delay)
    rec.sent = time.perf_counter()
    writer = None
    try:
        with jax.profiler.TraceAnnotation(SPAN_SEND):
            reader, writer = await hc.open_request(
                port, "POST", "/v1/completions", rec.req.payload())
            head = await hc.read_head(reader)
        rec.status = int(head.split(" ", 2)[1])
        if rec.status != 200:
            rec.error = f"HTTP {rec.status}"
            return
        async for chunk in hc.iter_sse(reader):
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation(SPAN_RECV):
                if chunk.get("token") is not None:
                    if chunk["index"] != len(rec.tokens):
                        rec.error = f"chunk index {chunk['index']} after {len(rec.tokens)}"
                    rec.tokens.append(int(chunk["token"]))
                    rec.times.append(t)
                if chunk.get("finish_reason") is not None:
                    rec.finish = chunk["finish_reason"]
        rec.done = True
    except asyncio.CancelledError:
        raise
    except Exception as e:  # a broken stream is a failed request
        rec.error = f"{type(e).__name__}: {e}"
    finally:
        if writer is not None:
            writer.close()


class Window:
    """Open and close the measured window: counters, queue, profiler."""

    def __init__(self, engine, trace_dir: Optional[str]):
        self.engine, self.trace_dir = engine, trace_dir
        self.ann = None

    def open(self) -> None:
        import jax

        if self.trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # Python call tracing would slow the host
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self.ann = jax.profiler.TraceAnnotation(tr.WINDOW_SPAN)
            self.ann.__enter__()
        self.t_open = time.perf_counter()
        self.c_open = _counters(self.engine.metrics)
        self.queued_open = self.engine.queue_depth()
        self.active_open = self.engine.num_active()

    def close(self) -> None:
        import jax

        self.t_close = time.perf_counter()
        self.c_close = _counters(self.engine.metrics)
        self.queued_close = self.engine.queue_depth()
        self.active_close = self.engine.num_active()
        if self.trace_dir is not None:
            self.ann.__exit__(None, None, None)
            jax.profiler.stop_trace()


async def serve(engine, records: List[Record], warm: List[Record], traffic: dict,
                seconds: float, window: Window) -> float:
    """Serve the warm-up, then the timed traffic; returns once the window
    has closed and the requests still in flight are dropped.  Returns the
    time arrivals started."""
    from repro.serving import AsyncEngine, CompletionServer

    server = CompletionServer(AsyncEngine(engine, max_queued=1 << 16))
    await server.start(port=0)
    serving = asyncio.ensure_future(server.serve_forever())
    try:
        await asyncio.gather(*(_request(server.port, r) for r in warm))
        bad = [r for r in warm if not r.ok]
        if bad:
            raise RuntimeError(f"warm-up request failed: {bad[0].error or bad[0].finish}")
        _log(f"warm-up of {len(warm)} requests done")
        t0 = time.perf_counter()
        t_open = t0 + traffic["ramp_s"]
        t_close = t_open + seconds
        for r in records:
            r.due += t0
        sent = [r for r in records if r.due < t_close]
        tasks = [asyncio.ensure_future(_request(server.port, r)) for r in sent]
        await asyncio.sleep(max(0.0, t_open - time.perf_counter()))
        window.open()
        await asyncio.sleep(max(0.0, t_close - time.perf_counter()))
        window.close()
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        return t0
    finally:
        serving.cancel()
        await asyncio.gather(serving, return_exceptions=True)
        await server.stop()


def end_to_end(ctx: Ctx, setup_s: float) -> Dict[str, float]:
    n_out = sum(ctx.t_open <= t < ctx.t_close for r in ctx.records for t in r.times)
    return {"output_tokens_per_s": n_out / ctx.window_s, "setup_s": setup_s}


def _log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def run(workload: str, seed: int, seconds: float, trace: bool, t_start: float,
        root: Path = ROOT, survey_path: Optional[str] = None,
        compile_cache: bool = True, keep: Optional[dict] = None) -> dict:
    """One run of a cell; returns the result object.  ``keep``, where
    given, receives the checked sample with the weights and the reference,
    for the control's readings."""
    import jax

    from repro.runtime.compile_cache import enable_compile_cache

    bench = Bench(root)
    w = bench.workload(workload)
    config = bench.config(w["config"])
    traffic = bench.traffic(w["traffic"])
    reference = bench.reference(config["reference"])
    served = bench.served(config["served"])
    dev = jax.devices()[0]
    peaks = peaks_for(dev.device_kind)  # an unknown chip fails before any work

    cache_dir = None
    if compile_cache:
        cache_dir = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = _CompileCounter()
    _log(f"{workload} seed {seed} on {dev.device_kind}; compile cache {cache_dir}")

    tparams, engine = build(config, traffic, seed, served)
    _log(f"built in {time.perf_counter() - t_start:.1f} s")
    vocab = config["vocab_size"]
    records = [Record(req=q, due=q.due_s)
               for q in loadgen.schedule(traffic, seed, seconds, vocab)]
    warm = [Record(req=q, due=0.0) for q in loadgen.warmup(traffic, seed, vocab)]
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    window = Window(engine, trace_dir)
    asyncio.run(serve(engine, records, warm, traffic, seconds, window))
    setup_s = window.t_open - t_start

    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    engine = window.engine = None  # frees the pools before the reference runs
    gc.collect()

    ctx = Ctx(t_open=window.t_open, t_close=window.t_close, records=records,
              counters_open=window.c_open, counters_close=window.c_close,
              model=config, peaks=peaks, served=served)
    n_compiles = compiles.between(window.t_open, window.t_close)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"correct": False, "attempted": sum(r.sent is not None for r in records),
              "failed": sum(r.failed for r in records), "metrics": {}, "device": device}
    kinds = ("per_layer",) if trace else ("end_to_end",)
    if trace:
        path = tr.find_xplane(trace_dir)
        if survey_path:
            Path(survey_path).write_text(json.dumps(tr.survey(path), indent=1))
        ctx.trace = tr.read(path)
        busy = ctx.trace.busy_ns()
        if busy <= 0:
            raise RuntimeError("the traced window holds no device operation")
        device["busy_s"] = busy * 1e-9
        device["window_s"] = ctx.trace.window_ns * 1e-9
        ops = ctx.trace.in_window(ctx.trace.ops)
        gaps = tr.idle_gaps(ops, ctx.trace.t0_ns, ctx.trace.t1_ns)
        result["breakdown"] = {
            "device_ops": [list(x) for x in tr.top_ops(ops, ctx.trace.in_window(ctx.trace.modules))],
            "idle_gaps": [list(x) for x in tr.label_gaps(gaps, ctx.trace.host)],
        }
        shutil.rmtree(trace_dir, ignore_errors=True)
    values = end_to_end(ctx, setup_s)
    for m in bench.metrics_for(workload, kinds[0]):
        v = values.get(m["name"]) if kinds[0] == "end_to_end" else bench.reader(m["name"])(ctx)
        if v is not None and not (isinstance(v, float) and math.isnan(v)):
            result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    _log(f"window {ctx.window_s:.2f} s: {len(ctx.due_in_window())} requests due, "
         f"{len(ctx.first_token_in_window())} first tokens, "
         f"{len(ctx.finished_in_window())} finished; rows active {window.active_open} -> "
         f"{window.active_close}, queued {window.queued_open} -> {window.queued_close}; "
         f"{n_compiles} compiles; end to end {json.dumps(values)}")

    # the comparison, once the program's state is gone
    ck = traffic["check"]
    sample = check.pick(records, seed, ck["served_tokens"], ck["max_requests"])
    per_request = [check.gaps(reference, tparams, config, r.req.prompt, r.tokens)["program"]
                   for r in sample]
    if keep is not None:
        keep.update(sample=sample, params=tparams, config=config, reference=reference,
                    traffic=traffic)
    result["correct"], checks = check.verdict(per_request, result["failed"], ck)
    result["window_compiles"] = n_compiles
    result["checks"] = checks
    for name, c in checks.items():
        op = ">=" if name == "sampled_tokens" else "<="
        print(f"check {name} {c['value']} {op} {c['limit']}", file=sys.stderr)
    return result
