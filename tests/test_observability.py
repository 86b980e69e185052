"""Observability layer: metrics registry semantics, Prometheus scrape
format, Chrome-trace export schema, and the no-perturbation contract —
tokens stay bit-identical with tracing enabled, including under
``par_mode="wdos"``.
"""
import asyncio
import json

import numpy as np
import pytest

from repro.launch.serve import build_pair
from repro.serving import (
    AsyncEngine,
    CompletionServer,
    Engine,
    EngineConfig,
    MetricsRegistry,
    NULL_TRACER,
    RATIO_BUCKETS,
    SamplingParams,
    Tracer,
    validate_chrome_trace,
)
from repro.serving import http_client as hc


# ---------------------------------------------------------------------------
# MetricsRegistry unit tests (no models involved)
# ---------------------------------------------------------------------------


def test_counter_monotonicity():
    m = MetricsRegistry()
    c = m.counter("reqs_total", "h")
    c.inc()
    c.inc(2.5)
    assert m.value("reqs_total") == 3.5
    with pytest.raises(ValueError):
        c.labels().inc(-1.0)
    with pytest.raises(ValueError):
        c.dec()  # counters have no dec at all


def test_gauge_moves_both_ways():
    m = MetricsRegistry()
    g = m.gauge("depth", "h")
    g.set(5)
    g.inc(2)
    g.dec(4)
    assert m.value("depth") == 3.0


def test_histogram_bucketing_cumulative_and_sum():
    m = MetricsRegistry()
    h = m.histogram("lat_seconds", "h", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.7, 3.0):
        h.observe(v)
    assert h.value() == 4  # value() is the observation count
    assert h.sum_value() == pytest.approx(4.25)
    text = m.render()
    # cumulative buckets: le=0.1 -> 1, le=1 -> 3, le=+Inf -> 4
    assert 'serving_lat_seconds_bucket{le="0.1"} 1' in text
    assert 'serving_lat_seconds_bucket{le="1"} 3' in text
    assert 'serving_lat_seconds_bucket{le="+Inf"} 4' in text
    assert "serving_lat_seconds_count 4" in text


def test_labels_and_registration_idempotence():
    m = MetricsRegistry()
    c = m.counter("by_kind_total", "h", ("kind",))
    c.labels(kind="a").inc(2)
    c.labels(kind="b").inc()
    assert m.value("by_kind_total", kind="a") == 2.0
    assert c.total() == 3.0
    # same name+kind returns the SAME family; kind mismatch raises
    assert m.counter("by_kind_total", "h", ("kind",)) is c
    with pytest.raises(ValueError):
        m.gauge("by_kind_total")
    with pytest.raises(ValueError):
        c.labels(wrong="x")
    with pytest.raises(ValueError):
        c.inc()  # labeled family has no default child


def test_noop_mode_is_inert():
    m = MetricsRegistry(enabled=False)
    c = m.counter("x_total", "h")
    h = m.histogram("h_seconds", "h")
    c.inc(100)
    h.observe(1.0)
    m.gauge("g").set(9)
    assert m.value("x_total") == 0.0
    assert m.value("h_seconds") == 0.0
    # render still emits headers (families register), but no samples
    assert "# TYPE serving_x_total counter" in m.render()
    assert "serving_x_total 100" not in m.render()


def test_render_prometheus_text_shape():
    m = MetricsRegistry()
    m.counter("a_total", 'help with "quotes"').inc()
    m.counter("l_total", "h", ("pool",)).labels(pool="tar\nget").inc()
    text = m.render()
    assert text.endswith("\n")
    assert "# HELP serving_a_total" in text
    assert "# TYPE serving_a_total counter" in text
    # label values escape newlines
    assert 'serving_l_total{pool="tar\\nget"} 1' in text
    snap = m.snapshot()
    assert snap["serving_a_total"]["type"] == "counter"
    assert snap["serving_l_total"]["series"]["pool=tar\nget"] == 1.0


# ---------------------------------------------------------------------------
# Tracer + Chrome-trace export schema
# ---------------------------------------------------------------------------


def test_tracer_chrome_trace_schema(tmp_path):
    t = Tracer(jsonl_path=str(tmp_path / "events.jsonl"))
    t.instant("engine", "submit", cat="lifecycle", rid=0)
    with t.span("engine", "step#1", cat="step"):
        t.rec("row0", "draft", t.now(), t.now() + 0.001, cat="draft", rid=0)
    t.close()
    trace = t.to_chrome_trace()
    assert validate_chrome_trace(trace) == []
    evs = trace["traceEvents"]
    # one thread_name metadata event per track, in first-seen order
    meta = [e for e in evs if e["ph"] == "M"]
    assert [e["args"]["name"] for e in meta] == ["engine", "row0"]
    # complete events carry integer-ish ts/dur; instants are thread-scoped
    x = [e for e in evs if e["ph"] == "X"]
    assert all(e["dur"] >= 1 for e in x)
    i = [e for e in evs if e["ph"] == "i"]
    assert all(e.get("s") == "t" for e in i)
    # args thread the request id through
    assert any(e.get("args", {}).get("rid") == 0 for e in evs)
    # the JSONL stream has one JSON object per event
    lines = (tmp_path / "events.jsonl").read_text().splitlines()
    assert len(lines) == len(t.events())
    assert all(json.loads(l)["name"] for l in lines)
    # export round-trips through the schema checker
    t.export(str(tmp_path / "trace.json"))
    loaded = json.loads((tmp_path / "trace.json").read_text())
    assert validate_chrome_trace(loaded) == []


def test_null_tracer_is_inert():
    assert not NULL_TRACER.enabled
    NULL_TRACER.instant("x", "y")
    NULL_TRACER.rec("x", "y", 0.0, 1.0)
    with NULL_TRACER.span("x", "y"):
        pass
    assert NULL_TRACER.events() == []
    with pytest.raises(RuntimeError):
        NULL_TRACER.export("/dev/null")


def test_validate_chrome_trace_flags_problems():
    assert validate_chrome_trace({}) != []
    assert validate_chrome_trace({"traceEvents": "nope"}) != []
    bad = {"traceEvents": [{"ph": "X", "name": "a", "pid": 0, "tid": 0,
                            "ts": 1}]}
    assert any("dur" in p for p in validate_chrome_trace(bad))
    assert validate_chrome_trace(
        {"traceEvents": [{"ph": "Z", "name": "a", "pid": 0, "tid": 0}]}
    ) != []


def _one_track_trace(track, spans):
    """Minimal trace: one thread_name M record + X spans on that tid."""
    evs = [{"ph": "M", "name": "thread_name", "pid": 1, "tid": 1,
            "args": {"name": track}}]
    evs += [
        {"ph": "X", "name": f"s{i}", "pid": 1, "tid": 1, "ts": ts,
         "dur": dur, "cat": "t"}
        for i, (ts, dur) in enumerate(spans)
    ]
    return {"traceEvents": evs}


def test_validator_requires_thread_name_metadata():
    # a tid never introduced by a thread_name M event is an anonymous
    # track in Perfetto — always a tracer bug here
    bad = {"traceEvents": [
        {"ph": "X", "name": "a", "pid": 1, "tid": 7, "ts": 0, "dur": 5},
    ]}
    assert any("thread_name" in p for p in validate_chrome_trace(bad))
    # the same span with metadata is clean
    assert validate_chrome_trace(_one_track_trace("engine", [(0, 5)])) == []


# ---------------------------------------------------------------------------
# Engine end-to-end: metrics populate, tracing never perturbs tokens
# ---------------------------------------------------------------------------


def _prompts(n, seed=0, vocab=512):
    rng = np.random.RandomState(seed)
    return [
        np.asarray(rng.randint(0, vocab, size=rng.randint(3, 7)), np.int32)
        for _ in range(n)
    ]


@pytest.fixture(scope="module")
def pair():
    return build_pair(seed=0, s_max=128, quantize=False)


def test_wdos_bit_identity_with_tracing_enabled(pair):
    """The headline no-perturbation contract: a traced+metered wdos run
    emits exactly the tokens of an uninstrumented two-phase run."""
    target, draft = pair
    prompts = _prompts(3, seed=11)
    sp = SamplingParams(max_tokens=12)

    ref_eng = Engine(target, draft, EngineConfig(max_batch=2, page_size=8))
    ref, _ = ref_eng.run(prompts, sp)

    tracer = Tracer()
    eng = Engine(
        target, draft,
        EngineConfig(max_batch=2, page_size=8, par_mode="wdos"),
        trace=tracer,
    )
    outs, summary = eng.run(prompts, sp)
    for a, b in zip(ref, outs):
        assert [int(t) for t in a] == [int(t) for t in b]

    # the trace is Perfetto-loadable and shows per-row staggering
    trace = tracer.to_chrome_trace()
    assert validate_chrome_trace(trace) == []
    tracks = {e["args"]["name"] for e in trace["traceEvents"]
              if e["ph"] == "M"}
    assert "engine" in tracks
    assert any(t.startswith("row") for t in tracks)
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"submit", "prefill", "fused_slot", "commit", "finish"} <= names

    # the registry carries the same fused numbers summary() reports
    m = eng.metrics
    assert m.value("requests_submitted_total") == 3
    assert m.value("tokens_drafted_total") > 0
    assert m.value("ttft_seconds") == 3  # one TTFT observation per request
    assert m.value("round_wall_seconds") > 0
    fused = summary["fused"]
    assert fused["slots"] == m.get("fused_slots_total").total()
    assert 0.0 <= summary["acceptance_rate"] <= 1.0
    assert m.value("requests_finished_total", reason="length") == 3


def test_engine_metrics_two_phase_and_round_acceptance(pair):
    target, draft = pair
    eng = Engine(target, draft, EngineConfig(max_batch=2, page_size=8))
    eng.run(_prompts(2, seed=3), SamplingParams(max_tokens=8))
    m = eng.metrics
    assert m.value("steps_total") > 0
    assert m.value("tokens_emitted_total") >= 16
    # per-round acceptance lands in the [0, 1] ratio buckets
    h = m.get("round_acceptance")
    assert h.buckets[:-1] == RATIO_BUCKETS
    assert h.value() > 0
    assert m.value("itl_seconds") > 0  # multi-round requests have gaps
    # levels settle to idle after the drain
    assert m.value("active_requests") == 0
    assert m.value("pool_pages", pool="target", state="used") == 0
    assert m.value("table_upload_seconds_total") > 0


def test_paged_walk_counters_advance_by_one_round(pair):
    """One chain round of two requests in three rows: the draft runs dl + 1
    steps of width 1 and the target one verify of width dl + 1, every row
    starting from its prompt's length less one (the last prompt token is
    the round's first input; an empty row from 0).  Each dispatch adds the
    rows' cdiv(length, page_size) to the live counter and rows x table
    width to the table counter."""
    from repro.serving.paged_cache import pages_for

    target, draft = pair
    ps, rows = 8, 3
    eng = Engine(target, draft, EngineConfig(max_batch=rows, page_size=ps))
    prompts = [np.arange(1, 12, dtype=np.int32), np.arange(1, 21, dtype=np.int32)]
    rids = [eng.add_request(p, SamplingParams(max_tokens=8)) for p in prompts]
    eng.step()
    dl = max(eng.request(r).drafted for r in rids)
    assert dl > 0
    base = np.zeros(rows, np.int64)
    base[: len(prompts)] = [len(p) - 1 for p in prompts]
    cdiv = lambda n: -(-n // ps)
    width = pages_for(eng.max_model_len, ps)
    m = eng.metrics
    assert m.value("paged_attn_live_pages_total", model="draft") == sum(
        cdiv(base + j + 1).sum() for j in range(dl + 1)
    )
    assert m.value("paged_attn_table_pages_total", model="draft") == (
        (dl + 1) * rows * width
    )
    assert m.value("paged_attn_live_pages_total", model="target") == (
        cdiv(base + dl + 1).sum()
    )
    assert m.value("paged_attn_table_pages_total", model="target") == rows * width
    text = m.render()
    for name in ("live", "table"):
        for role in ("target", "draft"):
            assert f'serving_paged_attn_{name}_pages_total{{model="{role}"}}' in text


# ---------------------------------------------------------------------------
# /metrics scrape through the real HTTP server
# ---------------------------------------------------------------------------


CORE_SERIES = (
    "serving_ttft_seconds",
    "serving_itl_seconds",
    "serving_round_wall_seconds",
    "serving_admission_wait_seconds",
    "serving_round_acceptance",
    "serving_acceptance_rate",
    "serving_rounds_total",
    "serving_steps_total",
    "serving_queue_depth",
    "serving_active_requests",
    "serving_pool_pages",
    "serving_requests_submitted_total",
    "serving_requests_finished_total",
    "serving_tokens_emitted_total",
    "serving_http_requests_total",
    "serving_http_429_total",
)


def test_metrics_scrape_format_and_core_series(pair):
    target, draft = pair

    async def scenario():
        engine = Engine(target, draft, EngineConfig(max_batch=2, page_size=8))
        server = CompletionServer(AsyncEngine(engine, max_queued=8))
        await server.start(port=0)
        task = asyncio.ensure_future(server.serve_forever())
        try:
            prompt = [int(t) for t in _prompts(1, seed=7)[0]]
            status, _, chunks = await hc.sse_request(
                server.port, {"prompt": prompt, "max_tokens": 6}
            )
            assert status == 200 and len(chunks) == 6
            status, head, body = await hc.request(
                server.port, "GET", "/metrics"
            )
            assert status == 200
            assert "text/plain; version=0.0.4" in head
            # the flight recorder is served, with rounds from the drain
            fstatus, _, fbody = await hc.request(
                server.port, "GET", "/debug/flight"
            )
            assert fstatus == 200
            flight = json.loads(fbody.decode())
            assert flight["enabled"] and flight["rounds_recorded"] > 0
            assert flight["ring"][-1]["mode"] == "two_phase"
            return body.decode()
        finally:
            task.cancel()
            try:
                await task
            except asyncio.CancelledError:
                pass
            await server.stop()

    text = asyncio.run(scenario())
    families = {
        line.split()[2] for line in text.splitlines()
        if line.startswith("# TYPE ")
    }
    assert len(families) >= 12, sorted(families)
    for name in CORE_SERIES:
        assert name in families, f"missing series family {name}"
    # histograms expose the full bucket/sum/count triple
    assert 'serving_ttft_seconds_bucket{le="+Inf"} 1' in text
    assert "serving_ttft_seconds_count 1" in text
    # the scrape counted itself
    assert 'serving_http_requests_total{route="/metrics",status="200"} 1' \
        in text


def test_tree_and_anomaly_families_registered_when_idle(pair):
    """The tree + flight-recorder families are registered (and zero) on a
    chain-mode engine that never ran — scrape shape is config-independent."""
    target, draft = pair
    eng = Engine(target, draft, EngineConfig(max_batch=2, page_size=8))
    text = eng.metrics.render()
    for fam in ("serving_tree_nodes_total", "serving_tree_branches_total",
                "serving_tree_accept_depth",
                "serving_tree_compactions_total",
                "serving_anomalies_total"):
        assert f"# TYPE {fam}" in text, fam
    # every anomaly kind is materialized at 0 for delta-friendly scrapes
    from repro.serving import ANOMALY_KINDS
    for kind in ANOMALY_KINDS:
        assert f'serving_anomalies_total{{kind="{kind}"}} 0' in text, kind


def test_stats_snapshot_is_single_view(pair):
    """/stats is served from one worker-published snapshot: the engine
    fields all come from the same dict object, and queue/active/pool keys
    are present and consistent after a drain."""
    target, draft = pair

    async def scenario():
        engine = Engine(target, draft, EngineConfig(max_batch=2, page_size=8))
        async with AsyncEngine(engine, max_queued=4) as aeng:
            outs = [
                o async for o in aeng.generate(
                    _prompts(1, seed=9)[0], SamplingParams(max_tokens=5)
                )
            ]
            assert outs[-1].finished
            st = aeng.stats()
            assert st["queued"] == 0 and st["active"] == 0
            assert st["finished_requests"] == 1
            assert st["target_pool"]["used_pages"] == 0
            assert st["pending_admission"] == 0 and st["max_queued"] == 4
            assert 0.0 <= st["acceptance_rate"] <= 1.0
            # the snapshot object itself is replaced wholesale, never
            # mutated: two stats() calls with no engine activity agree
            assert aeng.stats() == st
        return True

    assert asyncio.run(scenario())
