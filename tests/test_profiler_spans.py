"""Engine and server spans on the profiler's clock.

Under ``jax.profiler`` the engine's round is a sequence of non-overlapping
leaf spans (``engine.admit``, ``engine.prefill``, ``engine.table_upload``,
``engine.draft_dispatch`` / ``draft_pull`` / ``draft_sample``,
``engine.verify_dispatch`` / ``verify_pull``, ``engine.accept``,
``engine.emit``), each carrying its round; the profiler never changes a
token; the server observes ``stream_lag_seconds`` once per written output.
"""
import asyncio
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.launch.serve import build_pair
from repro.serving import (
    AsyncEngine,
    CompletionServer,
    Engine,
    EngineConfig,
    SamplingParams,
    Tracer,
)
from repro.serving import http_client as hc

ROUND = "test.round"  # this test's own span around each Engine.step()
CHAIN_SPANS = {
    "engine.admit", "engine.prefill", "engine.table_upload",
    "engine.draft_dispatch", "engine.draft_pull", "engine.draft_sample",
    "engine.verify_dispatch", "engine.verify_pull", "engine.accept",
    "engine.emit",
}
MODES = {
    "chain": dict(max_batch=2, page_size=8),
    "wdos": dict(max_batch=2, page_size=8, par_mode="wdos"),
    "tree": dict(max_batch=2, page_size=8, spec_mode="tree", tree_budget=6,
                 spec_branches=2, branch_threshold=1.0),
}


@pytest.fixture(scope="module")
def pair():
    return build_pair(seed=0, s_max=128, quantize=False)


def _requests():
    rng = np.random.RandomState(5)
    prompts = [np.asarray(rng.randint(0, 512, size=n), np.int32) for n in (5, 7, 4)]
    sps = [SamplingParams(max_tokens=10),
           SamplingParams(temperature=0.9, seed=3, max_tokens=4),
           SamplingParams(max_tokens=6)]
    return prompts, sps


def _drain(eng):
    """Submit the requests and step to the end, each step inside ROUND;
    returns each request's streamed tokens."""
    prompts, sps = _requests()
    rids = [eng.add_request(p, sp) for p, sp in zip(prompts, sps)]
    toks = {r: [] for r in rids}
    while eng.has_unfinished():
        with jax.profiler.TraceAnnotation(ROUND):
            outs = eng.step()
        for out in outs:
            toks[out.request_id].extend(int(t) for t in out.new_token_ids)
    return [toks[r] for r in rids]


def _profiled(fn, log_dir):
    """Run ``fn`` under the profiler; returns (its result, the host
    events of the trace as (line, name, start_ns, end_ns, stats))."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        str(log_dir), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                events.append((line.name, e.name, e.start_ns,
                               e.start_ns + e.duration_ns, dict(e.stats)))
    return out, events


def _engine_line(events):
    """Events of the one thread line that holds the engine's spans."""
    lines = {ln for ln, name, *_ in events if name.startswith("engine.")}
    assert len(lines) == 1, lines
    (line,) = lines
    return [e for e in events if e[0] == line]


def test_chain_round_leaf_spans_cover_each_round(pair, tmp_path):
    target, draft = pair
    eng = Engine(target, draft, EngineConfig(**MODES["chain"]))
    _, events = _profiled(lambda: _drain(eng), tmp_path)
    line = _engine_line(events)
    leaves = sorted((s, e, name, st) for _, name, s, e, st in line
                    if name.startswith("engine."))
    rounds = sorted((s, e) for _, name, s, e, _ in line if name == ROUND)
    assert len(rounds) == eng._batcher.step_count >= 4

    # every span of the table, each with its round as parent key
    assert {name for _, _, name, _ in leaves} == CHAIN_SPANS
    assert all("round" in st for _, _, _, st in leaves)
    assert {st["round"] for *_, st in leaves} == set(range(1, len(rounds) + 1))
    pulls = [st for _, _, name, st in leaves if name == "engine.draft_pull"]
    assert all(st["bytes"] > 0 and "j" in st for st in pulls)
    prefills = [st for _, _, name, st in leaves if name == "engine.prefill"]
    assert sorted(st["model"] for st in prefills) == ["draft"] * 3 + ["target"] * 3

    # leaf spans never overlap one another ...
    for (s0, e0, n0, _), (s1, _, n1, _) in zip(leaves, leaves[1:]):
        assert e0 <= s1, (n0, n1)
    # ... and cover at least 90% of each round's wall time
    for k, (rs, re_) in enumerate(rounds, start=1):
        mine = [(s, e) for s, e, _, st in leaves if st["round"] == k]
        assert all(rs <= s and e <= re_ for s, e in mine)
        covered = sum(e - s for s, e in mine)
        assert covered >= 0.9 * (re_ - rs), (k, covered / (re_ - rs))


@pytest.mark.parametrize("mode", sorted(MODES))
def test_tokens_bit_identical_with_profiler_on(pair, tmp_path, mode):
    target, draft = pair
    ref = _drain(Engine(target, draft, EngineConfig(**MODES[mode])))
    eng = Engine(target, draft, EngineConfig(**MODES[mode]))
    got, events = _profiled(lambda: _drain(eng), tmp_path)
    assert got == ref
    names = {name for _, name, *_ in _engine_line(events)}
    # the phases a chain round shares with this mode keep their names
    assert {"engine.admit", "engine.prefill", "engine.table_upload",
            "engine.draft_pull", "engine.draft_sample", "engine.verify_pull",
            "engine.accept", "engine.emit"} <= names, names
    if mode == "wdos":
        assert "engine.slot_dispatch" in names
    else:
        assert {"engine.draft_dispatch", "engine.verify_dispatch"} <= names


def test_in_memory_tracer_records_the_same_leaf_spans(pair):
    target, draft = pair
    tracer = Tracer()
    eng = Engine(target, draft, EngineConfig(**MODES["chain"]), trace=tracer)
    _drain(eng)
    spans = {(ev["track"], ev["name"]) for ev in tracer.events() if ev["ph"] == "X"}
    assert {("engine", n.split(".", 1)[1]) for n in CHAIN_SPANS} <= spans
    # the in-memory round and phase spans stay beside them
    assert {("engine", "step#1"), ("engine", "draft_phase"),
            ("engine", "verify_phase")} <= spans


def test_outputs_carry_the_round_end_on_perf_counter(pair):
    import time

    target, draft = pair
    eng = Engine(target, draft, EngineConfig(**MODES["chain"]))
    prompts, sps = _requests()
    eng.add_request(prompts[0], sps[0])
    t0 = time.perf_counter()
    outs = eng.step()
    t1 = time.perf_counter()
    assert outs and all(t0 < o.round_end < t1 for o in outs)


def test_server_observes_stream_lag_once_per_written_output(pair):
    target, draft = pair

    async def scenario():
        tracer = Tracer()
        engine = Engine(target, draft, EngineConfig(max_batch=2, page_size=8),
                        trace=tracer)
        server = CompletionServer(AsyncEngine(engine, max_queued=8))
        await server.start(port=0)
        task = asyncio.ensure_future(server.serve_forever())
        try:
            prompt = [int(t) for t in _requests()[0][0]]
            status, _, chunks = await hc.sse_request(
                server.port, {"prompt": prompt, "max_tokens": 7}
            )
            assert status == 200 and len(chunks) == 7
        finally:
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)
            await server.stop()
        return engine.metrics, tracer

    metrics, tracer = asyncio.run(scenario())
    writes = [ev for ev in tracer.events()
              if ev["ph"] == "X" and (ev["track"], ev["name"]) == ("server", "sse_write")]
    lag = metrics.get("stream_lag_seconds")
    assert len(writes) >= 2
    assert lag.value() == len(writes)  # one observation per written output
    assert sum(ev["args"]["tokens"] for ev in writes) == 7
    assert 0 < lag.sum_value() < 60
    posts = [ev for ev in tracer.events()
             if ev["ph"] == "X" and (ev["track"], ev["name"]) == ("async", "post")]
    assert posts and sum(ev["args"]["outputs"] for ev in posts) >= len(writes)
