"""The readers of the engine's profiler spans: ``sampling_idle_share`` on
synthetic traces, on a trace the profiler recorded on the CPU, and in a
traced run of the harness at smoke widths."""
from __future__ import annotations

import time

import numpy as np
import pytest

from chipbench import harness, smoke
from chipbench import trace as tr
from chipbench.peaks import PEAKS
from chipbench.spec import Bench

E = tr.Event
OPS = [E("fusion.1", 0, 40), E("fusion.2", 60, 40)]  # idle 40-60 of 0-100


def _ctx(trace):
    return harness.Ctx(t_open=0.0, t_close=1.0, records=[], counters_open={},
                       counters_close={}, model={}, peaks={}, trace=trace)


@pytest.mark.parametrize("host,expect", [
    ([E("engine.draft_pull", 35, 30)], 20.0),  # the whole gap under a pull
    ([E("engine.accept", 50, 30)], 10.0),  # half of it
    ([E("engine.draft_pull", 40, 10), E("engine.draft_sample", 45, 15)], 20.0),
    ([E("engine.verify_pull#bytes=64#", 55, 10)], 5.0),  # name before '#'
    ([E("engine.accept", 0, 30)], 0.0),  # a span over busy time only
    ([E("engine.table_upload", 40, 20)], None),  # not a sampling span
    ([E("np.asarray(jax.Array)", 40, 20)], None),  # a program without spans
])
def test_sampling_idle_share_on_a_synthetic_window(host, expect):
    host = [E(tr.WINDOW_SPAN, 0, 100)] + host
    dt = tr.DeviceTrace(ops=OPS, modules=[], host=host, t0_ns=0, t1_ns=100)
    got = Bench().reader("sampling_idle_share")(_ctx(dt))
    assert got == (None if expect is None else pytest.approx(expect))


def test_sampling_idle_share_finds_nothing_without_a_trace():
    assert Bench().reader("sampling_idle_share")(_ctx(None)) is None


def test_sampling_idle_share_of_a_recorded_engine_window(tmp_path):
    import jax

    from repro.launch.serve import build_pair
    from repro.serving import Engine, EngineConfig, SamplingParams

    target, draft = build_pair(seed=0, s_max=64, quantize=False)
    eng = Engine(target, draft, EngineConfig(max_batch=2, page_size=8))
    rng = np.random.RandomState(2)
    for i in range(2):
        eng.add_request(np.asarray(rng.randint(0, 512, size=5), np.int32),
                        SamplingParams(temperature=1.0, seed=i, max_tokens=8))
    eng.step()  # compiles outside the window
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        while eng.has_unfinished():
            eng.step()
    jax.profiler.stop_trace()
    dt = tr.read(tr.find_xplane(str(tmp_path)))
    ctx = _ctx(dt)
    share = Bench().reader("sampling_idle_share")(ctx)
    idle = Bench().reader("device_idle_share")(ctx)
    assert share is not None and 0 < share <= idle


def test_traced_harness_run_reports_sampling_idle_share(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "peaks_for", lambda kind: PEAKS["TPU v5 lite"])
    root = smoke.make_root(tmp_path)
    res = harness.run("qwen3-8b.chat", 6, 2.0, True, time.perf_counter(),
                      root=root, compile_cache=False)
    m = res["metrics"]
    assert res["correct"] is True
    assert 0 < m["sampling_idle_share"]["value"] <= m["device_idle_share"]["value"]
    assert m["sampling_idle_share"]["unit"] == "%"
