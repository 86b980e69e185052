"""The harness end to end on the CPU, at smoke widths of the same shape
ratios as the cells (``chipbench/smoke.py``): HTTP serving through the
real engine, the measured window, the trace reduction and the reference
check.  The chip's look is skipped: the runs are on the CPU, and the peaks
row of the v5e stands in for the missing one."""
from __future__ import annotations

import time

import pytest

from chipbench import harness, smoke
from chipbench.peaks import PEAKS


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return smoke.make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def v5e_peaks(monkeypatch):
    monkeypatch.setattr(harness, "peaks_for", lambda kind: PEAKS["TPU v5 lite"])


def _run(root, cell, trace=False, seed=5, keep=None):
    return harness.run(cell, seed, 2.0, trace, time.perf_counter(), root=root,
                       compile_cache=False, keep=keep)


@pytest.mark.parametrize("cell", sorted(smoke.SMOKE_CELLS))
def test_cell_serves_a_window_and_checks_correct(root, cell):
    res = _run(root, cell)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"output_tokens_per_s", "itl_p95_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["window_compiles"] == 0
    assert res["checks"]["served_gap"]["value"] <= res["checks"]["served_gap"]["limit"]
    assert res["checks"]["sampled_tokens"]["value"] >= res["checks"]["sampled_tokens"]["limit"]
    assert list(res)[-1] == "checks"


def test_traced_run_reports_per_layer_metrics_and_breakdown(root):
    res = _run(root, "qwen3-smoke.chat", trace=True)
    assert res["correct"] is True
    names = set(res["metrics"])
    assert {"acceptance_rate", "round_ms_mean", "device_idle_share", "step_mfu"} <= names
    assert "setup_s" not in names
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]
    assert 0 < res["metrics"]["step_mfu"]["value"] < 100
