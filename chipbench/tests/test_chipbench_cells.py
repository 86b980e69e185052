"""The harness end to end on the CPU, at smoke widths of the same kind of
model as each cell's configuration (``chipbench/smoke.py``): HTTP serving
through the real engine, the measured window, the trace reduction and the
reference check.  The chip's look is skipped: the runs are on the CPU, and
the peaks row of the v5e stands in for the missing one."""
from __future__ import annotations

import json
import time

import pytest

from chipbench import harness, smoke
from chipbench.peaks import PEAKS
from chipbench.spec import ROOT, Bench


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return smoke.make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def v5e_peaks(monkeypatch):
    monkeypatch.setattr(harness, "peaks_for", lambda kind: PEAKS["TPU v5 lite"])


def _run(root, cell, trace=False, seed=5, keep=None):
    return harness.run(cell, seed, 2.0, trace, time.perf_counter(), root=root,
                       compile_cache=False, keep=keep)


def _serves_correct(res):
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["window_compiles"] == 0
    assert res["checks"]["served_gap"]["value"] <= res["checks"]["served_gap"]["limit"]
    assert res["checks"]["sampled_tokens"]["value"] >= res["checks"]["sampled_tokens"]["limit"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell", smoke.cells())
def test_cell_serves_a_window_and_checks_correct(root, cell):
    _serves_correct(_run(root, cell))


def test_untied_mha_pair_with_its_own_draft_head_serves_correct(tmp_path):
    """deepseek-coder's form: as many K/V heads as query heads, and a draft
    head drawn apart from its embedding.  The program has no RoPE scaling
    factor yet, so this CPU copy leaves ``rope_scaling`` out of both
    models, program and reference alike."""
    bench = Bench()
    real = json.loads((ROOT / "chipbench/configs/dscoder-6.7b-spec.json").read_text())
    for m in (real, real["draft"]):
        del m["rope_scaling"]
    cfg = bench.served(real["served"]).smoke(real)
    assert cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    assert not cfg["draft"]["tie_word_embeddings"]
    root = smoke.make_root(tmp_path)
    smoke.add_cell(root, "dscoder.code", cfg, bench.traffic("code"), True, 1.0)
    _serves_correct(_run(root, "dscoder.code"))


def test_traced_run_reports_per_layer_metrics_and_breakdown(root):
    res = _run(root, "qwen3-8b.chat", trace=True)
    assert res["correct"] is True
    names = set(res["metrics"])
    assert {"acceptance_rate", "round_ms_mean", "device_idle_share", "step_mfu"} <= names
    assert "setup_s" not in names
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]
    assert 0 < res["metrics"]["step_mfu"]["value"] < 100
