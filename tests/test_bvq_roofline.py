"""``bvq_matmul_roofline`` (``chipbench/metrics/bvq_matmul_roofline.py``) on
synthetic traces: the bytes a draft step's BVQ linears move, and which
kernel calls and draft steps of the window it counts."""
from __future__ import annotations

import json

import pytest

from chipbench import trace as tr
from chipbench.harness import Ctx
from chipbench.peaks import peaks_for
from chipbench.spec import ROOT, Bench

E = tr.Event


def _config(name):
    return json.loads((ROOT / f"chipbench/configs/{name}.json").read_text())


def _read(trace, config="dscoder-6.7b-spec"):
    ctx = Ctx(t_open=0.0, t_close=1.0, records=[], counters_open={}, counters_close={},
              model=_config(config), peaks=peaks_for("TPU v5 lite"), trace=trace)
    return Bench().reader("bvq_matmul_roofline")(ctx)


def _metric():
    import importlib.util

    path = ROOT / "chipbench/metrics/bvq_matmul_roofline.py"
    spec = importlib.util.spec_from_file_location("bvq_matmul_roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_step_bytes_of_the_deepseek_coder_draft():
    """deepseek-coder-1.3b, 24 layers of d 2048, f 5504, 16 / 16 heads of
    128; v 8, C 256, 128-column blocks: the weights' bytes alone."""
    m = _metric()
    cfg = _config("dscoder-6.7b-spec")
    shapes = [(2048, 2048)] * 4 + [(2048, 5504)] * 2 + [(5504, 2048)]
    assert sorted(m.linear_shapes(cfg["draft"])) == sorted(shapes)
    per_linear = lambda k, n: (k * n // 2  # int32 index per 8 weights
                               + (n // 128) * 8 * 2 * 128 * 4  # lane tables
                               + n * 4)  # scales
    want = 24 * sum(per_linear(k, n) for k, n in shapes)
    assert m.step_bytes(cfg) == want
    # the indices are most of it, tables and scales under a tenth
    assert 24 * sum(k * n // 2 for k, n in shapes) / want > 0.9
    # Qwen3-0.6B: 28 layers of d 1024, f 3072, 16 / 8 heads of 128
    q = [(1024, 2048), (1024, 1024), (1024, 1024), (2048, 1024)] + [(1024, 3072)] * 2 \
        + [(3072, 1024)]
    assert m.step_bytes(_config("qwen3-8b-spec")) == 28 * sum(per_linear(k, n) for k, n in q)


def test_roofline_counts_kernel_calls_of_draft_steps_inside_the_window():
    """Window [0, 1000] ns.  The draft step (jit_step(7), four runs) has
    three runs inside the window and one across its close; the verify
    (jit_step(9)) runs once.  Only the kernel calls inside the three whole
    draft steps count: not the one in a prefill, nor the one
    in the step cut by the close."""
    m = _metric()
    mods = [E("jit_step(7)", 100, 100), E("jit_step(7)", 300, 100), E("jit_step(7)", 500, 100),
            E("jit_step(7)", 950, 100), E("jit_step(9)", 700, 100),
            E("jit__prefill(3)", 820, 100)]
    ops = [E("bvq_matmul.4", 110, 20), E("bvq_matmul.4", 125, 20),  # overlap: union 35
           E("bvq_matmul.5", 310, 30), E("bvq_matmul.4", 510, 10),
           E("fusion.2", 520, 50),
           E("bvq_matmul.4", 960, 30),  # the step cut by the close
           E("bvq_matmul.9", 830, 40)]  # a prefill call
    dt = tr.DeviceTrace(ops=ops, modules=mods, host=[], t0_ns=0, t1_ns=1000)
    cfg = _config("dscoder-6.7b-spec")
    want = 100.0 * m.step_bytes(cfg) * 3 / ((35 + 30 + 10) * 1e-9) / 819e9
    assert _read(dt) == pytest.approx(want)


def test_roofline_finds_nothing_without_kernel_calls():
    mods = [E("jit_step(7)", 100 * i, 50) for i in range(4)] + [E("jit_step(9)", 600, 50)]
    gather = tr.DeviceTrace(ops=[E("fusion.111", 110, 20)], modules=mods, host=[],
                            t0_ns=0, t1_ns=1000)
    assert _read(gather) is None
    assert _read(None) is None
    one_program = tr.DeviceTrace(ops=[E("bvq_matmul.1", 110, 20)], modules=mods[:1], host=[],
                                 t0_ns=0, t1_ns=1000)
    assert _read(one_program) is None
