"""Unit tests of the benchmark's own arithmetic: trace reduction, operation
count, peaks table, traffic generation, discovery by name, the served form's
draw, the reference's rotation, unpacking and RoPE scaling, and the
command's refusal of a machine without a TPU.  CPU only; nothing here loads
the TPU library."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import loadgen
from chipbench import trace as tr
from chipbench.peaks import UnknownDevice, peaks_for
from chipbench.spec import ROOT, Bench, SpecError

E = tr.Event
DENSE = Bench().served("dense_w4a8_bvq")


def test_union_merges_overlaps_and_clips_to_the_window():
    evs = [E("a", 0, 10), E("b", 5, 10), E("c", 30, 5), E("d", 100, 50)]
    assert tr.union_ns(evs) == 15 + 5 + 50
    assert tr.union_ns(evs, 8, 120) == 7 + 5 + 20


def test_idle_gaps_are_the_complement_longest_first():
    evs = [E("a", 10, 10), E("b", 15, 10), E("c", 40, 8)]
    gaps = tr.idle_gaps(evs, 0, 60)
    assert gaps == [(25, 40), (48, 60), (0, 10)]
    assert sum(e - s for s, e in gaps) + tr.union_ns(evs, 0, 60) == 60


def test_gap_label_is_the_host_span_overlapping_most():
    host = [E(tr.WINDOW_SPAN, 0, 100), E("chipbench.send", 20, 3), E("PjitFunction(step)", 21, 30)]
    labels = tr.label_gaps([(20, 30), (80, 90)], host)
    assert labels[0][0] == "PjitFunction(step)"
    assert labels[1] == ("idle", pytest.approx(10e-9))


def test_busy_and_idle_of_a_recorded_window():
    ops = [E("fusion.1", 0, 40), E("paged_decode_attention_pallas.2", 40, 20),
           E("fusion.1", 70, 10)]
    dt = tr.DeviceTrace(ops=ops, modules=[], host=[], t0_ns=0, t1_ns=100)
    assert dt.busy_ns() == 70
    assert tr.top_ops(ops, [])[0] == ("?/fusion.1", pytest.approx(50e-9))


def test_op_names_are_taken_from_the_hlo_text():
    text = "%paged_decode_attention_pallas.9 = f32[16,8,2,128]{3,2,1,0} custom-call(s32[16,129] %p)"
    assert tr.op_name(text) == "paged_decode_attention_pallas.9"
    assert tr.op_name("copy.3") == "copy.3"


def test_nested_operations_count_once_by_self_time():
    # a while loop (0-100) holding two fusions; a fusion after it
    ops = [E("while.5", 0, 100), E("fusion.1", 10, 30), E("fusion.2", 50, 40),
           E("fusion.3", 120, 5)]
    own = {e.name: t for e, t in tr.self_times(ops)}
    assert own == {"while.5": 30, "fusion.1": 30, "fusion.2": 40, "fusion.3": 5}
    mods = [E("jit_step(11)", 0, 110), E("jit__prefill(4)", 115, 20)]
    top = dict(tr.top_ops(ops, mods))
    assert top["jit_step/fusion.2"] == pytest.approx(40e-9)
    assert top["jit__prefill/fusion.3"] == pytest.approx(5e-9)
    assert sum(top.values()) == pytest.approx(tr.union_ns(ops) * 1e-9)


def test_steps_told_apart_by_how_often_they_run():
    mods = [E("jit_step(7)", i * 10, 2) for i in range(8)]
    mods += [E("jit_step(9)", i * 40 + 5, 6) for i in range(2)]
    mods += [E("jit__prefill(3)", 300, 50)]
    assert tr.classify_steps(mods, "jit_step") == {"verify": "jit_step(9)", "draft": "jit_step(7)"}
    assert tr.classify_steps(mods[:8], "jit_step") is None
    assert tr.program_labels(mods)["jit_step(9)"] == "jit_step[verify]"


def _ctx(trace=None, **kw):
    from chipbench.harness import Ctx

    base = dict(t_open=0.0, t_close=10.0, records=[], counters_open={"drafted": 0, "accepted": 0},
                counters_close={"drafted": 300, "accepted": 120},
                model=json.loads((ROOT / "chipbench/configs/qwen3-8b-spec.json").read_text()),
                peaks=peaks_for("TPU v5 lite"), trace=trace, served=DENSE)
    base.update(kw)
    return Ctx(**base)


@pytest.mark.parametrize("name,expect", [
    ("acceptance_rate", 0.4),
    ("device_idle_share", 30.0),
    ("paged_attn_busy_share", 100.0 * 20 / 70),
    ("verify_device_ms", 6e-6),
    ("draft_device_ms", 2e-6),
])
def test_metric_readers_on_a_synthetic_window(name, expect):
    ops = [E("fusion.1", 0, 40), E("paged_decode_attention_pallas.9", 40, 20),
           E("fusion.4", 70, 10)]
    mods = [E("jit_step(7)", i, 2) for i in range(4)] + [E("jit_step(9)", 9, 6)]
    dt = tr.DeviceTrace(ops=ops, modules=mods, host=[], t0_ns=0, t1_ns=100)
    read = Bench().reader(name)
    assert read(_ctx(trace=dt)) == pytest.approx(expect)


def test_stream_gap_p95_reads_the_gaps_that_end_in_the_window():
    from chipbench.harness import Record

    req = loadgen.Request(0, 0.0, [1] * 4, 30, 0.0, 1.0, 0, 0)
    # gaps ending in [0, 10): 1.0 (ends at 1.0) and 0.4 twenty times
    inside = Record(req=req, due=0.0, times=[0.0, 1.0] + [1.0 + 0.4 * i for i in range(1, 21)],
                    tokens=[1] * 22)
    outside = Record(req=req, due=0.0, times=[-5.0, -1.0, 12.0], tokens=[1, 2, 3])
    read = Bench().reader("stream_gap_p95_s")
    gaps = [1.0] + [0.4] * 20
    assert read(_ctx(records=[inside, outside])) == pytest.approx(np.percentile(gaps, 95))
    assert read(_ctx(records=[outside])) is None


def test_trace_readers_find_nothing_without_a_trace():
    bench = Bench()
    for name in ("verify_device_ms", "draft_device_ms", "paged_attn_busy_share",
                 "device_idle_share"):
        assert bench.reader(name)(_ctx()) is None


def test_operation_count_of_the_target():
    m = json.loads((ROOT / "chipbench/configs/qwen3-8b-spec.json").read_text())
    per_layer = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096 + 3 * 4096 * 12288
    n = 36 * per_layer + 4096 * 151936
    assert DENSE.matmul_params(m) == n
    # one token at position 0 attends to one key; one at 99 to a hundred
    assert DENSE.model_ops(m, [0]) == 2 * n + 4 * 36 * 32 * 128
    assert DENSE.model_ops(m, [0, 99]) == 4 * n + 4 * 36 * 32 * 128 * 101


def _digest(tree) -> str:
    import hashlib

    import jax

    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = np.asarray(x)
        h.update(f"{jax.tree_util.keystr(path)}:{x.dtype}:{x.shape}".encode())
        h.update(x.tobytes())
    return h.hexdigest()[:16]


def _qwen3_smoke():
    return DENSE.smoke(json.loads((ROOT / "chipbench/configs/qwen3-8b-spec.json").read_text()))


def test_dense_draw_is_the_weights_the_qwen3_cell_drew_before():
    """Digests of ``weights.w4a8_target`` and ``weights.bvq_draft`` (the
    modules this one replaced) at the Qwen3 pair's smoke sizes, each drawn
    from its half of ``PRNGKey(7)`` split in two."""
    import jax

    target, draft = DENSE.draw(jax.random.PRNGKey(7), _qwen3_smoke())
    assert (_digest(target), _digest(draft)) == ("37e39a95355ca094", "90e3b6afd6c121ab")


def test_draft_head_is_tied_or_drawn_on_its_own_as_the_configuration_says():
    import jax
    import jax.numpy as jnp

    cfg = _qwen3_smoke()
    _, tied = DENSE.draw(jax.random.PRNGKey(3), cfg)
    cfg["draft"]["tie_word_embeddings"] = False
    _, untied = DENSE.draw(jax.random.PRNGKey(3), cfg)
    assert tied["head"].dtype == untied["head"].dtype == jnp.bfloat16
    assert tied["head"].shape == untied["head"].shape == (64, 512)
    np.testing.assert_array_equal(np.asarray(tied["head"]), np.asarray(tied["embed"]).T)
    # the other leaves are drawn as before; only the head is new
    np.testing.assert_array_equal(np.asarray(untied["embed"]), np.asarray(tied["embed"]))
    assert not np.array_equal(np.asarray(untied["head"]), np.asarray(untied["embed"]).T)
    rms = float(np.sqrt(np.mean(np.asarray(untied["head"], np.float32) ** 2)))
    assert rms == pytest.approx(1 / np.sqrt(64), rel=0.1)


def test_program_config_passes_the_sizes_and_refuses_unknown_rope_scaling():
    cfg = json.loads((ROOT / "chipbench/configs/dscoder-6.7b-spec.json").read_text())
    for m in (cfg, cfg["draft"]):
        m.pop("rope_scaling")
    t = DENSE.program_config(cfg, "target")
    assert (t.n_layers, t.d_model, t.n_heads, t.n_kv, t.d_ff, t.vocab, t.hd) == (
        32, 4096, 32, 32, 11008, 32256, 128)
    assert t.rope_theta == 100000.0 and not t.qk_norm and t.name == "dscoder-6.7b-spec"
    d = DENSE.program_config(cfg, "draft")
    assert (d.n_layers, d.d_model, d.n_heads, d.n_kv, d.d_ff, d.name) == (
        24, 2048, 16, 16, 5504, "dscoder-6.7b-spec-draft")
    cfg["rope_scaling"] = {"type": "yarn", "factor": 4.0}
    with pytest.raises(SpecError):
        DENSE.program_config(cfg, "target")


def test_reference_rope_divides_positions_by_the_linear_factor():
    ref = Bench().reference("dense_w4a8")
    x = np.random.default_rng(2).standard_normal((7, 2, 8)).astype(np.float32)
    half, theta = 4, 100000.0
    inv = theta ** (-np.arange(half) / half)
    for factor in (1.0, 4.0):
        ang = (np.arange(7) / factor)[:, None, None] * inv
        x1, x2 = x[..., :half], x[..., half:]
        want = np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                               x2 * np.cos(ang) + x1 * np.sin(ang)], axis=-1)
        np.testing.assert_allclose(np.asarray(ref._rope(x, theta, factor)), want, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(ref._rope(x, theta, 1.0)),
                                  np.asarray(ref._rope(x, theta)))
    assert ref._rope_factor({}) == 1.0
    assert ref._rope_factor({"rope_scaling": {"type": "linear", "factor": 4.0}}) == 4.0
    with pytest.raises(ValueError):
        ref._rope_factor({"rope_scaling": {"rope_type": "yarn", "factor": 4.0}})


def test_step_mfu_counts_prompt_and_output_tokens_of_the_window():
    from chipbench.harness import Record

    m = json.loads((ROOT / "chipbench/configs/qwen3-8b-spec.json").read_text())
    req = loadgen.Request(0, 0.0, [1] * 10, 3, 0.0, 1.0, 0, 0)
    rec = Record(req=req, due=0.0, times=[1.0, 2.0, 20.0], tokens=[1, 2, 3])
    ctx = _ctx(records=[rec])
    want = DENSE.model_ops(m, list(range(10)) + [10, 11]) / 10.0 / 393e12 * 100
    assert Bench().reader("step_mfu")(ctx) == pytest.approx(want)


def test_unknown_device_has_no_peaks():
    assert peaks_for("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(UnknownDevice):
        peaks_for("cpu")


def test_every_seed_gets_the_same_sizes_in_another_order():
    t = Bench().traffic("chat")
    a = loadgen.schedule(t, 1, 30, 1000)
    b = loadgen.schedule(t, 2**33 + 5, 30, 1000)
    assert len(a) == len(b) == loadgen.count(t, 30)
    for f in (lambda q: len(q.prompt), lambda q: q.max_tokens):
        assert sorted(map(f, a)) == sorted(map(f, b))
    # greedy requests keep their places in the arrival order
    assert [q.greedy for q in a] == [q.greedy for q in b]
    assert 0 < sum(q.greedy for q in a) < len(a)
    # and in the same order: the seed draws only the tokens and sampling seeds
    for f in (lambda q: len(q.prompt), lambda q: q.max_tokens, lambda q: q.due_s):
        assert list(map(f, a)) == list(map(f, b))
    assert [q.prompt for q in a] != [q.prompt for q in b]
    assert [q.seed for q in a] != [q.seed for q in b]
    assert len(set(map(lambda q: q.max_tokens, a))) > 1
    assert [q.max_tokens for q in a] != sorted(q.max_tokens for q in a)
    assert sorted(np.diff([q.due_s for q in a])) != [] and a[0].due_s == 0.0
    grid = set(t["prompt_len"]["grid"])
    assert {len(q.prompt) for q in a} <= grid
    assert all(t["max_tokens"]["min"] <= q.max_tokens <= t["max_tokens"]["max"] for q in a)
    same = loadgen.schedule(t, 1, 30, 1000)
    assert [q.prompt for q in same] == [q.prompt for q in a]


def test_warmup_covers_every_prompt_length_and_decoding_kind():
    t = Bench().traffic("chat")
    warm = loadgen.warmup(t, 3, 1000)
    kinds = {(len(q.prompt), q.greedy) for q in warm}
    assert kinds == {(n, g) for n in t["prompt_len"]["grid"] for g in (True, False)}


TOY_SERVED = '''"""A throwaway served form: the dense pair with an expert axis added."""
from pathlib import Path

import jax.numpy as jnp

from chipbench.spec import _load_module

_dense = _load_module(Path(__file__).with_name("dense_w4a8_bvq.py"), "toy_dense")
CALLS = []


def draw(key, config):
    CALLS.append("draw")
    target, draft = _dense.draw(key, config)
    target["layers"]["experts"] = jnp.zeros((config["num_hidden_layers"], 4, 8), jnp.int8)
    return target, draft


def program_config(config, role):
    CALLS.append("program_config:" + role)
    return _dense.program_config(config, role)


def model_ops(config, positions):
    CALLS.append("model_ops")
    return 1e9 * len(list(positions))


def smoke(config):
    return _dense.smoke(config)
'''


def test_cells_files_and_readers_found_by_name_alone(tmp_path):
    """A throwaway traffic mix, configuration, served form and metric in a
    copy of the benchmark directory are picked up with no edit to the
    harness: the harness draws and shapes the models through the served
    form, and ``step_mfu`` counts through it."""
    import shutil

    from chipbench import harness

    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "chipbench/configs/qwen3-8b-spec.json").read_text())
    cfg["name"] = "other-spec"
    (tmp_path / "chipbench/configs/other-spec.json").write_text(json.dumps(cfg))
    mix = dict(Bench().traffic("chat"), rate_per_s=0.25)
    (tmp_path / "chipbench/traffic/burst.json").write_text(json.dumps(mix))
    (tmp_path / "chipbench/metrics/always_seven.py").write_text("def read(ctx):\n    return 7.0\n")
    spec["configs"].append({"name": "other-spec", "source": "x",
                            "file": "chipbench/configs/other-spec.json", "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "other.burst", "config": "other-spec",
                              "traffic": "burst", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "always_seven", "unit": "s", "better": "lower",
                              "source": "host_clock", "layer": "x", "moves": "output_tokens_per_s",
                              "workloads": ["other.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    bench = Bench(tmp_path)
    w = bench.workload("other.burst")
    assert bench.config(w["config"])["name"] == "other-spec"
    assert bench.traffic(w["traffic"])["rate_per_s"] == 0.25
    names = [m["name"] for m in bench.metrics_for("other.burst", "per_layer")]
    assert "always_seven" in names and "step_mfu" in names
    assert "always_seven" not in [m["name"] for m in bench.metrics_for("qwen3-8b.chat", "per_layer")]
    assert bench.reader("always_seven")(None) == 7.0
    assert bench.reference(bench.config("other-spec")["reference"]).logits_rows
    with pytest.raises(SpecError):
        bench.traffic("missing")

    (tmp_path / "chipbench/served/toy_experts.py").write_text(TOY_SERVED)
    toy_cfg = dict(DENSE.smoke(cfg), name="toy-spec", served="toy_experts")
    (tmp_path / "chipbench/configs/toy-spec.json").write_text(json.dumps(toy_cfg))
    nameless = {k: v for k, v in toy_cfg.items() if k != "served"}
    nameless["name"] = "nameless-spec"
    (tmp_path / "chipbench/configs/nameless-spec.json").write_text(json.dumps(nameless))
    for name in ("toy-spec", "nameless-spec"):
        spec["configs"].append({"name": name, "source": "x", "reduced": [], "why": "x",
                                "file": f"chipbench/configs/{name}.json"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    bench = Bench(tmp_path)
    with pytest.raises(SpecError):
        bench.config("nameless-spec")
    toy_cfg = bench.config("toy-spec")
    toy = bench.served(toy_cfg["served"])
    tparams, engine = harness.build(toy_cfg, dict(mix, engine=dict(max_batch=2, pool_tokens=64)),
                                    3, toy)
    assert toy.CALLS == ["draw", "program_config:target", "program_config:draft"]
    assert tparams["layers"]["experts"].shape == (2, 4, 8)
    assert engine.target.cfg.name == "toy-spec" and engine.draft.cfg.n_layers == 2
    from chipbench.harness import Record

    req = loadgen.Request(0, 0.0, [1] * 10, 2, 0.0, 1.0, 0, 0)
    rec = Record(req=req, due=0.0, times=[1.0, 2.0], tokens=[1, 2])
    ctx = _ctx(records=[rec], model=toy_cfg, served=toy)
    want = 100.0 * 1e9 * 12 / 10.0 / 393e12
    assert bench.reader("step_mfu")(ctx) == pytest.approx(want)
    assert toy.CALLS[-1] == "model_ops"


def test_benchmark_entries_resolve():
    bench = Bench()
    for w in bench.spec["workloads"]:
        cfg, mix = bench.config(w["config"]), bench.traffic(w["traffic"])
        assert cfg["reference"] and mix["check"]["served_gap_limit"] > 0
    for kind in ("end_to_end", "per_layer"):
        for m in bench.spec[kind]:
            if kind == "per_layer":
                assert callable(bench.reader(m["name"]))


@pytest.mark.parametrize("n", [384, 1536, 344])
def test_reference_rotation_is_the_configured_lru_rotation(n):
    from repro.core.rotation import plan_rotation, rotation_matrix

    ref = Bench().reference("dense_w4a8")
    plan = plan_rotation(n)
    h_m = "sylvester" if plan.m & (plan.m - 1) == 0 else "paley1"
    rot = {"n": n, "m": plan.m, "k": plan.k, "kind": plan.kind, "h_m": h_m}
    x = np.random.default_rng(0).standard_normal((3, n)).astype(np.float32)
    got = np.asarray(ref._rotate(x, ref.rotation_block(rot), plan.kind))
    np.testing.assert_allclose(got, x @ rotation_matrix(n), atol=2e-5)


def test_reference_unpacks_the_served_int4_layout():
    import jax.numpy as jnp

    from repro.core.quantization import pack_int4, unpack_int4

    ref = Bench().reference("dense_w4a8")
    codes = jnp.asarray(np.random.default_rng(1).integers(-8, 8, (8, 5)), jnp.int8)
    packed = pack_int4(codes, axis=0)
    np.testing.assert_array_equal(np.asarray(ref._unpack(packed)), np.asarray(codes))
    np.testing.assert_array_equal(np.asarray(ref._unpack(packed)),
                                  np.asarray(unpack_int4(packed, axis=0)))


def test_command_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, str(ROOT / "chipbench/run.py"), "--workload",
                        "qwen3-8b.chat", "--seed", str(2**33 + 1), "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_reading_a_small_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        return (x @ x).sum()

    x = jnp.ones((64, 64))
    step(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for _ in range(3):
            step(x).block_until_ready()
    jax.profiler.stop_trace()
    dt = tr.read(tr.find_xplane(str(tmp_path)))
    mine = [e for e in dt.in_window(dt.ops) if e.module == "jit_step"]
    assert len(mine) >= 3
    assert 0 < dt.busy_ns() <= dt.window_ns
    assert any(h.name == tr.WINDOW_SPAN for h in dt.host)


@pytest.mark.parametrize("share,n,want", [
    (0.0, 6, []), (0.5, 6, [0, 2, 4]), (0.25, 8, [0, 4]), (1.0, 3, [0, 1, 2]),
])
def test_greedy_requests_sit_at_fixed_evenly_spread_places(share, n, want):
    assert list(np.flatnonzero(loadgen.greedy_flags(share, n))) == want


def _rec(index, greedy, tokens, done, max_tokens=4, error=None):
    from chipbench.harness import Record

    req = loadgen.Request(index, 0.0, [1] * (10 + index), max_tokens,
                          0.0 if greedy else 1.0, 1.0, 0, 0)
    return Record(req=req, due=0.0, times=[1.0] * len(tokens), tokens=tokens,
                  finish="length" if done else None, status=200, error=error, done=done)


def test_sample_scores_streams_cut_at_the_close_and_skips_the_rest():
    from chipbench import check

    whole = _rec(0, True, [1, 2, 3, 4], True)
    cut = _rec(1, True, [5, 6], False)
    recs = [whole, cut, _rec(2, False, [1, 2, 3, 4], True),  # sampled
            _rec(3, True, [], False),  # no token yet
            _rec(4, True, [1], False, error="chunk index 3 after 0"),
            _rec(5, True, [1, 2], True)]  # ended short of max_tokens
    sample = check.pick(recs, 3, 100, 8)
    assert sample[0] is whole and {r.req.index for r in sample} == {0, 1}


def test_verdict_holds_each_number_to_its_limit():
    from chipbench import check

    ck = {"served_gap_limit": 0.5, "mean_gap_limit": 0.2, "min_tokens": 3}
    ok, checks = check.verdict([np.array([0.0, 0.1]), np.array([0.4])], 0, ck)
    assert ok and checks["served_gap"]["value"] == pytest.approx(0.4)
    assert checks["mean_gap"] == {"value": pytest.approx(0.5 / 3), "limit": 0.2}
    assert checks["sampled_tokens"] == {"value": 3, "limit": 3}
    assert not check.verdict([np.array([0.0, 0.6, 0.0])], 0, ck)[0]  # a wide gap
    assert not check.verdict([np.array([0.3, 0.3, 0.3])], 0, ck)[0]  # every position off
    assert not check.verdict([np.array([0.0, 0.1])], 0, ck)[0]  # too few tokens
    assert not check.verdict([np.array([0.0, 0.1, 0.0])], 1, ck)[0]  # a broken stream
    ok, checks = check.verdict([], 0, ck)
    assert not ok and checks["served_gap"]["value"] is None
    assert checks["mean_gap"]["value"] is None
