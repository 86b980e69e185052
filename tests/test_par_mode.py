"""Fused cross-request PAR execution (EngineConfig.par_mode="wdos").

The contract under test: switching the engine from two-phase rounds
(draft-all-then-verify-all, par_mode="off") to WDOS-planned fused rounds
changes ONLY the grouping of work into dispatches — greedy and sampled
tokens are bit-identical across the modes and to the single-request
reference — while a staggered-admission workload with heterogeneous draft
windows drains in strictly fewer engine rounds (the schedule-quality win
the paper's out-of-order scheduler exists for).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.scheduler import MixedSlotPlan, RowPhase, plan_mixed_slot
from repro.core.speculative import SDConfig, sd_generate
from repro.launch.serve import build_pair
from repro.serving import Engine, EngineConfig, SamplingParams
from repro.serving.engine import make_interface


def _prompts(n, seed=0, vocab=512):
    rng = np.random.RandomState(seed)
    return [
        rng.randint(0, vocab, size=rng.randint(2, 7)).astype(np.int32)
        for _ in range(n)
    ]


@pytest.fixture(scope="module")
def pair():
    return build_pair(seed=0, s_max=128, quantize=False)


@pytest.fixture(scope="module")
def qpair():
    """The paper pair: W4A8 target + BVQ draft."""
    return build_pair(seed=0, s_max=128, quantize=True)


def _drain(target, draft, prompts, sps, par_mode, **cfg_kw):
    eng = Engine(target, draft, EngineConfig(
        max_batch=len(prompts), page_size=8, par_mode=par_mode, **cfg_kw
    ))
    outs, summary = eng.run(prompts, sps)
    return outs, summary


# ---------------------------------------------------------------------------
# Bit-identity across modes (the acceptance criterion)
# ---------------------------------------------------------------------------


def test_fused_greedy_bit_identical_bf16(pair):
    target, draft = pair
    prompts = _prompts(4, seed=1)
    sp = SamplingParams(max_tokens=12)
    off, _ = _drain(target, draft, prompts, sp, "off", draft_len=3)
    wdos, _ = _drain(target, draft, prompts, sp, "wdos", draft_len=3)
    for i, (a, b) in enumerate(zip(off, wdos)):
        assert bool(jnp.all(a == b)), f"request {i} diverged across modes"
    # and both match the pre-batching single-request reference
    for i, p in enumerate(prompts):
        ref, _ = sd_generate(
            jax.random.PRNGKey(0),
            make_interface(target), target.params,
            make_interface(draft), draft.params,
            jnp.asarray(np.asarray(p)[None]),
            SDConfig(draft_len=3, temperature=0.0, max_tokens=12),
        )
        assert bool(jnp.all(wdos[i] == ref)), f"request {i} vs sd_generate"


def test_fused_parity_quantized_mixed_sampling(qpair):
    """W4A8 target + BVQ draft, greedy and sampled rows mixed in one batch:
    fused rounds must reproduce the two-phase tokens bit for bit (sampled
    determinism rides on the per-request key streams, whose (round,
    position) indices the fused scheduler preserves)."""
    target, draft = qpair
    prompts = _prompts(3, seed=2)
    sps = [
        SamplingParams(max_tokens=10),  # greedy
        SamplingParams(temperature=0.8, seed=11, max_tokens=10),
        SamplingParams(temperature=1.1, top_k=12, seed=5, max_tokens=10),
    ]
    off, _ = _drain(target, draft, prompts, sps, "off", draft_len=3)
    wdos, _ = _drain(target, draft, prompts, sps, "wdos", draft_len=3)
    for i, (a, b) in enumerate(zip(off, wdos)):
        assert bool(jnp.all(a == b)), f"request {i} diverged across modes"


def test_fused_parity_adaptive_controllers(pair):
    """Per-request APSD controllers must walk the same mode sequence under
    fused scheduling (observe() fires once per committed window either
    way), so adaptive batches stay bit-identical too."""
    target, draft = pair
    prompts = _prompts(4, seed=3)
    sp = SamplingParams(max_tokens=14)
    kw = dict(adaptive=True, short_dl=2, long_dl=4)
    off, s_off = _drain(target, draft, prompts, sp, "off", **kw)
    wdos, s_wd = _drain(target, draft, prompts, sp, "wdos", **kw)
    for a, b in zip(off, wdos):
        assert bool(jnp.all(a == b))
    assert s_off["acceptance_rate"] == s_wd["acceptance_rate"]


def test_fused_parity_pallas_impl(pair):
    """The fused dispatch drives the paged Pallas kernel (fixed-width
    causally-padded verify window + role masks) to the same tokens."""
    target, draft = pair
    prompts = _prompts(2, seed=4)
    sp = SamplingParams(max_tokens=8)
    ref, _ = _drain(target, draft, prompts, sp, "wdos", draft_len=3)
    pal, _ = _drain(target, draft, prompts, sp, "wdos", draft_len=3,
                    paged_attn_impl="pallas")
    for a, b in zip(ref, pal):
        assert bool(jnp.all(a == b))


def test_fused_sampled_deterministic_across_runs(pair):
    target, draft = pair
    prompts = _prompts(2, seed=5)
    sps = [SamplingParams(temperature=0.9, seed=21, max_tokens=10),
           SamplingParams(temperature=0.9, seed=22, max_tokens=10)]
    a, _ = _drain(target, draft, prompts, sps, "wdos", draft_len=3)
    b, _ = _drain(target, draft, prompts, sps, "wdos", draft_len=3)
    for x, y in zip(a, b):
        assert bool(jnp.all(x == y)), "fused sampled decode not reproducible"


# ---------------------------------------------------------------------------
# Schedule quality: fused rounds <= two-phase rounds, strictly fewer when
# windows are heterogeneous (the out-of-order win)
# ---------------------------------------------------------------------------


def _staggered_drain(target, draft, prompts, par_mode, max_tokens=24):
    """One request admitted per step for the first len(prompts) steps —
    the continuous-arrival pattern that desynchronizes APSD controllers."""
    eng = Engine(target, draft, EngineConfig(
        max_batch=len(prompts), page_size=8,
        adaptive=True, short_dl=2, long_dl=6, par_mode=par_mode,
    ))
    rids = []
    for p in prompts:
        rids.append(eng.add_request(p, SamplingParams(max_tokens=max_tokens)))
        eng.step()
    while eng.has_unfinished():
        eng.step()
    return [eng.output_tokens(r) for r in rids], eng.summary()


def test_fused_strictly_fewer_rounds_on_staggered_workload(pair):
    """Self-draft (acceptance 1.0) sends each controller NONPAR->PAR after
    its first window; staggered admission therefore mixes 2-token and
    6-token windows for several steps.  The fused scheduler lets short-
    window rows commit multiple windows per round while long-window rows
    draft — strictly fewer rounds to drain, same tokens."""
    target, _ = pair
    off, s_off = _staggered_drain(target, target, _prompts(4, seed=6), "off")
    wdos, s_wd = _staggered_drain(target, target, _prompts(4, seed=6), "wdos")
    for a, b in zip(off, wdos):
        assert bool(jnp.all(a == b))
    assert s_wd["rounds"] < s_off["rounds"], (
        f"fused {s_wd['rounds']} rounds vs two-phase {s_off['rounds']}"
    )
    # the telemetry must witness true cross-request overlap: slots where
    # one request verified while another drafted in the same dispatch
    assert s_wd["fused"]["occupancy"] > 0.0
    assert s_wd["fused"]["modeled_overlap_speedup"] > 1.0


def test_fused_rounds_never_exceed_two_phase(pair):
    """On a homogeneous lockstep workload the fused schedule degenerates to
    the two-phase cadence — never worse."""
    target, draft = pair
    prompts = _prompts(4, seed=7)
    sp = SamplingParams(max_tokens=12)
    _, s_off = _drain(target, draft, prompts, sp, "off", draft_len=3)
    _, s_wd = _drain(target, draft, prompts, sp, "wdos", draft_len=3)
    assert s_wd["rounds"] <= s_off["rounds"]


def test_fused_streams_every_token_and_finishes_once(pair):
    """RequestOutput invariants hold under fused rounds: every step's
    new_token_ids concatenate to the final output, cumulative token_ids
    stay consistent, finish arrives exactly once."""
    target, draft = pair
    prompts = _prompts(2, seed=8)
    eng = Engine(target, draft, EngineConfig(
        max_batch=2, page_size=8, draft_len=2, par_mode="wdos"
    ))
    rids = [eng.add_request(p, SamplingParams(max_tokens=7)) for p in prompts]
    streamed = {rid: [] for rid in rids}
    finishes = {rid: 0 for rid in rids}
    while eng.has_unfinished():
        for out in eng.step():
            streamed[out.request_id].extend(out.new_token_ids)
            assert out.token_ids == streamed[out.request_id]
            if out.finished:
                finishes[out.request_id] += 1
    for rid in rids:
        assert streamed[rid] == [int(t) for t in eng.output_tokens(rid)]
        assert len(streamed[rid]) == 7
        assert finishes[rid] == 1
    t_stats, d_stats = eng.pool_stats()
    assert t_stats.used_pages == 0 and d_stats.used_pages == 0


def test_par_mode_validation():
    with pytest.raises(ValueError, match="par_mode"):
        EngineConfig(par_mode="sideways")


# ---------------------------------------------------------------------------
# The planner itself (pure scheduling logic)
# ---------------------------------------------------------------------------


def test_plan_mixed_slot_roles_by_readiness():
    rows = [
        RowPhase(slot=0, window=2, drafted=2),  # full -> verify
        RowPhase(slot=1, window=6, drafted=3),  # mid-window -> draft
        RowPhase(slot=2, window=2, drafted=0),  # fresh -> draft
        RowPhase(slot=3, window=4, drafted=4),  # full -> verify
    ]
    plan = plan_mixed_slot(rows)
    assert plan.verify_rows == (0, 3)
    assert plan.draft_rows == (1, 2)
    assert plan.fused  # cross-request draft/verify co-residency
    assert not plan_mixed_slot(rows[1:3]).fused  # draft-only slot
    assert plan_mixed_slot([]).rows == ()
    solo_verify = plan_mixed_slot([RowPhase(slot=0, window=2, drafted=2)])
    assert solo_verify.verify_rows == (0,) and not solo_verify.fused


# ---------------------------------------------------------------------------
# Compressed KV under fused rounds (EngineConfig.kv_quant="mixed")
# ---------------------------------------------------------------------------


def test_wdos_mixed_fp_int8_batch_bit_matches_two_phase(pair):
    """A batch interleaving dense and int8-stored requests, drained under
    the fused wdos scheduler: token-for-token identical to the same mixed
    batch under two-phase rounds — the per-storage-kind dispatch split
    composes with fused cross-request execution, and sharing one page
    allocator across kinds never leaks between rows."""
    target, draft = pair
    prompts = _prompts(4, seed=23)
    sps = [SamplingParams(max_tokens=12, kv_quant=k)
           for k in ("none", "int8", "none", "int8")]
    off, s_off = _drain(target, draft, prompts, sps, "off",
                        draft_len=3, kv_quant="mixed")
    wdos, s_wdos = _drain(target, draft, prompts, sps, "wdos",
                          draft_len=3, kv_quant="mixed")
    for a, b in zip(off, wdos):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert s_wdos["par_mode"] == "wdos" and s_wdos["kv_quant"] == "mixed"
    # the fp rows are additionally bit-identical to a PURE dense wdos drain
    dense, _ = _drain(target, draft, [prompts[0], prompts[2]],
                      SamplingParams(max_tokens=12), "wdos",
                      draft_len=3, kv_quant="none")
    np.testing.assert_array_equal(np.asarray(wdos[0]), np.asarray(dense[0]))
    np.testing.assert_array_equal(np.asarray(wdos[2]), np.asarray(dense[1]))


def test_bvq_linear_impl_gauge(qpair):
    """The engine reports the path its BVQ draft's linears resolved to:
    the reference off the TPU, the kernel when the caller forces it."""
    import dataclasses

    target, draft = qpair
    eng = Engine(target, draft, EngineConfig(max_batch=2, page_size=8))
    read = lambda impl: eng.metrics.value("bvq_linear_impl", model="draft", impl=impl)
    assert (read("reference"), read("pallas")) == (7, 0)
    assert eng.metrics.value("bvq_linear_impl", model="target", impl="pallas") == 0
    forced = Engine(target, dataclasses.replace(draft, use_pallas=True),
                    EngineConfig(max_batch=2, page_size=8))
    assert forced.metrics.value(
        "bvq_linear_impl", model="draft", impl="pallas") == 7
