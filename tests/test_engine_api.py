"""Stepwise Engine API: mid-flight admission, abort, streaming outputs,
per-request sampling determinism, impl auto-selection, deprecation hygiene.

The closed-batch parity suites (test_serving_batch.py / test_serving_paged.py)
cover greedy bit-identity through the deprecated wrappers; this module covers
what only the stepwise redesign can do — requests joining and leaving a LIVE
batch — plus the sampled (temperature > 0) path.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.speculative import SDConfig, sd_generate
from repro.launch.serve import build_pair
from repro.serving import (
    Engine,
    EngineConfig,
    SamplingParams,
    resolve_paged_attn_impl,
)
from repro.serving.engine import make_interface
from repro.serving.request import RequestState


def _prompts(n, seed=0, vocab=512):
    rng = np.random.RandomState(seed)
    return [
        rng.randint(0, vocab, size=rng.randint(2, 7)).astype(np.int32)
        for _ in range(n)
    ]


@pytest.fixture(scope="module")
def pair():
    return build_pair(seed=0, s_max=128, quantize=False)


def _sd_ref(target, draft, prompt, max_tokens, dl=3):
    """Pre-redesign reference: the dense-cache sd_generate driver."""
    toks, _ = sd_generate(
        jax.random.PRNGKey(0),
        make_interface(target), target.params,
        make_interface(draft), draft.params,
        jnp.asarray(np.asarray(prompt)[None]),
        SDConfig(draft_len=dl, temperature=0.0, max_tokens=max_tokens),
    )
    return toks


# ---------------------------------------------------------------------------
# Mid-flight admission (the acceptance criterion)
# ---------------------------------------------------------------------------


def test_midflight_admission_without_drain(pair):
    """A request added after the batch has run rounds is prefilled and
    scheduled on the NEXT step — the active requests keep decoding
    throughout, and everyone's output matches the solo reference."""
    target, draft = pair
    p0, p1, p2 = _prompts(3, seed=1)
    eng = Engine(target, draft, EngineConfig(max_batch=3, page_size=8, draft_len=3))
    r0 = eng.add_request(p0, SamplingParams(max_tokens=16))
    r1 = eng.add_request(p1, SamplingParams(max_tokens=16))
    eng.step()
    eng.step()
    assert eng.request(r0).rounds == 2 and not eng.request(r0).done
    # late arrival: joins the live batch
    r2 = eng.add_request(p2, SamplingParams(max_tokens=8))
    assert eng.request(r2).state is RequestState.QUEUED
    eng.step()
    # admitted AND ran its first round while r0/r1 kept decoding (no drain)
    assert eng.request(r2).state is not RequestState.QUEUED
    assert eng.request(r2).rounds == 1
    assert eng.request(r0).rounds == 3 and not eng.request(r0).done
    while eng.has_unfinished():
        eng.step()
    for rid, p, m in [(r0, p0, 16), (r1, p1, 16), (r2, p2, 8)]:
        ref = _sd_ref(target, draft, p, m)
        assert bool(jnp.all(eng.output_tokens(rid) == ref)), f"request {rid}"


def test_step_streams_incremental_outputs(pair):
    """Each step's RequestOutputs carry exactly the newly verified tokens;
    their concatenation is the final output; finish arrives once with
    reason "length"."""
    target, draft = pair
    prompts = _prompts(2, seed=2)
    eng = Engine(target, draft, EngineConfig(max_batch=2, page_size=8, draft_len=2))
    rids = [eng.add_request(p, SamplingParams(max_tokens=6)) for p in prompts]
    streamed = {rid: [] for rid in rids}
    finishes = {rid: [] for rid in rids}
    while eng.has_unfinished():
        for out in eng.step():
            streamed[out.request_id].extend(out.new_token_ids)
            assert out.prompt_token_ids == [int(t) for t in
                                            prompts[out.request_id]]
            assert out.token_ids == streamed[out.request_id]  # cumulative
            if out.finished:
                finishes[out.request_id].append(out.outputs[0].finish_reason)
    for rid in rids:
        assert streamed[rid] == [int(t) for t in eng.output_tokens(rid)]
        assert len(streamed[rid]) == 6
        assert finishes[rid] == ["length"]


def test_step_on_idle_engine_is_a_noop(pair):
    target, draft = pair
    eng = Engine(target, draft, EngineConfig(max_batch=2))
    assert eng.step() == []
    assert not eng.has_unfinished()


# ---------------------------------------------------------------------------
# Abort
# ---------------------------------------------------------------------------


def test_abort_active_returns_pages_and_spares_the_rest(pair):
    target, draft = pair
    p0, p1, p2 = _prompts(3, seed=3)
    eng = Engine(target, draft, EngineConfig(max_batch=3, page_size=8, draft_len=3))
    r0 = eng.add_request(p0, SamplingParams(max_tokens=12))
    r1 = eng.add_request(p1, SamplingParams(max_tokens=12))
    r2 = eng.add_request(p2, SamplingParams(max_tokens=12))
    eng.step()
    t_stats, d_stats = eng.pool_stats()
    used_before = t_stats.used_pages
    assert used_before > 0
    victim_pages = len(eng.request(r1).t_seq.pages)
    assert eng.abort(r1) is True
    t_stats, _ = eng.pool_stats()
    # pages came back immediately, not at drain time
    assert t_stats.used_pages == used_before - victim_pages
    assert eng.request(r1).state is RequestState.FINISHED
    assert eng.request(r1).finish_reason == "abort"
    assert eng.abort(r1) is False  # already finished
    assert eng.abort(999) is False  # unknown id
    while eng.has_unfinished():
        eng.step()
    for rid, p in [(r0, p0), (r2, p2)]:
        ref = _sd_ref(target, draft, p, 12)
        assert bool(jnp.all(eng.output_tokens(rid) == ref)), f"request {rid}"
    t_stats, d_stats = eng.pool_stats()
    assert t_stats.used_pages == 0 and t_stats.reserved_pages == 0
    assert d_stats.used_pages == 0 and d_stats.reserved_pages == 0


def test_abort_queued_request(pair):
    target, draft = pair
    p0, p1 = _prompts(2, seed=4)
    eng = Engine(target, draft, EngineConfig(max_batch=1, page_size=8, draft_len=2))
    r0 = eng.add_request(p0, SamplingParams(max_tokens=8))
    r1 = eng.add_request(p1, SamplingParams(max_tokens=8))
    eng.step()
    assert eng.request(r1).state is RequestState.QUEUED
    assert eng.abort(r1) is True
    assert eng.request(r1).finish_reason == "abort"
    while eng.has_unfinished():
        eng.step()
    assert list(eng.output_tokens(r1)) == []  # never decoded
    ref = _sd_ref(target, draft, p0, 8, dl=2)
    assert bool(jnp.all(eng.output_tokens(r0) == ref))


# ---------------------------------------------------------------------------
# Sampled speculative decoding (temperature > 0)
# ---------------------------------------------------------------------------


def test_sampled_deterministic_across_runs_and_batch_compositions(pair):
    """Fixed per-request seed => the same tokens whether the request runs
    solo or inside a batch of 4, and across repeated runs."""
    target, draft = pair
    prompts = _prompts(4, seed=5)
    sp0 = SamplingParams(temperature=0.8, seed=123, max_tokens=10)
    others = [SamplingParams(temperature=0.8, seed=200 + i, max_tokens=10)
              for i in range(3)]

    solo = Engine(target, draft, EngineConfig(max_batch=1, page_size=8))
    out_solo, _ = solo.run([prompts[0]], sp0)

    def batch4():
        eng = Engine(target, draft, EngineConfig(max_batch=4, page_size=8))
        return eng.run(prompts, [sp0] + others)

    out_a, _ = batch4()
    out_b, _ = batch4()
    assert bool(jnp.all(out_a[0] == out_solo[0])), "batch composition leaked"
    for a, b in zip(out_a, out_b):
        assert bool(jnp.all(a == b)), "sampled decode not reproducible"
    # a different seed must decouple the stream
    eng = Engine(target, draft, EngineConfig(max_batch=1, page_size=8))
    out_seed2, _ = eng.run(
        [prompts[0]], SamplingParams(temperature=0.8, seed=124, max_tokens=10)
    )
    assert not bool(jnp.all(out_seed2[0] == out_solo[0]))
    # and temperature>0 actually samples (differs from greedy)
    greedy = _sd_ref(target, draft, prompts[0], 10)
    assert not bool(jnp.all(out_solo[0] == greedy))


def test_mixed_greedy_and_sampled_batch_keeps_greedy_bit_identical(pair):
    """A sampled neighbour in the batch must not perturb a greedy row."""
    target, draft = pair
    prompts = _prompts(2, seed=6)
    eng = Engine(target, draft, EngineConfig(max_batch=2, page_size=8))
    outs, _ = eng.run(prompts, [
        SamplingParams(max_tokens=8),  # greedy
        SamplingParams(temperature=1.0, seed=7, max_tokens=8),
    ])
    ref = _sd_ref(target, draft, prompts[0], 8)
    assert bool(jnp.all(outs[0] == ref))


def test_top_k_one_is_greedy(pair):
    """top_k=1 collapses both draft and target distributions to the argmax,
    so sampled decoding degenerates to the greedy output exactly."""
    target, draft = pair
    prompts = _prompts(1, seed=8)
    eng = Engine(target, draft, EngineConfig(max_batch=1, page_size=8))
    outs, _ = eng.run(
        prompts, SamplingParams(temperature=0.7, top_k=1, seed=42, max_tokens=8)
    )
    ref = _sd_ref(target, draft, prompts[0], 8)
    assert bool(jnp.all(outs[0] == ref))


def test_self_draft_sampled_accepts_everything(pair):
    """draft == target => q == p, so the rejection rule accepts every
    draft token (u*q < p for u in [0,1)) — a direct check of the lossless
    acceptance rule's host implementation."""
    target, _ = pair
    prompts = _prompts(2, seed=9)
    eng = Engine(target, target, EngineConfig(max_batch=2, page_size=8))
    _, summary = eng.run(
        prompts, SamplingParams(temperature=0.9, seed=3, max_tokens=10)
    )
    assert summary["acceptance_rate"] == 1.0


# ---------------------------------------------------------------------------
# paged_attn_impl auto-selection
# ---------------------------------------------------------------------------


def test_resolve_paged_attn_impl_branches():
    assert resolve_paged_attn_impl(None, backend="tpu") == "pallas"
    assert resolve_paged_attn_impl("auto", backend="tpu") == "pallas"
    assert resolve_paged_attn_impl(None, backend="cpu") == "gather"
    # the kernel is TPU-dialect Pallas: auto must NOT hand it to GPU
    assert resolve_paged_attn_impl("auto", backend="gpu") == "gather"
    # an explicit impl always wins over the backend
    assert resolve_paged_attn_impl("gather", backend="tpu") == "gather"
    assert resolve_paged_attn_impl("pallas", backend="cpu") == "pallas"
    assert resolve_paged_attn_impl(None) == (
        "pallas" if jax.default_backend() == "tpu" else "gather"
    )
    with pytest.raises(ValueError, match="paged_attn_impl"):
        resolve_paged_attn_impl("floppy")


def test_engine_config_impl_override_end_to_end(pair):
    """EngineConfig.paged_attn_impl="pallas" routes every decode/verify
    through the paged Pallas kernel (interpret mode on CPU) and keeps the
    greedy tokens."""
    target, draft = pair
    prompts = _prompts(2, seed=10)
    ref_eng = Engine(target, draft, EngineConfig(max_batch=2, page_size=8))
    ref_outs, _ = ref_eng.run(prompts, SamplingParams(max_tokens=6))
    eng = Engine(
        target, draft,
        EngineConfig(max_batch=2, page_size=8, paged_attn_impl="pallas"),
    )
    assert eng.target.paged_attn_impl == "pallas"
    outs, _ = eng.run(prompts, SamplingParams(max_tokens=6))
    for a, b in zip(outs, ref_outs):
        assert bool(jnp.all(a == b))


# ---------------------------------------------------------------------------
# Validation + deprecation hygiene
# ---------------------------------------------------------------------------


def test_add_request_validates_against_max_model_len(pair):
    target, draft = pair
    eng = Engine(target, draft, EngineConfig(max_batch=1, max_model_len=32))
    with pytest.raises(ValueError, match="max_model_len"):
        eng.add_request(np.arange(2, 12), SamplingParams(max_tokens=64))
    with pytest.raises(ValueError, match="max_model_len"):
        Engine(target, draft, EngineConfig(max_model_len=4096))  # > s_max


def test_sampling_params_validation():
    with pytest.raises(ValueError):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError):
        SamplingParams(max_tokens=0)
    with pytest.raises(dataclasses.FrozenInstanceError):
        sp = SamplingParams()
        sp.temperature = 1.0


def test_deprecated_wrappers_warn_exactly_once(pair):
    from repro.serving import api
    from repro.serving.engine import BatchConfig, serve_batch, serve_sd

    target, draft = pair
    prompts = _prompts(1, seed=11)
    cfg = BatchConfig(max_batch=1, page_size=8, max_tokens=4, draft_len=2)
    for name in ("serve_batch", "serve_sd"):
        api._DEPRECATION_EMITTED.discard(name)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        serve_batch(jax.random.PRNGKey(0), target, draft, prompts, cfg)
        serve_batch(jax.random.PRNGKey(0), target, draft, prompts, cfg)
        serve_sd(
            jax.random.PRNGKey(0), target, draft,
            jnp.asarray(prompts[0][None]),
            SDConfig(draft_len=2, temperature=0.0, max_tokens=4),
        )
        serve_sd(
            jax.random.PRNGKey(0), target, draft,
            jnp.asarray(prompts[0][None]),
            SDConfig(draft_len=2, temperature=0.0, max_tokens=4),
        )
    deps = [w for w in caught if issubclass(w.category, DeprecationWarning)]
    assert sorted(str(w.message).split("(")[0] for w in deps) == [
        "serve_batch", "serve_sd"
    ]


# ---------------------------------------------------------------------------
# top_p (nucleus) sampling through the Engine
# ---------------------------------------------------------------------------


def test_top_p_deterministic_across_batch_compositions(pair):
    """Nucleus sampling keeps the per-request determinism contract: the
    same (prompt, seed, top_p) yields the same tokens solo and batched."""
    target, draft = pair
    prompts = _prompts(3, seed=12)
    sp0 = SamplingParams(temperature=0.8, top_p=0.8, seed=55, max_tokens=8)
    others = [
        SamplingParams(temperature=0.8, top_p=0.9, seed=60 + i, max_tokens=8)
        for i in range(2)
    ]
    solo = Engine(target, draft, EngineConfig(max_batch=1, page_size=8))
    out_solo, _ = solo.run([prompts[0]], sp0)
    eng = Engine(target, draft, EngineConfig(max_batch=3, page_size=8))
    out_batch, _ = eng.run(prompts, [sp0] + others)
    assert bool(jnp.all(out_batch[0] == out_solo[0]))


def test_top_p_tiny_collapses_to_greedy(pair):
    """top_p -> 0 keeps only the argmax in both distributions, so sampled
    decoding degenerates to the greedy output exactly (like top_k=1)."""
    target, draft = pair
    prompts = _prompts(1, seed=13)
    eng = Engine(target, draft, EngineConfig(max_batch=1, page_size=8))
    outs, _ = eng.run(
        prompts,
        SamplingParams(temperature=0.9, top_p=1e-6, seed=17, max_tokens=8),
    )
    ref = _sd_ref(target, draft, prompts[0], 8)
    assert bool(jnp.all(outs[0] == ref))


def test_top_p_self_draft_lossless_acceptance(pair):
    """draft == target with a shared nucleus filter => q' == p', so the
    rejection rule accepts every draft — top_p is lossless end to end."""
    target, _ = pair
    prompts = _prompts(2, seed=14)
    eng = Engine(target, target, EngineConfig(max_batch=2, page_size=8))
    _, summary = eng.run(
        prompts,
        SamplingParams(temperature=0.9, top_p=0.7, seed=5, max_tokens=10),
    )
    assert summary["acceptance_rate"] == 1.0


def test_top_p_validation():
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=1.5)


# ---------------------------------------------------------------------------
# stop sequences (SamplingParams.stop over the detokenized stream)
# ---------------------------------------------------------------------------


def _stop_ref(target, draft, prompt, max_tokens):
    ref = _sd_ref(target, draft, prompt, max_tokens)
    return [int(t) for t in ref]


def _stop_cut(ref, stops):
    """Tokens a stop leaves of `ref`: scan the detokenized stream token by
    token, and at the first token where any stop text has appeared keep
    the tokens whose text ends at or before the earliest match.  A stop
    built from ref[i] can match earlier than i (a repeated token, or text
    that is a suffix of an earlier token's text)."""
    from repro.serving.api import default_detokenize

    text, ends = "", []
    for t in ref:
        text += default_detokenize(t)
        ends.append(len(text))
        hits = [m for m in (text.find(s) for s in stops) if m >= 0]
        if hits:
            return sum(1 for e in ends if e <= min(hits))
    return len(ref)


@pytest.mark.parametrize("par_mode", ["off", "wdos"])
def test_stop_string_truncates_and_frees_pages(pair, par_mode):
    """Generation ends at the first stop match with finish_reason="stop";
    the stop string is excluded from the output; the request's pages
    return through normal retirement — in BOTH round schedulers."""
    target, draft = pair
    prompts = _prompts(2, seed=15)
    ref = _stop_ref(target, draft, prompts[0], 12)
    stop_s = f"{ref[5]} "  # the 6th token's detokenized text
    eng = Engine(target, draft, EngineConfig(
        max_batch=2, page_size=8, par_mode=par_mode,
    ))
    outs, _ = eng.run(prompts, [
        SamplingParams(max_tokens=12, stop=(stop_s,)),
        SamplingParams(max_tokens=12),  # untouched neighbour
    ])
    assert [int(t) for t in outs[0]] == ref[:5]
    assert eng.request(0).finish_reason == "stop"
    # the neighbour is unperturbed by the early retirement
    ref1 = _stop_ref(target, draft, prompts[1], 12)
    assert [int(t) for t in outs[1]] == ref1
    t_stats, d_stats = eng.pool_stats()
    assert t_stats.used_pages == 0 and d_stats.used_pages == 0


def test_stop_string_spanning_token_boundary(pair):
    """A stop string covering two adjacent tokens' text truncates at the
    FIRST token of the match (both are excluded)."""
    target, draft = pair
    prompts = _prompts(1, seed=16)
    ref = _stop_ref(target, draft, prompts[0], 12)
    stop_s = f"{ref[3]} {ref[4]} "  # spans tokens 3 and 4
    eng = Engine(target, draft, EngineConfig(max_batch=1, page_size=8))
    outs, _ = eng.run(prompts, SamplingParams(max_tokens=12, stop=(stop_s,)))
    assert [int(t) for t in outs[0]] == ref[:3]
    assert eng.request(0).finish_reason == "stop"


def test_stop_earliest_of_multiple_stops_wins(pair):
    target, draft = pair
    prompts = _prompts(1, seed=17)
    ref = _stop_ref(target, draft, prompts[0], 12)
    stops = (f"{ref[7]} ", f"{ref[2]} ")
    cut = _stop_cut(ref, stops)
    assert cut <= 2  # the later stop never decides the cut
    eng = Engine(target, draft, EngineConfig(max_batch=1, page_size=8))
    outs, _ = eng.run(prompts, SamplingParams(max_tokens=12, stop=stops))
    assert [int(t) for t in outs[0]] == ref[:cut]
    assert eng.request(0).finish_reason == "stop"


def test_stop_streams_only_surviving_tokens(pair):
    """The per-request sink must never emit a token that the stop
    truncation later removes (within-round holdback)."""
    target, draft = pair
    prompts = _prompts(1, seed=18)
    ref = _stop_ref(target, draft, prompts[0], 12)
    stop_s = f"{ref[4]} "
    cut = _stop_cut(ref, (stop_s,))
    eng = Engine(target, draft, EngineConfig(max_batch=1, page_size=8))
    streamed = []
    eng.add_request(
        prompts[0], SamplingParams(max_tokens=12, stop=(stop_s,)),
        sink=streamed.append,
    )
    while eng.has_unfinished():
        eng.step()
    assert streamed == ref[:cut]


def test_stop_validation_and_custom_detokenizer(pair):
    with pytest.raises(ValueError, match="stop"):
        SamplingParams(stop=("",))
    # a bare string is promoted to a 1-tuple
    assert SamplingParams(stop="x ").stop == ("x ",)
    # a custom detokenizer changes what the stop strings match against
    target, draft = pair
    prompts = _prompts(1, seed=19)
    ref = _stop_ref(target, draft, prompts[0], 8)
    eng = Engine(
        target, draft, EngineConfig(max_batch=1, page_size=8),
        detokenize=lambda t: f"<{t}>",
    )
    outs, _ = eng.run(prompts, SamplingParams(
        max_tokens=8, stop=(f"<{ref[3]}>",),
    ))
    assert [int(t) for t in outs[0]] == ref[:3]


def test_stop_holdback_never_retracts_streamed_tokens():
    """A stop string spanning a ROUND boundary (committed across two
    commit() calls) must not retract tokens already delivered: the
    holdback rule defers at-risk tokens instead (reviewer repro: without
    holdback the sink saw [5, 7] but the final output was [5])."""
    from repro.serving.api import default_detokenize
    from repro.serving.request import Request

    seen = []
    req = Request(
        rid=0, prompt=np.array([1, 2], np.int32), max_new_tokens=16,
        sink=seen.append,
        sampling=SamplingParams(max_tokens=16, stop=("7 9 ",)),
        detokenize=default_detokenize,
    )
    req.commit([5, 7])  # "7 " is a prefix of the stop string: 7 is at risk
    assert seen == [5]
    assert req.take_delta() == [5]
    req.commit([9, 3])  # completes "7 9 " -> stop; 7 was never delivered
    assert req.stop_hit and req.finish_reason == "stop"
    assert [int(t) for t in req.out] == [5]
    assert seen == [5]  # nothing retracted, nothing leaked
    assert req.take_delta() == []
    # and a held token that turns out SAFE flushes late, not never
    seen2 = []
    req2 = Request(
        rid=1, prompt=np.array([1, 2], np.int32), max_new_tokens=16,
        sink=seen2.append,
        sampling=SamplingParams(max_tokens=16, stop=("7 9 ",)),
        detokenize=default_detokenize,
    )
    req2.commit([5, 7])
    assert seen2 == [5]
    req2.commit([8])  # "7 8 " breaks the partial match: 7 becomes safe
    assert seen2 == [5, 7, 8]
    assert req2.take_delta() == [5, 7, 8]


def test_stop_spanning_round_boundary_engine_invariants(pair):
    """End to end with draft_len=1 (1-2 tokens per round) and a 3-token
    stop string: whatever the round split, the concatenated deltas and the
    per-step cumulative token_ids must agree with the final output — no
    retraction through the streaming surface."""
    target, draft = pair
    prompts = _prompts(1, seed=20)
    ref = [int(t) for t in _sd_ref(target, draft, prompts[0], 14, dl=1)]
    stop_s = f"{ref[5]} {ref[6]} {ref[7]} "
    eng = Engine(target, draft, EngineConfig(
        max_batch=1, page_size=8, draft_len=1,
    ))
    eng.add_request(prompts[0], SamplingParams(max_tokens=14, stop=(stop_s,)))
    streamed = []
    while eng.has_unfinished():
        for out in eng.step():
            streamed.extend(out.new_token_ids)
            assert out.token_ids == streamed  # cumulative == deltas so far
    assert streamed == ref[:5]
    assert eng.request(0).finish_reason == "stop"


def test_stop_never_fires_on_overshoot_beyond_budget():
    """A speculative round can commit past max_tokens; those overshoot
    tokens are never delivered, so a stop string completed only by them
    must NOT fire (regression: the scan used to read the overshoot)."""
    from repro.serving.api import default_detokenize
    from repro.serving.request import Request

    req = Request(
        rid=0, prompt=np.array([1, 2], np.int32), max_new_tokens=2,
        sampling=SamplingParams(max_tokens=2, stop=("7 9 ",)),
        detokenize=default_detokenize,
    )
    req.commit([5, 7, 9])  # 9 is overshoot: the user only ever sees "5 7 "
    assert not req.stop_hit
    assert req.finish_reason is None
    assert req.done  # by budget
    assert req.emittable_len() == 2
    req.finish(step=0)
    assert req.finish_reason == "length"
    assert [int(t) for t in req.out] == [5, 7]
