"""spec_mode="tree": the engine-level tree-speculation contract.

- greedy tree == greedy chain token stream (lossless, every impl/par_mode)
- sampled tree: run-to-run deterministic, fused wdos == two-phase,
  pallas == gather, mixed per-request kv kinds agree across schedulers
- compaction oracle: after rounds that accepted a NON-leftmost branch the
  pool's committed prefix equals a fresh dense prefill of the same tokens
- low-acceptance A/B: branch fan-out strictly raises accepted tokens/round
"""
import numpy as np
import pytest

from repro.launch.serve import build_pair
from repro.serving import Engine, EngineConfig, SamplingParams


@pytest.fixture(scope="module")
def pair():
    return build_pair(seed=0, s_max=128, quantize=False)


def _prompts(n, seed=0, vocab=512):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, size=rng.randint(2, 7)).astype(np.int32)
            for _ in range(n)]


def _drain(pair, prompts, sps, **kw):
    target, draft = pair
    eng = Engine(target, draft,
                 EngineConfig(max_batch=len(prompts), page_size=8, **kw))
    outs, summary = eng.run(prompts, sps)
    return [np.asarray(t) for t in outs], summary, eng


TREE = dict(spec_mode="tree", tree_budget=6, spec_branches=2)


@pytest.mark.parametrize(
    "impl,par_mode", [("gather", "off"), ("pallas", "off"), ("gather", "wdos")]
)
def test_greedy_tree_matches_chain_stream(pair, impl, par_mode):
    """Greedy tree verify only ever commits target-argmax tokens, so the
    stream must equal chain speculation token-for-token — the tree changes
    rounds, never content."""
    prompts = _prompts(3, seed=1)
    sp = SamplingParams(max_tokens=10)
    chain, _, _ = _drain(pair, prompts, sp, draft_len=3,
                         paged_attn_impl=impl, par_mode=par_mode)
    tree, s_tree, eng = _drain(pair, prompts, sp, draft_len=3,
                               paged_attn_impl=impl, par_mode=par_mode,
                               **TREE)
    for a, b in zip(chain, tree):
        np.testing.assert_array_equal(a, b)
    assert s_tree["emitted"] == sum(len(t) for t in tree)
    t_st, d_st = eng.pool_stats()
    assert t_st.used_pages == 0 and d_st.used_pages == 0


def test_sampled_tree_parity_and_determinism(pair):
    """Mixed per-request sampling params: reruns are bit-identical, and so
    are the fused-wdos scheduler and the pallas kernel path."""
    prompts = _prompts(3, seed=1)
    sps = [SamplingParams(temperature=0.9, seed=21, max_tokens=10),
           SamplingParams(temperature=1.1, top_k=12, seed=5, max_tokens=10),
           SamplingParams(max_tokens=10)]
    a, _, _ = _drain(pair, prompts, sps, draft_len=3, **TREE)
    b, _, _ = _drain(pair, prompts, sps, draft_len=3, **TREE)
    fused, _, _ = _drain(pair, prompts, sps, draft_len=3, par_mode="wdos",
                         **TREE)
    pallas, _, _ = _drain(pair, prompts, sps, draft_len=3,
                          paged_attn_impl="pallas", **TREE)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    for x, y in zip(a, fused):
        np.testing.assert_array_equal(x, y)
    for x, y in zip(a, pallas):
        np.testing.assert_array_equal(x, y)


def test_tree_mixed_kv_kinds_parity(pair):
    """Per-request int8/fp pools under tree speculation: the two schedulers
    must agree (compaction runs per storage kind)."""
    prompts = _prompts(3, seed=2)
    sps = [SamplingParams(max_tokens=8, kv_quant=k)
           for k in ("none", "int8", "none")]
    off, _, _ = _drain(pair, prompts, sps, draft_len=3, kv_quant="mixed",
                       **TREE)
    wdos, _, _ = _drain(pair, prompts, sps, draft_len=3, kv_quant="mixed",
                        par_mode="wdos", **TREE)
    for x, y in zip(off, wdos):
        np.testing.assert_array_equal(x, y)


def test_tree_compaction_matches_fresh_prefill(pair):
    """KV-content oracle: drive a branchy sampled drain until at least one
    round accepts a non-leftmost branch (device compaction moved BFS slots
    into chain order), then compare the pool's committed prefix rows with a
    fresh dense prefill of exactly those tokens."""
    target, draft = pair
    eng = Engine(target, draft, EngineConfig(
        max_batch=1, page_size=8, spec_mode="tree", tree_budget=6,
        spec_branches=2, branch_threshold=1.0,
    ))
    moved = []
    orig = eng._compact_pools

    def spy(moves_t, moves_d):
        moved.append(sum(len(src) for src, _ in moves_t.values()))
        orig(moves_t, moves_d)

    eng._compact_pools = spy
    prompt = np.arange(5, 11, dtype=np.int32)
    rid = eng.add_request(
        prompt, SamplingParams(temperature=3.0, seed=9, max_tokens=24)
    )
    while eng.has_unfinished() and not sum(moved):
        eng.step()
    assert sum(moved) > 0, "workload never accepted a non-leftmost branch"
    assert eng.has_unfinished(), "request finished before the oracle ran"

    req = eng._requests[rid]
    length = req.t_seq.length
    emitted = np.asarray(eng.output_tokens(rid))
    committed = np.concatenate([prompt, emitted])[:length].astype(np.int32)

    ref_eng = Engine(target, draft,
                     EngineConfig(max_batch=1, page_size=8, draft_len=3))
    rid2 = ref_eng.add_request(committed, SamplingParams(max_tokens=2))
    ref_eng.step()  # prefill writes positions [0, length); decode writes past

    def pool_rows(engine, request, store_attr, name):
        seq = request.t_seq if store_attr == "_t_store" else request.d_seq
        store = getattr(engine, store_attr)[request.kv_kind]
        arr = np.asarray(store[name])  # (L, P+1, kvh, ps, hd)
        slots = seq.flat_slots(np.arange(length))
        ps = arr.shape[3]
        return arr[:, slots // ps, :, slots % ps]

    req2 = ref_eng._requests[rid2]
    for store_attr, name in (("_t_store", "k"), ("_t_store", "v"),
                             ("_d_store", "k")):
        got = pool_rows(eng, req, store_attr, name)
        want = pool_rows(ref_eng, req2, store_attr, name)
        np.testing.assert_allclose(got, want, atol=2e-3, err_msg=store_attr)


def test_tree_accepts_more_per_round_on_branchy_workload(pair):
    """The A/B the bench gates: same drafting depth, every position
    branching top-2 with a budget that covers the full fan-out
    (2 + 4 + 8 = 14 > depth 3), on a low-acceptance sampled workload — the
    tree engine must accept strictly more tokens per request-round than
    chain speculation.  (Engine-step counts are batched and can tie; the
    per-request round counters are the comparable denominator.)"""
    prompts = _prompts(4, seed=3)
    sps = [SamplingParams(temperature=1.5, seed=100 + i, max_tokens=16)
           for i in range(4)]

    def acc_per_round(**kw):
        target, draft = pair
        eng = Engine(target, draft,
                     EngineConfig(max_batch=len(prompts), page_size=8, **kw))
        rids = [eng.add_request(p, sp) for p, sp in zip(prompts, sps)]
        while eng.has_unfinished():
            eng.step()
        reqs = [eng._requests[r] for r in rids]
        return (sum(r.accepted for r in reqs)
                / max(sum(r.rounds for r in reqs), 1))

    chain_acc = acc_per_round(draft_len=3)
    tree_acc = acc_per_round(draft_len=3, spec_mode="tree", tree_budget=14,
                             spec_branches=2, branch_threshold=1.0)
    assert tree_acc > chain_acc, (tree_acc, chain_acc)
