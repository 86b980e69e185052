"""Paged decode-attention kernel vs oracles (interpret mode on CPU)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels.paged_attn import (
    BLOCK_TOKENS, BUFFER_BUDGET, paged_decode_attention_pallas, pages_per_block,
)
from repro.models import layers as L


def _case(seed, b, kvs, g, hd, pool_pages, page_size, max_pages, lengths):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, kvs, g, hd).astype(np.float32))
    kp = jnp.asarray(rng.randn(pool_pages, kvs, page_size, hd).astype(np.float32))
    vp = jnp.asarray(rng.randn(pool_pages, kvs, page_size, hd).astype(np.float32))
    # each request owns a disjoint shuffled page set (as the pool allocator
    # would hand out); unused table slots point at page 0 (masked)
    perm = rng.permutation(pool_pages)[: b * max_pages].reshape(b, max_pages)
    pt = jnp.asarray(perm.astype(np.int32))
    lens = jnp.asarray(np.asarray(lengths, np.int32))
    return q, kp, vp, pt, lens


@pytest.mark.parametrize(
    "b,kvs,g,hd,page_size,max_pages,lengths",
    [
        (1, 1, 1, 16, 4, 2, [5]),
        (2, 2, 2, 32, 8, 4, [1, 32]),
        (3, 2, 4, 64, 16, 2, [16, 7, 29]),
        (4, 4, 1, 32, 8, 3, [3, 24, 17, 8]),
    ],
)
def test_matches_paged_oracle(b, kvs, g, hd, page_size, max_pages, lengths):
    q, kp, vp, pt, lens = _case(
        0, b, kvs, g, hd, b * max_pages + 3, page_size, max_pages, lengths
    )
    got = paged_decode_attention_pallas(q, kp, vp, pt, lens)
    want = ref.paged_attn_ref(q, kp, vp, pt, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_matches_dense_decode_attention():
    """Gathering pages into a contiguous cache and running the dense decode
    path must agree with attending through the page table directly."""
    b, kvs, g, hd, ps, mp = 2, 2, 2, 32, 8, 4
    q, kp, vp, pt, lens = _case(1, b, kvs, g, hd, b * mp, ps, mp, [11, 27])
    got = paged_decode_attention_pallas(q, kp, vp, pt, lens)
    k_dense = ref.gather_pages_ref(kp, pt)  # (B, S, KVS, hd)
    v_dense = ref.gather_pages_ref(vp, pt)
    h = kvs * g
    # dense path expects (B, 1, H, hd) with H laid out (kv-head, group)-major
    # — exactly the (KVS, G) order of the paged kernel's q
    q_dense = q.reshape(b, 1, h, hd)
    for i in range(b):
        # dense path takes one scalar length; compare row by row
        want = L._decode_attention(
            q_dense[i : i + 1], k_dense[i : i + 1], v_dense[i : i + 1], lens[i]
        )  # (1, 1, H, hd)
        want = want.reshape(kvs, g, hd)
        np.testing.assert_allclose(
            np.asarray(got[i]), np.asarray(want), atol=2e-5
        )


def test_page_permutation_invariance():
    """Physical page placement must not matter: permuting the pool pages and
    the table together leaves the output unchanged."""
    b, kvs, g, hd, ps, mp = 2, 2, 1, 16, 4, 3
    q, kp, vp, pt, lens = _case(2, b, kvs, g, hd, 12, ps, mp, [9, 12])
    base = paged_decode_attention_pallas(q, kp, vp, pt, lens)
    perm = np.random.RandomState(3).permutation(12)
    inv = np.argsort(perm)
    kp2 = kp[jnp.asarray(perm)]
    vp2 = vp[jnp.asarray(perm)]
    pt2 = jnp.asarray(inv.astype(np.int32))[pt]
    moved = paged_decode_attention_pallas(q, kp2, vp2, pt2, lens)
    np.testing.assert_allclose(np.asarray(base), np.asarray(moved), atol=1e-6)


def test_unused_table_slots_are_masked():
    """Slots past `length` may point at arbitrary pages without effect."""
    b, kvs, g, hd, ps, mp = 1, 2, 2, 32, 8, 4
    q, kp, vp, pt, lens = _case(4, b, kvs, g, hd, 8, ps, mp, [10])
    base = paged_decode_attention_pallas(q, kp, vp, pt, lens)
    pt_junk = np.asarray(pt).copy()
    pt_junk[0, 2:] = 7  # length 10 uses ceil(10/8)=2 pages; rest is junk
    got = paged_decode_attention_pallas(q, kp, vp, jnp.asarray(pt_junk), lens)
    np.testing.assert_allclose(np.asarray(base), np.asarray(got), atol=0)


def test_ragged_page_tables_one_page_vs_max():
    """One batch mixing a 1-page request with a request spanning every
    table slot (the continuous-batching steady state) must match the
    oracle row-for-row — the short request's unused slots are masked."""
    b, kvs, g, hd, ps, mp = 4, 2, 2, 32, 8, 6
    lengths = [3, ps * mp, 1, ps * (mp - 1) + 5]  # 1 page .. all mp pages
    q, kp, vp, pt, lens = _case(5, b, kvs, g, hd, b * mp, ps, mp, lengths)
    # point the short rows' dead slots at the long rows' pages (worst case)
    pt_np = np.asarray(pt).copy()
    pt_np[0, 1:] = pt_np[1, 1:]
    pt_np[2, 1:] = pt_np[3, 1:]
    pt = jnp.asarray(pt_np)
    got = paged_decode_attention_pallas(q, kp, vp, pt, lens)
    want = ref.paged_attn_ref(q, kp, vp, pt, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("lengths", [[1, 7, 13], [29, 31, 37], [5, 23, 47]])
def test_non_power_of_two_lengths(lengths):
    """Prefix lengths that straddle page boundaries at odd offsets (primes,
    not powers of two) must agree with the gather+dense oracle."""
    b, kvs, g, hd, ps, mp = 3, 2, 2, 48, 8, 6  # hd 48: also non-pow2
    q, kp, vp, pt, lens = _case(6, b, kvs, g, hd, b * mp + 1, ps, mp, lengths)
    got = paged_decode_attention_pallas(q, kp, vp, pt, lens)
    want = ref.paged_attn_ref(q, kp, vp, pt, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------------
# Multi-token verify window (5-D q)
# ---------------------------------------------------------------------------


def _window_case(seed, b, w, kvs, g, hd, pool_pages, page_size, mp, lengths):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, w, kvs, g, hd).astype(np.float32))
    kp = jnp.asarray(rng.randn(pool_pages, kvs, page_size, hd).astype(np.float32))
    vp = jnp.asarray(rng.randn(pool_pages, kvs, page_size, hd).astype(np.float32))
    perm = rng.permutation(pool_pages)[: b * mp].reshape(b, mp)
    return q, kp, vp, jnp.asarray(perm.astype(np.int32)), jnp.asarray(
        np.asarray(lengths, np.int32)
    )


@pytest.mark.parametrize("w,lengths", [(2, [9, 30]), (4, [5, 17]), (3, [3, 32])])
def test_window_matches_oracle(w, lengths):
    """W-query verify windows (the speculative round's [last_tok, drafts...]
    span) match the causally-masked oracle."""
    b, kvs, g, hd, ps, mp = 2, 2, 2, 32, 8, 4
    q, kp, vp, pt, lens = _window_case(7, b, w, kvs, g, hd, b * mp, ps, mp, lengths)
    got = paged_decode_attention_pallas(q, kp, vp, pt, lens)
    want = ref.paged_attn_ref(q, kp, vp, pt, lens)
    assert got.shape == (b, w, kvs, g, hd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_window_last_query_equals_single_token_call():
    """The window's LAST query sees the full prefix — it must equal a 4-D
    single-token call at the same length (causal consistency)."""
    b, w, kvs, g, hd, ps, mp = 2, 3, 2, 2, 32, 8, 4
    q, kp, vp, pt, lens = _window_case(8, b, w, kvs, g, hd, b * mp, ps, mp, [11, 26])
    win = paged_decode_attention_pallas(q, kp, vp, pt, lens)
    single = paged_decode_attention_pallas(q[:, -1], kp, vp, pt, lens)
    np.testing.assert_allclose(
        np.asarray(win[:, -1]), np.asarray(single), atol=1e-6
    )


# ---------------------------------------------------------------------------
# int8 compressed pools (kv_quant="int8"): in-kernel dequant epilogue
# ---------------------------------------------------------------------------


def _quantized_pools(kp, vp):
    """Pool-shaped symmetric int8 quantization: (P, KVS, ps, hd) f32 ->
    int8 values + (P, KVS, ps, 1) f32 scales (the engine's storage rule)."""
    from repro.serving.paged_cache import kv_quantize_np

    kq, ks = kv_quantize_np(np.asarray(kp, np.float32))
    vq, vs = kv_quantize_np(np.asarray(vp, np.float32))
    return (jnp.asarray(kq), jnp.asarray(vq),
            jnp.asarray(ks), jnp.asarray(vs))


@pytest.mark.parametrize(
    "b,kvs,g,hd,page_size,max_pages,lengths",
    [
        (1, 1, 1, 16, 4, 2, [5]),
        (3, 2, 2, 48, 8, 6, [29, 31, 37]),  # non-pow2 hd, prime raggedness
        (4, 4, 1, 32, 8, 3, [3, 24, 17, 8]),
    ],
)
def test_int8_matches_quantized_oracle(b, kvs, g, hd, page_size, max_pages,
                                       lengths):
    """The in-kernel dequant epilogue must match the gather-then-dequant
    oracle exactly (both expand int8*scale to f32 before the fp math)."""
    q, kp, vp, pt, lens = _case(
        10, b, kvs, g, hd, b * max_pages + 3, page_size, max_pages, lengths
    )
    kq, vq, ks, vs = _quantized_pools(kp, vp)
    got = paged_decode_attention_pallas(q, kq, vq, pt, lens,
                                        k_scale=ks, v_scale=vs)
    want = ref.paged_attn_ref(q, kq, vq, pt, lens, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_int8_equals_predequantized_fp_kernel():
    """Dequantizing inside the kernel is numerically equivalent to running
    the fp kernel over pools dequantized up front — the contract that keeps
    the pallas path and the models/layers gather fallback interchangeable."""
    b, kvs, g, hd, ps, mp = 2, 2, 2, 32, 8, 4
    q, kp, vp, pt, lens = _case(11, b, kvs, g, hd, b * mp, ps, mp, [11, 27])
    kq, vq, ks, vs = _quantized_pools(kp, vp)
    got = paged_decode_attention_pallas(q, kq, vq, pt, lens,
                                        k_scale=ks, v_scale=vs)
    kd = kq.astype(jnp.float32) * ks
    vd = vq.astype(jnp.float32) * vs
    base = paged_decode_attention_pallas(q, kd, vd, pt, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base), atol=1e-6)


@pytest.mark.parametrize("lengths", [[1, 7, 13], [5, 23, 47]])
def test_int8_close_to_float_reference(lengths):
    """Quantization error stays small: int8 pools attend within a loose
    tolerance of the ORIGINAL full-precision pools (ragged prime lengths,
    non-pow2 hd)."""
    b, kvs, g, hd, ps, mp = 3, 2, 2, 48, 8, 6
    q, kp, vp, pt, lens = _case(12, b, kvs, g, hd, b * mp + 1, ps, mp, lengths)
    kq, vq, ks, vs = _quantized_pools(kp, vp)
    got = paged_decode_attention_pallas(q, kq, vq, pt, lens,
                                        k_scale=ks, v_scale=vs)
    want = ref.paged_attn_ref(q, kp, vp, pt, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0.08)


@pytest.mark.parametrize("w,lengths", [(2, [9, 30]), (4, [5, 17])])
def test_int8_window_matches_oracle(w, lengths):
    """5-D verify windows over int8 pools: the causally-masked window path
    shares the dequant epilogue with the 4-D decode path."""
    b, kvs, g, hd, ps, mp = 2, 2, 2, 32, 8, 4
    q, kp, vp, pt, lens = _window_case(13, b, w, kvs, g, hd, b * mp, ps, mp,
                                       lengths)
    kq, vq, ks, vs = _quantized_pools(kp, vp)
    got = paged_decode_attention_pallas(q, kq, vq, pt, lens,
                                        k_scale=ks, v_scale=vs)
    want = ref.paged_attn_ref(q, kq, vq, pt, lens, k_scale=ks, v_scale=vs)
    assert got.shape == (b, w, kvs, g, hd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_int8_window_last_query_equals_single_token_call():
    b, w, kvs, g, hd, ps, mp = 2, 3, 2, 2, 32, 8, 4
    q, kp, vp, pt, lens = _window_case(14, b, w, kvs, g, hd, b * mp, ps, mp,
                                       [11, 26])
    kq, vq, ks, vs = _quantized_pools(kp, vp)
    win = paged_decode_attention_pallas(q, kq, vq, pt, lens,
                                       k_scale=ks, v_scale=vs)
    single = paged_decode_attention_pallas(q[:, -1], kq, vq, pt, lens,
                                           k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(
        np.asarray(win[:, -1]), np.asarray(single), atol=1e-6
    )


# ---------------------------------------------------------------------------
# Speculation-tree windows (tree_mask): kernel vs oracle vs gather fallback
# ---------------------------------------------------------------------------

from _optional import given, settings, st  # noqa: E402
from repro.core.speculative import tree_ancestor_mask  # noqa: E402


def _random_parents(rng, n):
    """A valid drafting-order topology: node i's parent is -1 (the window
    root) or any earlier node — uniform, so draws range over chains, stars
    and ragged mixed-fanout trees."""
    return [int(rng.randint(-1, i)) for i in range(n)]


def _tree_case(seed, b, w, kvs, g, hd, ps, mp, lengths, node_counts=None):
    """A `_window_case` plus a per-row (W, W) ancestor mask; rows with fewer
    than w - 1 nodes get self-visible-only padding rows (the engine's fixed
    dispatch width)."""
    q, kp, vp, pt, lens = _window_case(
        seed, b, w, kvs, g, hd, b * mp, ps, mp, lengths
    )
    rng = np.random.RandomState(seed + 1)
    tm = np.zeros((b, w, w), np.float32)
    for i in range(b):
        n = w - 1 if node_counts is None else node_counts[i]
        tm[i] = tree_ancestor_mask(_random_parents(rng, n), w)
    return q, kp, vp, pt, lens, jnp.asarray(tm)


@pytest.mark.parametrize(
    "seed,w,lengths", [(20, 3, [9, 30]), (21, 5, [7, 17]), (22, 7, [8, 32])]
)
def test_tree_window_matches_oracle(seed, w, lengths):
    """Random topologies: the kernel's in-window tree mask must agree with
    the gather+dense oracle row-for-row (full prefix + ancestor columns)."""
    b, kvs, g, hd, ps, mp = 2, 2, 2, 32, 8, 4
    q, kp, vp, pt, lens, tm = _tree_case(seed, b, w, kvs, g, hd, ps, mp, lengths)
    got = paged_decode_attention_pallas(q, kp, vp, pt, lens, tree_mask=tm)
    want = ref.paged_attn_ref(q, kp, vp, pt, lens, tree_mask=tm)
    assert got.shape == (b, w, kvs, g, hd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_chain_tree_mask_equals_causal_window():
    """A chain-shaped tree (lower-triangular ancestor mask) must reproduce
    the causal-window path bit-for-bit-close on BOTH implementations — the
    equivalence spec_mode='tree' relies on when every fan-out is 1."""
    b, w, kvs, g, hd, ps, mp = 2, 4, 2, 2, 32, 8, 4
    q, kp, vp, pt, lens = _window_case(23, b, w, kvs, g, hd, b * mp, ps, mp,
                                       [11, 26])
    chain = tree_ancestor_mask([i - 1 for i in range(w - 1)], w)
    tm = jnp.asarray(np.broadcast_to(chain, (b, w, w)).copy())
    got = paged_decode_attention_pallas(q, kp, vp, pt, lens, tree_mask=tm)
    causal = paged_decode_attention_pallas(q, kp, vp, pt, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(causal), atol=1e-6)
    got_ref = ref.paged_attn_ref(q, kp, vp, pt, lens, tree_mask=tm)
    causal_ref = ref.paged_attn_ref(q, kp, vp, pt, lens)
    np.testing.assert_allclose(
        np.asarray(got_ref), np.asarray(causal_ref), atol=1e-6
    )


def test_tree_gather_fallback_matches_oracle():
    """models/layers._tree_window_attention (the non-pallas engine path)
    computes the same tree semantics over a dense gathered cache."""
    b, w, kvs, g, hd, ps, mp = 2, 5, 2, 2, 32, 8, 4
    q, kp, vp, pt, lens, tm = _tree_case(24, b, w, kvs, g, hd, ps, mp, [9, 28])
    kd = ref.gather_pages_ref(kp, pt)
    vd = ref.gather_pages_ref(vp, pt)
    got = L._tree_window_attention(
        q.reshape(b, w, kvs * g, hd), kd, vd, lens, tm
    ).reshape(b, w, kvs, g, hd)
    want = ref.paged_attn_ref(q, kp, vp, pt, lens, tree_mask=tm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_tree_window_ragged_node_counts():
    """Rows with different live node counts share ONE fixed-width dispatch;
    padded (self-visible-only) rows must not perturb any live row."""
    b, w, kvs, g, hd, ps, mp = 3, 6, 2, 2, 32, 8, 4
    q, kp, vp, pt, lens, tm = _tree_case(
        25, b, w, kvs, g, hd, ps, mp, [7, 19, 30], node_counts=[0, 2, 5]
    )
    got = paged_decode_attention_pallas(q, kp, vp, pt, lens, tree_mask=tm)
    want = ref.paged_attn_ref(q, kp, vp, pt, lens, tree_mask=tm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert bool(np.isfinite(np.asarray(got)).all())


@pytest.mark.parametrize("seed,w,lengths", [(26, 3, [9, 30]), (27, 5, [7, 17])])
def test_int8_tree_window_matches_oracle(seed, w, lengths):
    """Tree masks compose with the int8 dequant epilogue: quantized pools,
    random topologies, kernel vs gather-then-dequant oracle."""
    b, kvs, g, hd, ps, mp = 2, 2, 2, 32, 8, 4
    q, kp, vp, pt, lens, tm = _tree_case(seed, b, w, kvs, g, hd, ps, mp, lengths)
    kq, vq, ks, vs_ = _quantized_pools(kp, vp)
    got = paged_decode_attention_pallas(q, kq, vq, pt, lens,
                                        k_scale=ks, v_scale=vs_, tree_mask=tm)
    want = ref.paged_attn_ref(q, kq, vq, pt, lens,
                              k_scale=ks, v_scale=vs_, tree_mask=tm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@settings(max_examples=20, deadline=None)
@given(st.data())
def test_tree_window_matches_oracle_property(data):
    """Property sweep (hypothesis, skipped when absent — the seeded variants
    above always run): random width, batch, ragged per-row node counts,
    ragged prefix lengths, both precisions."""
    w = data.draw(st.integers(min_value=2, max_value=6), label="w")
    b = data.draw(st.integers(min_value=1, max_value=3), label="b")
    seed = data.draw(st.integers(min_value=0, max_value=2**16 - 1),
                     label="seed")
    quantized = data.draw(st.booleans(), label="int8")
    ps, mp = 8, 3
    counts = [
        data.draw(st.integers(min_value=0, max_value=w - 1), label=f"n{i}")
        for i in range(b)
    ]
    lengths = [
        data.draw(st.integers(min_value=w, max_value=ps * mp), label=f"len{i}")
        for i in range(b)
    ]
    q, kp, vp, pt, lens, tm = _tree_case(
        seed, b, w, 2, 2, 32, ps, mp, lengths, node_counts=counts
    )
    if quantized:
        kp, vp, ks, vs_ = _quantized_pools(kp, vp)
        kw = {"k_scale": ks, "v_scale": vs_}
    else:
        kw = {}
    got = paged_decode_attention_pallas(q, kp, vp, pt, lens, tree_mask=tm, **kw)
    want = ref.paged_attn_ref(q, kp, vp, pt, lens, tree_mask=tm, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------------
# The walk: only each row's live pages are read, in blocks of pages
# ---------------------------------------------------------------------------

WALK_KVS, WALK_G, WALK_HD, WALK_PS, WALK_W = 2, 2, 32, 16, 4
# pages per block when the table is wide enough not to cap it
WALK_PPB = pages_per_block(WALK_PS, WALK_KVS, WALK_HD, jnp.float32, 10**6)
WALK_EDGE = WALK_PPB * WALK_PS  # tokens in one full block


def _walk_case(variant, lengths, mp, seed):
    """Pools whose every page outside the rows' live pages — the scratch
    page (the pool's last), and a junk page each row's first dead table
    slot points at — holds NaN (int8: NaN scales).  Returns the kernel's
    output on those pools and the oracle's on the same pools before the
    NaN went in."""
    b = len(lengths)
    w = 1 if variant == "decode" else WALK_W
    rng = np.random.RandomState(seed)
    live = [-(-n // WALK_PS) for n in lengths]
    pool_pages = sum(live) + b + 1
    scratch = pool_pages - 1
    perm = iter(rng.permutation(scratch).tolist())
    pt = np.full((b, mp), scratch, np.int32)
    for i, n in enumerate(live):
        pt[i, :n] = [next(perm) for _ in range(n)]
    for i, n in enumerate(live):
        if n < mp:
            pt[i, n] = next(perm)  # a junk page, not the scratch page
    used = {int(p) for i, n in enumerate(live) for p in pt[i, :n]}
    dead = np.array(sorted(set(range(pool_pages)) - used))
    qshape = (b, WALK_KVS, WALK_G, WALK_HD)
    if w > 1:
        qshape = (b, w) + qshape[1:]
    q = jnp.asarray(rng.randn(*qshape).astype(np.float32))
    pshape = (pool_pages, WALK_KVS, WALK_PS, WALK_HD)
    kp = rng.randn(*pshape).astype(np.float32)
    vp = rng.randn(*pshape).astype(np.float32)
    kw = {}
    if variant == "tree":
        tm = np.stack([
            tree_ancestor_mask(_random_parents(rng, w - 1), w) for _ in range(b)
        ])
        kw["tree_mask"] = jnp.asarray(tm)
    pt, lens = jnp.asarray(pt), jnp.asarray(np.asarray(lengths, np.int32))
    if variant == "int8":
        kq, vq, ks, vs = _quantized_pools(kp, vp)
        want = ref.paged_attn_ref(q, kq, vq, pt, lens, k_scale=ks, v_scale=vs)
        got = paged_decode_attention_pallas(
            q, kq, vq, pt, lens, k_scale=ks.at[dead].set(np.nan),
            v_scale=vs.at[dead].set(np.nan),
        )
        return got, want
    want = ref.paged_attn_ref(q, jnp.asarray(kp), jnp.asarray(vp), pt, lens, **kw)
    kp[dead] = np.nan
    vp[dead] = np.nan
    got = paged_decode_attention_pallas(
        q, jnp.asarray(kp), jnp.asarray(vp), pt, lens, **kw
    )
    return got, want


WALK_CASES = {
    # lengths one short of, at, and one past a full block of pages
    "block_edges": ([WALK_EDGE - 1, WALK_EDGE, WALK_EDGE + 1], WALK_PPB + 2),
    # a table width no block divides: full, ragged, and one-block rows
    "ragged_table": (
        [(2 * WALK_PPB + 1) * WALK_PS, (2 * WALK_PPB + 1) * WALK_PS - 3,
         WALK_EDGE + 7],
        2 * WALK_PPB + 1,
    ),
    # rows of length 0 beside long rows
    "empty_rows": ([0, 2 * WALK_EDGE + 5, 0, WALK_EDGE], 2 * WALK_PPB + 1),
}


@pytest.mark.parametrize("case", sorted(WALK_CASES))
@pytest.mark.parametrize("variant", ["decode", "verify", "int8", "tree"])
def test_walk_reads_only_live_pages(variant, case):
    """NaN everywhere past each row's live pages cannot reach the output;
    rows of length 0 write zeros; every other row matches the oracle at
    block edges and over a table width no block divides."""
    lengths, mp = WALK_CASES[case]
    assert mp % WALK_PPB, "the table width must leave a ragged last block"
    got, want = _walk_case(variant, lengths, mp, seed=40 + len(case))
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all()
    for i, n in enumerate(lengths):
        if n == 0:
            np.testing.assert_array_equal(got[i], 0.0)
        else:
            np.testing.assert_allclose(got[i], want[i], atol=2e-5)


def test_pages_per_block_follows_the_shapes():
    """Pages per copy come from the shapes alone: a block covers at most
    BLOCK_TOKENS tokens and the table's width, and the double-buffered K and
    V blocks fit the VMEM budget."""
    assert pages_per_block(16, 8, 128, jnp.bfloat16, 129) * 16 <= BLOCK_TOKENS
    assert pages_per_block(16, 8, 128, jnp.bfloat16, 3) == 3
    for kvs, dtype in [(8, jnp.bfloat16), (32, jnp.bfloat16), (32, jnp.float32),
                       (64, jnp.float32), (32, jnp.int8)]:
        ppb = pages_per_block(16, kvs, 128, dtype, 145)
        page = kvs * 16 * 128 * jnp.dtype(dtype).itemsize
        assert ppb >= 1 and (ppb == 1 or 4 * ppb * page <= BUFFER_BUDGET)
