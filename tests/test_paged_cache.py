"""Paged KV pool allocator invariants (serving/paged_cache.py)."""
import numpy as np
import pytest

from repro.serving.paged_cache import PagedKVPool, pages_for


def make_pool(num_pages=8, page_size=4, n_layers=2, kvh=2, hd=8):
    return PagedKVPool(n_layers, kvh, hd, num_pages=num_pages, page_size=page_size)


def span(pool, l, val=1.0):
    x = np.full((pool.n_layers, l, pool.kv_heads, pool.head_dim), val, np.float32)
    return x, -x


def test_pages_for():
    assert pages_for(0, 4) == 0
    assert pages_for(1, 4) == 1
    assert pages_for(4, 4) == 1
    assert pages_for(5, 4) == 2


def test_append_gather_roundtrip():
    pool = make_pool()
    seq = pool.allocate_sequence(10)
    k1, v1 = span(pool, 6, 1.0)
    seq.append(k1, v1)
    k2, v2 = span(pool, 3, 2.0)
    seq.append(k2, v2)
    assert seq.length == 9 and len(seq.pages) == 3
    kd = np.zeros((pool.n_layers, 12, pool.kv_heads, pool.head_dim), np.float32)
    vd = np.zeros_like(kd)
    seq.gather_into(kd, vd)
    np.testing.assert_array_equal(kd[:, :6], k1)
    np.testing.assert_array_equal(kd[:, 6:9], k2)
    np.testing.assert_array_equal(vd[:, :9], np.concatenate([v1, v2], 1))


def test_reservation_blocks_admission():
    pool = make_pool(num_pages=8, page_size=4)
    a = pool.allocate_sequence(16)  # 4 pages reserved, 0 backed
    assert a is not None and pool.available_pages == 4
    b = pool.allocate_sequence(17)  # needs 5 > 4 available
    assert b is None
    c = pool.allocate_sequence(16)
    assert c is not None and pool.available_pages == 0
    assert pool.allocate_sequence(1) is None


def test_request_larger_than_pool_raises():
    pool = make_pool(num_pages=4, page_size=4)
    with pytest.raises(ValueError, match="capacity"):
        pool.allocate_sequence(17)


def test_rewind_restores_free_pages_and_regrow():
    pool = make_pool(num_pages=8, page_size=4)
    seq = pool.allocate_sequence(12)
    seq.append(*span(pool, 10))
    assert pool.used_pages == 3
    seq.rewind(6)  # length 4 -> 1 page kept
    assert seq.length == 4 and pool.used_pages == 1
    # rewound pages return to the reservation, so the sequence can regrow
    seq.append(*span(pool, 8, 3.0))
    assert seq.length == 12 and pool.used_pages == 3
    with pytest.raises(ValueError, match="over-rewind"):
        seq.rewind(13)
    with pytest.raises(ValueError):
        seq.rewind(-1)


def test_rewind_is_partial_page_aware():
    pool = make_pool(num_pages=8, page_size=4)
    seq = pool.allocate_sequence(12)
    seq.append(*span(pool, 9))  # 3 pages, last holds 1 token
    seq.rewind(1)  # length 8: drops the partial page
    assert pool.used_pages == 2
    seq.rewind(1)  # length 7: page boundary not crossed
    assert pool.used_pages == 2


def test_release_returns_pages_and_reservation():
    pool = make_pool(num_pages=8, page_size=4)
    seq = pool.allocate_sequence(16)  # reserve 4
    seq.append(*span(pool, 5))  # backs 2 of the 4 reserved pages
    assert pool.free_pages == 6
    assert pool.available_pages == 4  # 6 free minus 2 still-unbacked reserved
    seq.release()
    assert pool.used_pages == 0
    assert pool.available_pages == 8
    assert seq.released
    with pytest.raises(RuntimeError, match="double release"):
        seq.release()


def test_page_reuse_after_release():
    pool = make_pool(num_pages=2, page_size=4)
    a = pool.allocate_sequence(8)
    a.append(*span(pool, 8))
    pages_a = list(a.pages)
    assert pool.allocate_sequence(4) is None  # full
    a.release()
    b = pool.allocate_sequence(8)
    b.append(*span(pool, 8, 9.0))
    assert sorted(b.pages) == sorted(pages_a)  # physical reuse


def test_exceeding_reservation_raises():
    pool = make_pool(num_pages=8, page_size=4)
    seq = pool.allocate_sequence(8)  # 2 pages
    with pytest.raises(RuntimeError, match="reservation"):
        seq.append(*span(pool, 9))


def test_gather_into_clamps_page_overhang():
    """A dst buffer that is not a multiple of page_size must not overflow:
    the last page's junk tail is clamped (regression: s_max=110, ps=16)."""
    pool = make_pool(num_pages=8, page_size=16)
    seq = pool.allocate_sequence(110)
    k, v = span(pool, 100, 5.0)
    seq.append(k, v)  # 7 pages = 112 slots > 110-row dst
    kd = np.zeros((pool.n_layers, 110, pool.kv_heads, pool.head_dim), np.float32)
    vd = np.zeros_like(kd)
    seq.gather_into(kd, vd)
    np.testing.assert_array_equal(kd[:, :100], k)
    with pytest.raises(AssertionError):
        short = np.zeros((pool.n_layers, 99, pool.kv_heads, pool.head_dim), np.float32)
        seq.gather_into(short, short.copy())  # dst smaller than valid data


def test_over_rewind_at_page_boundary():
    """Regression: when length sits EXACTLY on a page boundary, rewinding
    one past it must raise (not wrap / pop a non-existent page), and the
    sequence must stay usable afterwards.  Also mirrors the engine's rewind
    contract: n must be >= 0."""
    pool = make_pool(num_pages=8, page_size=4)
    seq = pool.allocate_sequence(12)
    seq.append(*span(pool, 8))  # exactly 2 full pages
    assert seq.length == 8 and len(seq.pages) == 2
    with pytest.raises(ValueError, match="over-rewind"):
        seq.rewind(9)
    with pytest.raises(ValueError, match="n >= 0"):
        seq.rewind(-1)
    # state unchanged by the failed rewinds; a full boundary rewind is fine
    assert seq.length == 8 and len(seq.pages) == 2
    seq.rewind(8)
    assert seq.length == 0 and pool.used_pages == 0


def test_rewind_keep_pages_for_device_mode():
    """release_pages=False (device-resident pools): the length drops but
    every backed page stays owned, so the page table is lifetime-stable."""
    pool = make_pool(num_pages=8, page_size=4)
    seq = pool.allocate_sequence(12)
    seq.append(*span(pool, 10))  # 3 pages
    pages = list(seq.pages)
    seq.rewind(7, release_pages=False)
    assert seq.length == 3 and seq.pages == pages and pool.used_pages == 3
    with pytest.raises(ValueError, match="over-rewind"):
        seq.rewind(4, release_pages=False)
    seq.advance(9)  # regrow over the kept pages, no new allocation
    assert seq.length == 12 and seq.pages == pages


def test_storageless_pool_is_pure_allocator():
    """alloc_storage=False: bookkeeping works, host data paths refuse."""
    pool = PagedKVPool(2, 2, 8, num_pages=4, page_size=4, alloc_storage=False)
    assert pool.k is None and pool.v is None
    seq = pool.allocate_sequence(8)
    seq.ensure_backed(8)
    assert len(seq.pages) == 2 and pool.used_pages == 2
    seq.advance(5)
    assert seq.length == 5
    with pytest.raises(RuntimeError, match="storage-less"):
        seq.append(
            np.zeros((2, 1, 2, 8), np.float32), np.zeros((2, 1, 2, 8), np.float32)
        )
    with pytest.raises(RuntimeError, match="storage-less"):
        seq.gather_into(
            np.zeros((2, 8, 2, 8), np.float32), np.zeros((2, 8, 2, 8), np.float32)
        )
    seq.release()
    assert pool.used_pages == 0 and pool.available_pages == 4


def test_device_pool_init_has_scratch_page():
    from repro.serving.paged_cache import device_pool_init

    pool = PagedKVPool(3, 2, 8, num_pages=5, page_size=4, alloc_storage=False)
    k, v = device_pool_init(pool)
    assert k.shape == (3, 6, 2, 4, 8)  # num_pages + 1 scratch, head-major
    assert v.shape == k.shape


def test_high_water_and_stats():
    pool = make_pool(num_pages=8, page_size=4)
    seq = pool.allocate_sequence(16)
    seq.append(*span(pool, 16))
    st = pool.stats()
    assert st.used_pages == 4 and st.high_water_pages == 4
    assert st.utilization == pytest.approx(0.5)
    seq.release()
    assert pool.stats().used_pages == 0
    assert pool.stats().high_water_pages == 4  # sticky


# ---------------------------------------------------------------------------
# int8 compressed pools (kv_quant="int8")
# ---------------------------------------------------------------------------


def make_int8_pool(num_pages=8, page_size=4, n_layers=2, kvh=2, hd=8):
    return PagedKVPool(
        n_layers, kvh, hd, num_pages=num_pages, page_size=page_size,
        kv_quant="int8",
    )


def rspan(pool, l, seed=0):
    rng = np.random.RandomState(seed)
    shape = (pool.n_layers, l, pool.kv_heads, pool.head_dim)
    return (rng.randn(*shape).astype(np.float32),
            rng.randn(*shape).astype(np.float32))


def test_int8_append_gather_roundtrip_close():
    """Host int8 pools: append quantizes, gather dequantizes; the roundtrip
    stays within the symmetric-quantization error bound (absmax/254 per
    slot-head row)."""
    pool = make_int8_pool()
    seq = pool.allocate_sequence(10)
    k1, v1 = rspan(pool, 6, seed=1)
    seq.append(k1, v1)
    k2, v2 = rspan(pool, 3, seed=2)
    seq.append(k2, v2)
    assert pool.k.dtype == np.int8 and pool.k_scale.dtype == np.float32
    kd = np.zeros((pool.n_layers, 12, pool.kv_heads, pool.head_dim), np.float32)
    vd = np.zeros_like(kd)
    seq.gather_into(kd, vd)
    ref_k = np.concatenate([k1, k2], 1)
    ref_v = np.concatenate([v1, v2], 1)
    bound = np.abs(ref_k).max() / 254 + 1e-6
    assert np.abs(kd[:, :9] - ref_k).max() <= bound
    assert np.abs(vd[:, :9] - ref_v).max() <= np.abs(ref_v).max() / 254 + 1e-6


def test_int8_bytes_accounting():
    pool = make_int8_pool(n_layers=2, kvh=2, hd=8)
    dense = make_pool(n_layers=2, kvh=2, hd=8)
    # K+V * layers * heads * (hd int8 bytes + 4B f32 scale)
    assert pool.bytes_per_token() == 2 * 2 * 2 * (8 + 4)
    assert dense.bytes_per_token() == 2 * 2 * 2 * 8 * 4
    assert dense.bytes_per_token() / pool.bytes_per_token() >= 1.8
    seq = pool.allocate_sequence(8)
    seq.append(*rspan(pool, 8))
    st = pool.stats()
    assert st.kv_quant == "int8"
    assert st.bytes_per_token == pool.bytes_per_token()
    assert st.kv_bytes_total == 2 * pool.bytes_per_page()
    assert pool.bytes_per_token_by_kind() == {"int8": pool.bytes_per_token()}


def test_mixed_pool_is_allocator_only_and_sums_bytes():
    with pytest.raises(NotImplementedError, match="allocator-only"):
        PagedKVPool(2, 2, 8, num_pages=4, page_size=4, kv_quant="mixed")
    pool = PagedKVPool(2, 2, 8, num_pages=4, page_size=4,
                       alloc_storage=False, kv_quant="mixed")
    by_kind = pool.bytes_per_token_by_kind()
    assert set(by_kind) == {"float32", "int8"}
    assert pool.bytes_per_token() == sum(by_kind.values())


def test_rewind_invalidates_scales_across_page_boundary():
    """Regression (stale per-page metadata): rewind(release_pages=False)
    must zero the dropped positions' scale entries — including positions in
    KEPT pages and positions in pages past the new boundary — so a stale
    scale can never silently dequantize a later append's bytes.  Freshness
    is restored only by the next append writing value+scale together."""
    pool = make_int8_pool(num_pages=8, page_size=4)
    seq = pool.allocate_sequence(12)
    seq.append(*rspan(pool, 10, seed=3))  # 3 pages: 4+4+2
    pages = list(seq.pages)
    flat = lambda p: pool.k_scale[:, p].swapaxes(1, 2)  # (L, ps, kvh, 1)
    assert np.all(flat(pages[2])[:, :2] > 0)
    # drop 7 positions: new length 3 sits mid-page-0; pages stay owned
    seq.rewind(7, release_pages=False)
    assert seq.pages == pages
    # positions 3 (page 0 tail), 4..7 (page 1), 8..9 (page 2) are zeroed
    assert np.all(flat(pages[0])[:, 3:] == 0)
    assert np.all(flat(pages[1]) == 0)
    assert np.all(flat(pages[2]) == 0)
    # kept prefix scales stay intact
    assert np.all(flat(pages[0])[:, :3] > 0)
    # regrow: append restores freshness and the roundtrip is exact again
    k, v = rspan(pool, 9, seed=4)
    seq.append(k, v)
    kd = np.zeros((pool.n_layers, 12, pool.kv_heads, pool.head_dim), np.float32)
    seq.gather_into(kd, np.zeros_like(kd))
    assert np.abs(kd[:, 3:12] - k).max() <= np.abs(k).max() / 254 + 1e-6


def test_release_zeroes_scales():
    pool = make_int8_pool(num_pages=4, page_size=4)
    seq = pool.allocate_sequence(8)
    seq.append(*rspan(pool, 8, seed=5))
    pages = list(seq.pages)
    seq.release()
    for p in pages:
        assert np.all(pool.k_scale[:, p] == 0)
        assert np.all(pool.v_scale[:, p] == 0)


def test_device_pool_store_shapes():
    from repro.serving.paged_cache import device_pool_store

    pool = PagedKVPool(3, 2, 8, num_pages=5, page_size=4,
                       alloc_storage=False, kv_quant="int8")
    st = device_pool_store(pool)
    assert set(st) == {"k", "v", "k_scale", "v_scale"}
    # head-major: (layers, pages + scratch, kv heads, page_size, hd)
    assert st["k"].shape == (3, 6, 2, 4, 8) and str(st["k"].dtype) == "int8"
    assert st["k_scale"].shape == (3, 6, 2, 4, 1)
    assert str(st["k_scale"].dtype) == "float32"
    dense = device_pool_store(pool, kv_quant="none")
    assert set(dense) == {"k", "v"} and str(dense["k"].dtype) == "float32"
    mixed = PagedKVPool(3, 2, 8, num_pages=5, page_size=4,
                        alloc_storage=False, kv_quant="mixed")
    with pytest.raises(ValueError, match="ONE storage kind"):
        device_pool_store(mixed)


def test_shared_page_refcounts_release_in_any_order():
    """Shared pages free only at the LAST reference: a donor sequence, the
    prefix tree's pin, and a follower mapping the same pages may release
    in any order without freeing a page another holder still maps."""
    pool = make_pool(num_pages=8, page_size=4)
    donor = pool.allocate_sequence(8)
    k, v = span(pool, 8, 3.0)
    donor.append(k, v)
    pages = list(donor.pages)
    for p in pages:  # the tree pins every full block
        pool.incref_page(p)
    follower = pool.allocate_sequence(
        12, shared_pages=pages, shared_tokens=8
    )
    assert [pool.page_ref(p) for p in pages] == [3, 3]
    assert pool.shared_page_count == 2
    free0 = pool.free_pages

    donor.release()  # donor exits first; its pages must NOT free
    assert pool.free_pages == free0
    assert [pool.page_ref(p) for p in pages] == [2, 2]
    kd = np.zeros((pool.n_layers, 12, pool.kv_heads, pool.head_dim), np.float32)
    follower.gather_into(kd, np.zeros_like(kd))
    np.testing.assert_array_equal(kd[:, :8], k)  # rows still readable

    follower.release()  # down to the tree's ref alone
    assert pool.free_pages == free0
    assert [pool.page_ref(p) for p in pages] == [1, 1]
    for p in pages:  # tree eviction drops the last ref -> pages free
        pool._give_page(p, back_to_reservation=False)
    assert pool.free_pages == free0 + 2
    assert pool.shared_page_count == 0


def test_double_release_raises():
    """Regression: releasing a sequence twice must fail loudly instead of
    double-decrefing pages another holder may since have re-acquired."""
    pool = make_pool(num_pages=8, page_size=4)
    seq = pool.allocate_sequence(8)
    seq.append(*span(pool, 6))
    seq.release()
    free0 = pool.free_pages
    with pytest.raises(RuntimeError, match="double release"):
        seq.release()
    assert pool.free_pages == free0  # second call changed nothing

    # the same guard holds for a sequence mapping shared pages
    donor = pool.allocate_sequence(4)
    donor.append(*span(pool, 4))
    pages = list(donor.pages)
    fol = pool.allocate_sequence(8, shared_pages=pages, shared_tokens=4)
    fol.release()
    with pytest.raises(RuntimeError, match="double release"):
        fol.release()
    assert pool.page_ref(pages[0]) == 1  # donor's ref untouched
    donor.release()


# ---------------------------------------------------------------------------
# flat_slots: the tree-compaction indexing contract
# ---------------------------------------------------------------------------


def test_flat_slots_maps_positions_to_pool_rows():
    pool = make_pool(num_pages=8, page_size=4)
    seq = pool.allocate_sequence(12)
    seq.append(*span(pool, 10))
    pages = list(seq.pages)
    got = seq.flat_slots([0, 3, 4, 9])
    assert got.tolist() == [
        pages[0] * 4 + 0, pages[0] * 4 + 3, pages[1] * 4 + 0, pages[2] * 4 + 1
    ]
    assert seq.flat_slots([]).size == 0


def test_flat_slots_requires_backed_positions():
    pool = make_pool(num_pages=8, page_size=4)
    seq = pool.allocate_sequence(12)
    seq.append(*span(pool, 5))  # 2 pages backed
    with pytest.raises(AssertionError):
        seq.flat_slots([8])  # 3rd page not backed
    seq.release()
    with pytest.raises(AssertionError, match="released"):
        seq.flat_slots([0])


def test_flat_slots_stable_across_tree_advance_rewind():
    """The engine queues compaction moves between advance(W) and
    rewind(W-1-n, release_pages=False); positions must keep mapping through
    the SAME physical pages across that dance."""
    pool = make_pool(num_pages=8, page_size=4)
    seq = pool.allocate_sequence(16)
    seq.append(*span(pool, 6))
    before = seq.flat_slots(np.arange(6))
    seq.advance(7)  # the W=7 tree window scattered in place on device
    mid = seq.flat_slots(np.arange(13))
    seq.rewind(5, release_pages=False)  # keep n_acc + 1 = 2
    after = seq.flat_slots(np.arange(8))
    np.testing.assert_array_equal(before, mid[:6])
    np.testing.assert_array_equal(mid[:8], after)


# ---------------------------------------------------------------------------
# Engine regression: abort mid-tree-round frees every sibling reservation
# ---------------------------------------------------------------------------


def test_abort_mid_tree_round_frees_sibling_pages():
    """A tree round reserves the full tree_budget + 1 window on both pools;
    aborting while branches are in flight must return every page (no leaked
    sibling reservations) and leave the other request draining normally."""
    from repro.launch.serve import build_pair
    from repro.serving import Engine, EngineConfig, SamplingParams

    target, draft = build_pair(seed=0, s_max=128, quantize=False)
    eng = Engine(target, draft, EngineConfig(
        max_batch=2, page_size=8, spec_mode="tree", tree_budget=6,
        spec_branches=2, branch_threshold=1.0, par_mode="wdos",
    ))
    rng = np.random.RandomState(0)
    sp = SamplingParams(temperature=2.0, seed=3, max_tokens=16)
    rid = eng.add_request(rng.randint(0, 512, size=5).astype(np.int32), sp)
    eng.add_request(rng.randint(0, 512, size=4).astype(np.int32), sp)
    eng.step()  # wdos trees stay in flight across steps
    assert eng.abort(rid)
    while eng.has_unfinished():
        eng.step()
    t_st, d_st = eng.pool_stats()
    assert t_st.used_pages == 0, t_st
    assert d_st.used_pages == 0, d_st
