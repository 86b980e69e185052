"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _optional import given, settings, st

from repro.core import bvq, quantization as q, rotation as rot
from repro.kernels import ops, ref
from repro.kernels.bvq_matmul import bvq_matmul_pallas, tile_rows
from repro.kernels.fwht import block_rotate_pallas
from repro.kernels.w4a8_matmul import w4a8_matmul_pallas


# ---------------------------------------------------------------------------
# FWHT / LRU rotation kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "m,k,nb,tokens",
    [
        (28, 5, 1, 16),  # 896 exact (internvl d_model)
        (28, 6, 8, 4),  # 14336 tiled (llama3-8b d_ff, paper example)
        (8, 6, 4, 32),
        (4, 6, 2, 8),
        (32, 6, 1, 64),  # 2048 exact (mamba2 d_model)
        (20, 6, 1, 5),  # 1280 exact (whisper d_model)
        (12, 3, 3, 7),
    ],
)
@pytest.mark.parametrize("transpose", [False, True])
def test_block_rotate_matches_oracle(m, k, nb, tokens, transpose):
    n = (m << k) * nb
    x = jnp.asarray(np.random.RandomState(0).randn(tokens, n).astype(np.float32))
    got = block_rotate_pallas(x, m, k, transpose=transpose)
    want = ref.block_rotate_ref(x, m, k, transpose=transpose)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_rotate_dtypes(dtype):
    x = jnp.asarray(np.random.RandomState(1).randn(8, 512), dtype=dtype)
    got = block_rotate_pallas(x, 8, 6)
    want = ref.block_rotate_ref(x, 8, 6)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )


def test_block_rotate_3d_batch():
    x = jnp.asarray(np.random.RandomState(2).randn(2, 5, 896).astype(np.float32))
    got = block_rotate_pallas(x, 28, 5)
    want = ref.block_rotate_ref(x, 28, 5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


@pytest.mark.parametrize("n", [896, 1792, 2048, 4864])
def test_lru_rotate_full_plan(n):
    p = rot.plan_rotation(n)
    x = jnp.asarray(np.random.RandomState(3).randn(6, n).astype(np.float32))
    got = ops.lru_rotate(x, p)
    want = rot.local_rotate(x, p)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)
    back = ops.lru_rotate_transpose(got, p)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x), atol=2e-4)


# ---------------------------------------------------------------------------
# W4A8 matmul kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "m,k,n,bm,bn,bk",
    [
        (8, 64, 32, 128, 128, 512),
        (128, 512, 256, 128, 128, 512),
        (4, 256, 128, 128, 128, 64),  # multiple K steps
        (96, 768, 384, 32, 128, 256),
        (1, 128, 64, 128, 128, 128),  # decode GEMV shape
    ],
)
def test_w4a8_matches_oracle(m, k, n, bm, bn, bk):
    rng = np.random.RandomState(4)
    xq = jnp.asarray(rng.randint(-127, 128, (m, k)).astype(np.int8))
    wq = jnp.asarray(rng.randint(-7, 8, (k, n)).astype(np.int8))
    wp = q.pack_int4(wq, axis=0)
    sx = jnp.asarray(rng.rand(m, 1).astype(np.float32))
    sw = jnp.asarray(rng.rand(1, n).astype(np.float32))
    got = w4a8_matmul_pallas(xq, wp, sx, sw, bm=bm, bn=bn, bk=bk)
    want = ref.w4a8_matmul_ref2(xq, wp, sx, sw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_w4a8_integer_exactness():
    """With unit scales the kernel must be bit-exact vs int64 numpy."""
    rng = np.random.RandomState(5)
    xq = rng.randint(-127, 128, (16, 256)).astype(np.int8)
    wq = rng.randint(-7, 8, (256, 64)).astype(np.int8)
    wp = q.pack_int4(jnp.asarray(wq), axis=0)
    got = w4a8_matmul_pallas(
        jnp.asarray(xq), wp,
        jnp.ones((16, 1), jnp.float32), jnp.ones((1, 64), jnp.float32),
    )
    ref64 = xq.astype(np.int64) @ wq.astype(np.int64)
    assert np.array_equal(np.asarray(got).astype(np.int64), ref64)


def test_w4a8_end_to_end_linear():
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(10, 256).astype(np.float32))
    w = jnp.asarray((rng.randn(256, 128) * 0.05).astype(np.float32))
    wq, sw = q.quantize_weight_int(w, bits=4, axis=0)
    wp = q.pack_int4(wq, axis=0)
    y = ops.w4a8_linear(x, wp, sw.reshape(1, -1))
    assert float(q.sqnr_db(x @ w, y)) > 15.0


# ---------------------------------------------------------------------------
# BVQ matmul kernel
# ---------------------------------------------------------------------------


def _random_bvq(rng, k, n, vec, cbs, bc, lead=()):
    """A BVQ weight in its served form, drawn directly: INT4 codebook
    entries, int32 indices, f32 block scales (optionally layer-stacked)."""
    nb = n // bc
    return bvq.BVQWeight(
        codebooks=jnp.asarray(rng.randint(-8, 8, lead + (nb, cbs, vec)).astype(np.int8)),
        scales=jnp.asarray(rng.rand(*lead, nb, 1, 1).astype(np.float32) / np.sqrt(k)),
        indices=jnp.asarray(rng.randint(0, cbs, lead + (nb, k // vec, bc)).astype(np.int32)),
        shape=(k, n),
        vec_dim=vec,
    )


@pytest.mark.parametrize(
    "mk,nn,vec,cbs,bc,layers",
    [
        ((8, 64), 48, 4, 32, 16, 0),
        ((32, 128), 128, 8, 64, 32, 0),
        ((1, 256), 64, 8, 16, 64, 0),  # decode GEMV
        ((16, 96), 96, 4, 16, 48, 0),
        # the served draft's blocks (v 8, C 256, 128 columns): a draft step,
        # an odd row count, a prefill tile, and a layer-stacked weight
        # sliced by a scan as apply_bvq_lm does
        ((16, 1024), 256, 8, 256, 128, 0),
        ((40, 3072), 256, 8, 256, 128, 0),
        ((256, 1024), 384, 8, 256, 128, 0),
        ((16, 3072), 256, 8, 256, 128, 3),
    ],
)
def test_bvq_matches_oracle(mk, nn, vec, cbs, bc, layers):
    m, k = mk
    rng = np.random.RandomState(7)
    if cbs < 256:
        cfg = bvq.BVQConfig(
            vec_dim=vec, codebook_size=cbs, block_cols=bc, kmeans_iters=4, qat_steps=0
        )
        w = jnp.asarray(rng.randn(k, nn).astype(np.float32))
        bw = bvq.bvq_compress(w, cfg, jax.random.PRNGKey(0))
    else:
        bw = _random_bvq(rng, k, nn, vec, cbs, bc, lead=(layers,) if layers else ())
    x = jnp.asarray(rng.randn(m, k).astype(np.float32))
    if layers:
        _, got = jax.lax.scan(lambda c, w: (c, bvq_matmul_pallas(x, w)), 0, bw)
    else:
        got = bvq_matmul_pallas(x, bw)
    with jax.default_matmul_precision("highest"):
        if layers:
            want = jnp.stack([
                ref.bvq_matmul_ref2(x, jax.tree.map(lambda a: a[i], bw))
                for i in range(layers)
            ])
        else:
            want = ref.bvq_matmul_ref2(x, bw)
    want = np.asarray(want)
    np.testing.assert_allclose(
        np.asarray(got), want, rtol=1e-5, atol=min(1e-4, 1e-5 * np.abs(want).max())
    )


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("m,bm", [(16, 16), (256, 224)])
def test_bvq_tiles_rows_to_fit_k_5504(m, bm, dtype):
    """deepseek-coder-1.3b's down projection (K 5504, N 2048): a draft step
    keeps its 16 rows in one tile, and a prefill's rows go in tiles of 224,
    the largest that fits the VMEM budget beside K 5504."""
    assert tile_rows(m, 5504, 128, 8, 256) == bm
    rng = np.random.RandomState(11)
    bw = _random_bvq(rng, 5504, 2048, 8, 256, 128)
    x = jnp.asarray(rng.randn(m, 5504).astype(np.float32)).astype(dtype)
    got = np.asarray(bvq_matmul_pallas(x, bw))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.bvq_matmul_ref2(x, bw))
    np.testing.assert_allclose(
        got, want, rtol=1e-5, atol=min(1e-4, 1e-5 * np.abs(want).max())
    )


@pytest.mark.parametrize("config", ["qwen3-8b-spec", "dscoder-6.7b-spec"])
def test_every_served_draft_linear_takes_the_kernel_on_a_tpu(config):
    """Each BVQ linear of both benchmark drafts, at its published shape,
    resolves to the Pallas kernel on a TPU (K 5504 included)."""
    from chipbench.spec import Bench

    bench = Bench()
    cfg = bench.config(config)
    _, draft = jax.eval_shape(
        lambda k: bench.served(cfg["served"]).draw(k, cfg), jax.random.PRNGKey(0)
    )
    linears = [w for w in jax.tree.leaves(
        draft, is_leaf=lambda t: isinstance(t, bvq.BVQWeight)
    ) if isinstance(w, bvq.BVQWeight)]
    assert len(linears) == 7
    assert {w.shape[0] for w in linears} >= {cfg["draft"]["intermediate_size"]}
    for w in linears:
        assert ops.resolve_bvq_impl(w, backend="tpu") == "pallas", w.shape


def test_resolve_bvq_impl_branches():
    """The kernel on a TPU at a shape it compiles for; the reference on
    the CPU, at 32-column blocks, or when the caller says so."""
    rng = np.random.RandomState(9)
    served = _random_bvq(rng, 1024, 256, 8, 256, 128)
    small = _random_bvq(rng, 1024, 256, 8, 256, 32)
    stacked = _random_bvq(rng, 1024, 256, 8, 256, 128, lead=(2,))
    assert ops.resolve_bvq_impl(served, backend="tpu") == "pallas"
    assert ops.resolve_bvq_impl(stacked, backend="tpu") == "pallas"
    assert ops.resolve_bvq_impl(served, backend="cpu") == "reference"
    assert ops.resolve_bvq_impl(small, backend="tpu") == "reference"
    assert ops.resolve_bvq_impl(served, use_pallas=False, backend="tpu") == "reference"
    assert ops.resolve_bvq_impl(small, use_pallas=True, backend="cpu") == "pallas"


def test_bvq_linear_wrapper_batched():
    rng = np.random.RandomState(8)
    cfg = bvq.BVQConfig(vec_dim=4, codebook_size=16, block_cols=16, kmeans_iters=4, qat_steps=0)
    w = jnp.asarray(rng.randn(64, 32).astype(np.float32))
    bw = bvq.bvq_compress(w, cfg, jax.random.PRNGKey(1))
    x = jnp.asarray(rng.randn(2, 3, 64).astype(np.float32))
    y = ops.bvq_linear(x, bw, use_pallas=True)
    want = x.reshape(-1, 64) @ bvq.bvq_reconstruct(bw)
    np.testing.assert_allclose(
        np.asarray(y).reshape(-1, 32), np.asarray(want), rtol=1e-4, atol=1e-4
    )


@settings(max_examples=10, deadline=None)
@given(
    m=st.integers(min_value=1, max_value=16),
    kblocks=st.integers(min_value=1, max_value=4),
    seed=st.integers(min_value=0, max_value=50),
)
def test_w4a8_property_random_shapes(m, kblocks, seed):
    k = 64 * kblocks
    n = 32
    rng = np.random.RandomState(seed)
    xq = jnp.asarray(rng.randint(-127, 128, (m, k)).astype(np.int8))
    wq = jnp.asarray(rng.randint(-7, 8, (k, n)).astype(np.int8))
    wp = q.pack_int4(wq, axis=0)
    sx = jnp.asarray(rng.rand(m, 1).astype(np.float32))
    sw = jnp.asarray(rng.rand(1, n).astype(np.float32))
    got = w4a8_matmul_pallas(xq, wp, sx, sw, bk=64)
    want = ref.w4a8_matmul_ref2(xq, wp, sx, sw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
