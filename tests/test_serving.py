"""Quantized serving paths + end-to-end SD/APSD on real models."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bvq as bvq_mod
from repro.core.apsd import APSDConfig
from repro.core.quantization import sqnr_db, unpack_int4
from repro.core.speculative import SDConfig
from repro.models import lm
from repro.models.common import Family, ModelConfig
from repro.serving import quantized_lm as qlm
from repro.serving.engine import ServingModel, make_interface, serve_apsd, serve_sd

KEY = jax.random.PRNGKey(0)

CFG = ModelConfig(
    name="t", family=Family.DENSE, n_layers=2, d_model=128, n_heads=4,
    n_kv=2, d_ff=256, vocab=97, dtype="float32",
)


@pytest.fixture(scope="module")
def model():
    p, _ = lm.init_lm(KEY, CFG, tp=1)
    return p


def test_rotation_folding_exact(model):
    """bits=None: the rotated/folded model must equal the original."""
    toks = jax.random.randint(KEY, (2, 12), 0, CFG.vocab)
    ref, _ = lm.apply_lm(model, CFG, None, toks)
    qp = qlm.quantize_dense_lm(model, CFG, bits=None, rotate=True)
    got, _ = qlm.apply_quantized_lm(qp, CFG, None, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=5e-5)


def test_w4a8_cache_path_consistent(model):
    qp = qlm.quantize_dense_lm(model, CFG, bits=4, rotate=True)
    toks = jax.random.randint(KEY, (2, 12), 0, CFG.vocab)
    full, _ = qlm.apply_quantized_lm(qp, CFG, None, toks)
    cache = lm.init_cache(CFG, 2, 32, tp=1)
    lgp, cache = qlm.apply_quantized_lm(qp, CFG, None, toks[:, :8], cache=cache)
    lgd, cache = qlm.apply_quantized_lm(qp, CFG, None, toks[:, 8:9], cache=cache)
    np.testing.assert_allclose(np.asarray(full[:, 7]), np.asarray(lgp[:, -1]), atol=1e-4)
    np.testing.assert_allclose(np.asarray(full[:, 8]), np.asarray(lgd[:, 0]), atol=1e-4)


def test_layerwise_build_equals_whole_model_quantization(model):
    """init_quantized_lm (one layer at a time, the path that fits llama2-7b
    on one chip) draws and packs the weights of quantizing the whole
    init_lm model, same key splits, up to float rounding: scales agree to
    f32 precision and an int4 weight moves at most one step, rarely."""
    want = qlm.quantize_dense_lm(model, CFG, bits=4, rotate=True)
    got = qlm.init_quantized_lm(KEY, CFG)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree.flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype, path
        if path[-1].key == "packed":
            step = np.abs(np.asarray(unpack_int4(g, axis=-2)).astype(np.int32)
                          - np.asarray(unpack_int4(w, axis=-2)))
            assert step.max() <= 1 and step.mean() < 1e-3, path
        else:
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-6, atol=1e-8, err_msg=str(path))


def test_rotation_beats_no_rotation_under_outliers(model):
    """The paper's W4A8 accuracy claim: LRU rotation must recover accuracy
    that plain W4A8 loses when activations carry outlier channels."""
    p = dict(model)
    emb = p["embed"]["tok"].at[:, jnp.array([3, 40, 77])].multiply(40.0)
    p = {**p, "embed": {**p["embed"], "tok": emb}}
    toks = jax.random.randint(KEY, (4, 16), 0, CFG.vocab)
    ref, _ = lm.apply_lm(p, CFG, None, toks)
    lg_rot, _ = qlm.apply_quantized_lm(
        qlm.quantize_dense_lm(p, CFG, 4, rotate=True), CFG, None, toks
    )
    lg_nor, _ = qlm.apply_quantized_lm(
        qlm.quantize_dense_lm(p, CFG, 4, rotate=False), CFG, None, toks
    )
    s_rot = float(sqnr_db(ref, lg_rot))
    s_nor = float(sqnr_db(ref, lg_nor))
    assert s_rot > s_nor + 5.0, (s_rot, s_nor)  # >5 dB win from rotation
    agree_rot = float(jnp.mean(jnp.argmax(lg_rot, -1) == jnp.argmax(ref, -1)))
    agree_nor = float(jnp.mean(jnp.argmax(lg_nor, -1) == jnp.argmax(ref, -1)))
    assert agree_rot > agree_nor


def test_bvq_lm_runs(model):
    bcfg = bvq_mod.BVQConfig(vec_dim=4, codebook_size=32, block_cols=32,
                             kmeans_iters=6, qat_steps=0)
    bp = qlm.bvq_compress_lm(model, CFG, bcfg, jax.random.PRNGKey(1))
    toks = jax.random.randint(KEY, (2, 10), 0, CFG.vocab)
    lg, _ = qlm.apply_bvq_lm(bp, CFG, None, toks)
    assert lg.shape == (2, 10, CFG.vocab)
    assert bool(jnp.all(jnp.isfinite(lg)))
    # compression ratio >4x vs f32 storage
    orig = sum(x.size * 4 for x in jax.tree.leaves(model))
    comp = 0
    for x in jax.tree.leaves(bp):
        itemsize = jnp.dtype(x.dtype).itemsize
        comp += x.size * (0.5 if x.dtype == jnp.int8 else itemsize)
    assert orig / comp > 2.0


def _pair(quantize):
    from repro.launch.serve import build_pair

    return build_pair(seed=0, s_max=128, quantize=quantize)


@pytest.mark.parametrize("quantize", [False, True])
def test_sd_serving_lossless_real_models(quantize):
    """Greedy SD output == greedy AD decode of the SAME target model."""
    from repro.launch.serve import greedy_reference

    target, draft = _pair(quantize)
    prompt = jnp.asarray([[5, 17, 3, 99]], jnp.int32)
    toks, stats = serve_sd(
        jax.random.PRNGKey(0), target, draft, prompt,
        SDConfig(draft_len=3, temperature=0.0, max_tokens=16),
    )
    ref = greedy_reference(target, prompt, 16)
    assert bool(jnp.all(toks == ref))


def test_apsd_serving_lossless_real_models():
    from repro.launch.serve import greedy_reference

    target, draft = _pair(True)
    prompt = jnp.asarray([[5, 17, 3, 99]], jnp.int32)
    toks, stats = serve_apsd(
        jax.random.PRNGKey(0), target, draft, prompt,
        APSDConfig(short_dl=2, long_dl=4, temperature=0.0, max_tokens=16),
    )
    ref = greedy_reference(target, prompt, 16)
    assert bool(jnp.all(toks == ref))
    assert stats.rounds > 0


def test_self_draft_apsd_stays_parallel():
    """Draft == target (quantized same weights) -> near-total acceptance and
    PAR-mode lock-in: the controller behaves as designed on real models."""
    p, _ = lm.init_lm(KEY, CFG, tp=1)
    sm = ServingModel(cfg=CFG, params=p, mode="bf16", s_max=128)
    prompt = jnp.asarray([[5, 17, 3]], jnp.int32)
    toks, stats = serve_apsd(
        jax.random.PRNGKey(1), sm, sm, prompt,
        APSDConfig(short_dl=2, long_dl=4, temperature=0.0, max_tokens=20),
    )
    assert stats.rejected_ratio < 0.05
    assert stats.par_rounds >= stats.rounds - 2
