"""Model zoo: per-family forward/decode consistency, loss, gradients."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import lm
from repro.models import whisper as W
from repro.launch.mesh import activate_mesh, make_cpu_mesh
from repro.models.common import Family, ModelConfig

KEY = jax.random.PRNGKey(0)


def tiny(family, **kw):
    base = dict(
        name="t", family=family, n_layers=2, d_model=64, n_heads=4, n_kv=2,
        d_ff=128, vocab=97, dtype="float32",
    )
    base.update(kw)
    return ModelConfig(**base)


CONFIGS = {
    "dense": tiny(Family.DENSE),
    "dense_sqrelu": tiny(Family.DENSE, act="squared_relu", n_kv=4),
    "dense_qknorm": tiny(Family.DENSE, qk_norm=True),
    "moe": tiny(Family.MOE, n_experts=4, top_k=2, moe_impl="dense"),
    "ssm": tiny(Family.SSM, ssm_state=16, ssm_headdim=16, ssm_chunk=8),
    "hybrid": tiny(
        Family.HYBRID, n_layers=5, attn_every=2, ssm_state=16,
        ssm_headdim=16, ssm_chunk=8,
    ),
    "vlm": tiny(Family.VLM, n_vision_tokens=4),
}


@pytest.mark.parametrize("name", list(CONFIGS))
def test_forward_shapes_and_finite(name):
    cfg = CONFIGS[name]
    p, specs = lm.init_lm(KEY, cfg, tp=1)
    toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab)
    vis = (
        jax.random.normal(KEY, (2, 4, cfg.d_model))
        if cfg.family is Family.VLM
        else None
    )
    logits, _ = lm.apply_lm(p, cfg, None, toks, vision_embeds=vis)
    exp_s = 16 + (4 if vis is not None else 0)
    assert logits.shape == (2, exp_s, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))
    # spec tree must mirror the param tree
    jax.tree.map(lambda a, b: None, p, specs)


@pytest.mark.parametrize("name", ["dense", "dense_qknorm", "ssm", "hybrid"])
def test_decode_matches_full_forward(name):
    cfg = CONFIGS[name]
    p, _ = lm.init_lm(KEY, cfg, tp=1)
    toks = jax.random.randint(KEY, (2, 12), 0, cfg.vocab)
    cache = lm.init_cache(cfg, 2, 32, tp=1)
    lgp, cache = lm.apply_lm(p, cfg, None, toks[:, :8], cache=cache)
    lgd, cache = lm.apply_lm(p, cfg, None, toks[:, 8:9], cache=cache)
    lge, cache = lm.apply_lm(p, cfg, None, toks[:, 9:12], cache=cache)  # extend
    lgf, _ = lm.apply_lm(p, cfg, None, toks)
    np.testing.assert_allclose(lgf[:, 7], lgp[:, -1], atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(lgf[:, 8], lgd[:, 0], atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(lgf[:, 9:12], lge, atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("name", ["dense", "moe", "ssm", "hybrid"])
def test_loss_and_grads_finite(name):
    cfg = CONFIGS[name]
    p, _ = lm.init_lm(KEY, cfg, tp=1)
    toks = jax.random.randint(KEY, (2, 9), 0, cfg.vocab)
    loss, grads = jax.value_and_grad(lm.loss_fn)(p, cfg, None, toks)
    assert np.isfinite(float(loss)) and float(loss) > 0
    flat = jax.tree.leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in flat)
    assert any(float(jnp.abs(g).max()) > 0 for g in flat)


def test_cache_rewind_semantics():
    """Rewinding the cache length must restore earlier logits exactly."""
    cfg = CONFIGS["dense"]
    p, _ = lm.init_lm(KEY, cfg, tp=1)
    toks = jax.random.randint(KEY, (1, 10), 0, cfg.vocab)
    cache = lm.init_cache(cfg, 1, 32, tp=1)
    _, cache = lm.apply_lm(p, cfg, None, toks[:, :6], cache=cache)
    lg_a, cache_a = lm.apply_lm(p, cfg, None, toks[:, 6:8], cache=cache)
    # rewind 2 and re-extend with the same tokens
    cache_rw = dict(cache_a)
    cache_rw["length"] = cache_a["length"] - 2
    lg_b, _ = lm.apply_lm(p, cfg, None, toks[:, 6:8], cache=cache_rw)
    np.testing.assert_allclose(np.asarray(lg_a), np.asarray(lg_b), atol=1e-5)


def test_flash_attention_matches_naive():
    from repro.models.layers import flash_attention

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 8, 4, 16).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 8, 2, 16).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 8, 2, 16).astype(np.float32))
    got = flash_attention(q, k, v, causal=True, kv_chunk=4)
    # naive reference
    qf = q.reshape(2, 8, 2, 2, 16)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qf, k) / np.sqrt(16)
    mask = np.tril(np.ones((8, 8), bool))
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    pv = jnp.einsum("bkgqs,bskh->bkgqh", jax.nn.softmax(scores, -1), v)
    want = pv.transpose(0, 3, 1, 2, 4).reshape(2, 8, 4, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_flash_attention_indivisible_kv_width():
    """Regression: KV widths > kv_chunk that don't divide into equal chunks
    (paged gather spans are sized by page count, not powers of two) must
    fall back to one chunk instead of crashing on the reshape, and per-row
    q_offset arrays must broadcast like the scalar form."""
    from repro.models.layers import flash_attention

    rng = np.random.RandomState(2)
    skv = 13  # 13 // kv_chunk(4) = 3 chunks, 13 % 3 != 0
    q = jnp.asarray(rng.randn(2, 2, 2, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(2, skv, 2, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(2, skv, 2, 8).astype(np.float32))
    got = flash_attention(q, k, v, causal=True, q_offset=5, kv_chunk=4)
    want = flash_attention(q, k, v, causal=True, q_offset=5, kv_chunk=skv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    # per-row offsets: row offsets equal to the scalar give the same rows
    per_row = flash_attention(
        q, k, v, causal=True, q_offset=jnp.asarray([5, 5]), kv_chunk=skv
    )
    np.testing.assert_allclose(np.asarray(per_row), np.asarray(want), atol=0)


def test_ssd_chunk_invariance():
    """SSD output must not depend on the chunk size."""
    from repro.models.ssm import ssd_chunked

    rng = np.random.RandomState(1)
    b, s, h, p, n = 2, 32, 3, 8, 4
    x = jnp.asarray(rng.randn(b, s, h, p).astype(np.float32))
    da = jnp.asarray(-np.abs(rng.randn(b, s, h)).astype(np.float32) * 0.1)
    bm = jnp.asarray(rng.randn(b, s, h, n).astype(np.float32))
    cm = jnp.asarray(rng.randn(b, s, h, n).astype(np.float32))
    y8, st8 = ssd_chunked(x, da, bm, cm, 8)
    y16, st16 = ssd_chunked(x, da, bm, cm, 16)
    y32, st32 = ssd_chunked(x, da, bm, cm, 32)
    np.testing.assert_allclose(np.asarray(y8), np.asarray(y16), atol=1e-4)
    np.testing.assert_allclose(np.asarray(y8), np.asarray(y32), atol=1e-4)
    np.testing.assert_allclose(np.asarray(st8), np.asarray(st32), atol=1e-4)


def test_whisper_decode_consistency():
    cfg = ModelConfig(
        name="w", family=Family.AUDIO, n_layers=2, n_encoder_layers=2,
        d_model=64, n_heads=4, n_kv=4, d_ff=128, vocab=101, act="gelu",
        n_audio_frames=24, dtype="float32",
    )
    p, _ = W.init_whisper(KEY, cfg, tp=1)
    toks = jax.random.randint(KEY, (2, 9), 0, 101)
    frames = jax.random.normal(KEY, (2, 24, 64))
    lgf, _ = W.apply_whisper(p, cfg, None, toks, frames=frames)
    cache = W.init_whisper_cache(cfg, 2, 32, tp=1)
    lgp, cache = W.apply_whisper(p, cfg, None, toks[:, :8], frames=frames, cache=cache)
    lgd, cache = W.apply_whisper(p, cfg, None, toks[:, 8:9], cache=cache)
    np.testing.assert_allclose(lgf[:, 7], lgp[:, -1], atol=1e-4)
    np.testing.assert_allclose(lgf[:, 8], lgd[:, 0], atol=1e-4)
    loss = W.whisper_loss_fn(p, cfg, None, toks, frames)
    assert np.isfinite(float(loss))


def test_moe_a2a_matches_dense_single_device():
    """On a 1-device mesh the a2a path must equal the dense reference
    (up to capacity drops — use generous capacity)."""
    cfg = tiny(Family.MOE, n_experts=4, top_k=2, moe_impl="a2a",
               capacity_factor=4.0, seq_shard=False)
    mesh = make_cpu_mesh()
    p, _ = lm.init_lm(KEY, cfg, tp=1)
    toks = jax.random.randint(KEY, (2, 8), 0, cfg.vocab)
    with activate_mesh(mesh):
        lg_a2a, _ = lm.apply_lm(p, cfg, mesh, toks)
    cfg_d = tiny(Family.MOE, n_experts=4, top_k=2, moe_impl="dense")
    lg_d, _ = lm.apply_lm(p, cfg_d, None, toks)
    np.testing.assert_allclose(
        np.asarray(lg_a2a), np.asarray(lg_d), atol=5e-4, rtol=1e-3
    )
