"""Linear RoPE scaling (``ModelConfig.rope_scaling``) in every forward and
through the engine's paged path.

- At factor 1 each of the four forwards traces the program it traced before
  the factor existed, and gives bit-identical logits.
- At factor 4 the engine serving deepseek-coder's CPU copy (the served form's
  ``smoke``: MHA, untied draft head, RoPE linear 4 in both models, seeded
  random weights) agrees on logits with plain references: its W4A8 target
  with ``chipbench/references/dense_w4a8``, its BVQ draft with the float32
  forward written out below.  Prefill and then decode through the paged
  pool are both compared, at every position the engine computed.
- The same engine without the factor fails that comparison past position 64.
- The ``rope_scaling_factor`` gauge reports each model's factor.
"""
from __future__ import annotations

import dataclasses
import json
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.spec import Bench
from repro.core import bvq as bvq_mod
from repro.models import layers as L
from repro.models import lm
from repro.models.common import Family, ModelConfig
from repro.serving import SamplingParams
from repro.serving import quantized_lm as qlm

KEY = jax.random.PRNGKey(0)
FACTOR = 4.0
# Largest |program - reference| logit allowed, target and draft alike.  The
# program runs the served dtype (bf16 activations; the target's int8 codes
# taken from bf16 rows, so a code can sit one step from the reference's),
# the references float32 at HIGHEST.  At these widths the largest gap at a
# position is 0.04-0.10 for the target and 0.02-0.08 for the draft, on
# logits that spread over about +-3.5; without the factor, past position
# 64, it is 0.43-1.41 and 1.80-4.60.  0.2 is twice the first and under half
# the second.
LOGIT_TOL = 0.2


def _old_rope(x, positions, theta, scaling=1.0):
    """``layers.rope`` as it was before the factor: the factor-1 program."""
    assert scaling == 1.0
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _tiny(family, **kw):
    base = dict(name="t", family=family, n_layers=2, d_model=64, n_heads=4, n_kv=2,
                d_ff=128, vocab=97, dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


def _forward(name):
    """(cfg, params, forward(params, cfg, tokens) -> logits) of one forward."""
    if name == "apply_quantized_moe_lm":
        cfg = _tiny(Family.MOE, n_experts=4, top_k=2, moe_impl="dense")
        params = qlm.quantize_moe_lm(lm.init_lm(KEY, cfg, tp=1)[0], cfg)
        return cfg, params, lambda p, c, t: qlm.apply_quantized_moe_lm(p, c, None, t)[0]
    cfg = _tiny(Family.DENSE)
    dense = lm.init_lm(KEY, cfg, tp=1)[0]
    if name == "apply_lm":
        return cfg, dense, lambda p, c, t: lm.apply_lm(p, c, None, t)[0]
    if name == "apply_quantized_lm":
        params = qlm.quantize_dense_lm(dense, cfg, bits=4, rotate=True)
        return cfg, params, lambda p, c, t: qlm.apply_quantized_lm(p, c, None, t)[0]
    bcfg = bvq_mod.BVQConfig(vec_dim=4, codebook_size=32, block_cols=32,
                             kmeans_iters=2, qat_steps=0)
    params = qlm.bvq_compress_lm(dense, cfg, bcfg, jax.random.PRNGKey(1))
    return cfg, params, lambda p, c, t: qlm.apply_bvq_lm(p, c, None, t)[0]


@pytest.mark.parametrize("name", ["apply_lm", "apply_quantized_lm", "apply_bvq_lm",
                                  "apply_quantized_moe_lm"])
def test_factor_one_is_the_program_before_the_factor(name, monkeypatch):
    cfg, params, fwd = _forward(name)
    assert cfg.rope_scaling == 1.0
    toks = jax.random.randint(KEY, (2, 12), 0, cfg.vocab)
    trace = lambda c: str(jax.make_jaxpr(lambda p, t: fwd(p, c, t))(params, toks))
    now, now_prog = np.asarray(fwd(params, cfg, toks)), trace(cfg)
    scaled = np.asarray(fwd(params, dataclasses.replace(cfg, rope_scaling=FACTOR), toks))
    with monkeypatch.context() as mp:
        mp.setattr(L, "rope", _old_rope)
        before, before_prog = np.asarray(fwd(params, cfg, toks)), trace(cfg)
    assert now_prog == before_prog
    np.testing.assert_array_equal(now, before)
    # the forward applies the factor: position 0 has angle 0 either way
    np.testing.assert_allclose(scaled[:, 0], now[:, 0], rtol=1e-6, atol=1e-6)
    assert np.abs(scaled[:, 1:] - now[:, 1:]).max() > 1e-3


# ---------------------------------------------------------------------------
# the engine at factor 4 against plain references
# ---------------------------------------------------------------------------


def _bvq_dense(bw) -> np.ndarray:
    """The (K, N) float32 matrix of one served BVQ weight: block b's column
    l, row ``r * v + t`` is component t of codebook entry ``indices[b, r,
    l]``, times the block's scale."""
    cb = np.asarray(bw.codebooks, np.float32)  # (nb, C, v)
    idx = np.asarray(bw.indices)  # (nb, K / v, bc)
    scale = np.asarray(bw.scales, np.float32).reshape(-1)
    nb, rows, bc = idx.shape
    v = cb.shape[-1]
    return np.concatenate([cb[b][idx[b]].transpose(0, 2, 1).reshape(rows * v, bc) * scale[b]
                           for b in range(nb)], axis=1)


def _draft_weights(params, m: dict) -> list:
    """Each layer's BVQ linears as float32 matrices."""
    return [{k: jnp.asarray(_bvq_dense(jax.tree.map(lambda a: a[i], params["layers"][k])))
             for k in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")}
            for i in range(m["num_hidden_layers"])]


@partial(jax.jit, static_argnames=("dims", "factor"))
def _draft_forward(params, weights, tokens, dims, factor):
    h, kv, hd, eps, theta = dims
    norm = lambda x, g: x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g
    t = tokens.shape[0]
    half = hd // 2
    inv = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = (jnp.arange(t, dtype=jnp.float32) / factor)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(x):
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        for i, w in enumerate(weights):
            z = norm(x, params["ln1"][i])
            q = rope((z @ w["wq"]).reshape(t, h, hd)).reshape(t, kv, h // kv, hd)
            k = rope((z @ w["wk"]).reshape(t, kv, hd))
            v = (z @ w["wv"]).reshape(t, kv, hd)
            s = jnp.einsum("tkgd,ukd->kgtu", q, k) / math.sqrt(hd)
            s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
            att = jnp.einsum("kgtu,ukd->tkgd", jax.nn.softmax(s, -1), v)
            x = x + att.reshape(t, h * hd) @ w["wo"]
            z = norm(x, params["ln2"][i])
            x = x + (jax.nn.silu(z @ w["w_gate"]) * (z @ w["w_up"])) @ w["w_down"]
        return norm(x, params["final_norm"]) @ params["head"]


def _draft_logits(params, weights, m: dict, tokens, factor: float) -> np.ndarray:
    """float32 logits of the BVQ draft (HF sizes ``m``) at every position of
    ``tokens``: pre-norm blocks with RMSNorm gains, RoPE on the two halves of
    each head at angle ``(p / factor) * theta^(-i/half)``, causal softmax over
    K/V heads shared by ``heads / kv_heads`` query heads, SiLU-gated FFN,
    final norm, the head.  Tokens are padded to a multiple of 64 (causal:
    the padding reaches no earlier row) so one program serves every call."""
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m.get("head_dim") or m["hidden_size"] // h
    assert not m.get("qk_norm", False)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    arrays = {"embed": f32(params["embed"]), "head": f32(params["head"]),
              "final_norm": f32(params["final_norm"]["g"]),
              "ln1": f32(params["layers"]["ln1"]["g"]), "ln2": f32(params["layers"]["ln2"]["g"])}
    padded = np.zeros(-(-len(tokens) // 64) * 64, np.int32)
    padded[:len(tokens)] = tokens
    dims = (h, kv, hd, float(m["rms_norm_eps"]), float(m["rope_theta"]))
    out = _draft_forward(arrays, weights, jnp.asarray(padded), dims, float(factor))
    return np.asarray(out)[:len(tokens)]


def _smoke_config(with_factor: bool = True) -> dict:
    bench = Bench()
    real = bench.config("dscoder-6.7b-spec")
    cfg = bench.served(real["served"]).smoke(real)
    if not with_factor:
        for m in (cfg, cfg["draft"]):
            del m["rope_scaling"]
    return cfg


def _serve(program_cfg: dict) -> dict:
    """One greedy request through the engine; every paged step's tokens,
    lengths and logits of the request's row, the prefill's logits, and the
    weights."""
    bench = Bench()
    served = bench.served(program_cfg["served"])
    traffic = dict(prompt_len=dict(grid=[96]), max_tokens=dict(max=24),
                   engine=dict(max_batch=2, pool_tokens=256))
    tparams, eng = harness.build(program_cfg, traffic, 3, served)
    calls = {"target": [], "draft": []}

    def record(step, log):
        def wrapped(params, tokens, store, table, lengths):
            logits, store = step(params, tokens, store, table, lengths)
            log.append((np.asarray(tokens)[0], int(np.asarray(lengths)[0]),
                        np.asarray(logits, np.float32)[0]))
            return logits, store
        return wrapped

    eng._t_step = record(eng._t_step, calls["target"])
    eng._d_step = record(eng._d_step, calls["draft"])
    prompt = np.random.default_rng(0).integers(0, program_cfg["vocab_size"], 90)
    eng.run([prompt.tolist()], SamplingParams(max_tokens=20, temperature=0.0))
    prefill = {role: np.asarray(iface.prefill(model.params, jnp.asarray(prompt[None, :-1]))[0],
                                np.float32)[0]
               for role, iface, model in (("target", eng._t_iface, eng.target),
                                          ("draft", eng._d_iface, eng.draft))}
    return dict(engine=eng, prompt=prompt, calls=calls, prefill=prefill,
                target=tparams, draft=eng.draft.params)


def _gaps(run: dict, cfg: dict, role: str) -> dict:
    """Position -> largest |program - reference| logit there, over the
    prefill and every paged step of ``role``.  A step at length n scores the
    positions n .. n+W-1 of the tokens its pool holds: each earlier call's
    window writes its tokens at its positions, so replaying the windows in
    order rebuilds that history exactly."""
    ref = Bench().reference(cfg["reference"])
    prompt = run["prompt"]

    weights = _draft_weights(run["draft"], cfg["draft"]) if role == "draft" else None

    def reference(tokens, start, n):
        if role == "target":
            return np.asarray(ref.logits_rows(run["target"], cfg, list(tokens), start, n))[:n]
        return _draft_logits(run["draft"], weights, cfg["draft"], tokens, FACTOR)[start:start + n]

    out = {}
    plen = len(prompt) - 1
    want = reference(prompt[:plen], 0, plen)
    for p in range(plen):
        out[p] = float(np.abs(run["prefill"][role][p] - want[p]).max())
    hist = list(prompt[:plen])
    for tokens, start, logits in run["calls"][role]:
        hist[start:] = list(tokens)
        want = reference(hist, start, len(tokens))
        for i in range(len(tokens)):
            out[start + i] = max(out.get(start + i, 0.0),
                                 float(np.abs(logits[i] - want[i]).max()))
    return out


@pytest.fixture(scope="module")
def scaled_run():
    return _serve(_smoke_config())


@pytest.mark.parametrize("role", ["target", "draft"])
def test_engine_agrees_with_the_reference_at_factor_four(scaled_run, role):
    cfg = _smoke_config()
    assert scaled_run["engine"].target.cfg.rope_scaling == FACTOR
    gaps = _gaps(scaled_run, cfg, role)
    assert max(gaps) >= 105  # prefill, then decode past the prompt of 90
    assert max(gaps.values()) <= LOGIT_TOL, max(gaps.items(), key=lambda kv: kv[1])


@pytest.fixture(scope="module")
def unscaled_run():
    return _serve(_smoke_config(with_factor=False))


@pytest.mark.parametrize("role", ["target", "draft"])
def test_engine_without_the_factor_fails_past_position_64(unscaled_run, role):
    assert unscaled_run["engine"].target.cfg.rope_scaling == 1.0
    gaps = _gaps(unscaled_run, _smoke_config(), role)
    late = [g for p, g in gaps.items() if p > 64]
    assert late and min(late) > LOGIT_TOL, min(late)


def test_rope_scaling_factor_gauge(scaled_run):
    metrics = scaled_run["engine"].metrics
    for role in ("target", "draft"):
        assert metrics.value("rope_scaling_factor", model=role) == FACTOR
    assert 'serving_rope_scaling_factor{model="draft"} 4' in metrics.render()
    bench = Bench()
    real = bench.config("qwen3-8b-spec")
    served = bench.served(real["served"])
    traffic = dict(prompt_len=dict(grid=[16]), max_tokens=dict(max=4),
                   engine=dict(max_batch=1, pool_tokens=64))
    _, eng = harness.build(served.smoke(real), traffic, 3, served)
    for role in ("target", "draft"):
        assert eng.metrics.value("rope_scaling_factor", model=role) == 1.0


def test_served_form_passes_the_published_factor():
    cfg = Bench().config("dscoder-6.7b-spec")
    served = Bench().served(cfg["served"])
    for role in ("target", "draft"):
        assert served.program_config(cfg, role).rope_scaling == FACTOR
    assert json.loads(json.dumps(cfg))["rope_scaling"]["type"] == "linear"
