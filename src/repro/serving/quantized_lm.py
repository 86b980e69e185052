"""W4A8 target-LM and BVQ draft-LM serving paths — the paper's technique as
a first-class feature (dense/GQA family: the TLM/DLM pairs are LLaMA-style).

QuaRot-style computational invariance with the LRU rotation:
  * RMSNorm scales fold into the following projections (g := 1); RMSNorm
    without scale commutes with any orthogonal R (||xR|| == ||x||).
  * The residual stream is rotated once, offline: embed <- embed @ R1,
    every in-projection W <- R1^T W, every out-projection W <- W @ R1,
    head <- R1^T head.  R1 = plan_rotation(d_model) — exactly orthogonal
    for every LRU scheme, so with bits=None this is EXACT (tested).
  * The down_proj input (the paper's worked example: LLaMA d_ff = 2^k * m)
    is rotated ONLINE by R2 = plan_rotation(d_ff) via the Pallas FWHT
    kernel, with R2^T folded into w_down offline.
  * All linears then quantize to INT4 weights / dynamic INT8 activations
    (kernels/w4a8_matmul.py).

The BVQ draft path compresses every linear into block codebooks + indices
(kernels/bvq_matmul.py) — the RS-PNM dataflow.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import bvq as bvq_mod
from repro.core import quantization as q
from repro.core import rotation as rot
from repro.kernels import ops
from repro.models import layers as L
from repro.models.common import Family, ModelConfig
from repro.models.lm import batch_axes_for

Params = Dict[str, Any]

__all__ = [
    "quantize_dense_lm",
    "init_quantized_lm",
    "apply_quantized_lm",
    "bvq_compress_lm",
    "apply_bvq_lm",
    "bvq_impl_counts",
    "quantized_param_specs",
    "abstract_quantized",
]


# ---------------------------------------------------------------------------
# Offline transformation (rotation folding + quantization)
# ---------------------------------------------------------------------------


def _fold_norm_into(w: jnp.ndarray, g: jnp.ndarray) -> jnp.ndarray:
    """Absorb an RMSNorm scale into the input side of a linear weight."""
    return w * g.reshape((-1,) + (1,) * (w.ndim - 1)).astype(w.dtype)


def _rot_in(w: jnp.ndarray, plan) -> jnp.ndarray:
    """W <- R^T W along the input (first) axis, any trailing shape."""
    shape = w.shape
    w2 = w.reshape(shape[0], -1)
    w2 = rot.rotate_weight_in(w2.astype(jnp.float32), plan)
    return w2.reshape(shape)


def _rot_out(w: jnp.ndarray, plan) -> jnp.ndarray:
    """W <- W R along the output (last) axis."""
    shape = w.shape
    w2 = w.reshape(-1, shape[-1]).astype(jnp.float32)
    w2 = rot.local_rotate(w2, plan)
    return w2.reshape(shape)


def _quant_pack(w: jnp.ndarray, bits: Optional[int]):
    """(K, N) -> packed int4 + scales, or passthrough when bits is None."""
    if bits is None:
        return {"w": w.astype(jnp.float32)}
    wq, sw = q.quantize_weight_int(w.astype(jnp.float32), bits=bits, axis=0)
    return {"packed": q.pack_int4(wq, axis=0), "sw": sw.reshape(1, -1)}


def _fold_layer(lp: Params, cfg: ModelConfig, r1, r2, bits: Optional[int],
                rotate: bool) -> Params:
    """One dense block's bf16 params -> its folded, rotated W4A8 form."""
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.hd
    wq_ = _fold_norm_into(lp["attn"]["wq"], lp["ln1"]["g"]).reshape(d, h * hd)
    wk_ = _fold_norm_into(lp["attn"]["wk"], lp["ln1"]["g"]).reshape(d, kv * hd)
    wv_ = _fold_norm_into(lp["attn"]["wv"], lp["ln1"]["g"]).reshape(d, kv * hd)
    wo_ = lp["attn"]["wo"].reshape(h * hd, d)
    wg_ = _fold_norm_into(lp["mlp"]["w_gate"], lp["ln2"]["g"])
    wu_ = _fold_norm_into(lp["mlp"]["w_up"], lp["ln2"]["g"])
    wd_ = lp["mlp"]["w_down"]
    if rotate:
        wq_, wk_, wv_ = (_rot_in(w, r1) for w in (wq_, wk_, wv_))
        wg_, wu_ = _rot_in(wg_, r1), _rot_in(wu_, r1)
        wo_ = _rot_out(wo_, r1)
        wd_ = _rot_out(wd_, r1)
        wd_ = _rot_in(wd_, r2)  # online R2 rotates the d_ff activation
    return {
        "wq": _quant_pack(wq_, bits),
        "wk": _quant_pack(wk_, bits),
        "wv": _quant_pack(wv_, bits),
        "wo": _quant_pack(wo_, bits),
        "w_gate": _quant_pack(wg_, bits),
        "w_up": _quant_pack(wu_, bits),
        "w_down": _quant_pack(wd_, bits),
        "qk_extra": {
            k: lp["attn"][k] for k in ("q_norm", "k_norm") if k in lp["attn"]
        },
    }


def _fold_embed_head(embed_p: Params, final_norm: Params, cfg: ModelConfig,
                     r1, bits: Optional[int], rotate: bool) -> Params:
    """Embedding and head of the W4A8 form: the final norm folds into the
    head, and R1 rotates the embedding's output and the head's input."""
    embed = embed_p["tok"].astype(jnp.float32)
    head = _fold_norm_into(embed_p["head"], final_norm["g"]).astype(jnp.float32)
    if rotate:
        embed = rot.local_rotate(embed, r1)  # (V, d): rotate output side
        head = _rot_in(head, r1)
    return {"embed": embed.astype(cfg.jdtype), "head": _quant_pack(head, bits)}


def _plans(cfg: ModelConfig, rotate: bool):
    if not rotate:
        return None, None
    return rot.plan_rotation(cfg.d_model), rot.plan_rotation(cfg.d_ff)


def quantize_dense_lm(
    params: Params, cfg: ModelConfig, bits: Optional[int] = 4, rotate: bool = True
) -> Params:
    """Transform bf16 dense-LM params into the W4A8 serving form.

    bits=None keeps float weights (validates rotation-folding exactness);
    rotate=False skips the LRU rotations (the no-rotation ablation the
    paper's perplexity table compares against)."""
    assert cfg.family in (Family.DENSE, Family.VLM), "W4A8 path: dense family"
    r1, r2 = _plans(cfg, rotate)
    layers = jax.vmap(
        lambda lp: _fold_layer(lp, cfg, r1, r2, bits, rotate)
    )(params["layers"])
    out = _fold_embed_head(
        params["embed"], params["final_norm"], cfg, r1, bits, rotate
    )
    out["layers"] = layers
    return out


def init_quantized_lm(key: jax.Array, cfg: ModelConfig) -> Params:
    """Random W4A8 (LRU-rotated) params built one layer at a time.

    Draws the weights of ``quantize_dense_lm(lm.init_lm(key, cfg)[0], cfg)``
    (same key splits) up to float rounding: each layer is initialised,
    folded and packed by one program before the next one exists, and
    written into a preallocated stack.  The build holds one layer's f32
    fold and the packed model at most, where the whole-model path holds
    every bf16 layer and their f32 folds at once — more than one 16 GB chip
    has at llama2-7b widths.  The fold runs as one program because, op by
    op, the host queues the rotation's full-size intermediates faster than
    the device frees them; XLA then fuses the scaling constants, so a few
    weights round to the neighbouring int4 step."""
    from repro.models.lm import _layer_init

    assert cfg.family in (Family.DENSE, Family.VLM), "W4A8 path: dense family"
    r1, r2 = _plans(cfg, True)
    ke, kl, _, _ = jax.random.split(key, 4)  # init_lm's split
    final_norm, _ = L.rmsnorm_init(cfg.d_model, cfg.jdtype)
    out = jax.jit(lambda k: _fold_embed_head(
        L.embed_init(k, cfg)[0], final_norm, cfg, r1, 4, True
    ))(ke)
    fold = jax.jit(lambda k: _fold_layer(
        _layer_init(k, cfg, 1, "dense")[0], cfg, r1, r2, 4, True
    ))

    @partial(jax.jit, donate_argnums=(0,))
    def put(stack, layer, i):
        return jax.tree.map(lambda s, x: s.at[i].set(x), stack, layer)

    keys = jax.random.split(kl, cfg.n_layers)  # _stacked_layers' split
    stack = jax.tree.map(
        lambda x: jnp.zeros((cfg.n_layers,) + x.shape, x.dtype),
        jax.eval_shape(fold, keys[0]),
    )
    for i in range(cfg.n_layers):
        # wait for each layer to land, so no two layers' transients coexist
        stack = jax.block_until_ready(put(stack, fold(keys[i]), i))
    out["layers"] = stack
    return out


# ---------------------------------------------------------------------------
# Quantized forward (decode/prefill/extend with cache)
# ---------------------------------------------------------------------------


def _qlinear(x: jnp.ndarray, qw: Params, use_pallas: bool) -> jnp.ndarray:
    if "w" in qw:  # float passthrough (bits=None)
        return x @ qw["w"].astype(x.dtype)
    return ops.w4a8_linear(x, qw["packed"], qw["sw"], use_pallas=use_pallas)


def _paged_attn(q_, k_, v_, kvs_, lengths, pctx):
    """One layer's paged attention: scatter the span into the pool slice,
    attend through the page table, return (att, new pool slices).  Pool
    slices carrying ``k_scale`` are compressed (int8 + per-slot scales) —
    the update quantizes on scatter and dequantizes at the consumer."""
    table, impl, tree_mask = pctx
    pc = L.PagedCache(
        k=kvs_["k"], v=kvs_["v"], page_table=table, length=lengths, impl=impl,
        k_scale=kvs_.get("k_scale"), v_scale=kvs_.get("v_scale"),
        tree_mask=tree_mask,
    )
    att, new_pools = L.paged_attention_update(q_, k_, v_, pc)
    return att, new_pools


def _norm_only(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype)


def apply_quantized_lm(
    qparams: Params,
    cfg: ModelConfig,
    mesh,
    tokens: jnp.ndarray,
    cache: Optional[Params] = None,
    rotate: bool = True,
    use_pallas: bool = False,
    last_logit_only: bool = False,
    paged_impl: str = "gather",
) -> Tuple[jnp.ndarray, Optional[Params]]:
    """W4A8 serving forward (dense family).  Mirrors lm.apply_lm's dense
    path with quantized linears; scan over layers.  A cache carrying
    ``page_table`` is the device-resident paged pool (per-row lengths)."""
    tp = mesh.shape["model"] if mesh is not None else 1
    b, s = tokens.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv, cfg.hd
    store = L.kv_store_heads(cfg, tp)
    r2 = rot.plan_rotation(cfg.d_ff) if rotate else None
    paged = cache is not None and "page_table" in cache
    offset, positions, pctx = L.forward_cache_ctx(cache, b, s, paged_impl)
    x = qparams["embed"][tokens].astype(cfg.jdtype)
    if mesh is not None:
        from repro.models.lm import batch_axes_for
        ba = batch_axes_for(mesh, b)
        x = jax.lax.with_sharding_constraint(x, P(ba, None, None))
    new_cache = dict(cache) if cache is not None else None

    def body(carry, xs):
        xc = carry
        p, kvs_ = xs
        z = _norm_only(xc)
        q_ = _qlinear(z, p["wq"], use_pallas).reshape(b, s, h, hd)
        k_ = _qlinear(z, p["wk"], use_pallas).reshape(b, s, kv, hd)
        v_ = _qlinear(z, p["wv"], use_pallas).reshape(b, s, kv, hd)
        if cfg.qk_norm:
            q_ = L._qk_head_norm(q_, p["qk_extra"]["q_norm"])
            k_ = L._qk_head_norm(k_, p["qk_extra"]["k_norm"])
        q_ = L.rope(q_, positions, cfg.rope_theta, cfg.rope_scaling)
        k_ = L.rope(k_, positions, cfg.rope_theta, cfg.rope_scaling)
        k_ = L._repeat_kv(k_, store)
        v_ = L._repeat_kv(v_, store)
        if pctx is not None:
            att, ys = _paged_attn(q_, k_, v_, kvs_, offset, pctx)
        elif kvs_ is not None and "k_scale" in kvs_:
            kq, ksc = L._kv_quantize(k_)
            vq, vsc = L._kv_quantize(v_)
            ck = jax.lax.dynamic_update_slice_in_dim(kvs_["k"], kq, offset, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(kvs_["v"], vq, offset, axis=1)
            cks = jax.lax.dynamic_update_slice_in_dim(kvs_["k_scale"], ksc, offset, axis=1)
            cvs = jax.lax.dynamic_update_slice_in_dim(kvs_["v_scale"], vsc, offset, axis=1)
            if s == 1:
                att = L._decode_attention(q_, ck, cv, offset + 1,
                                          k_scale=cks, v_scale=cvs)
            else:
                att = L.flash_attention(
                    q_, L._kv_dequant(ck, cks, xc.dtype),
                    L._kv_dequant(cv, cvs, xc.dtype),
                    causal=True, q_offset=offset,
                )
            ys = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
        elif kvs_ is not None:
            ck = jax.lax.dynamic_update_slice_in_dim(kvs_["k"], k_, offset, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(kvs_["v"], v_, offset, axis=1)
            if s == 1:
                att = L._decode_attention(q_, ck, cv, offset + 1)
            else:
                att = L.flash_attention(q_, ck, cv, causal=True, q_offset=offset)
            ys = {"k": ck, "v": cv}
        else:
            att = L.flash_attention(q_, k_, v_, causal=True)
            ys = None
        att = att.reshape(b, s, h * hd)
        xc = xc + _qlinear(att, p["wo"], use_pallas)
        z2 = _norm_only(xc)
        g_ = _qlinear(z2, p["w_gate"], use_pallas)
        u_ = _qlinear(z2, p["w_up"], use_pallas)
        hid = jax.nn.silu(g_.astype(jnp.float32)).astype(xc.dtype) * u_
        if rotate:  # the LRU's online stage (Pallas FWHT kernel)
            with jax.named_scope("lru_rotation"):
                hid = ops.lru_rotate(hid, r2, use_pallas=use_pallas)
        xc = xc + _qlinear(hid, p["w_down"], use_pallas)
        return xc, ys

    if cache is not None:
        x, kv_out = jax.lax.scan(body, x, (qparams["layers"], cache["attn"]))
        new_cache["attn"] = kv_out
        new_cache["lengths" if paged else "length"] = offset + s
    else:
        x, _ = jax.lax.scan(lambda c, p: body(c, (p, None)), x, qparams["layers"])
    x = _norm_only(x)
    if last_logit_only:
        x = x[:, -1:, :]
    with jax.named_scope("lm_head"):
        logits = _qlinear(x, qparams["head"], use_pallas)
    return logits, new_cache


# ---------------------------------------------------------------------------
# BVQ draft-LM path (RS-PNM dataflow)
# ---------------------------------------------------------------------------


def bvq_compress_lm(
    params: Params, cfg: ModelConfig, bcfg: bvq_mod.BVQConfig, key: jax.Array
) -> Params:
    """Compress every linear of a dense LM into BVQ codebooks + indices."""
    assert cfg.family in (Family.DENSE, Family.VLM)
    d, h, kv, hd, f = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.hd, cfg.d_ff

    def one(w, k):
        return bvq_mod.bvq_compress(w.astype(jnp.float32), bcfg, k)

    def fold_layer(lp: Params, k) -> Params:
        ks = jax.random.split(k, 7)
        return {
            "ln1": lp["ln1"],
            "ln2": lp["ln2"],
            "wq": one(lp["attn"]["wq"].reshape(d, h * hd), ks[0]),
            "wk": one(lp["attn"]["wk"].reshape(d, kv * hd), ks[1]),
            "wv": one(lp["attn"]["wv"].reshape(d, kv * hd), ks[2]),
            "wo": one(lp["attn"]["wo"].reshape(h * hd, d), ks[3]),
            "w_gate": one(lp["mlp"]["w_gate"], ks[4]),
            "w_up": one(lp["mlp"]["w_up"], ks[5]),
            "w_down": one(lp["mlp"]["w_down"], ks[6]),
            "qk_extra": {
                kk: lp["attn"][kk] for kk in ("q_norm", "k_norm") if kk in lp["attn"]
            },
        }

    keys = jax.random.split(key, cfg.n_layers)
    layers = jax.vmap(fold_layer)(params["layers"], keys)
    return {
        "embed": params["embed"]["tok"],
        "head": params["embed"]["head"],
        "final_norm": params["final_norm"],
        "layers": layers,
    }


def bvq_impl_counts(qparams: Params, use_pallas: Optional[bool] = None) -> dict:
    """How many of a BVQ model's linears (per layer) take each path of
    ``ops.bvq_linear``: ``{"pallas": n, "reference": n}``."""
    counts = {"pallas": 0, "reference": 0}
    for leaf in jax.tree.leaves(
        qparams, is_leaf=lambda t: isinstance(t, bvq_mod.BVQWeight)
    ):
        if isinstance(leaf, bvq_mod.BVQWeight):
            counts[ops.resolve_bvq_impl(leaf, use_pallas)] += 1
    return counts


def apply_bvq_lm(
    qparams: Params,
    cfg: ModelConfig,
    mesh,
    tokens: jnp.ndarray,
    cache: Optional[Params] = None,
    use_pallas: Optional[bool] = None,
    last_logit_only: bool = False,
    paged_impl: str = "gather",
) -> Tuple[jnp.ndarray, Optional[Params]]:
    """BVQ draft-LM forward: weights decoded from codebooks on the fly.
    A cache carrying ``page_table`` is the device-resident paged pool.
    ``use_pallas=None`` lets each linear take the path
    ``ops.resolve_bvq_impl`` picks."""
    tp = mesh.shape["model"] if mesh is not None else 1
    b, s = tokens.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv, cfg.hd
    store = L.kv_store_heads(cfg, tp)
    paged = cache is not None and "page_table" in cache
    offset, positions, pctx = L.forward_cache_ctx(cache, b, s, paged_impl)
    x = qparams["embed"][tokens].astype(cfg.jdtype)
    new_cache = dict(cache) if cache is not None else None

    def lin(xin, bw):
        return ops.bvq_linear(xin, bw, use_pallas=use_pallas)

    def body(carry, xs):
        xc = carry
        p, kvs_ = xs
        z = L.rmsnorm(p["ln1"], xc)
        q_ = lin(z, p["wq"]).reshape(b, s, h, hd).astype(xc.dtype)
        k_ = lin(z, p["wk"]).reshape(b, s, kv, hd).astype(xc.dtype)
        v_ = lin(z, p["wv"]).reshape(b, s, kv, hd).astype(xc.dtype)
        if cfg.qk_norm:
            q_ = L._qk_head_norm(q_, p["qk_extra"]["q_norm"])
            k_ = L._qk_head_norm(k_, p["qk_extra"]["k_norm"])
        q_ = L.rope(q_, positions, cfg.rope_theta, cfg.rope_scaling)
        k_ = L.rope(k_, positions, cfg.rope_theta, cfg.rope_scaling)
        k_ = L._repeat_kv(k_, store)
        v_ = L._repeat_kv(v_, store)
        if pctx is not None:
            att, ys = _paged_attn(q_, k_, v_, kvs_, offset, pctx)
        elif kvs_ is not None:
            ck = jax.lax.dynamic_update_slice_in_dim(kvs_["k"], k_, offset, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(kvs_["v"], v_, offset, axis=1)
            if s == 1:
                att = L._decode_attention(q_, ck, cv, offset + 1)
            else:
                att = L.flash_attention(q_, ck, cv, causal=True, q_offset=offset)
            ys = {"k": ck, "v": cv}
        else:
            att = L.flash_attention(q_, k_, v_, causal=True)
            ys = None
        att = att.reshape(b, s, h * hd)
        xc = xc + lin(att, p["wo"]).astype(xc.dtype)
        z2 = L.rmsnorm(p["ln2"], xc)
        g_ = lin(z2, p["w_gate"])
        u_ = lin(z2, p["w_up"]).astype(xc.dtype)
        hid = jax.nn.silu(g_.astype(jnp.float32)).astype(xc.dtype) * u_
        xc = xc + lin(hid, p["w_down"]).astype(xc.dtype)
        return xc, ys

    if cache is not None:
        x, kv_out = jax.lax.scan(body, x, (qparams["layers"], cache["attn"]))
        new_cache["attn"] = kv_out
        new_cache["lengths" if paged else "length"] = offset + s
    else:
        x, _ = jax.lax.scan(lambda c, p: body(c, (p, None)), x, qparams["layers"])
    x = L.rmsnorm(qparams["final_norm"], x)
    if last_logit_only:
        x = x[:, -1:, :]
    with jax.named_scope("lm_head"):
        logits = x @ qparams["head"].astype(x.dtype)
    return logits, new_cache

# ---------------------------------------------------------------------------
# Sharding specs + abstract params (for the quantized-decode dry-run cells)
# ---------------------------------------------------------------------------


def quantized_param_specs(cfg: ModelConfig) -> Params:
    fs = "data" if cfg.fsdp else None

    def lin_in():  # packed (K/2, N): N = TP columns
        return {"packed": P(fs, "model"), "sw": P(None, "model")}

    def lin_out():  # packed (K/2, N): K = TP rows (heads / d_ff)
        return {"packed": P("model", fs), "sw": P(None, None)}

    layer = {
        "wq": lin_in(), "wk": lin_in(), "wv": lin_in(),
        "wo": lin_out(),
        "w_gate": lin_in(), "w_up": lin_in(),
        "w_down": lin_out(),
        "qk_extra": (
            {"q_norm": P(None), "k_norm": P(None)} if cfg.qk_norm else {}
        ),
    }
    stacked = jax.tree.map(
        lambda sp: P(*((None,) + tuple(sp))), layer,
        is_leaf=lambda sp: isinstance(sp, P),
    )
    return {
        "embed": P("model", fs),
        "head": {"packed": P(fs, "model"), "sw": P(None, "model")},
        "layers": stacked,
    }


def abstract_quantized(cfg: ModelConfig, tp: int):
    """ShapeDtypeStruct tree of the W4A8 params (no allocation)."""
    from repro.models.lm import init_lm

    def build(key):
        p, _ = init_lm(key, cfg, tp)
        return quantize_dense_lm(p, cfg, bits=4, rotate=True)

    return jax.eval_shape(build, jax.random.PRNGKey(0)), quantized_param_specs(cfg)

# ---------------------------------------------------------------------------
# W4A8 MoE serving path (beyond-paper: the technique applied to experts)
# ---------------------------------------------------------------------------


def _quant_pack_experts(w: jnp.ndarray):
    """(E, K, F) -> int4-packed along K + per-(expert, out) scales."""
    wq, sw = q.quantize_weight_int(w.astype(jnp.float32), bits=4, axis=1)
    return {"packed": q.pack_int4(wq, axis=1), "sw": sw}  # (E,K/2,F), (E,1,F)


def quantize_moe_lm(params: Params, cfg: ModelConfig) -> Params:
    """W4A8 transform for the MoE family: attention + expert FFNs packed
    int4; router stays f32 (tiny, accuracy-critical).  No rotation folding
    (MoE residual rotation interacts with the router input; the LRU online
    stage is unnecessary for byte reduction, which is what decode needs)."""
    assert cfg.family is Family.MOE
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.hd

    def fold_layer(lp: Params) -> Params:
        return {
            "ln1": lp["ln1"],
            "ln2": lp["ln2"],
            "wq": _quant_pack(lp["attn"]["wq"].reshape(d, h * hd), 4),
            "wk": _quant_pack(lp["attn"]["wk"].reshape(d, kv * hd), 4),
            "wv": _quant_pack(lp["attn"]["wv"].reshape(d, kv * hd), 4),
            "wo": _quant_pack(lp["attn"]["wo"].reshape(h * hd, d), 4),
            "router": lp["moe"]["router"],
            "w_gate": _quant_pack_experts(lp["moe"]["w_gate"]),
            "w_up": _quant_pack_experts(lp["moe"]["w_up"]),
            "w_down": _quant_pack_experts(lp["moe"]["w_down"]),
        }

    layers = jax.vmap(fold_layer)(params["layers"])
    return {
        "embed": params["embed"]["tok"],
        "head": _quant_pack(params["embed"]["head"].astype(jnp.float32), 4),
        "final_norm": params["final_norm"],
        "layers": layers,
    }


def quantized_moe_param_specs(cfg: ModelConfig) -> Params:
    fs = "data" if cfg.fsdp else None

    def lin_in():
        return {"packed": P(fs, "model"), "sw": P(None, "model")}

    def lin_out():
        return {"packed": P("model", fs), "sw": P(None, None)}

    def experts():
        return {"packed": P("model", fs, None), "sw": P("model", None, None)}

    layer = {
        "ln1": {"g": P(None)}, "ln2": {"g": P(None)},
        "wq": lin_in(), "wk": lin_in(), "wv": lin_in(), "wo": lin_out(),
        "router": P(None, None),
        "w_gate": experts(), "w_up": experts(), "w_down": experts(),
    }
    stacked = jax.tree.map(
        lambda sp: P(*((None,) + tuple(sp))), layer,
        is_leaf=lambda sp: isinstance(sp, P),
    )
    return {
        "embed": P("model", fs),
        "head": {"packed": P(fs, "model"), "sw": P(None, "model")},
        "final_norm": {"g": P(None)},
        "layers": stacked,
    }


def abstract_quantized_moe(cfg: ModelConfig, tp: int):
    from repro.models.lm import init_lm

    def build(key):
        p, _ = init_lm(key, cfg, tp)
        return quantize_moe_lm(p, cfg)

    return jax.eval_shape(build, jax.random.PRNGKey(0)), quantized_moe_param_specs(cfg)


def _moe_a2a_quant(layer: Params, x: jnp.ndarray, cfg: ModelConfig, mesh,
                   seq_sharded: bool) -> jnp.ndarray:
    """GShard a2a with int4-packed expert weights: tokens quantize to INT8
    per-token, expert GEMMs accumulate INT32, dequant fuses into the gated
    combine — the TFTE dataflow applied to experts."""
    from repro.models.layers import moe_ff_split, pick_batch_axes, _topk_gates

    tp = mesh.shape["model"]
    e = cfg.n_experts
    split = moe_ff_split(cfg, tp)
    e_loc = max(e // tp, 1)
    batch_axes = pick_batch_axes(mesh, x.shape[0])

    def local(x_loc, router, wg_p, wg_s, wu_p, wu_s, wd_p, wd_s):
        b_loc, s_loc, d = x_loc.shape
        t = x_loc.reshape(-1, d)
        n_tok = t.shape[0]
        cap = max(int(cfg.capacity_factor * n_tok * cfg.top_k / e), 4)
        logits = t.astype(jnp.float32) @ router
        gates, ids = _topk_gates(logits, cfg.top_k)
        flat_ids = ids.reshape(-1)
        flat_gates = gates.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(n_tok), cfg.top_k)
        onehot = jax.nn.one_hot(flat_ids, e, dtype=jnp.int32)
        slot = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)
        keep = (slot >= 0) & (slot < cap)
        slot_c = jnp.clip(slot, 0, cap - 1)
        buf = jnp.zeros((e, cap, d), x_loc.dtype)
        buf = buf.at[flat_ids, slot_c].add(
            jnp.where(keep[:, None], t[flat_tok], 0.0).astype(x_loc.dtype)
        )
        if split > 1:
            buf = jnp.repeat(buf, split, axis=0)
        buf = buf.reshape(tp, e_loc, cap, d)
        recv = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=0)
        recv = recv.reshape(e_loc, tp * cap, d)
        # INT8 tokens x INT4 experts, INT32 accumulation
        xq, sx = q.quantize_act_int8(recv)  # (e_loc, C, d) int8, (e_loc,C,1)
        wg = q.unpack_int4(wg_p, axis=1).astype(jnp.int32)  # (e_loc, d, f)
        wu = q.unpack_int4(wu_p, axis=1).astype(jnp.int32)
        g_acc = jnp.einsum("ecd,edf->ecf", xq.astype(jnp.int32), wg,
                           preferred_element_type=jnp.int32)
        u_acc = jnp.einsum("ecd,edf->ecf", xq.astype(jnp.int32), wu,
                           preferred_element_type=jnp.int32)
        g_out = g_acc.astype(jnp.float32) * sx * wg_s
        u_out = u_acc.astype(jnp.float32) * sx * wu_s
        hmid = jax.nn.silu(g_out) * u_out  # (e_loc, C, f) f32
        hq, sh = q.quantize_act_int8(hmid)
        wd = q.unpack_int4(wd_p, axis=1).astype(jnp.int32)  # (e_loc, f, d)
        y_acc = jnp.einsum("ecf,efd->ecd", hq.astype(jnp.int32), wd,
                           preferred_element_type=jnp.int32)
        y = (y_acc.astype(jnp.float32) * sh * wd_s).astype(x_loc.dtype)
        y = y.reshape(e_loc, tp, cap, d).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(y, "model", split_axis=0, concat_axis=0)
        back = back.reshape(e, split, cap, d).sum(axis=1)
        picked = back[flat_ids, slot_c]
        picked = jnp.where(keep[:, None], picked, 0.0)
        contrib = picked.astype(jnp.float32) * flat_gates[:, None]
        out = jnp.zeros((n_tok, d), jnp.float32).at[flat_tok].add(contrib)
        return out.astype(x_loc.dtype).reshape(b_loc, s_loc, d)

    tok_spec = (
        P(batch_axes, "model", None) if seq_sharded else P(batch_axes, None, None)
    )
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(tok_spec, P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=tok_spec,
        check_vma=False,
    )
    return fn(x, layer["router"],
              layer["w_gate"]["packed"], layer["w_gate"]["sw"],
              layer["w_up"]["packed"], layer["w_up"]["sw"],
              layer["w_down"]["packed"], layer["w_down"]["sw"])


def apply_quantized_moe_lm(
    qparams: Params,
    cfg: ModelConfig,
    mesh,
    tokens: jnp.ndarray,
    cache: Optional[Params] = None,
    use_pallas: bool = False,
    last_logit_only: bool = False,
) -> Tuple[jnp.ndarray, Optional[Params]]:
    """W4A8 MoE decode/prefill: quantized attention + quantized a2a experts."""
    tp = mesh.shape["model"] if mesh is not None else 1
    b, s = tokens.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv, cfg.hd
    store = L.kv_store_heads(cfg, tp)
    offset = cache["length"] if cache is not None else jnp.zeros((), jnp.int32)
    positions = jnp.broadcast_to(offset + jnp.arange(s)[None, :], (b, s))
    x = qparams["embed"][tokens].astype(cfg.jdtype)
    if mesh is not None:
        from repro.models.lm import batch_axes_for
        ba = batch_axes_for(mesh, b)
        x = jax.lax.with_sharding_constraint(x, P(ba, None, None))
    new_cache = dict(cache) if cache is not None else None

    def body(carry, xs):
        xc = carry
        p, kvs_ = xs
        z = L.rmsnorm(p["ln1"], xc)
        q_ = _qlinear(z, p["wq"], use_pallas).reshape(b, s, h, hd).astype(xc.dtype)
        k_ = _qlinear(z, p["wk"], use_pallas).reshape(b, s, kv, hd).astype(xc.dtype)
        v_ = _qlinear(z, p["wv"], use_pallas).reshape(b, s, kv, hd).astype(xc.dtype)
        q_ = L.rope(q_, positions, cfg.rope_theta, cfg.rope_scaling)
        k_ = L.rope(k_, positions, cfg.rope_theta, cfg.rope_scaling)
        k_ = L._repeat_kv(k_, store)
        v_ = L._repeat_kv(v_, store)
        if kvs_ is not None and "k_scale" in kvs_:
            kq, ksc = L._kv_quantize(k_)
            vq, vsc = L._kv_quantize(v_)
            ck = jax.lax.dynamic_update_slice_in_dim(kvs_["k"], kq, offset, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(kvs_["v"], vq, offset, axis=1)
            cks = jax.lax.dynamic_update_slice_in_dim(kvs_["k_scale"], ksc, offset, axis=1)
            cvs = jax.lax.dynamic_update_slice_in_dim(kvs_["v_scale"], vsc, offset, axis=1)
            att = L._decode_attention(q_, ck, cv, offset + 1, k_scale=cks, v_scale=cvs) if s == 1 else L.flash_attention(q_, L._kv_dequant(ck, cks, xc.dtype), L._kv_dequant(cv, cvs, xc.dtype), causal=True, q_offset=offset)
            ys = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
        elif kvs_ is not None:
            ck = jax.lax.dynamic_update_slice_in_dim(kvs_["k"], k_, offset, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(kvs_["v"], v_, offset, axis=1)
            att = L._decode_attention(q_, ck, cv, offset + 1) if s == 1 else L.flash_attention(q_, ck, cv, causal=True, q_offset=offset)
            ys = {"k": ck, "v": cv}
        else:
            att = L.flash_attention(q_, k_, v_, causal=True)
            ys = None
        att = att.reshape(b, s, h * hd)
        xc = xc + _qlinear(att, p["wo"], use_pallas).astype(xc.dtype)
        z2 = L.rmsnorm(p["ln2"], xc)
        if mesh is not None:
            f = _moe_a2a_quant(p, z2, cfg, mesh, seq_sharded=False)
        else:
            # single-device reference: dequantize experts, dense dispatch
            e = cfg.n_experts
            wg = (q.unpack_int4(p["w_gate"]["packed"], axis=1).astype(jnp.float32)
                  * p["w_gate"]["sw"])
            wu = (q.unpack_int4(p["w_up"]["packed"], axis=1).astype(jnp.float32)
                  * p["w_up"]["sw"])
            wd = (q.unpack_int4(p["w_down"]["packed"], axis=1).astype(jnp.float32)
                  * p["w_down"]["sw"])
            from repro.models.layers import moe_apply_dense
            f = moe_apply_dense(
                {"router": p["router"], "w_gate": wg.astype(xc.dtype),
                 "w_up": wu.astype(xc.dtype), "w_down": wd.astype(xc.dtype)},
                z2, cfg,
            )
        xc = xc + f.astype(xc.dtype)
        return xc, ys

    if cache is not None:
        x, kv_out = jax.lax.scan(body, x, (qparams["layers"], cache["attn"]))
        new_cache["attn"] = kv_out
        new_cache["length"] = offset + s
    else:
        x, _ = jax.lax.scan(lambda c, pp: body(c, (pp, None)), x, qparams["layers"])
    x = L.rmsnorm(qparams["final_norm"], x)
    if last_logit_only:
        x = x[:, -1:, :]
    with jax.named_scope("lm_head"):
        logits = _qlinear(x, qparams["head"], use_pallas)
    return logits, new_cache
