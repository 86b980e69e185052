"""Unified decoder-only LM covering the dense / MoE / SSM / hybrid / VLM
families, with scan-over-layers (bounded HLO for 126-layer models), fully
functional KV/SSM caches, and sharding annotations for the
(pod) x data x model production mesh.

Entry points
------------
init_lm(key, cfg, tp)                         -> (params, specs)
apply_lm(params, cfg, mesh, tokens, ...)      -> (logits, cache)
init_cache(cfg, batch, s_max, tp, dtype)      -> cache pytree (+ specs)
loss_fn(params, cfg, mesh, tokens, targets)   -> scalar xent

Distribution notes
------------------
* batch shards over ('pod','data'); the residual stream's sequence dim
  shards over 'model' between blocks (Megatron-SP) when cfg.seq_shard and
  S > 1 — XLA inserts the gather/scatter pairs around attention/FFN.
* q heads shard over 'model' when divisible (see layers.attn_tp_enabled);
  KV is replicated to `kv_store_heads` virtual heads so the cache shards
  evenly with zero extra attention collectives.
* MoE layers run the GShard all-to-all path inside shard_map.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models import ssm as S
from repro.models.common import Family, ModelConfig

Params = Dict[str, Any]

__all__ = [
    "init_lm",
    "apply_lm",
    "init_cache",
    "cache_specs",
    "loss_fn",
    "batch_axes_for",
    "param_count",
]


def _unrolled_pairs(body, carry, xs_tree):
    n = jax.tree.leaves(xs_tree)[0].shape[0]
    ys = []
    for i in range(n):
        sl = jax.tree.map(lambda a: a[i], xs_tree)
        carry, y = body(carry, sl)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


def batch_axes_for(mesh, batch=None) -> Any:
    if mesh is None:
        return "data"
    if batch is None:
        return ("pod", "data") if "pod" in mesh.shape else "data"
    from repro.models.layers import pick_batch_axes

    return pick_batch_axes(mesh, batch)


def _tp_of(mesh) -> int:
    return mesh.shape["model"] if mesh is not None else 1


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _layer_init(key: jax.Array, cfg: ModelConfig, tp: int, block: str):
    """One block's (params, specs). block: dense | moe | ssm."""
    dt = cfg.jdtype
    if block == "ssm":
        k1, k2 = jax.random.split(key)
        mp, ms = S.mamba_init(k1, cfg)
        np_, ns = L.rmsnorm_init(cfg.d_model, dt)
        return {"ln": np_, "mamba": mp}, {"ln": ns, "mamba": ms}
    k1, k2, k3, k4 = jax.random.split(key, 4)
    ap, asp = L.attention_init(k1, cfg, tp)
    n1, n1s = L.rmsnorm_init(cfg.d_model, dt)
    n2, n2s = L.rmsnorm_init(cfg.d_model, dt)
    if block == "moe":
        fp, fsp = L.moe_init(k2, cfg, tp)
        return (
            {"ln1": n1, "attn": ap, "ln2": n2, "moe": fp},
            {"ln1": n1s, "attn": asp, "ln2": n2s, "moe": fsp},
        )
    fp, fsp = L.mlp_init(k2, cfg)
    return (
        {"ln1": n1, "attn": ap, "ln2": n2, "mlp": fp},
        {"ln1": n1s, "attn": asp, "ln2": n2s, "mlp": fsp},
    )


def _stacked_layers(key: jax.Array, cfg: ModelConfig, tp: int, block: str, n: int):
    keys = jax.random.split(key, n)
    params = jax.vmap(lambda k: _layer_init(k, cfg, tp, block)[0])(keys)
    _, specs1 = _layer_init(keys[0], cfg, tp, block)
    # stacked: prepend None (layer axis unsharded) to every leaf spec
    specs = jax.tree.map(
        lambda s: P(*((None,) + tuple(s))), specs1,
        is_leaf=lambda s: isinstance(s, P),
    )
    return params, specs


def hybrid_groups(cfg: ModelConfig) -> Tuple[int, int]:
    """(full groups of attn_every mamba blocks + shared attn, remainder)."""
    every = cfg.attn_every
    return cfg.n_layers // every, cfg.n_layers % every


def init_lm(key: jax.Array, cfg: ModelConfig, tp: int = 1) -> Tuple[Params, Params]:
    ke, kl, kf, ks = jax.random.split(key, 4)
    emb_p, emb_s = L.embed_init(ke, cfg)
    fin_p, fin_s = L.rmsnorm_init(cfg.d_model, cfg.jdtype)
    params: Params = {"embed": emb_p, "final_norm": fin_p}
    specs: Params = {"embed": emb_s, "final_norm": fin_s}
    fam = cfg.family
    if fam in (Family.DENSE, Family.VLM):
        params["layers"], specs["layers"] = _stacked_layers(
            kl, cfg, tp, "dense", cfg.n_layers
        )
    elif fam is Family.MOE:
        params["layers"], specs["layers"] = _stacked_layers(
            kl, cfg, tp, "moe", cfg.n_layers
        )
    elif fam is Family.SSM:
        params["layers"], specs["layers"] = _stacked_layers(
            kl, cfg, tp, "ssm", cfg.n_layers
        )
    elif fam is Family.HYBRID:
        ng, rem = hybrid_groups(cfg)
        grouped, gspecs = _stacked_layers(kl, cfg, tp, "ssm", ng * cfg.attn_every)
        # reshape leading axis (ng * every, ...) -> (ng, every, ...)
        params["layers"] = jax.tree.map(
            lambda x: x.reshape(ng, cfg.attn_every, *x.shape[1:]), grouped
        )
        specs["layers"] = jax.tree.map(
            lambda s: P(*((None,) + tuple(s))), gspecs,
            is_leaf=lambda s: isinstance(s, P),
        )
        if rem:
            params["tail"], specs["tail"] = _stacked_layers(kf, cfg, tp, "ssm", rem)
        # ONE shared attention block (zamba2), reused at every application
        sp, ss = _layer_init(ks, cfg, tp, "dense")
        params["shared_attn"] = sp
        specs["shared_attn"] = ss
    else:
        raise ValueError(f"init_lm does not handle family {fam}")
    return params, specs


def param_count(params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------


def _attn_cache(cfg, n_layers, batch, s_max, tp, dtype):
    kvs = L.kv_store_heads(cfg, tp)
    shape = (n_layers, batch, s_max, kvs, cfg.hd)
    if cfg.kv_quant:
        sshape = (n_layers, batch, s_max, kvs, 1)
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(sshape, jnp.float32),
            "v_scale": jnp.zeros(sshape, jnp.float32),
        }
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _attn_cache_spec(cfg, tp, batch_axes):
    hspec = "model" if L.attn_tp_enabled(cfg, tp) else None
    sp = P(None, batch_axes, None, hspec, None)
    out = {"k": sp, "v": sp}
    if cfg.kv_quant:
        out["k_scale"] = sp
        out["v_scale"] = sp
    return out


def init_cache(
    cfg: ModelConfig, batch: int, s_max: int, tp: int = 1, dtype=None
) -> Params:
    dtype = dtype or cfg.jdtype
    fam = cfg.family
    cache: Params = {"length": jnp.zeros((), jnp.int32)}
    if fam in (Family.DENSE, Family.VLM, Family.MOE):
        cache["attn"] = _attn_cache(cfg, cfg.n_layers, batch, s_max, tp, dtype)
    elif fam is Family.SSM:
        base = S.init_ssm_cache(cfg, batch, dtype)
        cache["ssm"] = jax.tree.map(
            lambda x: jnp.zeros((cfg.n_layers,) + x.shape, x.dtype), base
        )
    elif fam is Family.HYBRID:
        ng, rem = hybrid_groups(cfg)
        base = S.init_ssm_cache(cfg, batch, dtype)
        cache["ssm"] = jax.tree.map(
            lambda x: jnp.zeros((ng, cfg.attn_every) + x.shape, x.dtype), base
        )
        if rem:
            cache["ssm_tail"] = jax.tree.map(
                lambda x: jnp.zeros((rem,) + x.shape, x.dtype), base
            )
        cache["attn"] = _attn_cache(cfg, ng, batch, s_max, tp, dtype)
    return cache


def cache_specs(cfg: ModelConfig, tp: int, batch_axes) -> Params:
    fam = cfg.family
    specs: Params = {"length": P()}
    if fam in (Family.DENSE, Family.VLM, Family.MOE):
        specs["attn"] = _attn_cache_spec(cfg, tp, batch_axes)
    elif fam is Family.SSM:
        specs["ssm"] = {
            "state": P(None, batch_axes, "model", None, None),
            "conv": P(None, batch_axes, None, "model"),
        }
    elif fam is Family.HYBRID:
        _, rem = hybrid_groups(cfg)
        specs["ssm"] = {
            "state": P(None, None, batch_axes, "model", None, None),
            "conv": P(None, None, batch_axes, None, "model"),
        }
        if rem:
            specs["ssm_tail"] = {
                "state": P(None, batch_axes, "model", None, None),
                "conv": P(None, batch_axes, None, "model"),
            }
        specs["attn"] = _attn_cache_spec(cfg, tp, batch_axes)
    return specs


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _sp_constrain(x: jnp.ndarray, cfg: ModelConfig, mesh) -> jnp.ndarray:
    """Residual-stream sharding between blocks (Megatron-SP)."""
    if mesh is None:
        return x
    ba = batch_axes_for(mesh, x.shape[0])
    tp = _tp_of(mesh)
    if cfg.seq_shard and x.shape[1] % max(tp, 1) == 0 and x.shape[1] > 1:
        return jax.lax.with_sharding_constraint(x, P(ba, "model", None))
    return jax.lax.with_sharding_constraint(x, P(ba, None, None))


def _dense_block(
    p: Params,
    x: jnp.ndarray,
    cfg: ModelConfig,
    mesh,
    kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]],
    length: Optional[jnp.ndarray],
    positions: jnp.ndarray,
    is_moe: bool,
    paged: Optional[Tuple] = None,  # (page_table, impl, tree_mask) pool ctx
):
    """Pre-norm attn + FFN. kv = (k_slice, v_slice) cache buffers or None.

    With `paged` (a ``(page_table, impl, tree_mask)`` triple), kv holds one
    layer's slice of the device-resident paged pool and `length` is the
    per-row (B,) length vector; attention scatters and attends through the
    page table instead of the dense buffers."""
    tp = _tp_of(mesh)
    cache = None
    if paged is not None:
        page_table, impl, tree_mask = paged
        cache = L.PagedCache(k=kv[0], v=kv[1], page_table=page_table,
                             length=length, impl=impl,
                             k_scale=kv[2] if len(kv) > 2 else None,
                             v_scale=kv[3] if len(kv) > 2 else None,
                             tree_mask=tree_mask)
    elif kv is not None:
        cache = L.Cache(k=kv[0], v=kv[1], length=length,
                        k_scale=kv[2] if len(kv) > 2 else None,
                        v_scale=kv[3] if len(kv) > 2 else None)
    h, new_cache = L.attention_apply(
        p["attn"], L.rmsnorm(p["ln1"], x), cfg, tp, cache=cache, positions=positions
    )
    x = x + h if cfg.sp_once_per_block else _sp_constrain(x + h, cfg, mesh)
    z = L.rmsnorm(p["ln2"], x)
    if is_moe:
        sp = cfg.seq_shard and z.shape[1] % max(tp, 1) == 0 and z.shape[1] > 1
        f = _moe_call(p["moe"], z, cfg, mesh, sp)
    else:
        f = L.mlp_apply(p["mlp"], z, cfg)
    x = _sp_constrain(x + f, cfg, mesh)
    if new_cache is None:
        out_kv = None
    elif isinstance(new_cache, L.PagedCache):
        if new_cache.k_scale is not None:  # compressed pool: scales ride along
            out_kv = (new_cache.k, new_cache.v,
                      new_cache.k_scale, new_cache.v_scale)
        else:
            out_kv = (new_cache.k, new_cache.v)
    elif new_cache.k_scale is not None:
        out_kv = (new_cache.k, new_cache.v, new_cache.k_scale, new_cache.v_scale)
    else:
        out_kv = (new_cache.k, new_cache.v)
    return x, out_kv


def _moe_call(p, z, cfg, mesh, sp):
    if cfg.moe_impl == "a2a" and mesh is not None:
        # sp: tokens sharded over (batch, seq); else batch only (decode)
        return L.moe_apply_a2a(p, z, cfg, mesh, seq_sharded=sp)
    return L.moe_apply_dense(p, z, cfg)


def _ssm_block(p, x, cfg, mesh, state, decode: bool):
    """Pre-norm mamba2 block. state = per-layer ssm cache dict or None."""
    z = L.rmsnorm(p["ln"], x)
    if decode:
        y, new_state = S.mamba_decode(p["mamba"], z, cfg, state)
    else:
        init = state["state"] if state is not None else None
        conv_st = state["conv"] if state is not None else None
        y, fstate, conv_tail = S.mamba_apply(p["mamba"], z, cfg, init, conv_st)
        new_state = {"state": fstate, "conv": conv_tail} if state is not None else None
    x = _sp_constrain(x + y, cfg, mesh)
    return x, new_state


def apply_lm(
    params: Params,
    cfg: ModelConfig,
    mesh,
    tokens: jnp.ndarray,  # (B, S) int32
    cache: Optional[Params] = None,
    vision_embeds: Optional[jnp.ndarray] = None,  # (B, T_img, d) for VLM
    last_logit_only: bool = False,
    paged_impl: str = "gather",  # paged caches: "gather" | "pallas"
) -> Tuple[jnp.ndarray, Optional[Params]]:
    b, s = tokens.shape
    fam = cfg.family
    x = params["embed"]["tok"][tokens].astype(cfg.jdtype)
    if vision_embeds is not None:
        x = jnp.concatenate([vision_embeds.astype(cfg.jdtype), x], axis=1)
        s = x.shape[1]
    # a paged cache carries the shared device pool + per-row page tables /
    # lengths instead of per-request dense buffers + one scalar length
    paged = cache is not None and "page_table" in cache
    if paged and fam not in (Family.DENSE, Family.VLM, Family.MOE):
        raise NotImplementedError(f"paged KV cache: family {fam}")
    offset, positions, paged_ctx = L.forward_cache_ctx(cache, b, s, paged_impl)
    x = _sp_constrain(x, cfg, mesh)
    decode = cache is not None and s == 1

    remat = cfg.remat and cache is None

    def maybe_remat(fn):
        if not remat:
            return fn
        if cfg.remat_policy == "dots":
            return jax.checkpoint(
                fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            )
        return jax.checkpoint(fn)

    def layer_loop(body, x, xs_tree, n):
        """scan-over-layers or an unrolled python loop (cfg.scan_layers=False,
        used by the dry-run's depth-calibration lowers)."""
        if cfg.scan_layers:
            return jax.lax.scan(maybe_remat(body), x, xs_tree)
        wrapped = maybe_remat(body)
        ys = []
        for i in range(n):
            sl = jax.tree.map(lambda a: a[i], xs_tree)
            x, y = wrapped(x, sl)
            ys.append(y)
        if ys and ys[0] is not None:
            ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
        else:
            ys = None
        return x, ys

    new_cache = dict(cache) if cache is not None else None

    if fam in (Family.DENSE, Family.VLM, Family.MOE):
        is_moe = fam is Family.MOE

        def body(carry, xs):
            xc = carry
            p, kv = xs
            if kv is None:
                kvp = None
            elif "k_scale" in kv:
                kvp = (kv["k"], kv["v"], kv["k_scale"], kv["v_scale"])
            else:
                kvp = (kv["k"], kv["v"])
            xc, out_kv = _dense_block(
                p, xc, cfg, mesh, kvp, offset, positions, is_moe,
                paged=paged_ctx,
            )
            if out_kv is None:
                ys = None
            elif len(out_kv) == 4:
                ys = {"k": out_kv[0], "v": out_kv[1],
                      "k_scale": out_kv[2], "v_scale": out_kv[3]}
            else:
                ys = {"k": out_kv[0], "v": out_kv[1]}
            return xc, ys

        if cache is not None:
            xs = (params["layers"], cache["attn"])
            x, kv_out = layer_loop(body, x, xs, cfg.n_layers)
            new_cache["attn"] = kv_out
        else:
            x, _ = layer_loop(
                lambda c, p: body(c, (p, None)), x, params["layers"], cfg.n_layers
            )
    elif fam is Family.SSM:

        def body(carry, xs):
            xc = carry
            p, st = xs
            xc, new_st = _ssm_block(p, xc, cfg, mesh, st, decode)
            return xc, new_st

        if cache is not None:
            x, st_out = layer_loop(
                body, x, (params["layers"], cache["ssm"]), cfg.n_layers
            )
            new_cache["ssm"] = st_out
        else:
            x, _ = layer_loop(
                lambda c, p: body(c, (p, None)), x, params["layers"], cfg.n_layers
            )
    elif fam is Family.HYBRID:
        ng, rem = hybrid_groups(cfg)
        shared = params["shared_attn"]

        def group_body(carry, xs):
            xc = carry
            gp, gst, kv = xs  # (every, ...) mamba stack, ssm states, attn kv

            def inner(c, ixs):
                ip, ist = ixs
                c, nst = _ssm_block(ip, c, cfg, mesh, ist, decode)
                return c, nst

            if cfg.scan_layers:
                xc, new_states = jax.lax.scan(inner, xc, (gp, gst))
            else:
                xc, new_states = _unrolled_pairs(inner, xc, (gp, gst))
            kvp = (kv["k"], kv["v"]) if kv is not None else None
            xc, out_kv = _dense_block(
                shared, xc, cfg, mesh, kvp, offset, positions, False
            )
            ys = {
                "ssm": new_states,
                "kv": {"k": out_kv[0], "v": out_kv[1]} if out_kv else None,
            }
            return xc, ys

        ng_trips = ng
        if cache is not None:
            xs = (params["layers"], cache["ssm"], cache["attn"])
            x, outs = layer_loop(group_body, x, xs, ng_trips)
            new_cache["ssm"] = outs["ssm"]
            new_cache["attn"] = outs["kv"]
        else:
            def group_nc(c, gp):
                def inner(cc, ip):
                    cc, _ = _ssm_block(ip, cc, cfg, mesh, None, False)
                    return cc, None

                c, _ = jax.lax.scan(inner, c, gp) if cfg.scan_layers else _unrolled_pairs(inner, c, gp)
                c, _ = _dense_block(shared, c, cfg, mesh, None, offset, positions, False)
                return c, None

            x, _ = layer_loop(group_nc, x, params["layers"], ng_trips)
        if rem:
            def tail_body(carry, xs):
                p, st = xs
                c, nst = _ssm_block(p, carry, cfg, mesh, st, decode)
                return c, nst

            if cache is not None:
                x, st_out = layer_loop(
                    tail_body, x, (params["tail"], cache["ssm_tail"]), rem
                )
                new_cache["ssm_tail"] = st_out
            else:
                x, _ = layer_loop(
                    lambda c, p: (tail_body(c, (p, None))[0], None),
                    x, params["tail"], rem,
                )
    else:
        raise ValueError(fam)

    x = L.rmsnorm(params["final_norm"], x)
    if last_logit_only:
        x = x[:, -1:, :]
    with jax.named_scope("lm_head"):
        logits = x @ params["embed"]["head"].astype(cfg.jdtype)
        if cfg.vocab_padded != cfg.vocab:
            pad_mask = jnp.arange(cfg.vocab_padded) < cfg.vocab
            logits = jnp.where(pad_mask, logits, -1e9)
    if new_cache is not None:
        new_cache["lengths" if paged else "length"] = offset + s
    return logits, new_cache


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def loss_fn(
    params: Params,
    cfg: ModelConfig,
    mesh,
    tokens: jnp.ndarray,  # (B, S+1) int32 — input/target shifted views
    vision_embeds: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    logits, _ = apply_lm(params, cfg, mesh, inp, vision_embeds=vision_embeds)
    if vision_embeds is not None:
        logits = logits[:, vision_embeds.shape[1] :, :]  # score text positions
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
