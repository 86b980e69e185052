import os

os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=512"
)
# ^ MUST precede any jax import; an XLA_FLAGS already set is left as it is.

"""Perf-iteration harness (§Perf hillclimbing): re-lower a dry-run cell
under config variants and diff the roofline terms.

    python -m repro.launch.perf --arch deepseek-67b --shape decode_32k \\
        --variant baseline --variant kvq8 --variant w4a8 --variant w4a8+kvq8
    python -m repro.launch.perf --arch llama3-405b --shape train_4k \\
        --set seq_shard=False

Named variants:
  baseline       the dry-run configuration as-is
  kvq8           INT8 KV cache with per-token-per-head scales
  w4a8           W4A8 weights + LRU rotation (serving path; decode, dense)
  w4a8+kvq8      both
  nosp           seq_shard=False (replicated residual, Megatron-SP off)
  noremat        remat=False
  nofsdp         fsdp=False
  capacity1      MoE capacity_factor=1.0
"""
import argparse
import dataclasses
import json
import sys
import time
from typing import Any, Dict, Optional

NAMED_VARIANTS: Dict[str, Dict[str, Any]] = {
    "baseline": {},
    "kvq8": {"kv_quant": True},
    "w4a8": {"__quant__": "w4a8"},
    "w4a8+kvq8": {"__quant__": "w4a8", "kv_quant": True},
    "w4a8+kvq8+nofsdp": {"__quant__": "w4a8", "kv_quant": True, "fsdp": False},
    "nosp": {"seq_shard": False},
    "noremat": {"remat": False},
    "nofsdp": {"fsdp": False},
    "capacity1": {"capacity_factor": 1.0},
}


def build_quantized_decode_cell(cfg, shape, mesh):
    """W4A8 serving cell: the paper's technique at pod scale (dense + MoE);
    handles both decode (B,1) and prefill (B,S) shapes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.steps import CellSpec, abstract_cache, batch_axes, _cache_specs, _ns
    from repro.models.common import Family
    from repro.serving.quantized_lm import (
        abstract_quantized, abstract_quantized_moe,
        apply_quantized_lm, apply_quantized_moe_lm,
    )

    tp = mesh.shape["model"]
    ba = batch_axes(mesh, shape.global_batch)
    if cfg.family is Family.MOE:
        aparams, pspecs = abstract_quantized_moe(cfg, tp)
        apply_fn = apply_quantized_moe_lm
    else:
        aparams, pspecs = abstract_quantized(cfg, tp)
        apply_fn = apply_quantized_lm
    param_sh = _ns(mesh, pspecs)
    acache = abstract_cache(cfg, shape.global_batch, shape.seq_len, tp)
    cache_sh = _ns(mesh, _cache_specs(cfg, tp, ba))
    tok_len = 1 if shape.kind == "decode" else shape.seq_len
    tok = jax.ShapeDtypeStruct((shape.global_batch, tok_len), jnp.int32)
    tok_sh = NamedSharding(mesh, P(ba, None))

    def step(params, tokens, cache):
        return apply_fn(
            params, cfg, mesh, tokens, cache=cache, use_pallas=False,
            last_logit_only=shape.kind == "prefill",
        )

    fn = jax.jit(step, in_shardings=(param_sh, tok_sh, cache_sh),
                 donate_argnums=(2,))
    return CellSpec(fn=fn, args=(aparams, tok, acache), kind=f"{shape.kind}-w4a8")


def measure_variant(arch: str, shape_name: str, overrides: Dict[str, Any],
                    multi_pod: bool = False) -> Dict[str, Any]:
    import jax
    from repro.configs import get_config
    from repro.launch.dryrun import (
        _cal_configs, _extrapolate, _measure, parse_collectives, roofline_terms,
    )
    from repro.launch.mesh import activate_mesh, make_production_mesh
    from repro.launch.steps import build_cell
    from repro.models.common import SHAPES

    overrides = dict(overrides)
    quant = overrides.pop("__quant__", None)
    cfg = dataclasses.replace(get_config(arch), **overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)

    def measure_cfg(c, want_memory):
        import time as _t

        t0 = _t.time()
        with activate_mesh(mesh):
            if quant == "w4a8":
                cell = build_quantized_decode_cell(c, shape, mesh)
            else:
                cell = build_cell(c, shape, mesh)
            lowered = cell.fn.lower(*cell.args)
            compiled = lowered.compile()
        el = _t.time() - t0
        cost_raw = compiled.cost_analysis()
        cost = cost_raw if isinstance(cost_raw, dict) else (cost_raw[0] if cost_raw else {})
        rec = {
            "kind": cell.kind, "compile_s": round(el, 1),
            "flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "collectives": parse_collectives(compiled.as_text()),
        }
        if want_memory:
            mem = compiled.memory_analysis()
            rec["memory"] = {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            }
        return rec

    full = measure_cfg(cfg, True)
    c1, c2, trips = _cal_configs(cfg)
    f1 = measure_cfg(c1, False)
    f2 = measure_cfg(c2, False)
    corr = _extrapolate(f1, f2, trips)
    rl = roofline_terms(corr["flops"], corr["bytes"], corr["collectives"])
    return {"arch": arch, "shape": shape_name, "overrides": overrides,
            "quant": quant, "memory": full["memory"], "corrected": corr,
            "roofline": rl}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", action="append", default=[])
    ap.add_argument("--set", action="append", default=[],
                    help="field=value config override")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    variants = []
    for v in args.variant or []:
        variants.append((v, dict(NAMED_VARIANTS[v])))
    if args.set:
        ov = {}
        for kv in args.set:
            k, val = kv.split("=", 1)
            ov[k] = {"True": True, "False": False}.get(val, val)
            if isinstance(ov[k], str):
                try:
                    ov[k] = int(val)
                except ValueError:
                    try:
                        ov[k] = float(val)
                    except ValueError:
                        pass
        variants.append(("custom:" + ",".join(args.set), ov))
    if not variants:
        variants = [("baseline", {})]

    results = []
    base = None
    for name, ov in variants:
        print(f"=== {args.arch} x {args.shape} [{name}]", flush=True)
        rec = measure_variant(args.arch, args.shape, ov, multi_pod=args.multi_pod)
        rec["variant"] = name
        results.append(rec)
        r = rec["roofline"]
        line = (f"    t_comp={r['t_compute']*1e3:.2f}ms "
                f"t_mem={r['t_memory']*1e3:.2f}ms "
                f"t_coll={r['t_collective']*1e3:.2f}ms "
                f"args={rec['memory']['argument_bytes']}")
        if base is None:
            base = r
        else:
            line += (f"  | vs baseline: comp x{r['t_compute']/max(base['t_compute'],1e-12):.3f} "
                     f"mem x{r['t_memory']/max(base['t_memory'],1e-12):.3f} "
                     f"coll x{r['t_collective']/max(base['t_collective'],1e-12):.3f}")
        print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
