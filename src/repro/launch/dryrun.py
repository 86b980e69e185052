import os

os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=512"
)
# ^ MUST precede any jax import: jax locks the device count on first init.
# An XLA_FLAGS the caller already set is left as it is.

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract roofline terms from the compiled artifacts.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --both-meshes --out dryrun.json

Loop-body accounting: XLA's cost_analysis on the CPU backend counts a
while-loop body ONCE, and scan-over-layers puts the whole stack in one
loop.  Every cell is therefore lowered twice more at depth 1 and depth 2
(same weight shapes, tiny graphs): metric(L) = a + b*L is fitted and
extrapolated to the full depth — exact for homogeneous stacks (the hybrid
tail scan, 3 of 81 layers, stays once-counted; noted in EXPERIMENTS.md).
Collective bytes inside the loop get the same correction; ring factors per
collective kind are applied in the roofline terms.

Per cell this records: memory_analysis (fit proof), corrected HLO FLOPs /
bytes, the collective schedule, and the three roofline terms against TPU
v5e constants (197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI).
"""
import argparse
import dataclasses
import json
import re
import sys
import time
from typing import Any, Dict, Optional, Tuple

import numpy as np

# TPU v5e roofline constants (per chip)
PEAK_FLOPS = 197e12  # bf16
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link

# ring-bandwidth factors on a 16-wide axis: bytes crossing the busiest link
# per shard-byte of collective payload
RING_FACTOR = {
    "all-reduce": 2.0 * 15 / 16,
    "all-gather": 15 / 16,
    "reduce-scatter": 15 / 16,
    "all-to-all": 15 / 16,
    "collective-permute": 1.0,
}

_COLL_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\S+))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_SHAPE_RE = re.compile(
    r"(f32|bf16|f16|s32|u32|s8|u8|pred|s64|f64|u16|s16)\[([\d,]*)\]"
)
_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "u16": 2, "s16": 2, "s8": 1, "u8": 1, "pred": 1,
}


def parse_collectives(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per-kind totals of collective OUTPUT shard bytes in the compiled HLO."""
    out: Dict[str, Dict[str, float]] = {}
    for m in _COLL_RE.finditer(hlo_text):
        kind = m.group(3)
        shapes_str = m.group(1) if m.group(1) is not None else m.group(2)
        nbytes = 0
        for sm in _SHAPE_RE.finditer(shapes_str or ""):
            n = 1
            for d in sm.group(2).split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[sm.group(1)]
        slot = out.setdefault(kind, {"bytes": 0.0, "count": 0})
        slot["bytes"] += float(nbytes)
        slot["count"] += 1
    return out


# ---------------------------------------------------------------------------
# Cell measurement
# ---------------------------------------------------------------------------


def _measure(cfg, shape, mesh, want_memory: bool) -> Dict[str, Any]:
    import jax
    from repro.launch.mesh import activate_mesh
    from repro.launch.steps import build_cell

    t0 = time.time()
    with activate_mesh(mesh):
        cell = build_cell(cfg, shape, mesh)
        lowered = cell.fn.lower(*cell.args)
        compiled = lowered.compile()
    elapsed = time.time() - t0
    cost_raw = compiled.cost_analysis()
    cost = cost_raw if isinstance(cost_raw, dict) else (cost_raw[0] if cost_raw else {})
    coll = parse_collectives(compiled.as_text())
    rec = {
        "kind": cell.kind,
        "compile_s": round(elapsed, 1),
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collectives": coll,
    }
    if want_memory:
        mem = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        }
    return rec


def _cal_configs(cfg) -> Tuple[Any, Any, int]:
    """Two shallow UNROLLED configs + the full trip count for the linear
    extrapolation (unrolling makes per-layer cost visible to cost_analysis;
    weight shapes stay identical to the full config)."""
    from repro.models.common import Family

    if cfg.family is Family.HYBRID:
        rem = cfg.n_layers % cfg.attn_every
        mk = lambda ng: dataclasses.replace(
            cfg, n_layers=cfg.attn_every * ng + rem, scan_layers=False
        )
        return mk(1), mk(2), cfg.n_layers // cfg.attn_every
    if cfg.family is Family.AUDIO:
        mk = lambda L: dataclasses.replace(
            cfg, n_layers=L, n_encoder_layers=L, scan_layers=False
        )
        return mk(1), mk(2), cfg.n_layers
    mk = lambda L: dataclasses.replace(cfg, n_layers=L, scan_layers=False)
    return mk(1), mk(2), cfg.n_layers


def _extrapolate(f1: Dict, f2: Dict, trips: int) -> Dict[str, Any]:
    """metric(T) = a + b*T fitted on T=1,2 -> value at T=trips."""

    def lin(v1, v2):
        b = v2 - v1
        a = v1 - b
        return max(a + b * trips, 0.0)

    kinds = set(f1["collectives"]) | set(f2["collectives"])
    coll = {}
    for k in kinds:
        b1 = f1["collectives"].get(k, {"bytes": 0.0, "count": 0})
        b2 = f2["collectives"].get(k, {"bytes": 0.0, "count": 0})
        coll[k] = {
            "bytes": lin(b1["bytes"], b2["bytes"]),
            "count": int(lin(b1["count"], b2["count"])),
        }
    return {
        "flops": lin(f1["flops"], f2["flops"]),
        "bytes": lin(f1["bytes"], f2["bytes"]),
        "collectives": coll,
    }


def roofline_terms(flops: float, bytes_: float, coll: Dict) -> Dict[str, float]:
    """Three-term roofline; inputs are PER-DEVICE (the compiled module is the
    per-device program after SPMD partitioning)."""
    t_coll = 0.0
    coll_bytes = 0.0
    for k, v in coll.items():
        t_coll += v["bytes"] * RING_FACTOR.get(k, 1.0) / ICI_BW
        coll_bytes += v["bytes"]
    return {
        "t_compute": flops / PEAK_FLOPS,
        "t_memory": bytes_ / HBM_BW,
        "t_collective": t_coll,
        "flops_per_device": flops,
        "bytes_per_device": bytes_,
        "collective_bytes_per_device": coll_bytes,
    }


def model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6*N*D train / 2*N_active*D forward (per device)."""
    from repro.launch.steps import param_counts

    n_active = param_counts(cfg)["active"]
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        total = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        total = 2.0 * n_active * tokens
    else:
        tokens = shape.global_batch  # one token per sequence
        total = 2.0 * n_active * tokens
    return total


def dryrun_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool = False,
    calibrate: bool = True,
) -> Dict[str, Any]:
    import jax
    from repro.configs import get_config, shape_applicable
    from repro.launch.mesh import activate_mesh, make_production_mesh
    from repro.models.common import SHAPES

    if not shape_applicable(arch, shape_name):
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": "long_500k needs sub-quadratic attention"}
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))

    full = _measure(cfg, shape, mesh, want_memory=True)
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": dict(mesh.shape),
        "n_chips": n_chips, "status": "ok", "kind": full["kind"],
        "compile_s": full["compile_s"], "memory": full["memory"],
        "raw": {"flops": full["flops"], "bytes": full["bytes"],
                "collectives": full["collectives"]},
    }
    if calibrate:
        c1, c2, trips = _cal_configs(cfg)
        f1 = _measure(c1, shape, mesh, want_memory=False)
        f2 = _measure(c2, shape, mesh, want_memory=False)
        corr = _extrapolate(f1, f2, trips)
    else:
        corr = rec["raw"]
    rec["corrected"] = corr
    rec["roofline"] = roofline_terms(corr["flops"], corr["bytes"], corr["collectives"])
    mf = model_flops(cfg, shape) / n_chips
    rec["roofline"]["model_flops_per_device"] = mf
    rec["roofline"]["useful_flops_ratio"] = (
        mf / corr["flops"] if corr["flops"] else 0.0
    )
    terms = {k: rec["roofline"][f"t_{k}"] for k in ("compute", "memory", "collective")}
    rec["roofline"]["bottleneck"] = max(terms, key=terms.get)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-calibrate", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from repro.configs import cells

    if args.all:
        todo = cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        todo = [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    records = []
    for arch, shape in todo:
        for mp in meshes:
            label = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
            print(f"=== {label}", flush=True)
            try:
                rec = dryrun_cell(arch, shape, multi_pod=mp,
                                  calibrate=not args.no_calibrate)
            except Exception as e:  # a failing cell is a bug — surface it
                rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                       "status": "FAILED", "error": f"{type(e).__name__}: {e}"}
            records.append(rec)
            if args.out:  # checkpoint progress after every cell
                with open(args.out, "w") as f:
                    json.dump(records, f, indent=1)
            if rec["status"] == "ok":
                r = rec["roofline"]
                print(
                    f"    kind={rec['kind']} compile={rec['compile_s']}s "
                    f"flops/dev={r['flops_per_device']:.3e} "
                    f"bytes/dev={r['bytes_per_device']:.3e} "
                    f"coll/dev={r['collective_bytes_per_device']:.3e}B\n"
                    f"    t_comp={r['t_compute']*1e3:.2f}ms "
                    f"t_mem={r['t_memory']*1e3:.2f}ms "
                    f"t_coll={r['t_collective']*1e3:.2f}ms "
                    f"bottleneck={r['bottleneck']} "
                    f"useful={r['useful_flops_ratio']:.2f}",
                    flush=True,
                )
            elif rec["status"] == "skipped":
                print(f"    skipped: {rec['reason']}", flush=True)
            else:
                print(f"    FAILED: {rec.get('error')}", flush=True)
    failed = [r for r in records if r["status"] == "FAILED"]
    print(f"done: {len(records)} cells, {len(failed)} failed")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
