"""Readings that set a cell's correctness limit: the program's and the
control's, over many seeds, in one process.

    python3 chipbench/calibrate.py --workload <cell> --seconds 51 --seeds 101,102,103

For each seed it makes one run of the cell as ``run.py`` would (weights,
traffic, warm-up, ramp, a window of ``--seconds``), then scores the same
sample of served greedy requests twice: by the program's served tokens
against the reference (the gaps a run compares) and by the control, the
reference at int4 activations in the program's place (the gap of the token
that the control puts first).  Both go through ``check.verdict``, the
comparison ``run.py`` uses, so the control's line shows the ``correct`` it
would get.  For each side it prints the widest and the mean gap (the two
numbers with limits) and the share of positions whose gap is above 0.
One JSON line per seed.  Each limit sits above the largest program reading
and below the smallest control reading (see PERF.md).  Not part of a
benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 3
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from chipbench import check, harness

    for seed in (int(s) for s in args.seeds.split(",")):
        keep: dict = {}
        t0 = time.perf_counter()
        res = harness.run(args.workload, seed, args.seconds, False, t0, root=ROOT, keep=keep)
        ck = keep["traffic"]["check"]
        sides = {"program": [], "control": []}
        for r in keep["sample"]:
            g = check.gaps(keep["reference"], keep["params"], keep["config"],
                           r.req.prompt, r.tokens, control=True)
            for side in sides:
                sides[side].append(g[side])
        keep.clear()
        line = {"seed": seed, "correct": res["correct"], "checks": res["checks"],
                "metrics": res["metrics"], "seconds": time.perf_counter() - t0}
        for side, per_request in sides.items():
            allg = np.concatenate(per_request) if per_request else np.zeros(0)
            ok, checks = check.verdict(per_request, 0, ck)
            line[side] = {"correct": ok, "served_gap": checks["served_gap"]["value"],
                          "mean_gap": checks["mean_gap"]["value"],
                          "flip_share": float((allg > 0).mean()) if allg.size else None,
                          "positions": int(allg.size)}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
