#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 chipbench/run.py --workload <cell> --seed 7 --seconds 30 --trace 0

Runs on the machine it is started on and needs as many TPU chips as the
cell asks for: it exits non-zero, printing no result, before building
anything when JAX finds no TPU or too few chips.  The last line of standard
output is the JSON result (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``; with ``--trace 1`` also ``breakdown``); the last
lines of standard error give each number the correctness check compared,
beside its limit.  ``BENCHMARK.json`` names the cells; the harness finds a
cell's configuration, traffic mix and metrics by name (``chipbench/spec.py``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-survey", default=None, metavar="PATH",
                    help="with --trace 1: also write the trace's planes, lines "
                         "and most frequent event names to PATH as JSON")
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"chipbench: no workload {args.workload!r}", file=sys.stderr)
        return 2
    chips = cells[args.workload]["chips"]

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"chipbench: needs {chips} TPU chip(s); JAX found {len(devs)} "
              f"{devs[0].platform!r} device(s)", file=sys.stderr)
        return 3

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from chipbench import harness

    result = harness.run(args.workload, args.seed, args.seconds, bool(args.trace),
                         T_START, root=ROOT, survey_path=args.trace_survey)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
