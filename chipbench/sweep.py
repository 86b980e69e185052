"""Find a cell's knee: the highest open-loop rate its server sustains.

    python3 chipbench/sweep.py --workload <cell> --seed 5 --seconds 30 --rates 0.4,0.6,0.8

One process, one set-up; then one window per rate, each after the cell's
ramp, with the cell's traffic at that rate.  For each rate it prints one
JSON line: the end-to-end metrics, the requests waiting for admission at
the window's open and close, and the mean admission wait of the first and
second half of the requests admitted in the window.  A rate is sustained
where the queue does not grow over the window.  The cell's fixed rate is
then set by hand in its traffic file; the benchmark never searches.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 3
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.runtime.compile_cache import enable_compile_cache

    from chipbench import harness, loadgen
    from chipbench.spec import Bench

    enable_compile_cache()
    bench = Bench(ROOT)
    w = bench.workload(args.workload)
    config, traffic = bench.config(w["config"]), bench.traffic(w["traffic"])
    _, engine = harness.build(config, traffic, args.seed, bench.served(config["served"]))
    vocab = config["vocab_size"]
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(traffic, rate_per_s=rate)
        records = [harness.Record(req=q, due=q.due_s)
                   for q in loadgen.schedule(mix, args.seed + i, args.seconds, vocab)]
        warm = [harness.Record(req=q, due=0.0) for q in loadgen.warmup(mix, args.seed, vocab)]
        window = harness.Window(engine, None)
        asyncio.run(harness.serve(engine, records, warm, mix, args.seconds, window))
        ctx = harness.Ctx(t_open=window.t_open, t_close=window.t_close, records=records,
                          counters_open=window.c_open, counters_close=window.c_close,
                          model=config, peaks={})
        admitted = sorted((r for r in ctx.due_in_window() if r.times), key=lambda r: r.due)
        half = len(admitted) // 2
        ttft = [r.times[0] - r.due for r in admitted]
        out = {"rate_per_s": rate,
               "end_to_end": harness.end_to_end(ctx, window.t_open - T_START),
               "due_in_window": len(ctx.due_in_window()),
               "queued_open": window.queued_open, "queued_close": window.queued_close,
               "ttft_first_half_mean_s": sum(ttft[:half]) / max(half, 1),
               "ttft_second_half_mean_s": sum(ttft[half:]) / max(len(ttft) - half, 1),
               "failed": sum(r.failed for r in records)}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
