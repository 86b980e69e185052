"""Benchmark orchestrator — one module per paper figure.

    PYTHONPATH=src python -m benchmarks.run [--only lru,bvq,apsd,e2e,kernels]

Prints ``name,us_per_call,derived`` CSV rows (us_per_call is 0 for
analytic/derived rows)."""
import argparse
import sys
import traceback


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_apsd, bench_bvq, bench_e2e, bench_kernels, bench_lru,
        bench_server, bench_serving, roofline_report,
    )

    suites = {
        "lru": bench_lru,
        "bvq": bench_bvq,
        "apsd": bench_apsd,
        "e2e": bench_e2e,
        "kernels": bench_kernels,
        "serving": bench_serving,
        "server": bench_server,
        "roofline": roofline_report,
    }
    if args.only:
        keep = set(args.only.split(","))
        suites = {k: v for k, v in suites.items() if k in keep}
    print("name,us_per_call,derived")
    failed = 0
    for name, mod in suites.items():
        try:
            for row in mod.run():
                n, us, derived = row
                print(f"{n},{us:.1f},{derived}")
        except Exception:
            failed += 1
            print(f"{name},0.0,SUITE-FAILED", file=sys.stderr)
            traceback.print_exc()
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
