"""Render the §Dry-run and §Roofline tables of EXPERIMENTS.md from
dryrun.json (and splice them into EXPERIMENTS.md with --write).

    PYTHONPATH=src python -m benchmarks.roofline_report [--write]
"""
import argparse
import json
import os


def _fmt_bytes(b):
    if b is None:
        return "-"
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(b) < 1024:
            return f"{b:.1f}{unit}"
        b /= 1024
    return f"{b:.1f}PB"


def _ms(x):
    return f"{x*1e3:.1f}" if x is not None else "-"


def dryrun_table(records):
    lines = [
        "| arch | shape | mesh | kind | compile_s | args/dev | temps/dev | flops/dev | bytes/dev | coll bytes/dev | collective schedule |",
        "|---|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in records:
        if r["status"] == "skipped":
            lines.append(
                f"| {r['arch']} | {r['shape']} | - | skipped | - | - | - | - | - | - | {r['reason']} |"
            )
            continue
        if r["status"] != "ok":
            lines.append(
                f"| {r['arch']} | {r['shape']} | - | **FAILED** | - | - | - | - | - | - | {r.get('error','')[:60]} |"
            )
            continue
        mesh = "x".join(str(v) for v in r["mesh"].values())
        m = r["memory"]
        c = r["corrected"]
        coll = ", ".join(
            f"{k}:{v['count']}x/{_fmt_bytes(v['bytes'])}"
            for k, v in sorted(c["collectives"].items())
        ) or "none"
        coll_b = sum(v["bytes"] for v in c["collectives"].values())
        lines.append(
            f"| {r['arch']} | {r['shape']} | {mesh} | {r['kind']} | {r['compile_s']} "
            f"| {_fmt_bytes(m['argument_bytes'])} | {_fmt_bytes(m['temp_bytes'])} "
            f"| {c['flops']:.2e} | {c['bytes']:.2e} | {_fmt_bytes(coll_b)} | {coll} |"
        )
    return "\n".join(lines)


def roofline_table(records):
    """Single-pod table.  Two fraction columns:
    * `HLO frac` — t_compute / max(terms): how much of the critical-path
      proxy is MXU work as compiled;
    * `MFU bound` — MODEL_FLOPS time / max(terms): the classic MFU-style
      upper bound a perfectly-fused implementation of this sharding would
      reach (uses analytic model FLOPs, so it is comparable across cells).
    """
    PEAK = 197e12
    lines = [
        "| arch | shape | t_compute ms | t_memory ms | t_collective ms | bottleneck | MODEL_FLOPS/HLO | HLO frac | MFU bound |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in records:
        if r["status"] != "ok":
            continue
        if "pod" in r["mesh"]:
            continue  # roofline table is single-pod per the assignment
        rl = r["roofline"]
        terms = [rl["t_compute"], rl["t_memory"], rl["t_collective"]]
        crit = max(max(terms), 1e-12)
        frac = rl["t_compute"] / crit
        mfu = (rl["model_flops_per_device"] / PEAK) / crit
        lines.append(
            f"| {r['arch']} | {r['shape']} | {_ms(rl['t_compute'])} | {_ms(rl['t_memory'])} "
            f"| {_ms(rl['t_collective'])} | **{rl['bottleneck']}** "
            f"| {rl['useful_flops_ratio']:.2f} | {frac:.2f} | {mfu:.2f} |"
        )
    return "\n".join(lines)


def run():
    """benchmarks.run hook: emit summary rows if dryrun.json exists."""
    path = os.path.join(os.path.dirname(__file__), "..", "dryrun.json")
    if not os.path.exists(path):
        return [("roofline_report", 0.0, "dryrun.json missing (run dryrun --all)")]
    with open(path) as f:
        records = json.load(f)
    ok = sum(1 for r in records if r["status"] == "ok")
    skipped = sum(1 for r in records if r["status"] == "skipped")
    failed = sum(1 for r in records if r["status"] == "FAILED")
    rows = [("dryrun_cells", 0.0, f"{ok} ok / {skipped} skipped / {failed} failed")]
    bott = {}
    for r in records:
        if r["status"] == "ok" and "pod" not in r["mesh"]:
            bott[r["roofline"]["bottleneck"]] = bott.get(r["roofline"]["bottleneck"], 0) + 1
    rows.append(("roofline_bottlenecks", 0.0,
                 " ".join(f"{k}:{v}" for k, v in sorted(bott.items()))))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="dryrun.json")
    ap.add_argument("--write", action="store_true",
                    help="splice tables into EXPERIMENTS.md")
    args = ap.parse_args(argv)
    with open(args.json) as f:
        records = json.load(f)
    dt = dryrun_table(records)
    rt = roofline_table(records)
    if args.write:
        with open("EXPERIMENTS.md") as f:
            txt = f.read()
        txt = txt.replace("<!-- DRYRUN_TABLE -->", dt)
        txt = txt.replace("<!-- ROOFLINE_TABLE -->", rt)
        with open("EXPERIMENTS.md", "w") as f:
            f.write(txt)
        print("EXPERIMENTS.md updated")
    else:
        print(dt)
        print()
        print(rt)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
