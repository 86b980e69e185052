"""Smoke-sized copies of the benchmark's cells, for running the harness on the CPU.

``make_root(dir)`` writes a checkout-like directory: the harness's own
readers, references and served forms, and a ``BENCHMARK.json`` with the
same metrics and cells.  Each configuration there is its served form's CPU
copy (``smoke(config)``: the same kind of model at a few layers and narrow
widths), and each cell keeps its name and runs a short copy of its traffic
mix.  Each configuration also gets an all-greedy cell, ``<its first
cell>-greedy``.  ``chipbench/tests/test_chipbench_cells.py`` runs them end
to end on the CPU from such a directory (the Pallas kernels interpret).
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path
from typing import List

from chipbench.spec import BENCH_DIR, Bench

_SMALL = dict(rate_per_s=4.0, ramp_s=1.0,
              prompt_len=dict(grid=[8, 16, 24], median=16, sigma=0.8),
              max_tokens=dict(median=8, sigma=0.5, min=4, max=12),
              engine=dict(max_batch=4, pool_tokens=512))
_GREEDY = dict(temperature=0.0, top_p=1.0, top_k=0, greedy_share=1.0)


def _cells(spec: dict) -> List[tuple]:
    """(smoke cell, configuration, real traffic, all greedy) for each cell."""
    out, seen = [], set()
    for w in spec["workloads"]:
        out.append((w["name"], w["config"], w["traffic"], False))
        if w["config"] not in seen:
            seen.add(w["config"])
            out.append((w["name"] + "-greedy", w["config"], w["traffic"], True))
    return out


def cells() -> List[str]:
    """Names of the smoke cells ``make_root`` writes."""
    return sorted(c[0] for c in _cells(Bench().spec))


def add_cell(root: Path, cell: str, config: dict, real_traffic: dict, greedy: bool,
             gap_limit: float) -> None:
    """Write one smoke cell into ``root``: the configuration as given, under
    a short copy of the traffic mix, and their entries in its
    ``BENCHMARK.json``."""
    path = root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    name = config["name"]
    cfg_file = f"{BENCH_DIR}/configs/{name}.json"
    (root / cfg_file).write_text(json.dumps(config, indent=1))
    if name not in [c["name"] for c in bench["configs"]]:
        bench["configs"].append({"name": name, "source": "smoke", "file": cfg_file,
                                 "reduced": [], "why": "smoke"})
    mix = dict(real_traffic, **_SMALL)
    if greedy:
        mix["sampling"] = dict(real_traffic["sampling"], **_GREEDY)
    mix["check"] = dict(real_traffic["check"], served_gap_limit=gap_limit, min_tokens=4)
    (root / BENCH_DIR / "traffic" / f"{cell}.json").write_text(json.dumps(mix, indent=1))
    bench["workloads"].append({"name": cell, "config": name, "traffic": cell,
                               "chips": 1, "why": "smoke"})
    path.write_text(json.dumps(bench, indent=1))


def make_root(root: Path, gap_limit: float = 1.0) -> Path:
    root = Path(root)
    real = Bench()
    shutil.copytree(real.dir, root / BENCH_DIR, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__", "tests", "configs", "traffic"))
    (root / BENCH_DIR / "configs").mkdir(exist_ok=True)
    (root / BENCH_DIR / "traffic").mkdir(exist_ok=True)
    (root / "BENCHMARK.json").write_text(
        json.dumps(dict(real.spec, configs=[], workloads=[]), indent=1))
    for cell, config, traffic, greedy in _cells(real.spec):
        cfg = real.config(config)
        add_cell(root, cell, real.served(cfg["served"]).smoke(cfg), real.traffic(traffic),
                 greedy, gap_limit)
    return root
