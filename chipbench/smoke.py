"""Smoke-sized copies of the cell, for running the harness on the CPU.

``make_root(dir)`` writes a checkout-like directory: the harness's own
readers and references, ``BENCHMARK.json`` with the same metrics, a smoke
configuration of the Qwen3 pair's shape ratios (GQA 4:1 with qk-norm, and a
draft whose head_dim is not d_model / heads), and two short traffic mixes:
the cell's own mix of sampled and greedy requests, and an all-greedy one.
``chipbench/tests/test_chipbench_cells.py`` runs both end to end on the
CPU from such a directory (the Pallas kernels interpret).
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from chipbench.spec import BENCH_DIR, ROOT

CONFIG = "qwen3-8b-spec"
SMOKE_MODEL = dict(
    target=dict(num_hidden_layers=2, hidden_size=128, intermediate_size=384,
                num_attention_heads=4, num_key_value_heads=1, head_dim=32,
                vocab_size=512),
    draft=dict(num_hidden_layers=2, hidden_size=64, intermediate_size=192,
               num_attention_heads=4, num_key_value_heads=2, head_dim=32,
               vocab_size=512),
    bvq=dict(vec_dim=8, codebook_size=64, block_cols=32),
    rotation=dict(n=384, m=12, h_m="paley1", k=5, kind="exact"),
)

_SMALL = dict(rate_per_s=4.0, ramp_s=1.0,
              prompt_len=dict(grid=[8, 16, 24], median=16, sigma=0.8),
              max_tokens=dict(median=8, sigma=0.5, min=4, max=12),
              engine=dict(max_batch=4, pool_tokens=512))
SMOKE_TRAFFIC = {
    "chat": _SMALL,
    "greedy": dict(_SMALL, sampling=dict(temperature=0.0, top_p=1.0, top_k=0,
                                         greedy_share=1.0)),
}

SMOKE_CELLS = {"qwen3-smoke.chat": "chat", "qwen3-smoke.greedy": "greedy"}


def smoke_config() -> dict:
    real = json.loads((ROOT / BENCH_DIR / "configs" / f"{CONFIG}.json").read_text())
    s = SMOKE_MODEL
    cfg = dict(real, **s["target"])
    cfg["draft"] = dict(real["draft"], **s["draft"])
    cfg["serving"] = dict(real["serving"], bvq=s["bvq"], online_rotation=s["rotation"])
    cfg["reduced"] = sorted(set(s["target"]) | {"draft"})
    return cfg


def make_root(root: Path, gap_limit: float = 1.0) -> Path:
    root = Path(root)
    src = ROOT / BENCH_DIR
    shutil.copytree(src, root / BENCH_DIR, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__", "tests", "configs", "traffic"))
    (root / BENCH_DIR / "configs").mkdir(exist_ok=True)
    (root / BENCH_DIR / "traffic").mkdir(exist_ok=True)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    path = f"{BENCH_DIR}/configs/{CONFIG}.json"
    (root / path).write_text(json.dumps(smoke_config(), indent=1))
    bench["configs"] = [{"name": CONFIG, "source": "smoke", "file": path,
                         "reduced": [], "why": "smoke"}]
    real = json.loads((src / "traffic" / "chat.json").read_text())
    for name, mix in SMOKE_TRAFFIC.items():
        t = dict(real, **mix)
        t["check"] = dict(real["check"], served_gap_limit=gap_limit, min_tokens=4)
        (root / BENCH_DIR / "traffic" / f"{name}.json").write_text(json.dumps(t, indent=1))
    bench["workloads"] = [{"name": cell, "config": CONFIG, "traffic": traffic,
                           "chips": 1, "why": "smoke"}
                          for cell, traffic in SMOKE_CELLS.items()]
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root
