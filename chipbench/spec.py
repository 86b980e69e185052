"""Find everything a cell needs by name, from files alone.

``BENCHMARK.json`` at the root names the cells, the configurations and the
metrics.  Everything that belongs to one of them sits in a file of its own
under this directory, found by its name:

- a configuration: the ``file`` its ``configs`` entry names (JSON sizes),
  with its plain reference in ``references/<reference>.py`` and its served
  form in ``served/<served>.py``;
- a traffic mix: ``traffic/<traffic>.json``, read by ``loadgen.py``;
- a per-layer metric: ``metrics/<name>.py``, whose ``read(ctx)`` returns
  the value or ``None`` where it finds nothing to read.

A later cell, configuration or metric is added by adding files and entries;
no file here changes.

A served-form module says how one kind of model is drawn and served; the
harness knows a model only through its four names:

- ``draw(key, config) -> (target_params, draft_params)``: both models drawn
  on the device from the key, already in their served form, one jitted
  program each;
- ``program_config(config, role) -> ModelConfig``: the program's shape of
  the target (``role="target"``) or the draft (``role="draft"``); a key of
  the configuration the program cannot take raises ``SpecError``;
- ``model_ops(config, positions) -> float``: the target's model operations
  for tokens at the given 0-based positions (for a sparse model, those of
  the parameters one token activates);
- ``smoke(config) -> dict``: the configuration's CPU copy, small enough for
  the test suite, of the same kind of model.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Optional

BENCH_DIR = "chipbench"
ROOT = Path(__file__).resolve().parents[1]


class SpecError(ValueError):
    """A name in BENCHMARK.json has no file, or a file is malformed."""


def _load_module(path: Path, tag: str) -> ModuleType:
    if not path.is_file():
        raise SpecError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(f"chipbench_{tag}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """BENCHMARK.json of one checkout, and the files it names."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        path = self.root / "BENCHMARK.json"
        if not path.is_file():
            raise SpecError(f"no {path}")
        self.spec = json.loads(path.read_text())
        self.dir = self.root / BENCH_DIR

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                cfg = json.loads((self.root / c["file"]).read_text())
                if cfg.get("name") != name:
                    raise SpecError(f"{c['file']} names {cfg.get('name')!r}")
                for key in ("served", "reference"):
                    if key not in cfg:
                        raise SpecError(f"{c['file']} names no {key!r} module")
                return cfg
        raise SpecError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        path = self.dir / "traffic" / f"{name}.json"
        if not path.is_file():
            raise SpecError(f"no traffic file {path}")
        return json.loads(path.read_text())

    def reference(self, name: str) -> ModuleType:
        return _load_module(self.dir / "references" / f"{name}.py", f"ref_{name}")

    def served(self, name: str) -> ModuleType:
        return _load_module(self.dir / "served" / f"{name}.py", f"served_{name}")

    def metrics_for(self, workload: str, kind: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries a cell reports: those
        without a ``workloads`` key, and those that list the cell."""
        return [m for m in self.spec[kind]
                if "workloads" not in m or workload in m["workloads"]]

    def reader(self, metric: str) -> Callable[[object], Optional[float]]:
        mod = _load_module(self.dir / "metrics" / f"{metric}.py",
                           f"metric_{metric.replace('.', '_')}")
        return mod.read
