"""Finds a cell's files by name: the cell in ``BENCHMARK.json``, its
configuration, its traffic mix, its per-layer metric readers and its
family's reference and work modules."""

from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any
from typing import Callable
from typing import Dict
from typing import List

BENCH_DIR = "chipbench"


def load_benchmark(root: Path) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def workload(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def load_config(root: Path, name: str) -> Dict[str, Any]:
    return json.loads((root / BENCH_DIR / "configs" / f"{name}.json")
                      .read_text())


def as_run(cfile: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration as run: ``config``, with the sizes under
    ``assumed`` filled in where the source leaves them out (inside a
    nested group too)."""
    c = dict(cfile["config"])
    for k, v in cfile.get("assumed", {}).items():
        if isinstance(v, dict):
            c[k] = {**v, **c.get(k, {})}
        else:
            c.setdefault(k, v)
    return c


def load_traffic(root: Path, name: str) -> Dict[str, Any]:
    return json.loads((root / BENCH_DIR / "traffic" / f"{name}.json")
                      .read_text())


def per_layer_metrics(bench: Dict[str, Any], cell: str) -> List[Dict]:
    """The per-layer metrics that this cell reports."""
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell])]


def end_to_end_metrics(bench: Dict[str, Any], cell: str) -> List[Dict]:
    return [m for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]


def metric_reader(root: Path, name: str) -> Callable:
    """``read(ctx)`` of ``metrics/<name>.py``; it returns a number, or
    None where the run gave it nothing to read."""
    path = root / BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def family_module(kind: str, family: str) -> ModuleType:
    """``refs.<family>`` or ``work.<family>``."""
    return importlib.import_module(f"chipbench.{kind}.{family}")
