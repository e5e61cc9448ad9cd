"""BENCHMARK.json and the files it names, resolved for one cell.

A cell names a configuration (its `file`), a traffic mix
(`traffic/<name>.json`) and, through the metric lists, the readers
(`metrics/<metric name>.py`, each with `read(ctx) -> float | None`).
Adding a cell, a configuration, a mix or a metric adds files and
entries; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

from benchmarks.chip import traffic

ROOT = pathlib.Path(__file__).resolve().parents[2]
METRICS_DIR = pathlib.Path(__file__).resolve().parent / "metrics"


def load(root=ROOT) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(bench: dict, workload: str, root=ROOT) -> dict:
    """The cell's entry, configuration, mix and metric entries."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((pathlib.Path(root) / cfg_entry["file"]).read_text())
    return {
        "cell": cell,
        "config": config,
        "mix": traffic.load(cell["traffic"]),
        "end_to_end": [m for m in bench["end_to_end"]
                       if _applies(m, workload)],
        "per_layer": [m for m in bench["per_layer"] if _applies(m, workload)],
    }


def reader(name: str):
    """The `read` function of metrics/<name>.py."""
    path = METRICS_DIR / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
