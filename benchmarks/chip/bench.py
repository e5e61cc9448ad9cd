"""BENCHMARK.json and the files it names, resolved for one cell.

A cell names a configuration (its `file`), a traffic mix
(`traffic/<name>.json`) and, through the metric lists, the readers
(`metrics/<metric name>.py`, each with `read(ctx) -> float | None`).
A configuration names its model module under `"model"`
(`models/<name>.py`: weights, the program's pipeline, the plain
reference, work counts and answer shape; see `models/capsnet.py`).
Adding a cell, a configuration, a model, a mix or a metric adds files
and entries; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

from benchmarks.chip import traffic

ROOT = pathlib.Path(__file__).resolve().parents[2]
METRICS_DIR = pathlib.Path(__file__).resolve().parent / "metrics"
MODELS_DIR = pathlib.Path(__file__).resolve().parent / "models"


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


def _load(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    """The `read` function of metrics/<name>.py."""
    return _load(METRICS_DIR / f"{name}.py",
                 f"benchmarks.chip.metrics.{name.replace('.', '_')}").read


def model(config: dict):
    """The module models/<name>.py that the configuration names under
    "model"; a configuration without one, or naming none of the modules
    there, is refused.  A model module provides

        make_params(geom, rng)   float32 weights in the program's layout,
                                 made on the device from the seed
        pipeline(config)         the program's `CapsPipeline`
        reference(geom, params, calib, images, bits)
                                 (v in Q0.7, pred) of the plain integer
                                 reference (`reference.py`'s primitives)
        layers(geom)             work per image of each layer (`work.py`)
        out_shape(geom)          (J, O) of the answers
    """
    have = sorted(p.stem for p in MODELS_DIR.glob("*.py"))
    name = config.get("model")
    if name not in have:
        raise ValueError(f"configuration {config.get('name')!r}: \"model\" "
                         f"must name one of {have}, got {name!r}")
    return _load(MODELS_DIR / f"{name}.py", f"benchmarks.chip.models.{name}")
