"""Finds what belongs to a cell by the names in BENCHMARK.json, so that a
later PR adds a cell, a traffic mix, a configuration or a per-layer metric
by adding files and one entry, never by editing a file that is there:

  BENCHMARK.json                       the index: cells, metrics, units
  benchmark/configs/<config>.json      the deployment as it is run
  benchmark/traffic/<traffic>.json     parameters of the traffic generator
  benchmark/workloads/<cell>.json      the cell's kernel and trace settings
  benchmark/layer_metrics/<metric>.py  one reader per per-layer metric; a
                                       name `<base>.<suffix>` falls back
                                       to `<base>.py` (the suffix names
                                       the end-to-end metric it moves)
  benchmark/drivers/<driver>.py        the entry a configuration drives
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


_MODULES: dict = {}


def _module(path: str, name: str):
    """The module at `path`, executed once per process."""
    path = os.path.abspath(path)
    if path not in _MODULES:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def load_cell(root: str, workload: str) -> dict:
    """Everything one cell is made of, found by name. `root` holds
    BENCHMARK.json and the benchmark/ directory."""
    bench = os.path.join(root, "benchmark")
    manifest = _json(os.path.join(root, "BENCHMARK.json"))
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for row in manifest[group]:
            if not NAME.match(row["name"]):
                raise ValueError(f"{group}: bad name {row['name']!r}")
            if "unit" in row and not UNIT.match(row["unit"]):
                raise ValueError(f"{row['name']}: bad unit {row['unit']!r}")
    rows = [w for w in manifest["workloads"] if w["name"] == workload]
    if not rows:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    row = rows[0]

    def mine(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    cell = _json(os.path.join(bench, "workloads", workload + ".json"))
    cell.update(
        name=workload, chips=int(row["chips"]),
        config=_json(os.path.join(bench, "configs", row["config"] + ".json")),
        traffic=_json(os.path.join(bench, "traffic", row["traffic"] + ".json")),
        end_to_end=[m for m in manifest["end_to_end"] if mine(m)],
        per_layer=[m for m in manifest["per_layer"] if mine(m)],
        bench_dir=bench,
    )
    return cell


def load_driver(bench: str, name: str):
    return _module(os.path.join(bench, "drivers", name + ".py"),
                   "benchmark_driver_" + name)


def load_reader(bench: str, metric: str):
    """The reader module of a per-layer metric."""
    folder = os.path.join(bench, "layer_metrics")
    for stem in (metric, metric.rsplit(".", 1)[0]):
        path = os.path.join(folder, stem + ".py")
        if os.path.exists(path):
            return _module(path, "benchmark_reader_" + re.sub(r"\W", "_", stem))
    raise FileNotFoundError(f"no reader for per-layer metric {metric!r}")
