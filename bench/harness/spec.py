"""What one run measures: the cell's entry in BENCHMARK.json and the files
it names.

Everything a cell needs is found by name, so a later cell, configuration,
traffic mix or per-layer metric is added by adding files and entries:

  bench/configs/<config>.json        the graph deployment (sizes, options)
  bench/traffic/<traffic>.json       the mix's parameters
  bench/limits/<workload>.json       the correctness limits of one cell
  bench/layer_metrics/<metric>.py    one per-layer metric's reader
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple       # BENCHMARK.json metric entries this cell reports
    per_layer: tuple


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, workload: str, e2e_names: set) -> bool:
    cells = metric.get("workloads")
    if cells is not None:
        return workload in cells
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(workload: str, bench_file: Path | None = None) -> Cell:
    bench = _load_json(bench_file or ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    config = _load_json(ROOT / cfg_entry["file"])
    traffic = _load_json(BENCH / "traffic" / f"{entry['traffic']}.json")
    limits = _load_json(BENCH / "limits" / f"{workload}.json")
    e2e = tuple(m for m in bench["end_to_end"]
                if m.get("workloads") is None or workload in m["workloads"])
    names = {m["name"] for m in e2e}
    per_layer = tuple(m for m in bench["per_layer"]
                      if _reports(m, workload, names))
    return Cell(name=workload, chips=int(entry["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer)


def load_reader(metric_name: str):
    """The ``read(ctx)`` function of bench/layer_metrics/<metric>.py."""
    path = BENCH / "layer_metrics" / f"{metric_name}.py"
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + metric_name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
