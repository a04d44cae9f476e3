"""Find a cell's configuration, traffic mix and per-layer metric readers by
the names ``BENCHMARK.json`` gives them.

Layout, one file per thing (a later cell or metric adds files only):

* ``bench/configs/<config>.json``   fleet, window, quantum, judge limits;
* ``bench/traffic/<traffic>.json``  generator parameters, warm-up, rate;
* ``bench/layer_metrics/<metric>.py``  a ``read(ctx)`` returning a number
  or None when the run holds nothing for it to read.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: List[dict]      # the cell's end-to-end metric entries
    per_layer: List[dict]       # the cell's per-layer metric entries


def _load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def benchmark() -> dict:
    return _load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    """The cell ``name`` with its configuration and traffic files read."""
    bm = benchmark()
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bm["configs"]}[w["config"]]
    config = _load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = _load_json(os.path.join(BENCH_DIR, "traffic",
                                      w["traffic"] + ".json"))
    return Cell(name=name, config=config, traffic=traffic, chips=w["chips"],
                end_to_end=[m for m in bm["end_to_end"] if _reports(m, name)],
                per_layer=[m for m in bm["per_layer"] if _reports(m, name)])


def metric_reader(name: str) -> Callable[[object], Optional[float]]:
    """``read`` of ``layer_metrics/<name>.py``."""
    path = os.path.join(BENCH_DIR, "layer_metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if mod_spec is None or mod_spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read

