"""Find a cell's pieces by name.

``BENCHMARK.json`` at the root lists configurations, cells and metrics.
Each piece lives in a file of its own, found by its name:

- a configuration at the ``file`` its entry names (``bench/configs/``);
- a traffic mix at ``bench/traffic/<traffic>.json``;
- a metric's reader at ``bench/metrics/<metric>.py``, which defines
  ``read(ctx)`` returning a number or None.

A later cell, mix, configuration or metric is a new file plus an entry
in ``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: Optional[str]
    read: Callable


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(metrics_dir: str, name: str) -> Callable:
    path = os.path.join(metrics_dir, name + ".py")
    mod_name = "bench_metric_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None:
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _metrics_for(entries: List[dict], cell: str,
                 metrics_dir: str) -> List[Metric]:
    out = []
    for m in entries:
        cells = m.get("workloads")
        if cells is not None and cell not in cells:
            continue
        out.append(Metric(m["name"], m["unit"], m["better"], m["source"],
                          m.get("layer"), load_reader(metrics_dir, m["name"])))
    return out


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells: Dict[str, dict] = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    bench_dir = os.path.join(root, "bench")
    traffic = _load_json(os.path.join(bench_dir, "traffic",
                                      w["traffic"] + ".json"))
    metrics_dir = os.path.join(bench_dir, "metrics")
    return Cell(name=name, config_name=w["config"],
                traffic_name=w["traffic"], chips=int(w["chips"]),
                config=config, traffic=traffic,
                end_to_end=_metrics_for(spec["end_to_end"], name,
                                        metrics_dir),
                per_layer=_metrics_for(spec["per_layer"], name,
                                       metrics_dir))
