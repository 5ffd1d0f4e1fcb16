"""Find a cell's pieces by name.

``BENCHMARK.json`` at the root lists configurations, cells and metrics.
Each piece lives in a file of its own, found by its name:

- a configuration at the ``file`` its entry names (``bench/configs/``);
- the configuration's architecture at ``bench/arch/<model_type>.py``
  (see :func:`load_arch` for what it defines);
- a traffic mix at ``bench/traffic/<traffic>.json``;
- a metric's reader at ``bench/metrics/<metric>.py``, which defines
  ``read(ctx)`` returning a number or None.

A later cell, mix, configuration, architecture or metric is a new file
plus an entry in ``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys
from types import ModuleType
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
    arch: ModuleType
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _module_name(prefix: str, name: str) -> str:
    return prefix + "".join(c if c.isalnum() else "_" for c in name)


def _load_module(path: str, mod_name: str, what: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(f"no {what} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metrics_dir: str, name: str) -> Callable:
    path = os.path.join(metrics_dir, name + ".py")
    return _load_module(path, _module_name("bench_metric_", name),
                        f"reader for metric {name!r}").read


def load_arch(model_type: str, root: str = ROOT) -> ModuleType:
    """The module ``<root>/bench/arch/<model_type>.py``. It holds all the
    harness knows of one block architecture, as these functions:

    - ``program_config(m)``: the program's ``ModelConfig``;
    - ``leaves(m)``: every weight leaf, a list of
      :class:`harness.weights.Leaf`;
    - ``served_logits(m, seed, prompts, outputs, control=False)``: the
      float32 reference, as ``harness.check`` compares it;
    - ``token_flops(m, layer)``: weight products of one token in
      ``layer``;
    - ``attention_flops(m, layer, ctx)``: attention of one query token
      over ``ctx`` tokens, for an array of ``ctx``;
    - ``paged_decode_cost(ctx_lens, m, itemsize=2)``: operations and
      bytes of one call of the paged decode kernel.

    The module is kept in ``sys.modules``, where :func:`arch_of` finds it
    for a configuration of that ``model_type``."""
    if not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]*", model_type):
        raise ValueError(f"model_type {model_type!r} is not a name")
    path = os.path.abspath(os.path.join(root, "bench", "arch",
                                        model_type + ".py"))
    mod_name = _module_name("bench_arch_", model_type)
    mod = sys.modules.get(mod_name)
    if mod is None or mod.__file__ != path:
        mod = _load_module(path, mod_name,
                           f"module for model_type {model_type!r}")
        sys.modules[mod_name] = mod
    return mod


def arch_of(m: dict) -> ModuleType:
    """The architecture module of configuration ``m``: the one last loaded
    for its ``model_type``, else this benchmark's own."""
    mod = sys.modules.get(_module_name("bench_arch_", m["model_type"]))
    return mod if mod is not None else load_arch(m["model_type"])


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
                config=config, arch=load_arch(config["model_type"], root),
                traffic=traffic,
                end_to_end=_metrics_for(spec["end_to_end"], name,
                                        metrics_dir),
                per_layer=_metrics_for(spec["per_layer"], name,
                                       metrics_dir))
