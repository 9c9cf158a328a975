"""Resolve a cell of ``BENCHMARK.json`` to the files it is made of, by name.

A cell names a configuration and a traffic mix. The configuration's file
is the one ``BENCHMARK.json`` gives; it names its data generator
(``generators/<name>.py``) and plain reference (``references/<name>.py``).
The mix is ``traffic/<mix>.json``; it names its arrival law
(``arrivals/<name>.py``) and query-selection law (``selectors/<name>.py``).
An end-to-end metric ``<name>`` is read by ``e2e/<name>.py``. A per-layer
metric ``<name>`` is read by ``metrics/<name>.py`` or, where that file does
not exist, by the reader of its quantity, ``metrics/<name up to its last
dot>.py`` (``score_ms.p95`` and ``score_ms.p50`` would be one quantity
split by the end-to-end metric each moves).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file's contents
    traffic: dict         # the mix file's contents
    end_to_end: List[dict]
    per_layer: List[dict]


def load_module(path: str):
    name = "bench_" + os.path.relpath(path, BENCH_DIR).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def part(kind: str, name: str):
    """Module ``<kind>/<name>.py``: ``generators``, ``references``,
    ``arrivals``, ``selectors``, ``e2e`` or ``metrics``."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path) and kind == "metrics" and "." in name:
        path = os.path.join(BENCH_DIR, kind, name.rsplit(".", 1)[0] + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} file for {name!r} ({path})")
    return load_module(path)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def traffic(name: str) -> dict:
    """The mix file ``traffic/<name>.json``."""
    with open(os.path.join(BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def resolve(workload: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells: Dict[str, dict] = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=traffic(w["traffic"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])
