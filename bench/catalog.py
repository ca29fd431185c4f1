"""Finds a cell's files by the names in ``BENCHMARK.json``.

A workload names a configuration (``configs/<config>.json``) and a traffic
mix (``traffic/<traffic>.json``). A metric named ``<family>`` or
``<family>.<group>`` is read by ``metrics/<family>.py``. A cell reports
every metric whose ``workloads`` lists it, and every metric without that
key. Adding a configuration, a traffic mix or a metric therefore takes new
files and entries only.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class UnknownDevice(KeyError):
    """``peaks.json`` has no entry for this device kind."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple     # metric entries of BENCHMARK.json
    per_layer: tuple


def _reports(metric: dict, name: str) -> bool:
    return name in metric.get("workloads", (name,))


def load_cell(name: str, root: str = ROOT) -> Cell:
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = load_json(os.path.join(root, conf["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     w["traffic"] + ".json"))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=tuple(m for m in spec["end_to_end"]
                                 if _reports(m, name)),
                per_layer=tuple(m for m in spec["per_layer"]
                                if _reports(m, name)))


def metric_reader(metric_name: str):
    """The ``read(run)`` function of a metric, by its family's file."""
    family = metric_name.split(".")[0]
    return importlib.import_module(f"bench.metrics.{family}").read


def peaks_for(device_kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in "
                            f"peaks.json (known: {sorted(table)})")
    return table[device_kind]
