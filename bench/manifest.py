"""BENCHMARK.json and the files it names, found by name.

A cell (`workloads` entry) names a configuration and a traffic mix.  Its
files, all under the benchmark's directory (the first of `paths`):

  the configuration's `file`        the model's sizes and numerics
  traffic/<traffic>.json            the training job: workers and their
                                    layout, batch, schedule, optimizer
  cells/<cell>.json                 the limits of the comparison that
                                    decides correct
  reference/<architecture>.py       the plain reference of the model
  metrics/<metric>.py               one reader per per-layer metric
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict           # the configuration file, plus its "name"
    traffic: dict
    limits: dict         # cells/<name>.json
    end_to_end: list     # metric entries this cell reports, trace 0
    per_layer: list      # metric entries this cell reports, trace 1

    @property
    def model(self):
        """The reference module of the cell's architecture."""
        return importlib.import_module(
            f"bench.reference.{self.conf['architecture']}")


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str) -> dict:
    return _read(os.path.join(root, "BENCHMARK.json"))


def _reports(metric: dict, cell: str, e2e_of_cell: set | None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_of_cell is None or metric["moves"] in e2e_of_cell


def load_cell(root: str, name: str) -> Cell:
    man = load_manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    base = os.path.join(root, man["paths"][0])
    confs = {c["name"]: c for c in man["configs"]}
    conf = dict(_read(os.path.join(root, confs[w["config"]]["file"])),
                name=w["config"])
    e2e = [m for m in man["end_to_end"] if _reports(m, name, None)]
    per_layer = [m for m in man["per_layer"]
                 if _reports(m, name, {m["name"] for m in e2e})]
    return Cell(name=name, chips=w["chips"], conf=conf,
                traffic=_read(os.path.join(base, "traffic",
                                           w["traffic"] + ".json")),
                limits=_read(os.path.join(base, "cells",
                                          name + ".json"))["limits"],
                end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str):
    """The `read(record)` function of metrics/<name>.py ('.' and '-' in a
    name become '_' in its file)."""
    mod = name.replace(".", "_").replace("-", "_")
    return importlib.import_module(f"bench.metrics.{mod}").read
