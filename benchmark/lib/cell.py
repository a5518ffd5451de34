"""From a workload's name in BENCHMARK.json to everything a run needs.

A cell is resolved as data: ``BENCHMARK.json`` workload -> config file ->
``kind`` and ``builder``; traffic file -> ``generator``; the metrics that
list the cell. Nothing here knows the name of any cell, configuration,
traffic mix or metric.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_plugin(folder: str, name: str):
    """The module ``benchmark/<folder>/<name>.py``, loaded by path: the
    names come from data files and may hold ``-``. It is kept in
    ``sys.modules`` as ``benchmark.<folder>.<name>``, so a plugin's name
    can collide neither with another folder's plugin nor with a module
    the process has imported. One plugin reaches another through this
    function (``cell.plugin``), never by a bare ``import``."""
    path = os.path.join(BENCH_DIR, folder, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"benchmark/{folder}/{name}.py does not exist")
    mod_name = f"benchmark.{folder}.{name}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file, as it is run
    traffic_name: str
    traffic: dict         # the traffic file
    end_to_end: list      # BENCHMARK.json entries that list this cell
    per_layer: list
    rehearse: bool = False

    @property
    def model(self) -> dict:
        """The sizes the builder runs: the file's own, or its
        ``rehearse`` block under ``--rehearse-cpu``."""
        return self.config["rehearse"] if self.rehearse \
            else self.config["model"]

    @property
    def deployment(self) -> dict:
        d = dict(self.config.get("deployment", {}))
        if self.rehearse:
            d.update(self.config.get("rehearse_deployment", {}))
        return d

    @property
    def mix(self) -> dict:
        """The traffic parameters, with the file's ``rehearse``
        overrides under ``--rehearse-cpu``."""
        p = dict(self.traffic["parameters"])
        if self.rehearse:
            p.update(self.traffic.get("rehearse", {}))
        return p

    def plugin(self, folder: str, name: str):
        return load_plugin(folder, name)


def _lists(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(workload: str, rehearse: bool = False) -> Cell:
    bench = load_json("BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(by_name)}")
    w = by_name[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    return Cell(
        name=workload, chips=int(w["chips"]),
        config_name=w["config"], config=load_json(cfg_entry["file"]),
        traffic_name=w["traffic"],
        traffic=load_json("benchmark", "traffic", w["traffic"] + ".json"),
        end_to_end=[m for m in bench["end_to_end"] if _lists(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _lists(m, workload)],
        rehearse=rehearse)
