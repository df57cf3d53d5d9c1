"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

* a workload names its configuration (``configs[].file``) and its traffic
  (``perfbench/traffic/<traffic>.json``);
* the traffic names its entry point, ``perfbench/entries/<entry>.py``;
* the configuration names its plain reference (``reference``: a file
  under ``perfbench/``) and its channel kind, whose gains and mean are
  ``perfbench/channels/<kind>.py``;
* a cell's correctness limits are ``perfbench/limits/<workload>.json``;
* a per-layer metric is read by ``perfbench/metrics/<name>.py``, whose
  ``read(ctx)`` returns a number, or None where it finds nothing to read.

A new cell, configuration, channel, entry or metric is new files and
entries; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class Missing(Exception):
    """A name in BENCHMARK.json, or in a file it names, with no file behind
    it."""


def _json(path: str) -> Dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise Missing(f"missing file {path}") from None


def _module(path: str, tag: str):
    if not os.path.exists(path):
        raise Missing(f"no {tag} {path}")
    name = "perfbench_" + tag + "_" + os.path.splitext(
        os.path.basename(path))[0].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Benchmark:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.spec = _json(os.path.join(root, "BENCHMARK.json"))

    def _path(self, *parts: str) -> str:
        return os.path.join(self.root, "perfbench", *parts)

    def workload(self, name: str) -> Dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise Missing(f"no workload {name!r} in BENCHMARK.json")

    def config(self, workload: Dict) -> Dict:
        for c in self.spec["configs"]:
            if c["name"] == workload["config"]:
                return _json(os.path.join(self.root, c["file"]))
        raise Missing(f"no config {workload['config']!r} in BENCHMARK.json")

    def traffic(self, workload: Dict) -> Dict:
        return _json(self._path("traffic", workload["traffic"] + ".json"))

    def limits(self, workload: Dict) -> Dict:
        return _json(self._path("limits", workload["name"] + ".json"))

    def entry(self, traffic: Dict):
        return _module(self._path("entries", traffic["entry"] + ".py"),
                       "entry")

    def reference(self, config: Dict):
        path = os.path.normpath(os.path.join(self.root, config["reference"]))
        if not path.startswith(self._path("")):
            raise Missing(f"reference {config['reference']!r} is not under "
                          "perfbench/")
        return _module(path, "reference")

    def channel(self, config: Dict):
        return _module(self._path("channels",
                                  config["channel"]["kind"] + ".py"),
                       "channel")

    def cell(self, workload: Dict, seed: int):
        """The cell of ``workload`` at ``seed`` (not yet built)."""
        from perfbench.cell import Cell

        config, traffic = self.config(workload), self.traffic(workload)
        return Cell(config, traffic, seed, entry_mod=self.entry(traffic),
                    ref=self.reference(config), chan=self.channel(config))

    def metrics(self, kind: str, workload: Dict) -> List[Dict]:
        """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
        return [m for m in self.spec[kind]
                if workload["name"] in m.get("workloads", [workload["name"]])]

    def reader(self, metric: Dict):
        return _module(self._path("metrics", metric["name"] + ".py"),
                       "metric").read
