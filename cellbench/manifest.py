"""Finds a cell's files from ``BENCHMARK.json``. Driven by data: a cell, a
deployment, a traffic mix and a per-layer metric are each files of their
own, found by name in the directories ``paths`` lists, so a later PR adds
them without editing a file that is there.

    configuration   ``configs[].file``                       (JSON)
    rule family     ``<a path>/families/<family>.py``, named by the
                    configuration file's ``"family"`` (absent: ``flow``);
                    found by ``deploy.load`` in ``Cell.dirs``
    traffic mix     ``<a path>/traffic/<traffic>.json``
    per-layer       ``<a path>/layers/<metric name>.py`` with NAME, UNIT,
    reader          LAYER, MOVES, SOURCE and ``reduce(snap)``
    peaks           ``<a path>/peaks.json``
"""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Cell:
    def __init__(self, manifest_path: str, workload: str):
        self.root = os.path.dirname(os.path.abspath(manifest_path))
        with open(manifest_path, encoding="utf-8") as f:
            self.manifest = json.load(f)
        m = self.manifest
        cells = {w["name"]: w for w in m["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no workload {workload!r} in {manifest_path}; "
                             f"it has {sorted(cells)}")
        self.cell = cells[workload]
        self.name = workload
        self.chips = int(self.cell["chips"])
        cfg = next(c for c in m["configs"] if c["name"] == self.cell["config"])
        self.config_file = os.path.join(self.root, cfg["file"])
        self.dirs = [os.path.join(self.root, p) for p in m["paths"]]
        self.traffic_file = self._find(
            os.path.join("traffic", self.cell["traffic"] + ".json"))
        self.peaks_file = self._find("peaks.json")
        with open(self.traffic_file, encoding="utf-8") as f:
            self.traffic = json.load(f)

    def _find(self, rel: str) -> str:
        for p in self.manifest["paths"]:
            full = os.path.join(self.root, p, rel)
            if os.path.exists(full):
                return full
        raise SystemExit(f"{rel} is in none of {self.manifest['paths']}")

    def _wanted(self, group: str) -> list:
        out = []
        for metric in self.manifest[group]:
            cells = metric.get("workloads")
            if cells is None or self.name in cells:
                out.append(metric)
        return out

    def end_to_end(self) -> list:
        return self._wanted("end_to_end")

    def per_layer(self) -> list:
        return self._wanted("per_layer")

    def readers(self) -> dict:
        """``{metric name: module}`` of every reader file under the paths."""
        found = {}
        for p in self.manifest["paths"]:
            d = os.path.join(self.root, p, "layers")
            if not os.path.isdir(d):
                continue
            for fn in sorted(os.listdir(d)):
                if not fn.endswith(".py") or fn.startswith("_"):
                    continue
                spec = importlib.util.spec_from_file_location(
                    "cellbench_layer_" + fn[:-3].replace(".", "_"),
                    os.path.join(d, fn))
                mod = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(mod)
                found[mod.NAME] = mod
        return found
