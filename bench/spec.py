"""``BENCHMARK.json`` and the files it names, found by name.

A configuration is ``bench/configs/<config>.json`` (its path is the entry's
``file``), a traffic mix ``bench/traffic/<traffic>.json``, a per-layer
metric ``bench/metrics/<metric>.py``.  Adding any of them is adding files
and entries; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class Spec:
    def __init__(self, root: Path = ROOT, bench: Path = BENCH):
        self.root, self.bench = root, bench
        self.data = json.loads((root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        from .traffic import check_mix
        return check_mix(json.loads(
            (self.bench / "traffic" / f"{name}.json").read_text()), name)

    def metrics(self, kind: str, cell: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries a cell reports: those
        that list it, and those that list no cells."""
        return [m for m in self.data[kind]
                if cell in m.get("workloads", [cell])]

    def reader(self, metric: str):
        """The module ``bench/metrics/<metric>.py``."""
        path = self.bench / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench.metrics.{metric}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module
