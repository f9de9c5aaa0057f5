"""``BENCHMARK.json`` and the files it names, found by name.

* a cell's configuration: the ``file`` of its entry in ``configs``;
* a cell's traffic: ``benchmarks/chip/traffic/<traffic>.json``, whose
  ``kind`` names the driver in ``benchmarks/chip/kinds/<kind>.py``;
* a per-layer metric: its reader ``benchmarks/chip/metrics/<name>.py``.

Adding a cell, a configuration or a metric adds files and entries and
edits no file that is there.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
HARNESS = Path("benchmarks") / "chip"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class Spec:
    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.data = json.loads((self.root / "BENCHMARK.json").read_text())
        self.dir = self.root / HARNESS

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in self.data['workloads']]}")

    def config_entry(self, cell: dict) -> dict:
        for c in self.data["configs"]:
            if c["name"] == cell["config"]:
                return c
        raise KeyError(f"workload {cell['name']!r} names config "
                       f"{cell['config']!r}, which BENCHMARK.json lacks")

    def config(self, cell: dict) -> dict:
        return json.loads((self.root / self.config_entry(cell)["file"])
                          .read_text())

    def traffic(self, cell: dict) -> dict:
        return json.loads((self.dir / "traffic" / f"{cell['traffic']}.json")
                          .read_text())

    def end_to_end(self, cell: dict) -> list[dict]:
        return [m for m in self.data["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])]

    def per_layer(self, cell: dict) -> list[dict]:
        """The per-layer metrics this cell reports: those that list it,
        and those without a list whose ``moves`` metric it reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        out = []
        for m in self.data["per_layer"]:
            listed = m.get("workloads")
            if (cell["name"] in listed) if listed is not None \
                    else m["moves"] in e2e:
                out.append(m)
        return out

    def reader(self, metric: str):
        """The module under ``metrics/`` that reads ``metric``."""
        path = self.dir / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"benchmarks.chip.metrics.{metric.replace('.', '_')}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def kind(self, traffic: dict):
        return importlib.import_module(
            f"benchmarks.chip.kinds.{traffic['kind']}")
