"""Everything the runner knows about cells, configurations, mixes and layer
metrics it finds by file name — there is no list of them in any ``.py``.

    BENCHMARK.json workloads[].name  → cells/<name>.json
    cell["config"]                   → configs/<config>.json
    cell["traffic"]                  → traffic/<mix>.json
    config["reference"]              → references/<reference>.py
    per_layer[].name                 → whichever layer_metrics/*.py reports it
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(path: str):
    name = "bench_" + os.path.relpath(path, BENCH_DIR).replace(os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Manifest:
    """``BENCHMARK.json`` plus the data directories beside this package.
    ``bench_dir`` and ``manifest`` are arguments so a test can point it at a
    tree of its own."""

    def __init__(self, bench_dir: str = BENCH_DIR, manifest: str | None = None):
        self.bench_dir = bench_dir
        self.path = manifest or os.path.join(os.path.dirname(bench_dir), "BENCHMARK.json")
        self.doc = _load_json(self.path)

    def file(self, kind: str, name: str, ext: str = ".json") -> str:
        path = os.path.join(self.bench_dir, kind, name + ext)
        if not os.path.isfile(path):
            raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
        return path

    def cell(self, name: str) -> dict:
        """The cell's own file with its configuration and mix resolved."""
        cell = _load_json(self.file("cells", name))
        cell["config_spec"] = _load_json(self.file("configs", cell["config"]))
        cell["traffic_spec"] = _load_json(self.file("traffic", cell["traffic"]))
        return cell

    def reference(self, config_spec: dict):
        return load_module(self.file("references", config_spec["reference"], ".py"))

    def metrics(self, section: str, workload: str) -> list[dict]:
        """The metrics of ``section`` this cell reports: those with no
        ``workloads`` key, and those that list the cell."""
        return [m for m in self.doc[section]
                if "workloads" not in m or workload in m["workloads"]]

    def layer_readers(self) -> list:
        """Every reader under layer_metrics/, by listing the directory."""
        folder = os.path.join(self.bench_dir, "layer_metrics")
        return [load_module(os.path.join(folder, f))
                for f in sorted(os.listdir(folder)) if f.endswith(".py")]
