"""Reading `BENCHMARK.json` and finding a cell's files by name.

Whatever belongs to one configuration, one traffic mix or one metric sits
in a file of its own; the harness finds it by the name in the manifest, in
the benchmark's directory (or a directory handed in before it, which is how
the tests run a tiny cell without touching the manifest's files).
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]


class Manifest:
    def __init__(self, path, search=()):
        self.path = pathlib.Path(path).resolve()
        self.root = self.path.parent
        self.data = json.loads(self.path.read_text())
        self.search = [pathlib.Path(d) for d in search] + [BENCH_DIR]

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in {self.path}; it has "
                       f"{[w['name'] for w in self.data['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in {self.path}")

    def find(self, kind: str, filename: str) -> pathlib.Path:
        for d in self.search:
            p = d / kind / filename
            if p.exists():
                return p
        raise FileNotFoundError(
            f"{kind}/{filename} not found under {[str(d) for d in self.search]}")

    def json_of(self, kind: str, name: str) -> dict:
        return json.loads(self.find(kind, f"{name}.json").read_text())

    def module(self, kind: str, name: str):
        """Import `<kind>/<name>.py` by path (no package needed)."""
        path = self.find(kind, f"{name}.py")
        key = f"_bench_{kind}_{name}"
        if key in sys.modules and \
                getattr(sys.modules[key], "__file__", None) == str(path):
            return sys.modules[key]
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
        return mod

    def function(self, kind: str, ref: str):
        """"<module>:<function>" -> that function of `<kind>/<module>.py`."""
        module, fn = ref.split(":")
        return getattr(self.module(kind, module), fn)

    def metrics_of(self, cell: str, group: str) -> list:
        """The manifest's entries of `group` ("end_to_end" | "per_layer")
        that this cell reports."""
        return [m for m in self.data[group]
                if "workloads" not in m or cell in m["workloads"]]
