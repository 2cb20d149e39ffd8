"""Everything the harness knows about a cell it reads from files it finds
by name: the cell in ``BENCHMARK.json``, the configuration's file of
sizes, the traffic file of parameters, and the Python files (builder,
reference, one reader per per-layer metric) those files name.

No cell, configuration or metric is named in this directory or in
``run.py``; ``tests/benchmark/test_manifest.py`` greps for it.
"""

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_manifest():
    return load_json(ROOT, "BENCHMARK.json")


def load_module(kind, name):
    """``benchmarks/<kind>/<name>.py`` as a module. Loaded by path, since
    a name here may hold ``-`` and ``.``."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name}".replace("-", "_").replace(".", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _overlay(base, over):
    """``over``'s keys laid on ``base``, dicts merged one level down."""
    out = dict(base)
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = {**out[key], **value}
        else:
            out[key] = value
    return out


class Cell:
    """One entry of ``workloads`` with its configuration and traffic."""

    def __init__(self, name, rehearsal=False):
        manifest = load_manifest()
        entry = next((w for w in manifest["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            raise SystemExit(
                f"no cell {name!r} in BENCHMARK.json (has: "
                f"{[w['name'] for w in manifest['workloads']]})")
        self.name = name
        self.chips = entry["chips"]
        self.rehearsal = rehearsal
        config = next(c for c in manifest["configs"]
                      if c["name"] == entry["config"])
        self.config = load_json(ROOT, config["file"])
        self.traffic = load_json(BENCH_DIR, "traffic",
                                 entry["traffic"] + ".json")
        if rehearsal:
            # Tiny sizes for the CPU rehearsal, from the same two files.
            self.config = _overlay(self.config, self.config["rehearsal"])
            self.traffic = _overlay(self.traffic, self.traffic["rehearsal"])
        self.end_to_end = [m for m in manifest["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in manifest["per_layer"]
                          if name in m.get("workloads", [name])]
