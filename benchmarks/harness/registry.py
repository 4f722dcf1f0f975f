"""Everything the benchmark reads, found by name: ``BENCHMARK.json``
names a cell, the cell's file names its configuration, statement set
and traffic generator, and each per-layer metric is ``metrics/<name>.py``.
Nothing here knows any of those names."""

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)


def load_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """``<kind>/<name>.py`` as a module of its own (no package: a later
    PR adds a file, never an ``__init__``)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark_json():
    with open(os.path.join(REPO_DIR, "BENCHMARK.json")) as f:
        return json.load(f)


def reference_fn(spec):
    """``"<file>:<function>"`` under ``references/``."""
    mod, fn = spec.split(":")
    return getattr(load_module("references", mod), fn)


def statement_sets():
    return sorted(n[:-5] for n in os.listdir(
        os.path.join(BENCH_DIR, "statements")) if n.endswith(".json"))


class Cell:
    """One entry of ``workloads`` with every file it names loaded."""

    def __init__(self, name):
        bench = benchmark_json()
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.chips = entry["chips"]
        self.workload = load_json("workloads", name + ".json")
        self.config_name = self.workload["config"]
        self.config = load_json("configs", self.config_name + ".json")
        self.statement_set = self.workload["statements"]
        self.classes = load_json(
            "statements", self.statement_set + ".json")["classes"]
        self.traffic = dict(self.workload["traffic"])
        self.generator = load_module("traffic", self.traffic["generator"])

        def mine(m):
            return "workloads" not in m or name in m["workloads"]
        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        self.per_layer = [m for m in bench["per_layer"] if mine(m)]
