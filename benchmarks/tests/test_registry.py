"""Every name in BENCHMARK.json resolves to its file, and every file says
what BENCHMARK.json says."""

import os
import re

import pytest

from harness import peaks, registry

BENCH = registry.benchmark_json()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]+$")


def test_exactly_the_contracts_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_units_and_lines():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            for k in ("why", "layer", "source"):
                if k in e and group in ("configs", "workloads", "per_layer"):
                    assert 1 <= len(e[k]) <= 200, (e["name"], k, len(e[k]))
                    assert "\n" not in e[k] and "\t" not in e[k]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for w in BENCH["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    for c in BENCH["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_file_under_paths_has_a_permitted_name():
    for root, dirs, files in os.walk(registry.BENCH_DIR):
        dirs[:] = [d for d in dirs
                   if d not in (".store", "out", "__pycache__",
                                ".pytest_cache")]
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), registry.REPO_DIR)
            assert PATH.match(rel), rel


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves(w):
    cell = registry.Cell(w["name"])
    entry = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert cell.config_name == w["config"]
    assert cell.traffic["generator"] == w["traffic"]
    assert cell.workload["why"] == w["why"]
    assert entry["file"] == f"benchmarks/configs/{w['config']}.json"
    assert cell.config["source"] == entry["source"]
    assert cell.config["chips"] == w["chips"]
    assert set(entry["reduced"]) == set(cell.config["reduced"])
    for k in ("source", "schema", "scale", "settings", "guarantees",
              "reduced", "assumed", "chips"):
        assert k in cell.config, k
    assert callable(cell.generator.schedule)
    for cls, st in cell.classes.items():
        assert NAME.match(cls)
        for k in ("sql", "datasource", "columns", "approx", "reference"):
            assert k in st, (cls, k)
        assert callable(registry.reference_fn(st["reference"]))
    # every cell reports setup_s, another end-to-end metric, a per-layer one
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer


@pytest.mark.parametrize("m", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_file_says_what_the_benchmark_says(m):
    mod = registry.load_module("metrics", m["name"])
    assert (mod.UNIT, mod.BETTER, mod.SOURCE) == (
        m["unit"], m["better"], m["source"])
    assert callable(mod.compute)
    if "moves" in m:                    # per-layer
        assert mod.LAYER == m["layer"] and mod.MOVES == m["moves"]
        cells = m.get("workloads") or [w["name"] for w in BENCH["workloads"]]
        for name in cells:
            reported = {e["name"] for e in registry.Cell(name).end_to_end}
            assert m["moves"] in reported, (m["name"], name)


def test_every_config_is_used_and_every_metric_file_is_listed():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    listed = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    files = {f[:-3] for f in os.listdir(
        os.path.join(registry.BENCH_DIR, "metrics")) if f.endswith(".py")}
    assert files == listed


def test_harness_names_no_cell_statement_or_metric():
    names = {e["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BENCH[g]}
    for w in BENCH["workloads"]:
        cell = registry.Cell(w["name"])
        names |= {cell.statement_set, w["traffic"]}
    files = [os.path.join(registry.BENCH_DIR, "run.py")] + [
        os.path.join(registry.BENCH_DIR, "harness", f)
        for f in os.listdir(os.path.join(registry.BENCH_DIR, "harness"))
        if f.endswith(".py")]
    for path in files:
        text = open(path).read()
        for n in names:
            assert not re.search(rf"\b{re.escape(n)}\b", text), (path, n)


def test_unknown_device_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["hbm_gbps"] == 819.0
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
