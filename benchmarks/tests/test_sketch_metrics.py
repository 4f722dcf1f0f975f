"""``sketch_ms`` and ``sketch_fetch_kb`` on hand-written records: a
statement whose program estimated its two sketch columns on the device
(two short ``sketch`` spans under ``decode``, 80 KB of estimates), one
that fetched a dense register block (one long span, 655 MB), a fused
lane (its span under ``demux``), a statement without a sketch, and a
record from a program older than the span and the counter."""

import pytest

from harness import registry

T0_NS = 9_000_000_000_000


def metric(name, records):
    run = {"records": list(records), "samples": [], "pairs": [],
           "trace": None, "slice_s": None}
    return registry.load_module("metrics", name).compute(run)


def sparse():
    """uq_supplier-shaped: 95 ms, decode 90..91.2 with two sketch spans
    of 0.02 and 0.04 ms; 10,000 groups x 2 columns x 4 bytes."""
    return {"t0_ns": T0_NS, "total_ms": 95.0, "fetch_bytes": 200_000,
            "hll_form": "sparse", "sketch_fetch_bytes": 80_000,
            "sketch_groups": 10_000,
            "spans": [["http.request", 0.0, 95000.0, -1],
                      ["dispatch", 2000.0, 87000.0, 0],
                      ["decode", 90000.0, 1200.0, 0],
                      ["sketch", 90500.0, 20.0, 2],
                      ["sketch", 90600.0, 40.0, 2]]}


def dense():
    """The parent's form of the same statement at 2^14 registers: one
    column's block is 10,000 x 16,384 x 4 bytes, estimated for 8 s."""
    return {"t0_ns": T0_NS + 500_000_000, "total_ms": 9000.0,
            "fetch_bytes": 655_400_000, "hll_form": "scatter",
            "sketch_fetch_bytes": 655_360_000, "sketch_groups": 10_000,
            "spans": [["http.request", 0.0, 9000000.0, -1],
                      ["dispatch", 2000.0, 900000.0, 0],
                      ["decode", 910000.0, 8050000.0, 0],
                      ["sketch", 915000.0, 8000000.0, 2]]}


def lane():
    """A fused group's member: the span lies under ``demux``; members'
    records carry no counters."""
    return {"t0_ns": T0_NS + 800_000_000, "total_ms": 50.0,
            "spans": [["http.request", 0.0, 50000.0, -1],
                      ["demux", 30000.0, 900.0, 0],
                      ["sketch", 30100.0, 600.0, 1],
                      ["sketch", 31000.0, None, 1]]}     # still open


def plain():
    return {"t0_ns": T0_NS + 900_000_000, "total_ms": 8.0,
            "fetch_bytes": 5_000,
            "spans": [["http.request", 0.0, 8000.0, -1],
                      ["decode", 6000.0, 300.0, 0]]}


OLD = {"phases": {"decode": 1.2}, "total_ms": 95.0,
       "fetch_bytes": 200_000}             # the parent: no span, no counter


def test_sketch_ms_sums_a_statements_sketch_spans_and_means_over_them():
    assert metric("sketch_ms", [sparse()]) == pytest.approx(0.06)
    assert metric("sketch_ms", [dense()]) == pytest.approx(8000.0)
    assert metric("sketch_ms", [lane()]) == pytest.approx(0.6)
    # the mean over the statements that HAVE one: plain and OLD do not
    assert metric("sketch_ms", [sparse(), lane(), plain(), OLD]) \
        == pytest.approx(0.33)
    # ... which any class's gain moves by its share
    faster = sparse()
    faster["spans"][4][2] = 10.0
    assert metric("sketch_ms", [faster, lane()]) == pytest.approx(0.315)


def test_sketch_fetch_kb_is_the_mean_counter_in_thousands_of_bytes():
    assert metric("sketch_fetch_kb", [sparse()]) == pytest.approx(80.0)
    assert metric("sketch_fetch_kb", [dense()]) == pytest.approx(655_360.0)
    # records without the counter (a lane, a plain statement) are left out
    assert metric("sketch_fetch_kb", [sparse(), sparse(), dense(), lane(),
                                      plain()]) \
        == pytest.approx((80.0 + 80.0 + 655_360.0) / 3)


@pytest.mark.parametrize("name", ["sketch_ms", "sketch_fetch_kb"])
def test_nothing_to_read_is_none_not_an_error(name):
    assert metric(name, []) is None
    assert metric(name, [plain(), OLD]) is None


def test_both_are_per_layer_metrics_of_the_new_cell_alone():
    per_layer = {m["name"]: m for m in registry.benchmark_json()["per_layer"]}
    for name, moves, source in (
            ("sketch_ms", "class_geomean_ms", "program_span"),
            ("sketch_fetch_kb", "stmt_p95_ms", "program_counter")):
        entry, mod = per_layer[name], registry.load_module("metrics", name)
        assert entry["workloads"] == ["sketch_highcard"]
        assert (entry["moves"], entry["source"]) == (moves, source) \
            == (mod.MOVES, mod.SOURCE)
        assert entry["layer"] == mod.LAYER and entry["unit"] == mod.UNIT
