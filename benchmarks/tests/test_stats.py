import math

import pytest

from harness import registry, stats


def sample(cls, ms, ok=True):
    return {"cls": cls, "t0": 0.0, "t1": ms / 1000.0, "ms": ms, "ok": ok}


SAMPLES = ([sample("a", v) for v in (1.0, 2.0, 4.0)]
           + [sample("b", v) for v in (8.0, 32.0)]
           + [sample("b", 1e6, ok=False)])


def run_of(samples, seconds=2.0):
    return {"window": {"samples": samples, "seconds": seconds},
            "ready_s": 12.5}


def test_class_medians_exclude_failures_and_count():
    assert stats.class_medians(SAMPLES) == {"a": (2.0, 3), "b": (20.0, 2)}


def test_geomean_of_class_medians():
    assert stats.geomean_of_class_medians(SAMPLES) == pytest.approx(
        math.sqrt(2.0 * 20.0))


def test_percentile_with_sample_count():
    assert stats.percentile([], 95) == (None, 0)
    assert stats.percentile([5.0], 95) == (5.0, 1)
    v, n = stats.percentile(range(1, 102), 95)      # 1..101
    assert (v, n) == (96.0, 101)
    v, n = stats.percentile([1.0, 2.0, 3.0, 4.0], 50)
    assert (v, n) == (2.5, 4)


def test_failures_count_in_no_latency():
    assert stats.latencies(SAMPLES) == [1.0, 2.0, 4.0, 8.0, 32.0]
    assert stats.latencies(SAMPLES, "b") == [8.0, 32.0]


def test_phase_sum():
    rec = {"phases": {"parse": 1.0, "plan.memo": 2.0, "plan.build": 4.0,
                      "dispatch": 8.0}}
    assert stats.phase_sum(rec, ("parse",), ("plan.",)) == 7.0
    assert stats.phase_sum(rec, ("dispatch", "demux")) == 8.0
    assert stats.phase_sum({}, ("dispatch",)) == 0


def test_end_to_end_readers_on_hand_made_samples():
    run = run_of(SAMPLES)
    m = {n: registry.load_module("metrics", n).compute(run)
         for n in ("class_geomean_ms", "stmt_p95_ms", "stmts_per_s",
                   "setup_s")}
    assert m["class_geomean_ms"] == pytest.approx(math.sqrt(40.0))
    assert m["stmt_p95_ms"] == pytest.approx(
        stats.percentile([1, 2, 4, 8, 32], 95)[0])
    assert m["stmts_per_s"] == 2.5           # 5 answered, the failure not
    assert m["setup_s"] == 12.5


def test_a_reader_with_nothing_to_read_returns_nothing():
    empty = {"window": {"samples": [], "seconds": 0.0}, "ready_s": 1.0,
             "samples": [], "pairs": [], "records": [], "trace": None,
             "slice_s": None, "scan_bytes": None, "peaks": None,
             "counters": {"before": {"cache_entries": 3},
                          "after": {"cache_entries": 3}}}
    for m in registry.benchmark_json()["per_layer"]:
        v = registry.load_module("metrics", m["name"]).compute(empty)
        assert v is None or v == 0, m["name"]


def test_per_layer_readers_on_hand_made_records():
    recs = [{"total_ms": 10.0, "n_dispatch": 1, "n_transfer": 0,
             "phases": {"parse": 0.5, "plan.memo": 0.5, "wlm.admit": 1.0,
                        "dispatch": 6.0}},
            {"total_ms": 20.0, "n_dispatch": 0, "n_transfer": 2,
             "phases": {"plan.memo": 2.0, "wlm.admit": 3.0, "compile": 0.1,
                        "dispatch": 10.0, "demux": 2.0}}]
    samples = [sample("a", 12.0), sample("b", 25.0)]
    run = {"samples": samples, "pairs": list(zip(samples, recs)),
           "records": recs, "slice_s": 2.0,
           "trace": {"busy_s": 0.5}, "scan_bytes": {"a": 1e9, "b": 3e9},
           "peaks": {"hbm_gbps": 800.0},
           "counters": {"before": {"cache_entries": 3},
                        "after": {"cache_entries": 3}}}
    want = {"frontend_ms": 3.5, "plan_ms": 1.5, "admit_ms": 2.0,
            "unphased_ms": 2.45, "stmts_per_dispatch": 2.0,
            "compiles_in_window": 1, "transfers_in_window": 2,
            "dispatch_host_p50_ms": 9.0,
            "dispatch_host_p95_ms": 6.0 + 0.95 * 6.0,
            "device_ms_per_stmt": 250.0, "device_idle_pct": 75.0,
            "scan_hbm_pct": 100.0 * 4e9 / 0.5 / 800e9}
    for name, v in want.items():
        got = registry.load_module("metrics", name).compute(run)
        assert got == pytest.approx(v), name
