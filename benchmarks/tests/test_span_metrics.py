"""``harness/spans.py`` and the ten metrics that read the span tree, on
hand-written records: a leader and seven followers of one fused group,
a solo dense statement, a record whose handler had not finished, and a
record from a program without the tree."""

import pytest

from harness import registry, spans

T0_NS = 5_000_000_000_000          # perf_counter_ns of a root's start


def rec(rows, t0_ns=T0_NS, **extra):
    return {"spans": rows, "t0_ns": t0_ns, "total_ms": 0.0, **extra}


def leader(t0_ns=T0_NS):
    """http.request 0..60 ms: read, hold 1..30, plan 30..33, bind 33..34,
    dispatch 34..39 (launch 34..34.5, wait 34.5..37, fetch 37..39),
    demux x2 39..41, encode 55..58, write 58..59.5."""
    return rec([
        ["http.request", 0.0, 60000.0, -1],
        ["http.read", 100.0, 400.0, 0],
        ["coalesce.hold", 1000.0, 29000.0, 0],
        ["coalesce.plan", 30000.0, 3000.0, 0],
        ["bind", 33000.0, 1000.0, 0],
        ["dispatch", 34000.0, 5000.0, 0],
        ["dispatch.launch", 34000.0, 500.0, 5],
        ["dispatch.wait", 34500.0, 2500.0, 5],
        ["dispatch.fetch", 37000.0, 2000.0, 5],
        ["demux", 39000.0, 1000.0, 0],
        ["demux", 40000.0, 1000.0, 0],
        ["http.encode", 55000.0, 3000.0, 0],
        ["http.write", 58000.0, 1500.0, 0],
    ], t0_ns, kernel_launches=1, n_dispatch=1,
        sharedscan={"role": "leader"})


def follower(i, t0_ns=T0_NS):
    """Joins i ms after the leader's root began; the group closes at
    30 ms and its outcome is delivered at 42 ms, on the leader's clock."""
    own = t0_ns + i * 1_000_000
    hold = 30000.0 - i * 1000.0 - 500.0
    return rec([
        ["http.request", 0.0, 50000.0, -1],
        ["http.read", 50.0, 300.0, 0],
        ["coalesce.hold", 500.0, hold, 0],
        ["coalesce.ride", 500.0 + hold, 12000.0, 0],
        ["http.encode", 44000.0, 2000.0, 0],
        ["http.write", 46000.0, 700.0, 0],
    ], own, kernel_launches=0, n_dispatch=0,
        sharedscan={"role": "follower"})


def dense(t0_ns=T0_NS):
    """A solo dense statement, 8 ms, with one dispatch."""
    return rec([
        ["http.request", 0.0, 8000.0, -1],
        ["http.read", 50.0, 250.0, 0],
        ["plan.memo", 400.0, 100.0, 0],
        ["plan.engine", 900.0, 300.0, 0],
        ["bind", 1300.0, 100.0, 0],
        ["dispatch", 1500.0, 2500.0, 0],
        ["dispatch.launch", 1500.0, 600.0, 5],
        ["dispatch.wait", 2100.0, 900.0, 5],
        ["dispatch.fetch", 3000.0, 1000.0, 5],
        ["decode", 4100.0, 900.0, 0],
        ["http.encode", 6000.0, 1000.0, 0],
        ["http.write", 7000.0, 600.0, 0],
    ], t0_ns, kernel_launches=0, n_dispatch=1)


def unfinished():
    """Read before the handler's last lines: ``http.write`` and the
    root are still open."""
    r = dense()
    r["spans"][0][2] = None
    r["spans"][-1][2] = None
    return r


OLD = {"phases": {"dispatch": 2.5}, "total_ms": 6.0}      # no tree


def sample(t0, t1):
    return {"cls": "x", "t0": t0, "t1": t1, "ms": (t1 - t0) * 1e3,
            "ok": True}


def run_of(records, samples=(), trace=None):
    return {"records": list(records), "samples": list(samples),
            "pairs": [], "trace": trace, "slice_s": None}


def metric(name, run):
    return registry.load_module("metrics", name).compute(run)


STORM = [leader()] + [follower(i) for i in range(1, 8)]
NEW = ("hold_ms", "group_service_ms", "launch_ms", "device_wait_ms",
       "fetch_ms", "decode_ms", "server_http_ms", "uncovered_ms",
       "launch_in_flight_pct", "wave_kernel_ms")


# -- harness/spans.py ---------------------------------------------------------

def test_of_gives_absolute_seconds_and_keeps_indices():
    tree = spans.of(leader())
    assert [s.name for s in tree][:3] == ["http.request", "http.read",
                                          "coalesce.hold"]
    assert tree[0].start == pytest.approx(5000.0)
    assert tree[0].end == pytest.approx(5000.060)
    assert tree[6].parent == 5 and tree[5].name == "dispatch"
    open_tree = spans.of(unfinished())
    assert open_tree[0].end is None and open_tree[-1].end is None
    assert len(open_tree) == len(spans.of(dense()))    # indices kept
    assert spans.closed(open_tree, "http.write") == []


def test_record_without_spans_is_none_not_an_exception():
    assert spans.of(OLD) is None
    assert spans.of({"spans": [], "t0_ns": 1}) is None
    assert spans.trees([OLD, dense(), {}]) == [spans.of(dense())]


def test_union_of_intervals():
    assert spans.union([(5, 6), (1, 3), (2, 4), (6, 7)]) == [(1, 4), (5, 7)]
    assert spans.union([]) == []


def test_self_time_is_duration_minus_union_of_children():
    tree = spans.of(leader())
    # root 60 ms; children cover .4 + 29 + 3 + 1 + 5 + 2 + 3 + 1.5 = 44.9
    assert spans.self_ms(tree) == pytest.approx(60.0 - 44.9)
    # dispatch 5 ms is all launch + wait + fetch
    assert spans.self_ms(tree, 5) == pytest.approx(0.0, abs=1e-6)
    overlapping = spans.of(rec([["sql", 0.0, 10000.0, -1],
                                ["bind", 1000.0, 4000.0, 0],
                                ["dispatch", 3000.0, 4000.0, 0],
                                ["decode", 9000.0, 5000.0, 0]]))
    # union 1..7 and 9..10 (a child is clipped to its parent): 7 of 10
    assert spans.self_ms(overlapping) == pytest.approx(3.0)
    assert spans.self_ms(spans.of(unfinished())) is None


def test_in_flight_is_launch_start_to_wait_end():
    assert spans.in_flight(spans.of(dense())) == [
        (pytest.approx(5000.0015), pytest.approx(5000.0030))]
    assert spans.in_flight(spans.of(follower(3))) == []
    twice = rec([["sql", 0.0, 9000.0, -1],
                 ["dispatch", 0.0, 3000.0, 0],
                 ["dispatch.launch", 0.0, 500.0, 1],
                 ["bind", 500.0, 500.0, 1],          # next wave's bind
                 ["dispatch.wait", 1000.0, 1500.0, 1],
                 ["dispatch.fetch", 2500.0, 500.0, 1],
                 ["dispatch", 4000.0, 2000.0, 0],
                 ["dispatch.launch", 4000.0, 200.0, 6],
                 ["dispatch.wait", 4200.0, None, 6]])  # still open
    assert spans.in_flight(spans.of(twice)) == [
        (pytest.approx(5000.0), pytest.approx(5000.0025))]


# -- the metrics --------------------------------------------------------------

def test_storm_group_metrics():
    run = run_of(STORM)
    # holds: leader 29; followers 28.5, 27.5, ..., 22.5 -> median of 8
    assert metric("hold_ms", run) == pytest.approx(26.0)
    # leader: hold ends 30, last demux ends 41
    assert metric("group_service_ms", run) == pytest.approx(11.0)
    assert metric("launch_ms", run) == pytest.approx(0.5)
    assert metric("device_wait_ms", run) == pytest.approx(2.5)
    assert metric("fetch_ms", run) == pytest.approx(2.0)
    assert metric("decode_ms", run) is None            # nobody decodes solo
    # leader .4 + 3 + 1.5 = 4.9; followers .3 + 2 + .7 = 3.0
    assert metric("server_http_ms", run) == pytest.approx(3.0)
    # leader 15.1; followers 50 - (.3 + hold + 12 + 2 + .7), hold 28.5-i+1
    own = sorted([15.1] + [50.0 - (15.0 + 29.5 - i) for i in range(1, 8)])
    assert metric("uncovered_ms", run) == pytest.approx(
        (own[3] + own[4]) / 2)


def test_group_service_without_a_hold_starts_at_the_plan():
    r = leader()
    r["spans"] = [sp for sp in r["spans"] if sp[0] != "coalesce.hold"]
    for sp in r["spans"]:                      # re-point the children
        if sp[3] == 5:
            sp[3] = 4
    assert metric("group_service_ms", run_of([r])) == pytest.approx(11.0)
    assert metric("group_service_ms", run_of([dense()])) is None


def test_solo_statement_metrics():
    run = run_of([dense(), dense(T0_NS + 10_000_000)])
    assert metric("launch_ms", run) == pytest.approx(0.6)
    assert metric("device_wait_ms", run) == pytest.approx(0.9)
    assert metric("fetch_ms", run) == pytest.approx(1.0)
    assert metric("decode_ms", run) == pytest.approx(0.9)
    assert metric("server_http_ms", run) == pytest.approx(1.85)
    # 8 - (.25 + .1 + .3 + .1 + 2.5 + .9 + 1 + .6)
    assert metric("uncovered_ms", run) == pytest.approx(2.25)
    assert metric("hold_ms", run) is None


def test_record_without_http_write_is_left_out_of_what_needs_it():
    run = run_of([unfinished()])
    assert metric("server_http_ms", run) is None
    assert metric("uncovered_ms", run) is None
    assert metric("launch_ms", run) == pytest.approx(0.6)   # closed spans
    both = run_of([unfinished(), dense()])
    assert metric("server_http_ms", both) == pytest.approx(1.85)
    assert metric("uncovered_ms", both) == pytest.approx(2.25)


@pytest.mark.parametrize("name", NEW)
def test_program_without_the_tree_reads_none(name):
    """The parent commit's records: every new metric reads nothing and
    raises nothing, with and without a trace."""
    trace = {"device_ops": [["fused.1", 0.08]], "busy_s": 0.1}
    for run in (run_of([OLD, OLD], [sample(5000.0, 5000.1)], trace),
                run_of([], [], None)):
        assert metric(name, run) is None


def test_launch_in_flight_is_a_union_over_statements():
    # three statements 1 ms apart: in flight 1.5..3.0, 2.5..4.0 and
    # 11.5..13.0 ms after 5000 s -> union 2.5 + 1.5 ms of a 20 ms slice
    recs = [dense(), dense(T0_NS + 1_000_000), dense(T0_NS + 10_000_000)]
    run = run_of(recs, [sample(5000.0, 5000.008),
                        sample(5000.010, 5000.020)])
    assert metric("launch_in_flight_pct", run) == pytest.approx(
        100.0 * 4.0 / 20.0)
    # clipped to first send -> last answer
    late = run_of(recs, [sample(5000.002, 5000.012)])
    assert metric("launch_in_flight_pct", late) == pytest.approx(
        100.0 * (2.0 + 0.5) / 10.0)
    assert metric("launch_in_flight_pct", run_of(recs, [])) is None


def test_wave_kernel_ms_reads_the_stable_name():
    trace = {"device_ops": [["sdot_wave.1", 0.0861], ["copy.10", 0.01],
                            ["sdot_wave_prep_fusion", 0.02],
                            ["sdot_wave.2", 0.0139]]}
    run = run_of(STORM * 4, trace=trace)       # four groups, four launches
    assert metric("wave_kernel_ms", run) == pytest.approx(25.0)
    unnamed = run_of(STORM, trace={"device_ops": [["fused.1", 0.0861]]})
    assert metric("wave_kernel_ms", unnamed) is None
    no_launch = run_of([dense()], trace=trace)
    assert metric("wave_kernel_ms", no_launch) is None
    assert metric("wave_kernel_ms", run_of(STORM)) is None  # not traced


def test_new_metric_files_say_what_benchmark_json_says():
    entries = {m["name"]: m for m in registry.benchmark_json()["per_layer"]}
    for name in NEW:
        mod, e = registry.load_module("metrics", name), entries[name]
        assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE, mod.MOVES) == (
            e["layer"], e["unit"], e["better"], e["source"], e["moves"])
    assert entries["hold_ms"]["workloads"] == ["dash_storm"]
    assert entries["group_service_ms"]["workloads"] == ["dash_storm"]
    assert entries["wave_kernel_ms"]["workloads"] == ["dash_storm"]
    assert entries["decode_ms"]["workloads"] == ["adhoc_seq"]
    assert all("workloads" not in entries[n] for n in NEW
               if n not in ("hold_ms", "group_service_ms",
                            "wave_kernel_ms", "decode_ms"))
