"""The second half of reduce_trace on hand-made intervals (ns)."""

import pytest

from harness import reduce_trace as rt

DEV, OPS, HOST = "/device:TPU:0", "XLA Ops", "/host:CPU"
MS = 1e6


def ev(plane, line, name, start_ms, dur_ms):
    return (plane, line, name, start_ms * MS, dur_ms * MS)


EVENTS = [
    ev(DEV, OPS, "%fusion.1 = s32[8]{0:T(1024)} fusion(s32[8] %p.1), kind=kLoop",
       0, 10),                                # a TPU trace's spelling
    ev(DEV, OPS, "fusion.2", 5, 10),          # overlaps: union 0..15
    ev(DEV, OPS, "fusion.1", 40, 10),         # gap 15..40
    ev(DEV, OPS, "copy", 52, 1),              # gap 50..52
    ev(DEV, "XLA Modules", "jit_x", 0, 53),   # not an op line
    ev(HOST, "python", "client:q1", 0, 16),
    ev(HOST, "python", "between_rounds", 16, 30),
    ev(HOST, "python", "history_read", 20, 15),
    ev(HOST, "python", "client:q6", 46, 10),
    ev(HOST, "python", "PjitFunction(f)", 0, 60),     # not a label
]
LABELS = ("client:", "between_rounds", "history_read")


def test_union():
    assert rt.union([(5, 15), (0, 10), (40, 50), (50, 51)]) == [
        (0, 15), (40, 51)]
    assert rt.union([]) == []


def test_busy_idle_ops_and_labelled_gaps():
    r = rt.reduce_events(EVENTS, LABELS)
    assert r["chips"] == 1 and r["n_ops"] == 4 and r["n_gaps"] == 2
    assert r["busy_s"] == pytest.approx(0.026)      # 15 + 10 + 1 ms
    assert r["span_s"] == pytest.approx(0.053)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.020)]
    assert [n for n, _ in r["device_ops"]] == ["fusion.1", "fusion.2",
                                               "copy"]
    # 15..40: between_rounds covers 24 ms of it, history_read 15 ms
    assert r["idle_gaps"][0] == ["between_rounds", pytest.approx(0.025)]
    assert r["idle_gaps"][1] == ["client:q6", pytest.approx(0.002)]


def test_innermost_annotation_wins_a_tie_and_none_is_unlabelled():
    events = [ev(DEV, OPS, "a", 0, 1), ev(DEV, OPS, "b", 3, 1),
              ev(DEV, OPS, "c", 10, 1),
              ev(HOST, "t1", "between_rounds", 0, 5),
              ev(HOST, "t1", "history_read", 0.5, 3)]
    r = rt.reduce_events(events, LABELS)
    assert r["idle_gaps"] == [["unlabelled", pytest.approx(0.006)],
                              ["history_read", pytest.approx(0.002)]]


def test_mean_over_chips_and_top():
    events = [ev("/device:TPU:0", OPS, "a", 0, 10),
              ev("/device:TPU:1", OPS, "a", 0, 30)]
    r = rt.reduce_events(events, LABELS, top=1)
    assert r["chips"] == 2 and r["busy_s"] == pytest.approx(0.020)
    assert r["device_ops"] == [["a", pytest.approx(0.020)]]


def test_no_device_plane_reads_nothing():
    assert rt.reduce_events([ev(HOST, "python", "client:q1", 0, 1)],
                            LABELS) is None
