"""``subquery_ms`` and ``subquery_fetch_kb`` on hand-written records: a
q18-shaped statement (one subquery under the root, run inside
``plan.rewrite``'s interval), a
q15-shaped one whose subquery holds a subquery of its own, a statement
without one, and a record from a program older than the span."""

import pytest

from harness import registry

T0_NS = 7_000_000_000_000


def metric(name, records):
    run = {"records": list(records), "samples": [], "pairs": [],
           "trace": None, "slice_s": None}
    return registry.load_module("metrics", name).compute(run)


def q18():
    """120 ms: the inner 5..75 ms (dispatch 10..60), the outer's own
    dispatch 85..105."""
    return {"t0_ns": T0_NS, "total_ms": 120.0, "fetch_bytes": 24_006_400,
            "subqueries": 1, "subquery_rows": 57,
            "subquery_fetch_bytes": 24_000_000,
            "spans": [["http.request", 0.0, 120000.0, -1],
                      ["plan.rewrite", 4000.0, 73000.0, 0],
                      ["subquery", 5000.0, 70000.0, 0],
                      ["plan.build", 6000.0, 1000.0, 2],
                      ["dispatch", 10000.0, 50000.0, 2],
                      ["dispatch.wait", 11000.0, 40000.0, 4],
                      ["dispatch", 85000.0, 20000.0, 0]]}


def q15():
    """Two subqueries under the root, 6 and 4 ms; the first holds one of
    its own (3 ms), which must not be counted again."""
    return {"t0_ns": T0_NS + 200_000_000, "total_ms": 30.0,
            "fetch_bytes": 320_000, "subqueries": 3,
            "subquery_rows": 20_001, "subquery_fetch_bytes": 160_000,
            "spans": [["http.request", 0.0, 30000.0, -1],
                      ["subquery", 2000.0, 6000.0, 0],
                      ["subquery", 3000.0, 3000.0, 1],
                      ["dispatch", 3500.0, 2000.0, 2],
                      ["plan.composite", 9000.0, 500.0, 0],
                      ["subquery", 12000.0, 4000.0, 0],
                      ["subquery", 20000.0, None, 0]]}    # still open


def q10():
    return {"t0_ns": T0_NS + 400_000_000, "total_ms": 50.0,
            "fetch_bytes": 5_000,
            "spans": [["http.request", 0.0, 50000.0, -1],
                      ["plan.rewrite", 1000.0, 100.0, 0],
                      ["dispatch", 5000.0, 40000.0, 0]]}


OLD = {"phases": {"plan.rewrite": 70.0}, "total_ms": 120.0,
       "fetch_bytes": 24_006_400}          # the parent: no span, no counter


def test_subquery_ms_sums_the_roots_subquery_children_per_statement():
    assert metric("subquery_ms", [q18()]) == pytest.approx(70.0)
    assert metric("subquery_ms", [q15()]) == pytest.approx(10.0)
    # the mean over the statements that HAVE one: q10 and OLD do not
    assert metric("subquery_ms", [q18(), q15(), q10(), OLD]) \
        == pytest.approx(40.0)
    # ... which an odd slice moves by its share, not from class to class
    assert metric("subquery_ms", [q18(), q18(), q15(), q10()]) \
        == pytest.approx(50.0)
    # and a fifth off q18's inner moves it by a fifth of q18's share
    faster = q18()
    faster["spans"][2][2] = 56000.0
    assert metric("subquery_ms", [faster, q15()]) == pytest.approx(33.0)


def test_subquery_fetch_kb_is_the_mean_counter_in_thousands_of_bytes():
    assert metric("subquery_fetch_kb", [q18()]) == pytest.approx(24_000.0)
    assert metric("subquery_fetch_kb", [q15(), q10()]) \
        == pytest.approx(160.0)
    assert metric("subquery_fetch_kb", [q18(), q15(), q10(), OLD]) \
        == pytest.approx((24_000.0 + 160.0) / 2)
    assert metric("subquery_fetch_kb", [q18(), q15(), q15()]) \
        == pytest.approx((24_000.0 + 320.0) / 3)


def test_nothing_to_read_is_none_not_an_exception():
    for name in ("subquery_ms", "subquery_fetch_kb"):
        assert metric(name, []) is None
        assert metric(name, [q10(), OLD, {}]) is None
