"""Per-statement device round-trip accounting.

Every program dispatch / host->device transfer is a device round trip
(a launch plus a host sync), so `n_dispatch`/`n_transfer` in query
history stats are the round-trip budget made auditable (≈ the
reference's per-query druid-time vs total-time split in
DruidQueryHistory, DruidQueryExecutionMetric.scala:26-80).
"""

import pytest

import spark_druid_olap_tpu as sdot

from conftest import make_sales_df


@pytest.fixture(scope="module")
def ctx():
    c = sdot.Context()
    c.ingest_dataframe("sales", make_sales_df(), time_column="ts",
                       target_rows=4096)
    return c


def _stats(ctx):
    return ctx.history.entries()[-1].stats


def test_agg_query_counts_dispatches(ctx):
    ctx.sql("select region, sum(qty) as s from sales group by region")
    st = _stats(ctx)
    assert st["mode"] == "engine"
    assert st["n_dispatch"] >= 1
    # first run uploads the scan columns
    assert st["n_transfer"] >= 1


def test_warm_query_reuses_device_arrays(ctx):
    q = "select region, sum(qty) as s2 from sales group by region"
    ctx.sql(q)
    ctx.sql(q)
    st = _stats(ctx)
    # same columns already resident: no new transfers, same dispatch count
    assert st["n_transfer"] == 0
    assert st["n_dispatch"] >= 1


def test_counts_accumulate_across_subqueries(ctx):
    ctx.sql("select region, sum(qty) as s from sales "
            "where qty > (select avg(qty) from sales) group by region")
    st = _stats(ctx)
    assert st["mode"] == "engine"
    # subquery + outer each dispatch at least once (subquery may be
    # result-cached from a prior test run in this module, so >= 1 total)
    assert st["n_dispatch"] >= 1


def test_counters_are_monotone_and_thread_local(ctx):
    c0 = list(ctx.engine.dispatch_counts)
    ctx.sql("select count(*) as n from sales")
    c1 = ctx.engine.dispatch_counts
    assert c1[0] >= c0[0]
    assert c1[1] >= c0[1]


def test_cached_program_concurrent_failure_recovery(ctx):
    """If a compile owner raises, a waiter claims ownership and retries
    (per-signature compile events must not deadlock or cache garbage)."""
    import threading
    eng = ctx.engine
    sig = ("test-prog", "failure-recovery")
    calls = {"n": 0}
    lock = threading.Lock()

    def flaky_build():
        with lock:
            calls["n"] += 1
            mine = calls["n"]
        if mine == 1:
            raise RuntimeError("first build fails")
        return "compiled"

    results, errors = [], []

    def worker():
        try:
            results.append(eng._cached_program(sig, flaky_build))
        except RuntimeError as e:
            errors.append(e)

    ts = [threading.Thread(target=worker) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in ts), "deadlocked"
    # exactly one failure propagated to the first owner; everyone else
    # got the successfully-built program
    assert len(errors) == 1
    assert results == ["compiled"] * 3
    assert eng._programs.get(sig) == "compiled"
    eng._programs.pop(sig, None)
