"""Serving layer + aux subsystem tests (≈ reference thriftserver/
CancelDruidRequestTest/metadata-views suites)."""

import json
import time
import urllib.request
import urllib.error

import numpy as np
import pandas as pd
import pytest

import spark_druid_olap_tpu as sdot
from conftest import make_sales_df


@pytest.fixture(scope="module")
def server():
    from spark_druid_olap_tpu.server.http import SqlServer
    ctx = sdot.Context()
    ctx.ingest_dataframe("sales", make_sales_df(2000), time_column="ts")
    s = SqlServer(ctx, port=0).start()
    yield s
    s.stop()


def _get(server, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}{path}") as r:
        return r.status, json.loads(r.read().decode())


def _post(server, path, payload, raw=False):
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as r:
        body = r.read()
        return r.status, body if raw else json.loads(body.decode())


def test_status(server):
    code, body = _get(server, "/status")
    assert code == 200 and body["status"] == "ok"
    assert "sales" in body["datasources"]


def test_sql_endpoint(server):
    code, body = _post(server, "/sql", {
        "sql": "select region, sum(price) as rev from sales "
               "group by region order by region"})
    assert code == 200
    assert body["columns"] == ["region", "rev"]
    assert body["numRows"] == 4


def test_sql_arrow_format(server):
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}/sql",
        data=json.dumps({"sql": "select count(*) as c from sales",
                         "format": "arrow"}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as r:
        assert r.headers["Content-Type"] == \
            "application/vnd.apache.arrow.stream"
        import io
        import pyarrow as pa
        table = pa.ipc.open_stream(io.BytesIO(r.read())).read_all()
    assert table.num_rows == 1
    assert table.column("c")[0].as_py() == 2000


def test_raw_query_endpoint(server):
    code, body = _post(server, "/query", {
        "queryType": "topN", "dataSource": "sales",
        "dimension": {"dimension": "region", "outputName": "region"},
        "metric": "rev", "threshold": 2,
        "aggregations": [{"type": "doublesum", "name": "rev",
                          "fieldName": "price"}]})
    assert code == 200 and body["numRows"] == 2


def test_explain_endpoint(server):
    code, body = _get(server, "/explain?sql=select%20count(*)%20from%20sales")
    assert code == 200
    assert any("pushdown: YES" in line for line in body["plan"])


def test_metadata_and_history(server):
    code, body = _get(server, "/metadata/datasources")
    assert code == 200 and body["rows"][0]["name"] == "sales"
    code, body = _get(server, "/metadata/columns")
    assert any(r["column"] == "region" for r in body["rows"])
    code, body = _get(server, "/history")
    assert code == 200 and len(body["history"]) >= 1


def test_sql_error_handling(server):
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}/sql",
        data=json.dumps({"sql": "SELEC nope"}).encode(),
        headers={"Content-Type": "application/json"})
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req)
    assert ei.value.code == 400
    body = json.loads(ei.value.read().decode())
    assert body["error"] == "SqlSyntaxError"


def test_sys_views_in_sql():
    ctx = sdot.Context()
    ctx.ingest_dataframe("sales", make_sales_df(1000), time_column="ts")
    r = ctx.sql("select name, numRows from sys_datasources").to_pandas()
    assert list(r["name"]) == ["sales"]
    assert int(r["numRows"][0]) == 1000
    ctx.sql("select count(*) as c from sales")
    r = ctx.sql("select queryType from sys_queries").to_pandas()
    assert len(r) >= 1


def test_query_timeout():
    from spark_druid_olap_tpu.ir.spec import (
        AggregationSpec, QueryContext, TimeseriesQuerySpec,
    )
    from spark_druid_olap_tpu.parallel.executor import QueryTimeout
    ctx = sdot.Context()
    ctx.ingest_dataframe("sales", make_sales_df(1000), time_column="ts")
    q = TimeseriesQuerySpec(
        "sales", (AggregationSpec("count", "c"),),
        context=QueryContext(query_id="t1", timeout_millis=0))
    with pytest.raises(QueryTimeout):
        ctx.engine.execute(q)


def test_query_cancel_flag():
    from spark_druid_olap_tpu.ir.spec import (
        AggregationSpec, QueryContext, TimeseriesQuerySpec,
    )
    from spark_druid_olap_tpu.parallel.executor import QueryCancelled
    import threading
    ctx = sdot.Context()
    ctx.ingest_dataframe("sales", make_sales_df(1000), time_column="ts")
    # pre-set the cancel flag, then execute: first stage boundary raises
    ev = threading.Event()
    ev.set()
    ctx.engine._cancel_flags["c1"] = ev
    q = TimeseriesQuerySpec(
        "sales", (AggregationSpec("count", "c"),),
        context=QueryContext(query_id="c1"))
    with pytest.raises(QueryCancelled):
        ctx.engine.execute(q)


def test_retry_utils():
    from spark_druid_olap_tpu.utils.retry import retry_on_error
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    assert retry_on_error(flaky, tries=5, start=0.001) == "ok"
    assert len(calls) == 3
    with pytest.raises(ValueError):
        retry_on_error(lambda: (_ for _ in ()).throw(ValueError("no")),
                       tries=2, start=0.001,
                       retryable=lambda e: isinstance(e, OSError))


def test_subquery_inlining_pushdown():
    """Uncorrelated scalar/IN subqueries inline -> outer query still pushes
    down (≈ TPC-H Q11/Q15 pattern)."""
    ctx = sdot.Context()
    df = make_sales_df(5000)
    ctx.ingest_dataframe("sales", df, time_column="ts")
    r = ctx.sql("select region, count(*) as cnt from sales "
                "where qty > (select avg(qty) from sales) "
                "group by region order by region")
    assert ctx.history.entries()[-1].stats["mode"] == "engine"
    thresh = df.qty.mean()
    want = df[df.qty > thresh].groupby("region").size()
    got = dict(zip(r["region"], r["cnt"]))
    assert got == dict(want)
    # IN subquery
    r = ctx.sql("select count(*) as c from sales where product in "
                "(select distinct product from sales where price > 990)")
    assert ctx.history.entries()[-1].stats["mode"] == "engine"
    prods = set(df[df.price > np.float32(990)]["product"])
    assert int(r["c"][0]) == int(df["product"].isin(prods).sum())


def test_ui_page(server):
    import urllib.request
    _post(server, "/sql", {"sql": "select count(*) as c from sales"})
    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/ui") as r:
        assert r.status == 200
        assert "text/html" in r.headers["Content-Type"]
        body = r.read().decode()
    assert "Engine queries" in body
    assert "select count(*) as c from sales" in body
    assert "sales" in body


# -----------------------------------------------------------------------------
# cancellation + concurrency (≈ CancelDruidRequestTest + jmeter concurrency)
# -----------------------------------------------------------------------------

@pytest.fixture(scope="module")
def slow_server():
    """Server over a many-segment store with a 1-byte wave budget: engine
    queries run tens of waves with a stage-boundary check per wave, giving
    cancellation a real mid-flight window."""
    from spark_druid_olap_tpu.server.http import SqlServer
    ctx = sdot.Context(config={"sdot.engine.wave.max.bytes": 1})
    ctx.ingest_dataframe("sales", make_sales_df(150_000), time_column="ts",
                         target_rows=256)
    s = SqlServer(ctx, port=0).start()
    # warm every shape the tests use, so they measure execution (the
    # per-wave loop) rather than compilation
    _post(s, "/sql", {"sql": SLOW_SQL})
    _post(s, "/sql", {
        "sql": "select count(*) as n from sales where region = 'east'"})
    yield s
    s.stop()


SLOW_SQL = ("select region, product, sum(price) as rev, min(qty) as mn, "
            "max(qty) as mx, count(*) as n from sales "
            "group by region, product")


def test_sql_returns_query_id(server):
    code, body = _post(server, "/sql", {
        "sql": "select count(*) as n from sales", "queryId": "my-query-1"})
    assert code == 200 and body["queryId"] == "my-query-1"
    code, body = _post(server, "/sql", {
        "sql": "select count(*) as n from sales"})
    assert code == 200 and len(body["queryId"]) >= 16   # minted


def _completed_record(server, qid):
    """The history record of ``qid`` once its handler has closed the
    root (the client has its answer a moment before the last line)."""
    for _ in range(200):
        _, body = _get(server, "/history")
        rec = next(r for r in body["history"] if r.get("query_id") == qid)
        if rec["spans"][0][2] is not None:
            return rec
        time.sleep(0.01)
    raise AssertionError(f"root of {qid} never closed: {rec['spans']}")


def _self_us(dur, kids):
    """``dur`` minus the union of the ``kids`` rows' intervals."""
    covered, end = 0.0, float("-inf")
    for a, b in sorted((k[1], k[1] + k[2]) for k in kids):
        covered += max(0.0, b - max(a, end))
        end = max(end, b)
    return dur - covered


def test_sql_root_starts_at_the_accept(server):
    """A POST /sql's root starts where the server's accept returned,
    inside the client's own wall: its first child ``http.accept`` covers
    the hand-off (thread start, request line, headers) up to where
    ``http.read`` begins, so the root's self time is what the union rule
    gives without it — the interval the root gained is covered."""
    before = time.perf_counter_ns()
    _, body = _post(server, "/sql", {
        "sql": "select count(*) as n from sales"})
    after = time.perf_counter_ns()
    rec = _completed_record(server, body["queryId"])
    spans = rec["spans"]
    acc, read = spans[1], spans[2]
    assert (acc[0], acc[1], acc[3]) == ("http.accept", 0.0, 0)
    assert (read[0], read[3]) == ("http.read", 0)
    assert before <= rec["t0_ns"] < rec["t0_ns"] + acc[2] * 1e3 <= after
    assert 0.0 <= read[1] - acc[2] < 50_000.0      # us: where read opens
    assert 0.0 <= rec["cpu_us"] <= spans[0][2]
    kids = [sp for sp in spans[1:] if sp[3] == 0 and sp[2] is not None]
    without = [sp for sp in kids if sp[0] != "http.accept"]
    assert _self_us(spans[0][2], kids) == pytest.approx(
        _self_us(spans[0][2] - acc[2], without), abs=1e-6)


def test_cancel_unknown_id(server):
    code, body = _post(server, "/sql/cancel", {"queryId": "nope"})
    assert code == 200 and body["cancelled"] is False


def test_sql_cancel_mid_flight(slow_server):
    import threading
    import time

    qid = "cancel-me-1"
    result = {}

    def run():
        req = urllib.request.Request(
            f"http://127.0.0.1:{slow_server.port}/sql",
            data=json.dumps({"sql": SLOW_SQL, "queryId": qid}).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req) as r:
                result["status"] = r.status
                result["body"] = json.loads(r.read().decode())
        except urllib.error.HTTPError as e:
            result["status"] = e.code
            result["body"] = json.loads(e.read().decode())

    t = threading.Thread(target=run)
    t.start()
    # wait until the query is registered, then cancel it mid-flight
    deadline = time.time() + 30
    cancelled = False
    while time.time() < deadline:
        code, body = _post(slow_server, "/sql/cancel", {"queryId": qid})
        if body.get("cancelled"):
            cancelled = True
            break
        time.sleep(0.002)
    t.join(timeout=60)
    assert cancelled, "query id never became cancellable"
    assert result.get("status") == 499, result
    assert result["body"]["error"] == "QueryCancelled"
    assert result["body"]["queryId"] == qid


def test_concurrent_queries_overlap(slow_server):
    """A fast query must complete while a slow one is still executing —
    the server no longer serializes queries behind one lock."""
    import threading
    import time

    order = []

    def slow():
        _post(slow_server, "/sql", {"sql": SLOW_SQL})
        order.append("slow")

    def fast():
        time.sleep(0.02)   # let the slow query enter execution first
        _post(slow_server, "/sql", {
            "sql": "select count(*) as n from sales where region = 'east'"})
        order.append("fast")

    ts = [threading.Thread(target=slow), threading.Thread(target=fast)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    assert order and order[0] == "fast", order


def test_concurrent_correctness_hammer(slow_server):
    """8 threads x mixed queries against one engine: every response must
    equal the single-threaded result (thread-local stats/temp frames, locked
    compile cache)."""
    import threading

    queries = [
        "select region, sum(qty) as s from sales group by region",
        "select product, count(*) as n from sales group by product",
        "select count(*) as n from sales where qty > 25",
        "select region, min(price) as mn, max(price) as mx from sales "
        "group by region",
    ]
    want = {}
    for q in queries:
        _, want[q] = _post(slow_server, "/sql", {"sql": q})
    errors = []

    def worker(i):
        q = queries[i % len(queries)]
        try:
            _, body = _post(slow_server, "/sql", {"sql": q})
            b = dict(body)
            w = dict(want[q])
            b.pop("queryId", None)
            w.pop("queryId", None)
            srt = lambda d: sorted(map(str, d["rows"]))
            if srt(b) != srt(w):
                errors.append((q, "mismatch"))
        except Exception as e:  # noqa: BLE001
            errors.append((q, repr(e)))

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    assert not errors, errors


def test_concurrent_mixed_epilogues():
    """Concurrent sessions exercising the NEW two-dispatch paths (device
    having, hash compaction, top-k) must not corrupt each other's
    program caches or device tables (compile-only locking)."""
    import threading
    import spark_druid_olap_tpu as sdot
    from conftest import make_sales_df
    import numpy as np

    c = sdot.Context({"sdot.engine.having.device.min.keys": 64,
                      "sdot.engine.topn.device.min.keys": 64,
                      "sdot.engine.groupby.dense.max.keys": 1024,
                      "sdot.engine.groupby.hash.compact.min.slots": 1})
    df = make_sales_df(30_000)
    c.ingest_dataframe("sales", df, time_column="ts", target_rows=4096)
    want_top = df.groupby("product")["qty"].sum() \
        .sort_values(ascending=False).head(5).to_numpy()
    g = df.groupby("product")["qty"].sum()
    want_hav = np.sort(g[g > 600].to_numpy())
    errs = []

    def run(i):
        try:
            for _ in range(3):
                t = c.sql("select product, sum(qty) as s from sales "
                          "group by product order by s desc limit 5") \
                    .to_pandas()
                np.testing.assert_array_equal(
                    t["s"].to_numpy().astype(np.int64), want_top)
                h = c.sql("select product, sum(qty) as s from sales "
                          "group by product having sum(qty) > 600") \
                    .to_pandas()
                np.testing.assert_array_equal(
                    np.sort(h["s"].to_numpy().astype(np.int64)), want_hav)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs[:2]


def test_program_cache_survives_query_ids():
    """Per-request query ids must NOT key the compile cache: every server
    statement carries a fresh id, and a signature containing it would
    recompile per request (3-45s per statement on a TPU)."""
    import numpy as np
    import pandas as pd
    import spark_druid_olap_tpu as sdot
    rng = np.random.default_rng(2)
    n = 20_000
    df = pd.DataFrame({
        "ts": np.repeat(np.datetime64("2021-01-01"), n)
        .astype("datetime64[ns]"),
        "r": rng.choice(["a", "b"], n),
        "q": rng.integers(1, 10, n).astype(np.int64),
    })
    # low device-select threshold so the selmask program compiles too
    ctx = sdot.Context({"sdot.select.device.min.rows": 1024})
    ctx.ingest_dataframe("t", df, time_column="ts")
    for sql in ("select r, sum(q) as s from t group by r",
                "select r, q from t where q > 5 limit 20"):
        ctx.sql(sql, query_id="req-1")
        before = len(ctx.engine._programs)
        assert before > 0, sql             # a device program compiled
        ctx.sql(sql, query_id="req-2")
        assert len(ctx.engine._programs) == before, sql
