#!/usr/bin/env python
"""Concurrent SQL load test against the HTTP server.

≈ the reference's JMeter plans (docs/bi-benchmark/*.jmx,
scripts/jmeterscripts/*.jmx) that hammer the thriftserver with concurrent
BI queries. Spawns N client threads issuing queries round-robin for a
duration, then reports throughput and latency percentiles per query.

Usage:
  python scripts/loadtest.py --url http://127.0.0.1:8082 \\
      --threads 8 --duration 30 [--sql "select ..."] [--suite tpch]

With --selfcontained it starts an in-process server over a synthetic
dataset first (no external setup needed).
"""

import argparse
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request
from collections import defaultdict

import numpy as np

DEFAULT_QUERIES = [
    "select region, sum(price) as rev from sales group by region",
    "select region, flag, count(*) as c from sales group by region, flag",
    "select product, sum(price) as rev from sales "
    "group by product order by rev desc limit 5",
    "select count(*) as c from sales where qty >= 25 and status = 'O'",
    "select approx_count_distinct(product) as np from sales",
]

# aggregate shapes the sales_cube rollup can serve (--rollup mode): every
# grouping dim and filter column is a rollup dimension, every aggregate
# derives from the stored sum/count partials (avg via sum+count)
ROLLUP_QUERIES = [
    "select region, sum(price) as rev from sales group by region",
    "select region, flag, sum(qty) as q, count(*) as c from sales "
    "group by region, flag",
    "select product, sum(price) as rev from sales "
    "group by product order by rev desc limit 5",
    "select region, avg(price) as avg_price from sales group by region",
    "select status, count(*) as c from sales where flag = 'A' "
    "group by status",
]


def _synthetic_sales(n=200_000):
    import pandas as pd
    rng = np.random.default_rng(7)
    return pd.DataFrame({
        "ts": (np.datetime64("2015-01-01")
               + rng.integers(0, 730, n).astype("timedelta64[D]")),
        "region": rng.choice(["east", "west", "north", "south"], n),
        "product": rng.choice([f"p{i:03d}" for i in range(50)], n),
        "flag": rng.choice(["A", "N", "R"], n),
        "status": rng.choice(["O", "F"], n),
        "qty": rng.integers(1, 51, n).astype(np.int64),
        "price": np.round(rng.uniform(1, 1000, n), 2),
    })


def post_sql(url, sql, timeout=60):
    req = urllib.request.Request(
        url + "/sql", data=json.dumps({"sql": sql}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read().decode())


def make_http_caller(url):
    return lambda sql: post_sql(url, sql)


def get_json(url, path, timeout=30):
    with urllib.request.urlopen(url + path, timeout=timeout) as r:
        return json.loads(r.read().decode())


def run_hotcold(call, queries, url, iters=20):
    """Cold→warm loop over the result cache: each query once cold, then
    ``iters`` warm repeats; reports hit rate (from /metadata/cache) and
    cold vs warm p50/p99 side by side."""
    before = get_json(url, "/metadata/cache")
    cold, warm = [], []
    for sql in queries:
        t0 = time.perf_counter()
        call(sql)
        cold.append((time.perf_counter() - t0) * 1000)
        for _ in range(iters):
            t0 = time.perf_counter()
            call(sql)
            warm.append((time.perf_counter() - t0) * 1000)
    after = get_json(url, "/metadata/cache")
    served = len(cold) + len(warm)
    hits = (after["hits"] - before["hits"]) \
        + (after["subsumed"] - before["subsumed"])
    c, w = np.array(cold), np.array(warm)
    print(f"\n=== hot/cold ({len(queries)} queries x (1 cold + {iters} "
          f"warm)) ===")
    print(f"  hit rate: {hits}/{served} = {hits / served:.1%} "
          f"(cache enabled={after['enabled']}, "
          f"entries={after['entries']}, bytes={after['bytes']})")
    print(f"  cold p50={np.percentile(c, 50):7.1f}ms "
          f"p99={np.percentile(c, 99):7.1f}ms n={len(c)}")
    print(f"  warm p50={np.percentile(w, 50):7.1f}ms "
          f"p99={np.percentile(w, 99):7.1f}ms n={len(w)}")
    speedup = np.percentile(c, 50) / max(np.percentile(w, 50), 1e-9)
    print(f"  warm p50 speedup: {speedup:.1f}x")
    out = {"mode": "hotcold", "queries": len(queries), "iters": iters,
           "hit_rate": round(hits / served, 4),
           "cold_p50_ms": round(float(np.percentile(c, 50)), 2),
           "cold_p99_ms": round(float(np.percentile(c, 99)), 2),
           "warm_p50_ms": round(float(np.percentile(w, 50)), 2),
           "warm_p99_ms": round(float(np.percentile(w, 99)), 2),
           "warm_p50_speedup": round(float(speedup), 1)}
    print(json.dumps(out))
    return hits > 0


def make_flight_caller(url):
    """Per-thread Arrow Flight SQL caller: the same CommandStatementQuery
    envelope ADBC/JDBC-Flight drivers emit (get_flight_info -> do_get),
    so p95s here measure the BI wire path, not just HTTP JSON."""
    import pyarrow.flight as fl
    sys.path.insert(0, ".")
    from spark_druid_olap_tpu.server.flight import encode_statement_query
    client = fl.connect(url)

    def call(sql):
        desc = fl.FlightDescriptor.for_command(encode_statement_query(sql))
        info = client.get_flight_info(desc)
        return client.do_get(info.endpoints[0].ticket).read_all()

    return call


def run(make_caller, queries, n_threads, duration):
    stop = time.monotonic() + duration
    lat = defaultdict(list)
    errors = [0]
    lock = threading.Lock()

    def worker(tid):
        call = make_caller()
        i = tid
        while time.monotonic() < stop:
            sql = queries[i % len(queries)]
            i += 1
            t0 = time.perf_counter()
            try:
                call(sql)
            except Exception:
                with lock:
                    errors[0] += 1
                continue
            dt = (time.perf_counter() - t0) * 1000
            with lock:
                lat[sql].append(dt)

    threads = [threading.Thread(target=worker, args=(t,), daemon=True)
               for t in range(n_threads)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.monotonic() - t0
    total = sum(len(v) for v in lat.values())
    print(f"\n{total} queries in {elapsed:.1f}s = "
          f"{total / elapsed:.1f} qps over {n_threads} threads; "
          f"{errors[0]} errors")
    for sql, v in lat.items():
        a = np.array(v)
        print(f"  p50={np.percentile(a, 50):7.1f}ms "
              f"p95={np.percentile(a, 95):7.1f}ms "
              f"p99={np.percentile(a, 99):7.1f}ms n={len(a):5d}  "
              f"{sql[:70]}")
    return total, errors[0], elapsed, lat


# interactive BI-dashboard shapes over the flat index (the reference's
# JMeter plans hammer exactly this class: filtered aggregates, a trend
# line, a topN — docs/bi-benchmark/snap-sales-demo.jmx)
TPCH_DASHBOARD = [
    "select l_returnflag, l_linestatus, sum(l_quantity) as sq, "
    "count(*) as n from lineitem where l_shipdate <= date '1998-09-02' "
    "group by l_returnflag, l_linestatus",
    "select sum(l_extendedprice * l_discount) as revenue from lineitem "
    "where l_shipdate >= date '1994-01-01' "
    "and l_shipdate < date '1995-01-01' "
    "and l_discount between 0.05 and 0.07 and l_quantity < 24",
    "select l_shipmode, count(*) as c from lineitem "
    "group by l_shipmode order by l_shipmode",
    "select p_brand, sum(l_quantity) as s from lineitem "
    "join part on l_partkey = p_partkey "
    "group by p_brand order by s desc limit 10",
    "select o_orderpriority, count(*) as c from orders "
    "where o_orderdate >= date '1993-07-01' "
    "and o_orderdate < date '1993-10-01' group by o_orderpriority",
    # two widget variants sharing the global dashboard time window with
    # the pricing-summary tile: a coalesced wave carries the
    # `l_shipdate <= date '1998-09-02'` conjunct in >= 2 lanes, so the
    # fusion planner provably lowers it once (predicate_evals_saved > 0)
    "select l_linestatus, sum(l_extendedprice) as rev from lineitem "
    "where l_shipdate <= date '1998-09-02' and l_discount > 0.04 "
    "group by l_linestatus",
    "select count(*) as big_orders from lineitem "
    "where l_shipdate <= date '1998-09-02' and l_quantity >= 45",
]


def _summarize(lat_total_errs):
    total, errs, elapsed, lat = lat_total_errs
    alllat = np.concatenate([np.array(v) for v in lat.values()]) \
        if lat else np.array([0.0])
    return {"qps": round(total / max(elapsed, 1e-9), 1),
            "n": int(total), "errors": int(errs),
            "p50_ms": round(float(np.percentile(alllat, 50)), 1),
            "p95_ms": round(float(np.percentile(alllat, 95)), 1),
            "p99_ms": round(float(np.percentile(alllat, 99)), 1)}


def run_tpch_compare(args):
    """One TPC-H context served over BOTH endpoints; the same dashboard
    mix hammers each in turn. Prints a side-by-side + one JSON line for
    docs/bench/."""
    sys.path.insert(0, ".")
    import bench
    from spark_druid_olap_tpu.server.flight import SdotFlightServer
    from spark_druid_olap_tpu.server.http import SqlServer

    ctx, n_rows = bench.setup(args.tpch)
    if args.hotcold:
        # bench.setup disables the result cache for clean latency reps;
        # the hot/cold loop exists to measure that cache, so turn it
        # back on BEFORE the first query (one fingerprint for the run)
        ctx.config.set("sdot.cache.enabled", True)
    http_server = SqlServer(ctx, port=0)
    http_server.start()
    http_url = f"http://127.0.0.1:{http_server.port}"
    flight_server = SdotFlightServer(ctx, "grpc://127.0.0.1:0")
    flight_url = f"grpc://127.0.0.1:{flight_server.port}"

    queries = args.sql or TPCH_DASHBOARD
    if args.hotcold:
        try:
            ok = run_hotcold(make_http_caller(http_url), queries,
                             http_url, iters=args.hotcold)
        finally:
            http_server.stop()
            flight_server.shutdown()
        sys.exit(0 if ok else 1)
    for q in queries:                      # compile/warm before measuring
        post_sql(http_url, q, timeout=300)

    results = {}
    try:
        for name, mk in [("http", lambda: make_http_caller(http_url)),
                         ("flight",
                          lambda: make_flight_caller(flight_url))]:
            print(f"\n=== {name} leg ({args.threads} threads x "
                  f"{args.duration:.0f}s) ===")
            results[name] = _summarize(
                run(mk, queries, args.threads, args.duration))
    finally:
        try:
            http_server.stop()
        except Exception:   # noqa: BLE001
            pass
        try:
            flight_server.shutdown()
        except Exception:   # noqa: BLE001
            pass
    out = {"suite": "tpch_dashboard", "sf": args.tpch, "rows": n_rows,
           "threads": args.threads, "duration_s": args.duration,
           "legs": results}
    print("\n" + json.dumps(out))
    ok = all(r["n"] > 0 and r["errors"] <= r["n"] * 0.01
             for r in results.values())
    sys.exit(0 if ok else 1)


def _frames_close(a, b) -> bool:
    """Order-insensitive frame comparison with float tolerance (the
    rollup leg re-aggregates stored partials; float sums may differ in
    the last ulps)."""
    cols = sorted(a.columns)
    if cols != sorted(b.columns) or len(a) != len(b):
        return False
    a = a[cols].sort_values(cols).reset_index(drop=True)
    b = b[cols].sort_values(cols).reset_index(drop=True)
    for c in cols:
        av, bv = a[c].to_numpy(), b[c].to_numpy()
        if av.dtype.kind in "if" and bv.dtype.kind in "if":
            if not np.allclose(av.astype(float), bv.astype(float),
                               rtol=1e-4, atol=1e-6, equal_nan=True):
                return False
        elif not (av == bv).all():
            return False
    return True


def run_rollup(args):
    """In-process base-vs-rollup comparison: the same aggregate mix runs
    with the planner rewrite disabled, then enabled, over a context with
    BOTH the result cache and the statement caches off (every rep
    replans and re-executes). Reports the rewrite hit rate (per-query
    ``rollup`` status in sys_queries stats) and p50/p99 side by side,
    plus a differential check that both legs return the same rows."""
    sys.path.insert(0, ".")
    import spark_druid_olap_tpu as sdot
    ctx = sdot.Context({"sdot.cache.enabled": False,
                        "sdot.plan.cache.enabled": False})
    ctx.ingest_dataframe("sales", _synthetic_sales(), time_column="ts")
    msg = ctx.sql(
        "create rollup sales_cube on sales "
        "dimensions (region, product, flag, status) "
        "aggregations (sum(price), sum(qty), count(*))").to_pandas()
    rows = ctx.store.get("sales").num_rows
    print(f"[rollup] {msg['status'][0]} (base {rows:,} rows)")
    iters = max(1, args.rollup)
    queries = args.sql or ROLLUP_QUERIES
    legs, answers, statuses, mismatches = {}, {}, [], []
    for leg, enabled in (("base", False), ("rollup", True)):
        ctx.config.set("sdot.mv.rewrite.enabled", enabled)
        lat = []
        for sql in queries:
            df = ctx.sql(sql).to_pandas()      # warm (compile) rep
            if leg == "base":
                answers[sql] = df
            elif not _frames_close(answers[sql], df):
                mismatches.append(sql)
            for _ in range(iters):
                t0 = time.perf_counter()
                ctx.sql(sql)
                lat.append((time.perf_counter() - t0) * 1000)
                if leg == "rollup":
                    st = ctx.history.entries()[-1].stats
                    statuses.append(st.get("rollup"))
        a = np.array(lat)
        legs[leg] = {"p50_ms": round(float(np.percentile(a, 50)), 2),
                     "p99_ms": round(float(np.percentile(a, 99)), 2),
                     "n": len(a)}
        print(f"  {leg:6s} p50={legs[leg]['p50_ms']:7.1f}ms "
              f"p99={legs[leg]['p99_ms']:7.1f}ms n={len(a)}")
    hits = sum(1 for s in statuses
               if s and str(s).startswith("rollup:"))
    hit_rate = hits / max(len(statuses), 1)
    speedup = legs["base"]["p50_ms"] / max(legs["rollup"]["p50_ms"], 1e-9)
    print(f"  rewrite hit rate: {hits}/{len(statuses)} = {hit_rate:.1%}; "
          f"p50 speedup {speedup:.2f}x"
          + (f"; RESULT MISMATCH on {mismatches}" if mismatches else ""))
    out = {"mode": "rollup", "queries": len(queries), "iters": iters,
           "rewrite_hit_rate": round(hit_rate, 4),
           "base_p50_ms": legs["base"]["p50_ms"],
           "base_p99_ms": legs["base"]["p99_ms"],
           "rollup_p50_ms": legs["rollup"]["p50_ms"],
           "rollup_p99_ms": legs["rollup"]["p99_ms"],
           "p50_speedup": round(float(speedup), 2),
           "result_mismatches": mismatches}
    print(json.dumps(out))
    sys.exit(0 if (hits > 0 and not mismatches) else 1)


def run_coldtier(args):
    """Cold-tier comparison (tier/): build + checkpoint a synthetic
    store, capture unbudgeted (eager-recovery) answers, then reopen with
    ``sdot.tier.enabled`` under ``--budget`` bytes and replay the mix —
    first pass cold (every chunk faults from the memory-mapped blobs),
    then N hot reps. Reports cold vs hot p50/p99, hot-set hit rate,
    bytes faulted, and the prefetch overlap ratio; any differential
    mismatch against the unbudgeted answers exits 1."""
    sys.path.insert(0, ".")
    import shutil
    import tempfile
    import spark_druid_olap_tpu as sdot
    root = tempfile.mkdtemp(prefix="sdot-coldtier-")
    try:
        seed = sdot.Context({"sdot.persist.path": root})
        seed.ingest_dataframe("sales", _synthetic_sales(),
                              time_column="ts", target_rows=8192)
        col_bytes = sum(
            c["size"] for c in
            seed.store.get("sales").metadata()["columns"].values())
        seed.checkpoint()
        seed.close()
        queries = args.sql or DEFAULT_QUERIES
        common = {"sdot.persist.path": root,
                  "sdot.cache.enabled": False,
                  "sdot.plan.cache.enabled": False}
        eager = sdot.Context(dict(common))
        answers = {sql: eager.sql(sql).to_pandas() for sql in queries}
        eager.close()

        budget = int(args.budget)
        print(f"[coldtier] store {col_bytes:,} column bytes, "
              f"budget {budget:,} bytes "
              f"({col_bytes / max(budget, 1):.1f}x over)")
        # cap per-wave I/O well under the budget so scans split into
        # waves and the load-behind-compute overlap is measurable
        ctx = sdot.Context({**common, "sdot.tier.enabled": True,
                            "sdot.tier.budget.bytes": budget,
                            "sdot.tier.wave.io.bytes":
                                max(64 * 1024, budget // 8)})
        iters = 5
        mismatches, cold, hot = [], [], []
        for sql in queries:
            t0 = time.perf_counter()
            df = ctx.sql(sql).to_pandas()
            cold.append((time.perf_counter() - t0) * 1000)
            if not _frames_close(answers[sql], df):
                mismatches.append(sql)
        for _ in range(iters):
            for sql in queries:
                t0 = time.perf_counter()
                df = ctx.sql(sql).to_pandas()
                hot.append((time.perf_counter() - t0) * 1000)
                if not _frames_close(answers[sql], df):
                    mismatches.append(sql)
        st = ctx.persist.tier.stats_snapshot()
        ctx.close()
        hit_rate = st["hits"] / max(st["hits"] + st["faults"], 1)
        c, h = np.array(cold), np.array(hot)
        print(f"  cold p50={np.percentile(c, 50):7.1f}ms "
              f"p99={np.percentile(c, 99):7.1f}ms n={len(c)}")
        print(f"  hot  p50={np.percentile(h, 50):7.1f}ms "
              f"p99={np.percentile(h, 99):7.1f}ms n={len(h)}")
        print(f"  hit rate {hit_rate:.1%}, "
              f"faulted {st['bytes_faulted']:,}B, "
              f"evicted {st['bytes_evicted']:,}B, "
              f"peak-resident<= {st['budget_bytes']:,}B+pins, "
              f"prefetch overlap {st['prefetch_overlap_ratio']:.1%}"
              + (f"; RESULT MISMATCH on {mismatches}"
                 if mismatches else ""))
        out = {"mode": "coldtier", "queries": len(queries),
               "iters": iters, "budget_bytes": budget,
               "column_bytes": int(col_bytes),
               "cold_p50_ms": round(float(np.percentile(c, 50)), 2),
               "cold_p99_ms": round(float(np.percentile(c, 99)), 2),
               "hot_p50_ms": round(float(np.percentile(h, 50)), 2),
               "hot_p99_ms": round(float(np.percentile(h, 99)), 2),
               "hit_rate": round(float(hit_rate), 4),
               "bytes_faulted": st["bytes_faulted"],
               "bytes_evicted": st["bytes_evicted"],
               "prefetch_overlap_ratio": st["prefetch_overlap_ratio"],
               "result_mismatches": mismatches}
        print(json.dumps(out))
        sys.exit(1 if mismatches else 0)
    finally:
        shutil.rmtree(root, ignore_errors=True)


def run_encoded(args):
    """Encoded-vs-raw differential (encode/ + tier/): checkpoint the
    SAME synthetic store twice — once raw, once with
    ``sdot.encode.enabled`` — capture unbudgeted eager answers, then
    replay the mix through BOTH tiered recoveries under the same
    ``--budget``. Every reply on both legs is differentially checked
    against the eager answers (any mismatch exits 1). Reports the
    on-disk compression ratio, per-leg p50, physical bytes faulted, and
    hot-set residency at the shared budget — the encoded leg should
    hold ratio-times more chunks resident for the same bytes."""
    sys.path.insert(0, ".")
    import shutil
    import tempfile
    import spark_druid_olap_tpu as sdot
    root = tempfile.mkdtemp(prefix="sdot-encoded-")
    try:
        queries = args.sql or DEFAULT_QUERIES
        budget = int(args.budget)
        answers = None
        legs, mismatches = {}, []
        for leg, enabled in (("raw", False), ("encoded", True)):
            sub = os.path.join(root, leg)
            seed = sdot.Context({"sdot.persist.path": sub,
                                 "sdot.encode.enabled": enabled})
            seed.ingest_dataframe("sales", _synthetic_sales(),
                                  time_column="ts", target_rows=8192)
            col_bytes = sum(
                c["size"] for c in
                seed.store.get("sales").metadata()["columns"].values())
            seed.checkpoint()
            seed.close()
            common = {"sdot.persist.path": sub,
                      "sdot.cache.enabled": False,
                      "sdot.plan.cache.enabled": False}
            if answers is None:
                # eager (unbudgeted, undecoded-store) reference answers
                eager = sdot.Context(dict(common))
                answers = {sql: eager.sql(sql).to_pandas()
                           for sql in queries}
                eager.close()
            ctx = sdot.Context({**common, "sdot.tier.enabled": True,
                                "sdot.tier.budget.bytes": budget,
                                "sdot.tier.wave.io.bytes":
                                    max(64 * 1024, budget // 8)})
            lat = []
            for _ in range(5):
                for sql in queries:
                    t0 = time.perf_counter()
                    df = ctx.sql(sql).to_pandas()
                    lat.append((time.perf_counter() - t0) * 1000)
                    if not _frames_close(answers[sql], df):
                        mismatches.append(f"{leg}: {sql}")
            st = ctx.persist.tier.stats_snapshot()
            enc = ctx.engine.last_stats.get("encoding") or {}
            ctx.close()
            legs[leg] = {
                "p50_ms": round(float(np.percentile(lat, 50)), 2),
                "column_bytes": int(col_bytes),
                "bytes_faulted": int(st["bytes_faulted"]),
                "hot_entries": int(st["hot_entries"]),
                "hot_bytes": int(st["hot_bytes"]),
                "ratio": enc.get("ratio", 1.0),
            }
            print(f"[encoded] {leg}: p50 {legs[leg]['p50_ms']}ms, "
                  f"faulted {legs[leg]['bytes_faulted']:,}B, resident "
                  f"{legs[leg]['hot_entries']} chunks"
                  + (f", ratio {legs[leg]['ratio']}x"
                     if enc else ""))
        out = {"mode": "encoded", "queries": len(queries),
               "budget_bytes": budget,
               "ratio": legs["encoded"]["ratio"],
               "raw": legs["raw"], "encoded": legs["encoded"],
               "resident_gain": round(
                   legs["encoded"]["hot_entries"]
                   / max(legs["raw"]["hot_entries"], 1), 2),
               "result_mismatches": mismatches}
        print(json.dumps(out))
        sys.exit(1 if mismatches else 0)
    finally:
        shutil.rmtree(root, ignore_errors=True)


def run_coldstart(args):
    """Warm vs cold startup-to-first-result (persist/): build + checkpoint
    a synthetic store, then compare the first-query latency of the live
    (warm) context against a FRESH context that must recover the store
    from deep storage first (snapshot load + checksum verify + WAL
    replay). Differential: the cold context's answers must match the warm
    context's byte-for-byte."""
    import shutil
    import tempfile
    sys.path.insert(0, ".")
    import spark_druid_olap_tpu as sdot

    root = tempfile.mkdtemp(prefix="sdot-coldstart-")
    cfg = {"sdot.persist.path": root, "sdot.plan.cache.enabled": False,
           "sdot.cache.enabled": False}
    queries = args.sql or DEFAULT_QUERIES
    try:
        ctx = sdot.Context(cfg)
        df = _synthetic_sales()
        t0 = time.perf_counter()
        ctx.stream_ingest("sales", df, time_column="ts")
        ingest_ms = (time.perf_counter() - t0) * 1000
        t0 = time.perf_counter()
        summary = ctx.checkpoint("sales")[0]
        ckpt_ms = (time.perf_counter() - t0) * 1000
        for q in queries:        # compile once; both legs measure steady
            ctx.sql(q)           # state, not XLA compilation
        warm_lat, answers = [], {}
        for q in queries:
            t0 = time.perf_counter()
            answers[q] = ctx.sql(q).to_pandas()
            warm_lat.append((time.perf_counter() - t0) * 1000)
        ctx.close()

        t0 = time.perf_counter()
        ctx2 = sdot.Context(cfg)          # recovery runs in __init__
        recover_ms = (time.perf_counter() - t0) * 1000
        t0 = time.perf_counter()
        first = ctx2.sql(queries[0]).to_pandas()
        cold_first_ms = (time.perf_counter() - t0) * 1000
        pstat = dict(ctx2.engine.last_stats.get("persist") or {})
        mismatches = [] if first.equals(answers[queries[0]]) else [queries[0]]
        cold_lat = [cold_first_ms]
        for q in queries[1:]:
            t0 = time.perf_counter()
            got = ctx2.sql(q).to_pandas()
            cold_lat.append((time.perf_counter() - t0) * 1000)
            if not got.equals(answers[q]):
                mismatches.append(q)
        ctx2.close()
    finally:
        shutil.rmtree(root, ignore_errors=True)

    w, c = np.array(warm_lat), np.array(cold_lat)
    print(f"\n=== coldstart ({len(df):,} rows, snapshot "
          f"{summary['bytes']:,} bytes) ===")
    print(f"  ingest {ingest_ms:8.1f}ms   checkpoint {ckpt_ms:8.1f}ms")
    print(f"  warm  first-result p50={np.percentile(w, 50):7.1f}ms "
          f"(store already in memory)")
    print(f"  cold  recovery={recover_ms:7.1f}ms "
          f"(source={pstat.get('source')}, checksum verify "
          f"{pstat.get('checksum_verify_ms', 0)}ms) "
          f"+ first query {cold_first_ms:7.1f}ms")
    print(f"  cold startup-to-first-result: "
          f"{recover_ms + cold_first_ms:7.1f}ms"
          + (f"; RESULT MISMATCH on {mismatches}" if mismatches else ""))
    out = {"mode": "coldstart", "rows": len(df),
           "snapshot_bytes": int(summary["bytes"]),
           "checkpoint_ms": round(ckpt_ms, 1),
           "recover_ms": round(recover_ms, 1),
           "recovery_source": pstat.get("source"),
           "checksum_verify_ms": pstat.get("checksum_verify_ms"),
           "warm_first_ms": round(float(np.percentile(w, 50)), 1),
           "cold_first_ms": round(cold_first_ms, 1),
           "cold_startup_to_first_ms": round(recover_ms + cold_first_ms, 1),
           "result_mismatches": mismatches}
    print(json.dumps(out))
    sys.exit(0 if not mismatches else 1)


# WLM overload mix: cheap dashboard probes (the interactive lane's
# traffic) vs heavy scans that would otherwise monopolize the engine
WLM_INTERACTIVE = [
    "select count(*) as c from sales where status = 'O'",
    "select region, count(*) as c from sales group by region",
    "select count(*) as c from sales where qty >= 25",
]
WLM_HEAVY = [
    "select product, flag, status, sum(price) as rev, sum(qty) as q, "
    "count(*) as c from sales group by product, flag, status",
    "select product, approx_count_distinct(region) as nr, "
    "sum(price * (1 - 0.04)) as rev from sales group by product "
    "order by rev desc limit 20",
]


def run_wlm(args):
    """Overload comparison: the same interactive+heavy mix hammers the
    HTTP server at ~4x the interactive lane's concurrency, with WLM off
    then on (fixed seed, result/plan caches off — every rep executes).
    Heavy queries are tagged for the batch lane; with laning on they are
    capped at the batch slots and excess sheds as 429 + Retry-After
    instead of piling onto the engine. Reports per-class p50/p99 and
    shed rate per leg; exits 0 when the interactive p99 improves and no
    lane ever exceeded its concurrency cap."""
    sys.path.insert(0, ".")
    import spark_druid_olap_tpu as sdot
    from spark_druid_olap_tpu.server.http import SqlServer
    int_slots, batch_slots = 4, 1
    ctx = sdot.Context({
        "sdot.cache.enabled": False,          # cache-bypass hygiene: a
        "sdot.plan.cache.enabled": False,     # hit would fake the p99s
        "sdot.wlm.lanes":
            f"interactive:slots={int_slots},queue=64;"
            f"batch:slots={batch_slots},queue=2,wait_ms=250"})
    ctx.ingest_dataframe("sales", _synthetic_sales(), time_column="ts")
    server = SqlServer(ctx, port=0).start()
    url = f"http://127.0.0.1:{server.port}"
    for q in WLM_INTERACTIVE + WLM_HEAVY:    # compile/warm both shapes
        post_sql(url, q, timeout=300)

    def post_lane(sql, lane):
        req = urllib.request.Request(
            url + "/sql",
            data=json.dumps({"sql": sql, "lane": lane}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            return json.loads(r.read().decode())

    # 4x overload on the interactive lane + a heavy-scan backlog
    n_int, n_heavy = 4 * int_slots, 6
    duration = args.duration
    legs = {}
    for leg, enabled in (("wlm_off", False), ("wlm_on", True)):
        ctx.config.set("sdot.wlm.enabled", enabled)
        lat = {"interactive": [], "heavy": []}
        shed = {"interactive": 0, "heavy": 0}
        errors = [0]
        lock = threading.Lock()
        stop = time.monotonic() + duration

        def worker(tid, cls, queries, lane):
            i = tid                            # deterministic round-robin
            while time.monotonic() < stop:
                sql = queries[i % len(queries)]
                i += 1
                t0 = time.perf_counter()
                try:
                    post_lane(sql, lane)
                except urllib.error.HTTPError as e:
                    if e.code == 429:
                        retry = min(
                            float(e.headers.get("Retry-After") or 1), 0.25)
                        with lock:
                            shed[cls] += 1
                        time.sleep(retry)      # honor the hint (bounded)
                        continue
                    with lock:
                        errors[0] += 1
                    continue
                except Exception:   # noqa: BLE001
                    with lock:
                        errors[0] += 1
                    continue
                with lock:
                    lat[cls].append((time.perf_counter() - t0) * 1000)

        threads = [threading.Thread(
            target=worker, args=(t, "interactive", WLM_INTERACTIVE,
                                 "interactive"), daemon=True)
            for t in range(n_int)]
        threads += [threading.Thread(
            target=worker, args=(t, "heavy", WLM_HEAVY, "batch"),
            daemon=True) for t in range(n_heavy)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        leg_out = {"errors": errors[0]}
        for cls in ("interactive", "heavy"):
            a = np.array(lat[cls]) if lat[cls] else np.array([0.0])
            served = len(lat[cls])
            leg_out[cls] = {
                "n": served, "shed": shed[cls],
                "shed_rate": round(shed[cls] / max(served + shed[cls], 1),
                                   4),
                "p50_ms": round(float(np.percentile(a, 50)), 1),
                "p99_ms": round(float(np.percentile(a, 99)), 1)}
            print(f"  [{leg}] {cls:11s} p50={leg_out[cls]['p50_ms']:7.1f}ms"
                  f" p99={leg_out[cls]['p99_ms']:7.1f}ms n={served:5d}"
                  f" shed={shed[cls]}")
        legs[leg] = leg_out
    wlm_meta = get_json(url, "/metadata/wlm")
    server.stop()
    caps_held = all(ln["max_active_seen"] <= ln["slots"]
                    for ln in wlm_meta["lanes"])
    p99_off = legs["wlm_off"]["interactive"]["p99_ms"]
    p99_on = legs["wlm_on"]["interactive"]["p99_ms"]
    out = {"mode": "wlm", "overload": 4, "threads_interactive": n_int,
           "threads_heavy": n_heavy, "duration_s": duration,
           "legs": legs, "caps_held": caps_held,
           "interactive_p99_improvement":
               round(p99_off / max(p99_on, 1e-9), 2)}
    print(json.dumps(out))
    ok = caps_held and p99_on < p99_off \
        and legs["wlm_on"]["interactive"]["n"] > 0
    sys.exit(0 if ok else 1)


def _phase_deltas(ctx, mark):
    """Mean per-phase host milliseconds over the history entries recorded
    after ``mark`` (the last record before the leg started). History is a
    bounded deque, so a long storm covers the most recent <= maxlen
    queries of the leg — a representative per-query profile, not a total.
    Phase timers are inclusive (parents contain children): read rows
    individually, don't sum them."""
    sums, counts = {}, {}
    for rec in reversed(ctx.history.entries()):
        if rec is mark:
            break
        ph = rec.stats.get("phases") if isinstance(rec.stats, dict) else None
        if not isinstance(ph, dict):
            continue
        for k, v in ph.items():
            sums[k] = sums.get(k, 0.0) + float(v)
            counts[k] = counts.get(k, 0) + 1
    return {k: round(sums[k] / counts[k], 3) for k in sorted(sums)}


def _print_phase_deltas(tag, ph):
    if ph:
        print(f"  [{tag}] phases (mean ms/query): "
              + " ".join(f"{k}={v}" for k, v in ph.items()))


def run_sharedscan(args):
    """Shared-scan comparison: K client threads replay a fixed BI
    dashboard mix over one TPC-H star (in process, caches off so every
    rep executes), across four legs — coalescing off, coalesced unfused,
    fused (jaxpr), and fused through the hand-scheduled pallas wave
    kernel (where the backend supports it). Reports qps and p50/p99 per
    leg, the coalescing rate, device-dispatch totals, and wave-kernel
    launches; every reply is checked against the sequential reference
    answers and any mismatch exit-codes 1 (answers must be identical
    whichever path served the scan)."""
    sys.path.insert(0, ".")
    import bench
    sf = args.tpch if args.tpch is not None else 1.0
    ctx, n_rows = bench.setup(sf)
    window_ms = float(args.window if args.window is not None else 8.0)
    ctx.config.set("sdot.wlm.batch.window.ms", window_ms)
    queries = args.sql or TPCH_DASHBOARD

    # sequential reference (coalescing off): warm/compile, then answers
    ctx.config.set("sdot.sharedscan.enabled", False)
    answers = {}
    for q in queries:
        ctx.sql(q)                         # compile/warm rep
        answers[q] = ctx.sql(q).to_pandas()

    legs, mismatched = {}, []
    # four legs: coalescing off, coalesced but UNFUSED (fusion planner
    # disabled — the pre-fusion per-lane-re-eval program), fully fused
    # on the jaxpr path, and fused + hand-scheduled pallas wave kernel.
    # All are differentially checked against the sequential reference,
    # so "pallas == fused == pre-fusion fused == solo" is enforced
    # byte-for-byte on every reply. The pallas leg only runs where the
    # wave can engage (TPU backend, or SDOT_PALLAS=interpret on CPU).
    from spark_druid_olap_tpu.ops import pallas_groupby as _PG
    wave_available = (os.environ.get("SDOT_PALLAS", "") == "interpret"
                      or _PG._tpu_backend())
    leg_plan = [("sharedscan_off", False, True, False),
                ("sharedscan_on_nofusion", True, False, False),
                ("sharedscan_on", True, True, False)]
    if wave_available:
        leg_plan.append(("sharedscan_on_pallas", True, True, True))
    else:
        print("  [sharedscan_on_pallas] skipped: wave kernel unavailable "
              "on this backend (set SDOT_PALLAS=interpret to run it on "
              "CPU)")
    for leg, enabled, fused, wave in leg_plan:
        ctx.config.set("sdot.sharedscan.enabled", enabled)
        ctx.config.set("sdot.sharedscan.fusion.enabled", fused)
        ctx.config.set("sdot.pallas.wave.enabled", wave)
        coal0 = dict(ctx.engine.sharedscan.stats())
        ph_mark = (ctx.history.entries() or [None])[-1]
        lat, errors, dispatches = [], [0], [0]
        lock = threading.Lock()
        stop = time.monotonic() + args.duration

        def worker(tid):
            # dispatch_counts is thread-local and monotone: the diff is
            # exactly this client's device round trips for the leg
            d0 = ctx.engine.dispatch_counts[0]
            i = tid                        # deterministic round-robin
            my_lat, my_bad = [], []
            while time.monotonic() < stop:
                sql = queries[i % len(queries)]
                i += 1
                t0 = time.perf_counter()
                try:
                    df = ctx.sql(sql).to_pandas()
                except Exception:   # noqa: BLE001
                    with lock:
                        errors[0] += 1
                    continue
                my_lat.append((time.perf_counter() - t0) * 1000)
                if not _frames_close(df, answers[sql]):
                    my_bad.append(sql)
            dd = ctx.engine.dispatch_counts[0] - d0
            with lock:
                lat.extend(my_lat)
                dispatches[0] += dd
                mismatched.extend(f"[{leg}] {s[:70]}" for s in set(my_bad))

        threads = [threading.Thread(target=worker, args=(t,), daemon=True)
                   for t in range(args.threads)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.monotonic() - t0
        coal1 = dict(ctx.engine.sharedscan.stats())
        served = len(lat)
        a = np.array(lat) if lat else np.array([0.0])
        coalesced = coal1["queries_coalesced"] - coal0["queries_coalesced"]
        legs[leg] = {
            "n": served, "errors": errors[0],
            "qps": round(served / max(elapsed, 1e-9), 1),
            "p50_ms": round(float(np.percentile(a, 50)), 1),
            "p99_ms": round(float(np.percentile(a, 99)), 1),
            "dispatches": dispatches[0],
            "queries_coalesced": coalesced,
            "coalesce_rate": round(coalesced / max(served, 1), 4),
            "groups": coal1["groups_coalesced"] - coal0["groups_coalesced"],
            "binds_saved_bytes": (coal1["binds_saved_bytes"]
                                  - coal0["binds_saved_bytes"]),
            "dispatches_saved": (coal1["dispatches_saved"]
                                 - coal0["dispatches_saved"])}
        f0, f1 = coal0["fusion"], coal1["fusion"]
        evals = f1["predicate_evals_total"] - f0["predicate_evals_total"] \
            + f1["solo_evals_total"] - f0["solo_evals_total"]
        saved = f1["predicate_evals_saved"] - f0["predicate_evals_saved"] \
            + f1["solo_evals_saved"] - f0["solo_evals_saved"]
        legs[leg]["fusion"] = {
            "shared_predicates": (f1["shared_predicates"]
                                  - f0["shared_predicates"]),
            "predicate_evals_saved": (f1["predicate_evals_saved"]
                                      - f0["predicate_evals_saved"]),
            "column_streams_saved": (f1["column_streams_saved"]
                                     - f0["column_streams_saved"]),
            "plan_fallbacks": f1["plan_fallbacks"] - f0["plan_fallbacks"],
            "cse_hit_rate": round(saved / evals, 4) if evals else 0.0}
        p0, p1 = coal0.get("pallas") or {}, coal1.get("pallas") or {}
        legs[leg]["pallas"] = {
            k: int(p1.get(k, 0)) - int(p0.get(k, 0))
            for k in ("launches", "tiles", "fallbacks")}
        legs[leg]["phases_ms"] = _phase_deltas(ctx, ph_mark)
        _print_phase_deltas(leg, legs[leg]["phases_ms"])
        print(f"  [{leg}] qps={legs[leg]['qps']:7.1f} "
              f"p50={legs[leg]['p50_ms']:7.1f}ms "
              f"p99={legs[leg]['p99_ms']:7.1f}ms n={served:5d} "
              f"dispatches={dispatches[0]} "
              f"coalesce_rate={legs[leg]['coalesce_rate']:.1%} "
              f"cse_hit_rate={legs[leg]['fusion']['cse_hit_rate']:.1%} "
              f"evals_saved={saved}")

    on, off = legs["sharedscan_on"], legs["sharedscan_off"]
    fus = on["fusion"]
    qps_x = on["qps"] / max(off["qps"], 1e-9)
    disp_per_q_off = off["dispatches"] / max(off["n"], 1)
    disp_per_q_on = on["dispatches"] / max(on["n"], 1)
    disp_x = disp_per_q_off / max(disp_per_q_on, 1e-9)
    pal = legs.get("sharedscan_on_pallas")
    pal_note = ""
    if pal is not None:
        pal_note = (f"; pallas leg: p50={pal['p50_ms']:.1f}ms "
                    f"launches={pal['pallas']['launches']} "
                    f"fallbacks={pal['pallas']['fallbacks']}")
    print(f"  qps speedup {qps_x:.2f}x; dispatches/query "
          f"{disp_per_q_off:.2f} -> {disp_per_q_on:.2f} ({disp_x:.2f}x "
          f"fewer); fusion: cse_hit_rate={fus['cse_hit_rate']:.1%} "
          f"evals_saved={fus['predicate_evals_saved']} "
          f"col_streams_saved={fus['column_streams_saved']}" + pal_note
          + (f"; RESULT MISMATCH on {sorted(set(mismatched))}"
             if mismatched else ""))
    out = {"mode": "sharedscan", "sf": sf, "rows": n_rows,
           "threads": args.threads, "duration_s": args.duration,
           "window_ms": window_ms, "legs": legs,
           "pallas_available": bool(wave_available),
           "qps_speedup": round(qps_x, 2),
           "dispatch_reduction": round(disp_x, 2),
           "result_mismatches": sorted(set(mismatched))}
    print(json.dumps(out))
    # the fused leg must additionally have planned real cross-lane CSE:
    # shared predicates lowered once and union columns streamed once
    ok = not mismatched and on["n"] > 0 and off["n"] > 0 \
        and legs["sharedscan_on_nofusion"]["n"] > 0 \
        and on["queries_coalesced"] > 0 \
        and fus["predicate_evals_saved"] > 0 \
        and fus["column_streams_saved"] > 0 \
        and on["pallas"]["launches"] == 0
    if pal is not None:
        # when the wave can engage, the pallas leg must have served
        # traffic THROUGH the kernel (launches > 0, differentially
        # checked above like every other leg)
        ok = ok and pal["n"] > 0 and pal["pallas"]["launches"] > 0
    sys.exit(0 if ok else 1)


def run_mesh(args):
    """Multi-chip mesh differential + scaling leg (parallel/meshexec.py).

    In-process: ingest a TPC-H flat subset with mesh-sized segments,
    capture sequential single-device answers, then replay concurrent
    fused storms through (a) a single-device engine and (b) an engine
    sharding fused waves across every local device. Every reply is
    checked against the reference — any mismatch exit-codes 1 — and the
    summary reports the wall scaling ratio plus the merge-collective
    counters (collective_bytes, mesh dispatches/groups, fallback
    tallies, and the partial-buffer ledger gauge, which must drain to
    zero). With --cluster N an additional leg spawns N historical
    subprocesses on an 8-device emulated mesh with ``sdot.mesh.auto``
    on, storms the mix through an in-process broker, checks every
    broker answer against a single-process engine, and reports per-node
    mesh counters polled from /metadata/sharedscan."""
    import threading

    sys.path.insert(0, ".")
    import jax
    import spark_druid_olap_tpu as sdot
    from spark_druid_olap_tpu.ir import spec as S
    from spark_druid_olap_tpu.parallel.executor import QueryEngine
    from spark_druid_olap_tpu.parallel.mesh import make_mesh, mesh_size
    from spark_druid_olap_tpu.tools import tpch
    from spark_druid_olap_tpu.utils.config import Config

    n_dev = len(jax.devices())
    if n_dev < 2:
        print("[mesh] single-device process; set XLA_FLAGS="
              "--xla_force_host_platform_device_count=8 to emulate a mesh")
        sys.exit(1)

    sf = args.tpch if args.tpch is not None else 0.01
    ctx = sdot.Context()
    tpch.setup_context(ctx, sf=sf, target_rows=2048, flat_only=True)
    store = ctx.store
    n_rows = store.get("tpch_flat").num_rows
    window_ms = float(args.window if args.window is not None else 60.0)

    aggs = (S.AggregationSpec("doublesum", "rev", field="l_extendedprice"),
            S.AggregationSpec("longsum", "q", field="l_quantity"),
            S.AggregationSpec("count", "n"),
            S.AggregationSpec("doublemin", "mn", field="l_discount"),
            S.AggregationSpec("doublemax", "mx", field="l_extendedprice"),
            S.AggregationSpec("cardinality", "uo", field="l_orderkey"),
            S.AggregationSpec("thetasketch", "sk", field="l_suppkey"))
    specs = [
        S.GroupByQuerySpec(
            "tpch_flat",
            (S.DimensionSpec("l_returnflag", "l_returnflag"),
             S.DimensionSpec("l_linestatus", "l_linestatus")), aggs),
        S.GroupByQuerySpec(
            "tpch_flat", (S.DimensionSpec("l_shipmode", "l_shipmode"),),
            aggs, filter=S.SelectorFilter("l_returnflag", "N")),
        S.TimeseriesQuerySpec("tpch_flat", aggs,
                              granularity=S.Granularity("month")),
    ]

    def engine(mesh):
        return QueryEngine(store, config=Config({
            "sdot.sharedscan.enabled": True,
            "sdot.wlm.batch.window.ms": window_ms,
            "sdot.wlm.enabled": False,
            "sdot.querycostmodel.enabled": False,
        }), mesh=mesh)

    def run_batch(eng):
        res = [None] * len(specs)
        errs = [None] * len(specs)
        bar = threading.Barrier(len(specs))

        def worker(i):
            bar.wait()
            try:
                res[i] = eng.execute(specs[i]).to_pandas()
            except Exception as e:      # noqa: BLE001 — surfaced below
                errs[i] = e

        th = [threading.Thread(target=worker, args=(i,))
              for i in range(len(specs))]
        for t in th:
            t.start()
        for t in th:
            t.join()
        for e in errs:
            if e is not None:
                raise e
        return res

    ref = [QueryEngine(store).execute(q).to_pandas() for q in specs]
    mismatched = []

    def leg(name, eng):
        run_batch(eng)                  # warm: compile this leg's program
        walls, stop = [], time.monotonic() + max(args.duration, 3.0)
        while time.monotonic() < stop:
            t0 = time.perf_counter()
            frames = run_batch(eng)
            walls.append((time.perf_counter() - t0) * 1000)
            for i, (got, want) in enumerate(zip(frames, ref)):
                if not _frames_close(got, want):
                    mismatched.append(f"[{name}] spec {i}")
        mst = eng.sharedscan.stats()["mesh"]
        out = {"batches": len(walls),
               "p50_ms": round(float(np.percentile(walls, 50)), 2),
               "devices": mst["devices"],
               "mesh_groups": mst["groups"],
               "mesh_dispatches": mst["dispatches"],
               "collective_bytes": mst["collective_bytes"],
               "fallbacks": dict(mst["fallbacks"]),
               "partials_outstanding":
                   mst["partials"]["outstanding_bytes"]}
        print(f"  [{name}] p50={out['p50_ms']:7.2f}ms "
              f"batches={out['batches']} devices={out['devices']} "
              f"collective={out['collective_bytes']}B "
              f"dispatches={out['mesh_dispatches']}")
        return out

    print(f"[mesh] {n_rows} rows, "
          f"{store.get('tpch_flat').num_segments} segments, "
          f"{n_dev} devices")
    single = leg("single-device", engine(None))
    mesh = leg(f"mesh-{n_dev}dev", engine(make_mesh()))
    scaling = single["p50_ms"] / max(mesh["p50_ms"], 1e-9)
    out = {"mode": "mesh", "sf": sf, "rows": int(n_rows),
           "devices": n_dev, "window_ms": window_ms,
           "single": single, "mesh": mesh,
           "scaling_ratio": round(scaling, 3),
           "result_mismatches": sorted(set(mismatched))}
    print(f"  scaling {scaling:.2f}x at {n_dev} devices "
          f"(emulated meshes measure host-core contention, not ICI); "
          f"collective {mesh['collective_bytes']}B over "
          f"{mesh['mesh_dispatches']} mesh dispatches"
          + (f"; RESULT MISMATCH {sorted(set(mismatched))}"
             if mismatched else ""))

    ok = not mismatched and mesh["mesh_groups"] > 0 \
        and mesh["collective_bytes"] > 0 \
        and mesh["partials_outstanding"] == 0 \
        and single["mesh_dispatches"] == 0

    if args.cluster:
        cl = _run_mesh_cluster(args)
        out["cluster"] = cl
        ok = ok and cl["ok"]
    print(json.dumps(out))
    sys.exit(0 if ok else 1)


def _run_mesh_cluster(args):
    """--mesh --cluster N: N historical subprocesses, each on an 8-device
    emulated mesh with sdot.mesh.auto on, differentially checked through
    an in-process broker against a single-process engine."""
    import shutil
    import tempfile
    import threading

    import spark_druid_olap_tpu as sdot

    n_nodes = args.cluster
    window_ms = args.window if args.window is not None else 25.0
    root = tempfile.mkdtemp(prefix="sdot-mesh-cluster-")
    caches_off = {"sdot.cache.enabled": False,
                  "sdot.plan.cache.enabled": False,
                  "sdot.cluster.subq.cache.enabled": False}
    procs, broker, single = [], None, None
    try:
        seed = sdot.Context({"sdot.persist.path": root})
        seed.ingest_dataframe("sales", _synthetic_sales(400_000),
                              time_column="ts", target_rows=4096)
        seed.checkpoint()
        seed.close()

        import subprocess
        ports = [_free_port() for _ in range(n_nodes)]
        nodes = ",".join(f"127.0.0.1:{p}" for p in ports)
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
        for i in range(n_nodes):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "spark_druid_olap_tpu.cluster",
                 "historical", "--persist", root, "--nodes", nodes,
                 "--node-id", str(i),
                 "--set", "sdot.mesh.auto=true",
                 "--set", "sdot.cache.enabled=false",
                 "--set", "sdot.plan.cache.enabled=false",
                 "--set", "sdot.querycostmodel.enabled=false",
                 "--set", "sdot.sharedscan.enabled=true",
                 "--set", f"sdot.wlm.batch.window.ms={window_ms}"],
                env=env, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL))
        print(f"[mesh-cluster] waiting for {n_nodes} meshed historicals...")
        for p, proc in zip(ports, procs):
            _wait_ready(p, proc=proc)

        broker = sdot.Context({
            "sdot.persist.path": root, "sdot.cluster.nodes": nodes,
            "sdot.cluster.role": "broker", **caches_off})
        single = sdot.Context({"sdot.persist.path": root, **caches_off})
        queries = args.sql or DEFAULT_QUERIES
        answers = {q: single.sql(q).to_pandas() for q in queries}

        mismatched = []
        lock = threading.Lock()
        stop = time.monotonic() + max(args.duration, 5.0)

        def worker(tid):
            i = tid
            while time.monotonic() < stop:
                q = queries[i % len(queries)]
                i += 1
                try:
                    df = broker.sql(q).to_pandas()
                except Exception as e:   # noqa: BLE001 — gate below
                    with lock:
                        mismatched.append(f"error {type(e).__name__}: {q}")
                    continue
                if not _frames_close(df, answers[q]):
                    with lock:
                        mismatched.append(q)

        threads = [threading.Thread(target=worker, args=(t,), daemon=True)
                   for t in range(args.threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        node_mesh = []
        for p in ports:
            try:
                st = get_json(f"http://127.0.0.1:{p}", "/metadata/sharedscan")
                node_mesh.append(st.get("mesh", {}))
            except Exception as e:   # noqa: BLE001 — reported below
                node_mesh.append({"error": str(e)})
        meshed_nodes = sum(1 for m in node_mesh
                           if int(m.get("devices", 1)) > 1)
        print(f"[mesh-cluster] mismatches={len(mismatched)} "
              f"meshed_nodes={meshed_nodes}/{n_nodes} per-node mesh: "
              f"{json.dumps(node_mesh)}")
        # the gate: exact answers through meshed historicals, and every
        # node actually built its 8-device mesh (fused-group collective
        # traffic depends on storm timing; solo subqueries shard via the
        # executor's own route, so per-node counters are reported, not
        # pinned)
        ok = not mismatched and meshed_nodes == n_nodes
        return {"ok": bool(ok), "nodes": n_nodes,
                "meshed_nodes": meshed_nodes,
                "mismatches": sorted(set(mismatched))[:10],
                "node_mesh": node_mesh}
    finally:
        for proc in procs:
            try:
                proc.kill()
            except Exception:   # noqa: BLE001 — already dead
                pass
        for c in (broker, single):
            if c is not None:
                try:
                    c.close()
                except Exception:   # noqa: BLE001 — shutdown race
                    pass
        shutil.rmtree(root, ignore_errors=True)


def _join_tables(n=60_000):
    """Synthetic star-unservable join set: two fact tables sharing an
    order key, plus a small banding table for the non-equi residual."""
    import pandas as pd
    rng = np.random.default_rng(18)
    regions = ["na", "emea", "apac", "latam"]
    orders = pd.DataFrame({
        "ts": (np.datetime64("2024-03-01")
               + rng.integers(0, 90, n).astype("timedelta64[D]")
               ).astype("datetime64[ns]"),
        "order_id": np.arange(n, dtype=np.int64),
        # ~5 orders per user keeps the self-join's widest build group
        # far under the default sdot.join.max.matches budget
        "user_id": rng.integers(0, max(n // 5, 1), n).astype(np.int64),
        "region": rng.choice(regions, n),
        "channel": rng.choice(["web", "app", "store"], n),
        "amount": rng.normal(80, 30, n).round(2),
    })
    m = n // 3
    shipments = pd.DataFrame({
        "ts": (np.datetime64("2024-03-02")
               + rng.integers(0, 90, m).astype("timedelta64[D]")
               ).astype("datetime64[ns]"),
        "order_id": rng.integers(0, n, m).astype(np.int64),
        "carrier": rng.choice(["ups", "dhl", "fedex", "ems"], m),
        "weight": rng.normal(4.0, 1.5, m).round(3),
    })
    bands = list(zip([-1e9, 25.0, 50.0, 75.0, 100.0, 150.0],
                     [25.0, 50.0, 75.0, 100.0, 150.0, 1e9]))
    rates = pd.DataFrame([
        {"ts": pd.Timestamp("2024-03-01"), "region": rg,
         "band": "b%d" % i, "lo": lo, "hi": hi}
        for rg in regions for i, (lo, hi) in enumerate(bands)])
    return {"orders": orders, "shipments": shipments, "rates": rates}


# star-unservable shapes: fact-to-fact, self-join funnel, equi + non-equi
# range residual — none of these has a star edge the planner can collapse
JOIN_QUERIES = [
    """SELECT s.carrier AS c, count(*) AS n, sum(o.amount) AS amt
       FROM orders o JOIN shipments s ON o.order_id = s.order_id
       GROUP BY s.carrier ORDER BY c""",
    """SELECT a.channel AS c, count(*) AS n
       FROM orders a JOIN orders b
         ON a.user_id = b.user_id AND a.amount < b.amount
       GROUP BY a.channel ORDER BY c""",
    """SELECT r.band AS b, count(*) AS n, sum(o.amount) AS amt
       FROM orders o JOIN rates r
         ON o.region = r.region
        AND o.amount >= r.lo AND o.amount < r.hi
       GROUP BY r.band ORDER BY b""",
]


def _ingest_join_tables(ctx, n):
    tables = _join_tables(n)
    ctx.ingest_dataframe("orders", tables["orders"], time_column="ts",
                         target_rows=2048)
    ctx.ingest_dataframe("shipments", tables["shipments"],
                         time_column="ts", target_rows=1024)
    ctx.ingest_dataframe("rates", tables["rates"], time_column="ts",
                         target_rows=64)


def _storm_joins(ctx, queries, refs, n_threads, duration, tag):
    """Round-robin the join mix through ``ctx`` with ``n_threads``
    workers; every reply is differentially checked against ``refs`` and
    must have engaged a join tier (``last_stats["join"]`` is per-thread,
    so each worker audits its own statements). Returns (replies,
    mismatches, per-mode tallies, statement shuffle-bytes total)."""
    lock = threading.Lock()
    mismatched, modes = [], defaultdict(int)
    replies = [0]
    shuffle = [0]
    stop = time.monotonic() + max(duration, 5.0)

    def worker(tid):
        i = tid
        while time.monotonic() < stop:
            q = queries[i % len(queries)]
            i += 1
            try:
                df = ctx.sql(q).to_pandas()
                js = ctx.engine.last_stats.get("join")
            except Exception as e:   # noqa: BLE001 — gate below
                with lock:
                    mismatched.append(
                        f"[{tag}] error {type(e).__name__}: {q[:60]}")
                continue
            ok = _frames_close(df, refs[q])
            with lock:
                replies[0] += 1
                if not ok:
                    mismatched.append(f"[{tag}] {q[:60]}")
                if js is None:
                    # a silent host fallback answers correctly but
                    # load-tests nothing — count it as a failure
                    mismatched.append(f"[{tag}] no join tier: {q[:60]}")
                else:
                    modes[js["mode"]] += 1
                    shuffle[0] += int(js.get("shuffle_bytes", 0))

    threads = [threading.Thread(target=worker, args=(t,), daemon=True)
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return replies[0], mismatched, dict(modes), shuffle[0]


def run_joins(args):
    """--joins: device join-tier differential under storm (join/).

    In-process: ingest a synthetic orders/shipments/rates set, capture
    host-tier reference answers (``sdot.join.enabled`` off — the config
    fingerprint keys every cache, so both tiers execute for real), then
    storm the star-unservable join mix — fact-to-fact, self-join
    funnel, equi + non-equi range — through the broadcast tier with
    --threads workers. Every reply is checked against the host
    reference AND must have engaged a join tier (a silent host fallback
    would pass the differential while load-testing nothing). With
    --cluster N an additional leg runs N in-process historicals behind
    a broker forced to ``sdot.join.mode=partitioned``, re-checks every
    reply, and reports the per-leg shuffle-bytes / scatter counters
    (deltas of the broker's join_shuffle_bytes / join_scatters). Exit 1
    on any differential mismatch or missed tier engagement."""
    sys.path.insert(0, ".")
    import spark_druid_olap_tpu as sdot
    from spark_druid_olap_tpu.utils.config import JOIN_ENABLED

    n_rows = int(os.environ.get("SDOT_LOADTEST_JOIN_ROWS", "60000"))
    ctx = sdot.Context()
    try:
        _ingest_join_tables(ctx, n_rows)
        ctx.config.set(JOIN_ENABLED.key, False)
        try:
            refs = {q: ctx.sql(q).to_pandas() for q in JOIN_QUERIES}
        finally:
            ctx.config.set(JOIN_ENABLED.key, True)
        for q in JOIN_QUERIES:      # warm: compile each join program
            ctx.sql(q)
        print(f"[joins] {n_rows} order rows, {len(JOIN_QUERIES)} "
              f"star-unservable queries, {args.threads} threads")
        replies, mismatched, modes, stmt_shuffle = _storm_joins(
            ctx, JOIN_QUERIES, refs, args.threads, args.duration,
            "broadcast")
    finally:
        ctx.close()
    single = {"replies": replies, "modes": modes,
              "shuffle_bytes": stmt_shuffle,
              "mismatches": sorted(set(mismatched))[:10]}
    print(f"  [broadcast] replies={replies} modes={json.dumps(modes)} "
          f"shuffle={stmt_shuffle}B mismatches={len(mismatched)}")
    ok = replies > 0 and not mismatched \
        and modes.get("broadcast", 0) == replies \
        and stmt_shuffle == 0           # broadcast moves no wire bytes

    out = {"mode": "joins", "rows": n_rows, "threads": args.threads,
           "single": single}
    if args.cluster:
        cl = _run_joins_cluster(args, n_rows)
        out["cluster"] = cl
        ok = ok and cl["ok"]
    print(json.dumps(out))
    sys.exit(0 if ok else 1)


def _run_joins_cluster(args, n_rows):
    """--joins --cluster N: the same join mix through a broker forced to
    the partitioned tier over N in-process historicals, with per-leg
    shuffle-bytes accounting from the broker's lifetime counters."""
    import shutil
    import tempfile

    import spark_druid_olap_tpu as sdot
    from spark_druid_olap_tpu.cluster.historical import HistoricalNode
    from spark_druid_olap_tpu.utils.config import JOIN_ENABLED

    root = tempfile.mkdtemp(prefix="sdot-join-cluster-")
    caches_off = {"sdot.cache.enabled": False,
                  "sdot.plan.cache.enabled": False,
                  "sdot.cluster.subq.cache.enabled": False}
    hist, broker, single = [], None, None
    try:
        seed = sdot.Context({"sdot.persist.path": root})
        _ingest_join_tables(seed, n_rows)
        seed.checkpoint()
        seed.close()

        ports = [_free_port() for _ in range(args.cluster)]
        nodes = ",".join(f"127.0.0.1:{p}" for p in ports)
        common = {"sdot.persist.path": root, "sdot.cluster.nodes": nodes}
        hist = [HistoricalNode(dict(common), node_id=i).start()
                for i in range(args.cluster)]
        broker = sdot.Context({**common, "sdot.cluster.role": "broker",
                               "sdot.join.mode": "partitioned",
                               **caches_off})
        single = sdot.Context({"sdot.persist.path": root, **caches_off,
                               "sdot.join.enabled": False})
        refs = {q: single.sql(q).to_pandas() for q in JOIN_QUERIES}
        for q in JOIN_QUERIES:      # warm the exchange path
            broker.sql(q)

        with broker.cluster._lock:
            before = dict(broker.cluster.counters)
        replies, mismatched, modes, stmt_shuffle = _storm_joins(
            broker, JOIN_QUERIES, refs, args.threads, args.duration,
            "partitioned")
        with broker.cluster._lock:
            after = dict(broker.cluster.counters)
        d_shuffle = (after.get("join_shuffle_bytes", 0)
                     - before.get("join_shuffle_bytes", 0))
        d_scatters = (after.get("join_scatters", 0)
                      - before.get("join_scatters", 0))
        print(f"  [partitioned] replies={replies} "
              f"modes={json.dumps(modes)} stmt_shuffle={stmt_shuffle}B "
              f"leg_shuffle={d_shuffle}B scatters={d_scatters} "
              f"mismatches={len(mismatched)}")
        # the gate: exact answers through the exchange, every reply on
        # the partitioned tier, and the broker's lifetime counters moved
        # by at least the per-statement accounting (they also cover
        # retried scatters, so >= rather than ==)
        ok = replies > 0 and not mismatched \
            and modes.get("partitioned", 0) == replies \
            and stmt_shuffle > 0 and d_shuffle >= stmt_shuffle \
            and d_scatters > 0
        return {"ok": bool(ok), "nodes": args.cluster,
                "replies": replies, "modes": modes,
                "shuffle_bytes": stmt_shuffle,
                "leg_shuffle_bytes": int(d_shuffle),
                "leg_scatters": int(d_scatters),
                "mismatches": sorted(set(mismatched))[:10]}
    finally:
        for h in hist:
            try:
                h.stop()
            except Exception:   # noqa: BLE001 — already stopped
                pass
        for c in (broker, single):
            if c is not None:
                try:
                    c.close()
                except Exception:   # noqa: BLE001 — shutdown race
                    pass
        shutil.rmtree(root, ignore_errors=True)


def _window_sales(n=60_000):
    """Synthetic sales frame for the window storm. The ``id`` column is
    a UNIQUE order key: moving-frame answers are order-dependent, so a
    tied ORDER BY would make the differential ambiguous."""
    import pandas as pd
    rng = np.random.default_rng(23)
    return pd.DataFrame({
        "ts": (np.datetime64("2015-01-01")
               + rng.integers(0, 365 * 24 * 3600, n).astype(
                   "timedelta64[s]")).astype("datetime64[ns]"),
        "id": np.arange(n, dtype=np.int64),
        "region": rng.choice(["east", "west", "north", "south"], n),
        "product": rng.choice([f"p{i:03d}" for i in range(20)], n),
        "qty": rng.integers(1, 52, n).astype(np.int64),
        "price": rng.uniform(1.0, 100.0, n),
    })


# ranks over a GROUP BY base, moving/cumulative frames and lag over a
# row-level scan base — every tier the window post-pass composes with
WINDOW_QUERIES = [
    "SELECT region, product, SUM(qty) AS units, "
    "RANK() OVER (PARTITION BY region ORDER BY SUM(qty) DESC) AS r "
    "FROM wsales GROUP BY region, product",
    "SELECT id, region, qty, SUM(qty) OVER (PARTITION BY region "
    "ORDER BY id ROWS BETWEEN 3 PRECEDING AND CURRENT ROW) AS mv "
    "FROM wsales WHERE qty > 25",
    "SELECT id, region, price, LAG(price, 1) OVER "
    "(PARTITION BY region ORDER BY id) AS prev "
    "FROM wsales WHERE id < 2000",
    "SELECT id, region, AVG(price) OVER (PARTITION BY region "
    "ORDER BY id) AS cavg, ROW_NUMBER() OVER "
    "(PARTITION BY region ORDER BY id) AS rn "
    "FROM wsales WHERE id < 2000",
]
PCT_FRACTIONS = (0.5, 0.9, 0.99)


def _pct_sql(q):
    return (f"SELECT region, PERCENTILE_APPROX(price, {q}) AS p "
            f"FROM wsales GROUP BY region")


def _window_refs(df):
    """Exact pandas references for WINDOW_QUERIES (same order), plus
    per-region sorted price arrays for the percentile rank-error gate."""
    agg = (df.groupby(["region", "product"], as_index=False)
             .agg(units=("qty", "sum")))
    agg["r"] = (agg.groupby("region")["units"]
                .rank(method="min", ascending=False).astype(np.int64))
    flt = df[df["qty"] > 25].sort_values(["region", "id"],
                                         kind="mergesort")
    mv = flt[["id", "region", "qty"]].copy()
    mv["mv"] = (flt.groupby("region")["qty"]
                .rolling(4, min_periods=1).sum()
                .reset_index(level=0, drop=True)).astype(np.int64)
    head = df[df["id"] < 2000].sort_values(["region", "id"],
                                           kind="mergesort")
    lg = head[["id", "region", "price"]].copy()
    lg["prev"] = head.groupby("region")["price"].shift(1)
    cum = head[["id", "region"]].copy()
    cum["cavg"] = (head.groupby("region")["price"]
                   .expanding().mean().reset_index(level=0, drop=True))
    cum["rn"] = (head.groupby("region").cumcount() + 1).astype(np.int64)
    refs = dict(zip(WINDOW_QUERIES,
                    [f.reset_index(drop=True)
                     for f in (agg, mv, lg, cum)]))
    exact = {rg: np.sort(df.loc[df["region"] == rg, "price"].to_numpy())
             for rg in df["region"].unique()}
    return refs, exact


def _pct_failures(got, exact, q, eps):
    """Rank-error gate: each per-region estimate must land between the
    exact order statistics at rank (q - eps) and (q + eps)."""
    fails = []
    for _, row in got.iterrows():
        vals = exact[row["region"]]
        lo = vals[max(int(np.floor((q - eps) * len(vals))), 0)]
        hi = vals[min(int(np.ceil((q + eps) * len(vals))),
                      len(vals) - 1)]
        if not (lo <= float(row["p"]) <= hi):
            fails.append(f"{row['region']}@q{q}: {row['p']:.4f} outside "
                         f"[{lo:.4f}, {hi:.4f}]")
    return fails


def _storm_windows(ctx, refs, exact, eps, n_threads, duration, tag,
                   pct_refs=None, expect_scatter=False):
    """Round-robin the window + percentile mix through ``ctx``. Window
    replies are differentially checked against the exact pandas
    reference; percentile replies against the sketch's rank-error bound
    (and, when ``pct_refs`` carries the single-engine answers, required
    BYTE-IDENTICAL to them — the broker's register merge must not
    change the estimate). With ``expect_scatter`` every reply must have
    fanned out (engine.last_stats is per-thread, so each worker audits
    its own statements). Returns (replies, failures)."""
    lock = threading.Lock()
    failures, replies = [], [0]
    pcts = [(_pct_sql(q), q) for q in PCT_FRACTIONS]
    mix = [(sql, None) for sql in WINDOW_QUERIES] + pcts
    stop = time.monotonic() + max(duration, 5.0)

    def worker(tid):
        i = tid
        while time.monotonic() < stop:
            sql, frac = mix[i % len(mix)]
            i += 1
            try:
                df = ctx.sql(sql).to_pandas()
                cl = ctx.engine.last_stats.get("cluster")
            except Exception as e:   # noqa: BLE001 — gated below
                with lock:
                    failures.append(
                        f"[{tag}] error {type(e).__name__}: {sql[:60]}")
                continue
            errs = []
            if frac is None:
                if not _frames_close(df, refs[sql]):
                    errs.append(f"[{tag}] window mismatch: {sql[:60]}")
            else:
                errs.extend(f"[{tag}] {f}"
                            for f in _pct_failures(df, exact, frac, eps))
                if pct_refs is not None:
                    a = df.sort_values("region")["p"].to_numpy()
                    b = pct_refs[frac].sort_values("region")[
                        "p"].to_numpy()
                    if not np.array_equal(a, b):
                        errs.append(f"[{tag}] broker percentile not "
                                    f"byte-identical to single @q{frac}")
            if expect_scatter and (cl or {}).get("mode") != "scatter":
                errs.append(f"[{tag}] no scatter: {sql[:60]}")
            with lock:
                replies[0] += 1
                failures.extend(errs)

    threads = [threading.Thread(target=worker, args=(t,), daemon=True)
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return replies[0], failures


def run_windows(args):
    """--windows: window post-pass + KLL percentile differential under
    storm (window/ + ops/kll.py).

    In-process: ingest a synthetic sales set, compute exact pandas
    references for the window mix (ranks over a GROUP BY base, moving
    sum / lag / cumulative avg over row-level scans) and exact
    per-region order statistics for the percentile gate, then storm
    the mix with --threads workers. Every window reply must match its
    reference; every percentile reply must land within the sketch's
    declared rank-error bound (sdot.quantile.rank_bound). A cold pass
    first audits that every window statement actually engaged the
    post-pass (history stats carry a "window" block). With --cluster N
    an additional leg runs the same storm through a broker over N
    in-process historicals: every reply re-checked, scatter required,
    and broker percentile answers required byte-identical to the
    single-engine answers (the register merge must be lossless). Exit
    1 on any mismatch or out-of-bound estimate."""
    sys.path.insert(0, ".")
    import spark_druid_olap_tpu as sdot
    from spark_druid_olap_tpu.ops import kll as KLL

    n_rows = int(os.environ.get("SDOT_LOADTEST_WINDOW_ROWS", "60000"))
    df = _window_sales(n_rows)
    refs, exact = _window_refs(df)
    ctx = sdot.Context({"sdot.cache.enabled": False})
    eps = KLL.rank_bound(ctx.config)
    try:
        ctx.ingest_dataframe("wsales", df, time_column="ts",
                             target_rows=4096)
        engaged = []
        for sql in WINDOW_QUERIES:   # cold pass: post-pass engagement
            ctx.sql(sql)
            st = ctx.history.entries()[-1].stats
            if "window" not in st:
                engaged.append(f"no window post-pass "
                               f"(mode={st.get('mode')}): {sql[:60]}")
        print(f"[windows] {n_rows} rows, {len(WINDOW_QUERIES)} window + "
              f"{len(PCT_FRACTIONS)} percentile statements, "
              f"{args.threads} threads, rank bound {eps}")
        ph_mark = (ctx.history.entries() or [None])[-1]
        replies, failures = _storm_windows(
            ctx, refs, exact, eps, args.threads, args.duration, "single")
        failures = engaged + failures
        phases_ms = _phase_deltas(ctx, ph_mark)
    finally:
        ctx.close()
    print(f"  [single] replies={replies} failures={len(failures)}")
    _print_phase_deltas("single", phases_ms)
    ok = replies > 0 and not failures
    out = {"mode": "windows", "rows": n_rows, "threads": args.threads,
           "rank_bound": eps,
           "single": {"replies": replies,
                      "phases_ms": phases_ms,
                      "failures": sorted(set(failures))[:10]}}
    if args.cluster:
        cl = _run_windows_cluster(args, df, refs, exact, eps)
        out["cluster"] = cl
        ok = ok and cl["ok"]
    print(json.dumps(out))
    sys.exit(0 if ok else 1)


def _run_windows_cluster(args, df, refs, exact, eps):
    """--windows --cluster N: the same mix through a broker scattering
    over N in-process historicals; broker percentile answers must be
    byte-identical to a single-process engine over the same store."""
    import shutil
    import tempfile

    import spark_druid_olap_tpu as sdot
    from spark_druid_olap_tpu.cluster.historical import HistoricalNode

    root = tempfile.mkdtemp(prefix="sdot-window-cluster-")
    caches_off = {"sdot.cache.enabled": False,
                  "sdot.cluster.subq.cache.enabled": False}
    hist, broker, single = [], None, None
    try:
        seed = sdot.Context({"sdot.persist.path": root})
        seed.ingest_dataframe("wsales", df, time_column="ts",
                              target_rows=4096)
        seed.checkpoint()
        seed.close()

        ports = [_free_port() for _ in range(args.cluster)]
        nodes = ",".join(f"127.0.0.1:{p}" for p in ports)
        common = {"sdot.persist.path": root, "sdot.cluster.nodes": nodes}
        hist = [HistoricalNode(dict(common), node_id=i).start()
                for i in range(args.cluster)]
        broker = sdot.Context({**common, "sdot.cluster.role": "broker",
                               **caches_off})
        single = sdot.Context({"sdot.persist.path": root, **caches_off})
        pct_refs = {q: single.sql(_pct_sql(q)).to_pandas()
                    for q in PCT_FRACTIONS}
        for sql in WINDOW_QUERIES:   # warm + scatter engagement audit
            broker.sql(sql)
        ph_mark = (broker.history.entries() or [None])[-1]
        replies, failures = _storm_windows(
            broker, refs, exact, eps, args.threads, args.duration,
            "cluster", pct_refs=pct_refs, expect_scatter=True)
        phases_ms = _phase_deltas(broker, ph_mark)
        print(f"  [cluster] nodes={args.cluster} replies={replies} "
              f"failures={len(failures)}")
        _print_phase_deltas("cluster", phases_ms)
        ok = replies > 0 and not failures
        return {"ok": bool(ok), "nodes": args.cluster,
                "replies": replies,
                "phases_ms": phases_ms,
                "failures": sorted(set(failures))[:10]}
    finally:
        for h in hist:
            try:
                h.stop()
            except Exception:   # noqa: BLE001 — already stopped
                pass
        for c in (broker, single):
            if c is not None:
                try:
                    c.close()
                except Exception:   # noqa: BLE001 — shutdown race
                    pass
        shutil.rmtree(root, ignore_errors=True)


def _free_port():
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait_ready(port, timeout=240.0, proc=None):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc is not None and proc.poll() is not None:
            raise RuntimeError(
                f"historical on :{port} exited rc={proc.returncode} "
                "before becoming ready")
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/readyz", timeout=2) as r:
                if r.status == 200:
                    return
        except Exception:   # noqa: BLE001 — booting
            pass
        time.sleep(0.25)
    raise RuntimeError(f"historical on :{port} not ready in {timeout}s")


CHAOS_QUERIES = [
    "select region, sum(qty) as q, count(*) as c from sales "
    "group by region order by region",
    "select product, sum(price) as rev from sales "
    "group by product order by rev desc limit 5",
    "select region, flag, count(*) as c from sales "
    "group by region, flag order by region, flag",
    "select count(*) as c from sales where qty >= 25 and status = 'O'",
]


def run_chaos(args):
    """Seeded chaos differential (fault/, docs/CHAOS.md): one FaultPlan
    derived from --seed drives every leg over an in-process two-node
    cluster — RPC connection drops, slow replies, corrupt wire frames,
    historical 500s that trip and then close a circuit breaker, hedged
    scatter, a replication-1 partial outage, torn WAL appends, a
    cold-tier CRC flip, WLM shed/starvation, epoch-based elasticity
    (scale-out / scale-in / node killed mid-transition, each under a
    query storm, with measured shard movement checked against the
    modular-rotation naive bound), a subquery-cache hit curve, and a
    threaded mixed storm.

    Every strict-mode reply is differentially checked against a
    single-process reference (byte-exact up to float ulps); the degraded
    leg must match the reference RESTRICTED to the surviving shards and
    carry exact ``missing_shards``/coverage. The JSON report ends with a
    replay digest computed only from seed-deterministic quantities
    (count-rule fire totals, sequential p-rule draws, breaker
    transitions, coverage annotations, the torn-batch set): two runs
    with the same --seed must print the same digest."""
    import hashlib
    import os
    import shutil
    import tempfile
    sys.path.insert(0, ".")
    import pandas as pd
    import spark_druid_olap_tpu as sdot
    from spark_druid_olap_tpu.cluster.historical import HistoricalNode
    from spark_druid_olap_tpu.persist import snapshot as SNAP
    from spark_druid_olap_tpu.segment.store import slice_segments
    from spark_druid_olap_tpu.wlm.lanes import AdmissionRejected

    S = int(args.seed)
    # scoped rules: a site only misbehaves while its leg holds the scope
    # open, so the baseline/warmup traffic sees a healthy cluster.
    # Cluster legs use count rules (exact totals even though scatter
    # legs race); the WLM rules are evaluated once per query in call
    # order, so their p draws replay exactly too.
    plan = json.dumps({"seed": S, "rules": [
        {"site": "rpc.connect", "match": "node:0", "action": "error",
         "arg": "ConnectionRefusedError", "count": 3, "scope": "rpc_drop"},
        {"site": "rpc.request", "action": "delay", "arg": 0.02,
         "count": 4, "scope": "rpc_delay"},
        {"site": "rpc.response", "action": "flip", "count": 3,
         "scope": "rpc_corrupt"},
        {"site": "rpc.request", "action": "delay", "arg": 0.4,
         "count": 2, "scope": "hedge"},
        {"site": "wlm.admit", "action": "error", "arg": "LaneFullError",
         "p": 0.15, "scope": "wlm"},
        {"site": "wlm.admit", "action": "delay", "arg": 0.005, "p": 0.3,
         "scope": "wlm"},
        {"site": "rpc.connect", "match": "node:0", "action": "error",
         "arg": "ConnectionRefusedError", "p": 0.1, "scope": "storm"},
        {"site": "rpc.request", "action": "delay", "arg": 0.005,
         "p": 0.2, "scope": "storm"},
        {"site": "rpc.response", "action": "flip", "p": 0.05,
         "scope": "storm"},
    ]})
    degr_plan = json.dumps({"seed": S ^ 0x1D, "rules": [
        {"site": "rpc.connect", "match": "node:1", "action": "error",
         "arg": "ConnectionRefusedError", "scope": "degraded"}]})
    hist_plan = json.dumps({"seed": S ^ 0xB5, "rules": [
        {"site": "hist.handle", "action": "error", "scope": "hist500"}]})

    caches_off = {"sdot.cache.enabled": False,
                  "sdot.plan.cache.enabled": False,
                  # the shard-result cache would absorb the repeat
                  # queries the fault legs rely on to exercise the RPC
                  # path; the hit-curve leg opts back in explicitly
                  "sdot.cluster.subq.cache.enabled": False}
    root = tempfile.mkdtemp(prefix="sdot-chaos-")
    hists, ctxs = [], []
    legs, digest_src, failures = {}, [], []

    def check(name, ok_bool, detail=""):
        if not ok_bool:
            failures.append(name)
            print(f"  [FAIL] {name} {detail}")

    def fired_delta(inj, before):
        after = inj.stats()["by_site"] if inj else {}
        return {k: v - before.get(k, 0) for k, v in after.items()
                if v - before.get(k, 0)}

    def leg_seq(name, broker, want, scopes=(), n_iters=12, allow=()):
        """Sequential dashboard rounds with a per-reply differential."""
        inj = broker.engine.fault
        before = dict(inj.stats()["by_site"]) if inj else {}
        toks = [inj.begin_scope(s) for s in scopes]
        mism = errs = shed = 0
        lats = []
        try:
            for i in range(n_iters):
                q = CHAOS_QUERIES[i % len(CHAOS_QUERIES)]
                t0 = time.perf_counter()
                try:
                    got = broker.sql(q).to_pandas()
                except allow:
                    shed += 1
                    continue
                except Exception as e:      # noqa: BLE001
                    errs += 1
                    print(f"  [{name}] ERROR {type(e).__name__}: {e}")
                    continue
                lats.append((time.perf_counter() - t0) * 1000)
                if not _frames_close(got, want[q]):
                    mism += 1
                    print(f"  [{name}] MISMATCH: {q[:60]}")
        finally:
            for t in reversed(toks):
                inj.end_scope(t)
        fired = fired_delta(inj, before)
        leg = {"n": n_iters, "mismatches": mism, "errors": errs,
               "shed": shed, "fired": fired,
               "p50_ms": round(float(np.percentile(lats, 50)), 1)
               if lats else None}
        legs[name] = leg
        digest_src.append([name, sorted(fired.items()), mism, shed])
        check(name, mism == 0 and errs == 0)
        print(f"  [{name}] {json.dumps(leg)}")
        return leg

    try:
        print(f"[chaos] seed={S}: building deep storage ...")
        single = sdot.Context({"sdot.persist.path": root, **caches_off})
        ctxs.append(single)
        single.ingest_dataframe("sales", _synthetic_sales(150_000),
                                time_column="ts", target_rows=8192)
        single.checkpoint()

        ports = [_free_port() for _ in range(4)]
        nodes_r2 = ",".join(f"127.0.0.1:{p}" for p in ports[:2])
        nodes_r1 = ",".join(f"127.0.0.1:{p}" for p in ports[2:])
        shards = {"sdot.cluster.shards": 4}
        # two rings over the same deep storage: replication 2 for the
        # strict legs (every fault is survivable), replication 1 for the
        # degraded leg (losing a node loses exactly its shards)
        hists += [HistoricalNode(
            {"sdot.persist.path": root, "sdot.cluster.nodes": nodes_r2,
             "sdot.cluster.replication": 2, "sdot.fault.plan": hist_plan,
             **shards, **caches_off}, node_id=i).start()
            for i in range(2)]
        hists += [HistoricalNode(
            {"sdot.persist.path": root, "sdot.cluster.nodes": nodes_r1,
             "sdot.cluster.replication": 1,
             **shards, **caches_off}, node_id=i).start()
            for i in range(2)]

        def mk_broker(nodes, replication, plan_text, **over):
            cfg = {
                "sdot.persist.path": root, "sdot.cluster.nodes": nodes,
                "sdot.cluster.role": "broker",
                "sdot.cluster.replication": replication,
                "sdot.cluster.probe.interval.seconds": 0,
                "sdot.cluster.retry.backoff.start.seconds": 0.01,
                "sdot.cluster.retry.backoff.cap.seconds": 0.05,
                "sdot.cluster.scatter.threads": 16,
                "sdot.fault.plan": plan_text, **shards, **caches_off}
            cfg.update(over)
            ctx = sdot.Context(cfg)
            ctxs.append(ctx)
            return ctx

        # strict-fault broker: breakers/hedging OFF so count-rule fire
        # totals depend only on the plan, not on breaker skips
        broker = mk_broker(nodes_r2, 2, plan,
                           **{"sdot.cluster.breaker.failures": 0})
        # breaker/hedge broker: same plan text, its own injector
        broker_hb = mk_broker(nodes_r2, 2, plan, **{
            "sdot.cluster.breaker.failures": 2,
            "sdot.cluster.breaker.cooldown.seconds": 0.05,
            "sdot.cluster.hedge.enabled": True,
            "sdot.cluster.hedge.after.ms": 100})
        broker_r1 = mk_broker(nodes_r1, 1, degr_plan, **{
            "sdot.cluster.partial.results": True,
            "sdot.cluster.retry.tries": 1})

        want = {}
        for q in CHAOS_QUERIES:            # warm + baseline differential
            want[q] = single.sql(q).to_pandas()
            for b in (broker, broker_hb, broker_r1):
                if not _frames_close(b.sql(q).to_pandas(), want[q]):
                    print(f"[chaos] WARMUP MISMATCH: {q}")
                    sys.exit(1)

        f1 = hists[1].ctx.engine.fault

        def heal_node0():
            # a refused connect marks node 0 down, and a downed node is
            # only re-attempted when the healthy one fails — 500 node 1
            # for one query so the chain falls through to node 0, whose
            # success marks it back up
            with f1.scope("hist500"):
                got = broker.sql(CHAOS_QUERIES[0]).to_pandas()
            check("heal_node0", _frames_close(got, want[CHAOS_QUERIES[0]]))

        print("[chaos] strict legs (every reply differentially checked)")
        leg_seq("baseline", broker, want)
        # drop leg: three drop -> failover -> heal rounds, one refused
        # connect each (the down-mark shields node 0 for the rest of a
        # round), so the count rule's fire total is exactly 3
        inj0 = broker.engine.fault
        drop_before = dict(inj0.stats()["by_site"])
        fo0 = broker.cluster.counters["failovers"]
        mism_drop = 0
        for rnd in range(3):
            with inj0.scope("rpc_drop"):
                for q in CHAOS_QUERIES:
                    if not _frames_close(broker.sql(q).to_pandas(),
                                         want[q]):
                        mism_drop += 1
                        print(f"  [rpc_drop] MISMATCH: {q[:60]}")
            heal_node0()
        drop_fired = fired_delta(inj0, drop_before)
        legs["rpc_drop"] = {
            "n": 3 * len(CHAOS_QUERIES), "mismatches": mism_drop,
            "errors": 0, "fired": {"rpc.connect":
                                   drop_fired.get("rpc.connect", 0)},
            "failovers": broker.cluster.counters["failovers"] - fo0}
        digest_src.append(["rpc_drop",
                           drop_fired.get("rpc.connect", 0), mism_drop])
        check("rpc_drop", mism_drop == 0
              and drop_fired.get("rpc.connect", 0) == 3
              and broker.cluster.counters["failovers"] - fo0 >= 3,
              json.dumps(legs["rpc_drop"]))
        print(f"  [rpc_drop] {json.dumps(legs['rpc_drop'])}")
        c0 = dict(broker.cluster.counters)
        leg_seq("rpc_delay", broker, want, scopes=("rpc_delay",))
        leg_seq("rpc_corrupt", broker, want, scopes=("rpc_corrupt",))
        corrupt = broker.cluster.counters["wire_corrupt"] \
            - c0["wire_corrupt"]
        check("rpc_corrupt.crc", corrupt == 3, f"wire_corrupt={corrupt}")
        leg_seq("wlm", broker, want, scopes=("wlm",), n_iters=24,
                allow=(AdmissionRejected,))
        check("wlm.exercised",
              legs["wlm"]["fired"].get("wlm.admit", 0) >= 1)

        # breaker leg: node 0 answers every subquery 500 until its
        # breaker opens; answers stay exact via node 1. Past the
        # cooldown the half-open probe closes it again.
        f0 = hists[0].ctx.engine.fault
        with f0.scope("hist500"):
            leg_seq("breaker_500s", broker_hb, want, n_iters=6)
        snap = broker_hb.cluster.breakers.snapshot()
        check("breaker.opened",
              snap["states"][0] == "open" and snap["opens"] == 1,
              json.dumps(snap))
        time.sleep(0.08)
        # past the cooldown, fail node 1 so the chain falls through to
        # node 0's cooled breaker: its single half-open probe succeeds
        with f1.scope("hist500"):
            leg_seq("breaker_recovery", broker_hb, want, n_iters=4)
        snap2 = broker_hb.cluster.breakers.snapshot()
        check("breaker.closed",
              snap2["states"][0] == "closed" and snap2["closes"] >= 1,
              json.dumps(snap2))
        digest_src.append(["breaker", snap2["opens"], snap2["closes"],
                           snap2["states"]])

        h0 = dict(broker_hb.cluster.counters)
        leg_seq("hedge", broker_hb, want, scopes=("hedge",), n_iters=4)
        hc = broker_hb.cluster.counters
        check("hedge.launched",
              hc["hedges_launched"] - h0["hedges_launched"] >= 1
              and hc["hedges_won"] - h0["hedges_won"] >= 1)
        legs["hedge"]["hedges_launched"] = \
            hc["hedges_launched"] - h0["hedges_launched"]
        legs["hedge"]["hedges_won"] = hc["hedges_won"] - h0["hedges_won"]

        # degraded leg: node 1 of the replication-1 ring is down, so
        # exactly its shards go missing. The reference is the full
        # datasource RESTRICTED to the surviving shards' segments.
        print("[chaos] degraded leg (partial results, replication 1)")
        dp = broker_r1.cluster.plan.datasources["sales"]
        lost = sorted(sh.index for sh in dp.shards if sh.owners == (1,))
        kept = [sh for sh in dp.shards if sh.owners != (1,)]
        kept_rows = sum(sh.rows for sh in kept)
        surv_idx = sorted(i for sh in kept for i in sh.segment_indexes)
        ref = sdot.Context(caches_off)
        ctxs.append(ref)
        ref.store.restore(
            slice_segments(single.store.get("sales"), surv_idx,
                           name="sales"), ingest_version=1)
        inj1 = broker_r1.engine.fault
        deg_ann, mism = [], 0
        for trial in range(2):             # same annotation both times
            with inj1.scope("degraded"):
                for q in CHAOS_QUERIES:
                    r = broker_r1.sql(q)
                    if r.degraded is None or not _frames_close(
                            r.to_pandas(), ref.sql(q).to_pandas()):
                        mism += 1
                        print(f"  [degraded] MISMATCH: {q[:60]}")
                    if trial == 0:
                        deg_ann.append(r.degraded)
        ann_ok = all(
            d == {"missing_shards": lost, "coverage_rows": kept_rows,
                  "total_rows": dp.num_rows} for d in deg_ann)
        check("degraded", mism == 0 and ann_ok and lost and kept,
              json.dumps(deg_ann[:1]))
        legs["degraded"] = {
            "n": 2 * len(CHAOS_QUERIES), "mismatches": mism,
            "missing_shards": lost, "coverage_rows": kept_rows,
            "total_rows": dp.num_rows}
        digest_src.append(["degraded", deg_ann])

        # torn-WAL leg: one guaranteed torn append plus seed-dependent
        # extras; torn batches are never acked and never resurface
        print("[chaos] torn-WAL leg")
        wroot = os.path.join(root, "walleg")
        wctx = sdot.Context({
            "sdot.persist.enabled": True, "sdot.persist.path": wroot,
            "sdot.fault.plan": json.dumps({"seed": S ^ 0xA5, "rules": [
                {"site": "wal.append", "action": "truncate", "arg": 11,
                 "count": 1, "after": 2, "scope": "torn"},
                {"site": "wal.append", "action": "truncate", "arg": 7,
                 "p": 0.3, "scope": "torn"}]})})
        acked = []
        with wctx.engine.fault.scope("torn"):
            for i in range(14):
                df = pd.DataFrame({
                    "t": pd.to_datetime("2024-01-01"),
                    "k": [f"k{i:02d}"] * 50,
                    "v": np.arange(i * 50, (i + 1) * 50, dtype=np.int64)})
                try:
                    wctx.stream_ingest("events", df, time_column="t")
                    acked.append(i)
                except OSError:
                    pass
        wctx.close()
        wctx2 = sdot.Context({"sdot.persist.enabled": True,
                              "sdot.persist.path": wroot})
        ctxs.append(wctx2)
        if acked:
            n = int(wctx2.sql("select count(*) as n from events")
                    .data["n"][0])
            ks = sorted(set(wctx2.sql("select k from events")
                            .data["k"].tolist()))
        else:
            n, ks = 0, []
        torn = 14 - len(acked)
        check("torn_wal", torn >= 1 and acked and n == 50 * len(acked)
              and ks == [f"k{i:02d}" for i in acked],
              f"acked={acked} recovered_rows={n}")
        legs["torn_wal"] = {"batches": 14, "torn": torn,
                            "acked": len(acked), "recovered_rows": n}
        digest_src.append(["torn_wal", acked])

        # group-commit leg: concurrent producers share covering fsyncs;
        # an injected covering-fsync failure un-acks the WHOLE batch
        # and rolls it back. Recovery must serve exactly the acked set
        # — nothing more (no un-acked resurrection), nothing less
        # (ACK-implies-durable). Which producers land in the two failed
        # batches is timing-dependent, so the acked membership gates
        # but stays out of the digest; the fire count (count-based) and
        # the exactness verdict hash in.
        print("[chaos] group-commit leg")
        from spark_druid_olap_tpu.fault import FaultInjected as _FI
        groot = os.path.join(root, "gcleg")
        gctx = sdot.Context({
            "sdot.persist.enabled": True, "sdot.persist.path": groot,
            "sdot.fault.plan": json.dumps({"seed": S ^ 0xB7, "rules": [
                {"site": "wal.group_commit", "action": "error",
                 "count": 2, "after": 1, "scope": "gc"}]})})
        acked_g, alock = set(), threading.Lock()

        def gc_producer(tid):
            for b in range(6):
                key = f"p{tid}b{b}"
                df = pd.DataFrame({
                    "t": pd.to_datetime("2024-01-01"),
                    "k": [key] * 40,
                    "v": np.arange(40, dtype=np.int64)})
                try:
                    gctx.stream_ingest("gevents", df, time_column="t")
                    with alock:
                        acked_g.add(key)
                except (_FI, OSError):
                    pass

        with gctx.engine.fault.scope("gc"):
            gths = [threading.Thread(target=gc_producer, args=(i,))
                    for i in range(4)]
            for th in gths:
                th.start()
            for th in gths:
                th.join()
        gfired = gctx.engine.fault.stats()["by_site"] \
            .get("wal.group_commit", 0)
        gc_stats = gctx.persist.stats()["groupCommit"]
        gctx.close()
        gctx2 = sdot.Context({"sdot.persist.enabled": True,
                              "sdot.persist.path": groot})
        ctxs.append(gctx2)
        if acked_g:
            gn = int(gctx2.sql("select count(*) as n from gevents")
                     .data["n"][0])
            gks = sorted(set(gctx2.sql("select k from gevents")
                             .data["k"].tolist()))
        else:
            gn, gks = 0, []
        # every frame in a committed group was acked and vice versa,
        # so the lifetime frame counter equals the acked batch count
        gc_exact = (gn == 40 * len(acked_g)
                    and gks == sorted(acked_g)
                    and gc_stats["frames"] == len(acked_g)
                    and 1 <= gc_stats["commits"] <= gc_stats["frames"])
        check("group_commit", gfired == 2 and len(acked_g) < 24
              and gc_exact,
              f"fired={gfired} acked={len(acked_g)}/24 "
              f"commits={gc_stats['commits']} "
              f"frames={gc_stats['frames']} rows={gn}")
        legs["group_commit"] = {
            "producers": 4, "batches": 24, "acked": len(acked_g),
            "fired": gfired, "commits": gc_stats["commits"],
            "frames": gc_stats["frames"], "recovered_rows": gn}
        digest_src.append(["group_commit", gfired, gc_exact])
        print(f"  [group_commit] {json.dumps(legs['group_commit'])}")

        # compact-publish leg: a crash at the compaction publish site
        # must leave the OLD generation fully readable with the WAL
        # untouched; the retry swaps generations without moving the
        # ingest version, and answers stay byte-identical throughout
        print("[chaos] compact-publish leg")
        croot = os.path.join(root, "compactleg")
        cq = ("select k, sum(v) as s, count(*) as n from cevents "
              "group by k order by k")
        cctx = sdot.Context({
            "sdot.persist.enabled": True, "sdot.persist.path": croot,
            "sdot.fault.plan": json.dumps({"seed": S ^ 0xC3, "rules": [
                {"site": "compact.publish", "action": "error",
                 "count": 1}]}), **caches_off})
        for i in range(8):
            # descending days: compaction must re-sort globally
            df = pd.DataFrame({
                "t": pd.to_datetime(f"2024-01-{8 - i:02d}"),
                "k": [f"c{i % 3}"] * 64,
                "v": np.arange(i * 64, (i + 1) * 64, dtype=np.int64)})
            cctx.stream_ingest("cevents", df, time_column="t",
                               target_rows=48)
        want_c = cctx.sql(cq).to_pandas()
        segs0 = len(cctx.store.get("cevents").segments)
        wal_b0 = cctx.persist._wal_for("cevents").size_bytes()
        crashed = False
        try:
            cctx.persist.compact("cevents")
        except _FI:
            crashed = True
        old_ok = (crashed and wal_b0 > 0
                  and cctx.persist._wal_for("cevents").size_bytes()
                  == wal_b0
                  and len(cctx.store.get("cevents").segments) == segs0
                  and _frames_close(cctx.sql(cq).to_pandas(), want_c))
        cctx.close()
        # the crash "for real": recover from disk (old generation), then
        # retry the compaction fault-free and re-check the differential
        cctx2 = sdot.Context({"sdot.persist.enabled": True,
                              "sdot.persist.path": croot, **caches_off})
        ctxs.append(cctx2)
        rec_ok = _frames_close(cctx2.sql(cq).to_pandas(), want_c)
        iv0 = cctx2.store.datasource_version("cevents")
        summ = (cctx2.persist.compact("cevents") or [None])[0]
        swap_ok = (summ is not None
                   and summ["segments_after"] < segs0
                   and cctx2.store.datasource_version("cevents") == iv0
                   and cctx2.persist._wal_for("cevents").size_bytes()
                   < wal_b0
                   and _frames_close(cctx2.sql(cq).to_pandas(), want_c))
        check("compact_publish", old_ok and rec_ok and swap_ok,
              f"crashed={crashed} segs0={segs0} summ={summ}")
        legs["compact_publish"] = {
            "crashed": crashed, "segments_before": segs0,
            "segments_after": summ["segments_after"] if summ else None,
            "rows": summ["rows"] if summ else None,
            "old_generation_readable": old_ok,
            "recovered_exact": rec_ok, "swap_exact": swap_ok}
        digest_src.append(["compact_publish", crashed, segs0,
                           summ["segments_after"] if summ else None,
                           summ["rows"] if summ else None])
        print(f"  [compact_publish] "
              f"{json.dumps(legs['compact_publish'])}")

        # cold-tier CRC leg: a flipped blob quarantines the newest
        # snapshot version; the retry answers exactly from the older one
        print("[chaos] cold-tier CRC-flip leg")
        troot = os.path.join(root, "tierleg")
        tq = ("select region, sum(qty) as q, count(*) as n from tsales "
              "group by region order by region")
        si = dict(time_column="ts",
                  dimensions=["region", "product", "flag", "status"],
                  metrics=["qty", "price"])
        t1 = sdot.Context({"sdot.persist.path": troot, **caches_off})
        t1.stream_ingest("tsales", _synthetic_sales(20_000), **si)
        want_t = t1.sql(tq).to_pandas()
        t1.checkpoint("tsales")
        t1.stream_ingest("tsales", _synthetic_sales(2_000), **si)
        t1.checkpoint("tsales")
        cur = SNAP.current_version(t1.persist._ds_root("tsales"))
        t1.close()
        t2 = sdot.Context({
            "sdot.persist.path": troot, "sdot.tier.enabled": True,
            "sdot.fault.plan": json.dumps({"seed": S ^ 0x5C, "rules": [
                {"site": "tier.verify", "action": "flip", "count": 1}]}),
            **caches_off})
        ctxs.append(t2)
        corrupt_seen = False
        try:
            t2.sql(tq)
        except SNAP.SnapshotCorrupt:
            corrupt_seen = True
        rep = t2.persist.recovery_report
        tier_ok = (corrupt_seen and len(rep["quarantined"]) == 1
                   and rep["quarantined"][0]["version"] == cur
                   and _frames_close(t2.sql(tq).to_pandas(), want_t)
                   and t2.persist.tier.counters["crc_failures"] == 1)
        check("cold_crc", tier_ok, json.dumps(rep["quarantined"]))
        legs["cold_crc"] = {"quarantined_version": cur,
                            "recovered_exact": tier_ok}
        digest_src.append(["cold_crc", cur, corrupt_seen])

        # ---- elasticity legs: epoch-based rolling topology under a
        # storm (cluster/epoch.py). Own persist root: the r2/r1 rings
        # above must never observe a topology change. Movement counts
        # hash into the replay digest — logical node ids are
        # deterministic, so the diff is too.
        print("[chaos] elasticity legs (epoch rolling topology)")
        from spark_druid_olap_tpu.cluster import epoch as EPO
        from spark_druid_olap_tpu.cluster.assign import (
            plan_cluster, plan_diff)
        from spark_druid_olap_tpu.fault import (
            FaultInjected, FaultInjector, FaultPlan)
        eroot = os.path.join(root, "elastic")
        es = sdot.Context({"sdot.persist.path": eroot, **caches_off})
        ctxs.append(es)
        es.ingest_dataframe("esales", _synthetic_sales(60_000),
                            time_column="ts", target_rows=4096)
        es.checkpoint()
        eaddrs = [f"127.0.0.1:{_free_port()}" for _ in range(4)]
        drain_kill = json.dumps({"seed": S ^ 0xE1, "rules": [
            {"site": "node.drain", "action": "error", "count": 1}]})
        ecommon = {"sdot.persist.path": eroot,
                   "sdot.cluster.replication": 2,
                   # FIXED shard count: shard identity must survive the
                   # node-count changes below
                   "sdot.cluster.shards": 4,
                   "sdot.cluster.epoch.poll.seconds": 0.05,
                   "sdot.cluster.epoch.drain.grace.seconds": 0.05,
                   "sdot.cluster.epoch.drain.timeout.seconds": 5.0,
                   "sdot.cluster.retry.backoff.start.seconds": 0.01,
                   **caches_off}

        def estart(addr, csv, extra=None):
            h = HistoricalNode(
                {**ecommon, "sdot.cluster.nodes": csv, **(extra or {})},
                node_id=csv.split(",").index(addr)).start()
            hists.append(h)
            return h

        ecsv2 = ",".join(eaddrs[:2])
        for a in eaddrs[:2]:
            estart(a, ecsv2)
        ebroker = sdot.Context({
            **ecommon, "sdot.cluster.nodes": ecsv2,
            "sdot.cluster.role": "broker",
            "sdot.cluster.probe.interval.seconds": 0.05})
        ctxs.append(ebroker)
        EQ = ["select region, sum(qty) as q, count(*) as c from esales "
              "group by region order by region",
              "select product, sum(price) as rev from esales "
              "group by product order by rev desc, product limit 10",
              "select region, approx_count_distinct(product) as dp "
              "from esales group by region order by region"]
        ewant = {q: es.sql(q).to_pandas() for q in EQ}
        for q in EQ:
            if not _frames_close(ebroker.sql(q).to_pandas(), ewant[q]):
                print(f"[chaos] ELASTIC WARMUP MISMATCH: {q}")
                sys.exit(1)

        def naive_moved(n_old, n_new):
            return plan_diff(
                plan_cluster(eroot, n_old, 2, n_shards=4,
                             strategy="modular"),
                plan_cluster(eroot, n_new, 2, n_shards=4,
                             strategy="modular")).moved

        def elastic_leg(name, fn):
            """Run the topology change ``fn`` while a hammer thread
            storms the broker; ``fn`` returns the epoch the broker must
            converge to. Zero mismatches is the bar."""
            stop_ev = threading.Event()
            mism, errs, n = [0], [0], [0]

            def hammer():
                i = 0
                while not stop_ev.is_set():
                    q = EQ[i % len(EQ)]
                    i += 1
                    n[0] += 1
                    try:
                        got = ebroker.sql(q).to_pandas()
                    except Exception as e:      # noqa: BLE001
                        errs[0] += 1
                        print(f"  [{name}] ERROR "
                              f"{type(e).__name__}: {e}")
                        continue
                    if not _frames_close(got, ewant[q]):
                        mism[0] += 1
                        print(f"  [{name}] MISMATCH: {q[:60]}")

            th = threading.Thread(target=hammer)
            th.start()
            try:
                want_epoch = fn()
                deadline = time.monotonic() + 20.0
                while (time.monotonic() < deadline
                       and ebroker.cluster.stats()["epoch"]["active"]
                       != want_epoch):
                    time.sleep(0.05)
            finally:
                stop_ev.set()
                th.join()
            swapped = ebroker.cluster.stats()["epoch"]["active"] \
                == want_epoch
            reb = ebroker.cluster.last_rebalance or {}
            leg = {"n": n[0], "mismatches": mism[0], "errors": errs[0],
                   "to_epoch": want_epoch, "swapped": swapped,
                   "moved": reb.get("moved"), "total": reb.get("total")}
            legs[name] = leg
            digest_src.append([name, want_epoch, leg["moved"],
                               leg["total"], mism[0]])
            check(name, swapped and mism[0] == 0 and errs[0] == 0,
                  json.dumps(leg))
            print(f"  [{name}] {json.dumps(leg)}")
            return leg

        # scale-out mid-storm: N -> N+2; the broker must keep serving
        # the old epoch until both joiners warm + advertise
        def scale_out():
            rec = EPO.publish_epoch(eroot, eaddrs, note="scale-out")
            csv = ",".join(rec.nodes)
            estart(eaddrs[2], csv)
            # the second joiner carries a one-shot node.drain error: it
            # dies mid-handover when a later epoch drops it
            estart(eaddrs[3], csv, extra={"sdot.fault.plan": drain_kill})
            return rec.epoch

        leg = elastic_leg("elastic_scale_out", scale_out)
        nm = naive_moved(2, 4)
        check("elastic_scale_out.movement",
              leg["moved"] is not None and leg["moved"] <= nm,
              f"moved={leg['moved']} naive={nm}")
        legs["elastic_scale_out"]["naive_moved"] = nm

        # node killed during epoch transition: the publisher "crashes"
        # between the record write and the CURRENT flip (inert orphan,
        # readers hold), the re-publish allocates past it, and the
        # node being removed dies at its node.drain site instead of
        # draining gracefully — replicas absorb both
        pub_hold = []

        def kill_transition():
            prev = EPO.read_epoch(eroot).epoch
            inj_pub = FaultInjector(FaultPlan.parse(json.dumps(
                {"seed": S ^ 0x3E, "rules": [
                    {"site": "epoch.publish", "action": "error",
                     "count": 1}]})))
            try:
                EPO.publish_epoch(eroot, eaddrs[:3], note="kill-leg",
                                  fault=inj_pub)
                pub_hold.append(False)
            except FaultInjected:
                pub_hold.append(EPO.read_epoch(eroot).epoch == prev)
            rec = EPO.publish_epoch(eroot, eaddrs[:3], note="kill-retry")
            return rec.epoch

        elastic_leg("elastic_kill_transition", kill_transition)
        check("elastic_kill_transition.publish_crash",
              pub_hold == [True], f"pub_hold={pub_hold}")
        digest_src.append(["elastic_publish_crash", pub_hold])

        # scale-in mid-storm: back to N; the leaver drains in-flight
        # subqueries and fences only after the survivors cover its
        # shards
        def scale_in():
            return EPO.publish_epoch(eroot, eaddrs[:2],
                                     note="scale-in").epoch

        leg = elastic_leg("elastic_scale_in", scale_in)
        nm = naive_moved(3, 2)
        check("elastic_scale_in.movement",
              leg["moved"] is not None and leg["moved"] <= nm,
              f"moved={leg['moved']} naive={nm}")
        legs["elastic_scale_in"]["naive_moved"] = nm

        # subquery-cache hit curve: a cache-on broker must answer
        # byte-identically to the cache-off reference while its hit
        # counter climbs and its miss counter plateaus after round one
        print("[chaos] subquery-cache hit curve (cache on vs off)")
        cbroker = sdot.Context({
            **ecommon, "sdot.cluster.nodes": ecsv2,
            "sdot.cluster.role": "broker",
            "sdot.cluster.probe.interval.seconds": 0,
            "sdot.cluster.subq.cache.enabled": True})
        ctxs.append(cbroker)
        curve, mism_c = [], 0
        for _rnd in range(4):
            for q in EQ:
                if not _frames_close(cbroker.sql(q).to_pandas(),
                                     ewant[q]):
                    mism_c += 1
                    print(f"  [subq_cache] MISMATCH: {q[:60]}")
            cc = cbroker.cluster.counters
            curve.append([cc["subq_cache_hits"],
                          cc["subq_cache_misses"]])
        hit_ok = (curve[0][0] == 0
                  and all(curve[i][0] > curve[i - 1][0]
                          for i in range(1, len(curve)))
                  and curve[-1][1] == curve[0][1])
        legs["subq_cache"] = {"curve": curve, "mismatches": mism_c}
        digest_src.append(["subq_cache", curve, mism_c])
        check("subq_cache", mism_c == 0 and hit_ok, json.dumps(curve))
        print(f"  [subq_cache] {json.dumps(legs['subq_cache'])}")

        # mixed threaded storm: every survivable fault class at once;
        # timing-dependent, so it gates on zero mismatches/errors but
        # stays out of the replay digest
        storm_s = min(args.duration, 8.0)
        print(f"[chaos] mixed storm ({min(args.threads, 8)} threads x "
              f"{storm_s:.0f}s)")
        mism_storm = [0]
        mlock = threading.Lock()

        def storm_call(sql):
            got = broker.sql(sql).to_pandas()
            if not _frames_close(got, want[sql]):
                with mlock:
                    mism_storm[0] += 1

        tok = broker.engine.fault.begin_scope("storm")
        try:
            total, errs_s, elapsed, _ = run(
                lambda: storm_call, CHAOS_QUERIES,
                min(args.threads, 8), storm_s)
        finally:
            broker.engine.fault.end_scope(tok)
        check("storm", errs_s == 0 and mism_storm[0] == 0,
              f"errors={errs_s} mismatches={mism_storm[0]}")
        legs["storm"] = {"n": int(total), "errors": int(errs_s),
                         "mismatches": mism_storm[0],
                         "qps": round(total / max(elapsed, 1e-9), 1)}

        digest = hashlib.sha256(
            json.dumps(digest_src, sort_keys=True).encode()
        ).hexdigest()[:16]
        out = {"mode": "chaos", "seed": S, "scenarios": len(legs),
               "failures": failures, "replay_digest": digest,
               "legs": legs}
        print("\n" + json.dumps(out))
        if failures:
            print(f"CHAOS FAILURES: {failures}")
            sys.exit(1)
        print(f"OK: {len(legs)} chaos scenarios, zero mismatches; "
              f"replay digest {digest} (stable for --seed {S})")
        sys.exit(0)
    finally:
        for h in hists:
            try:
                h.stop()
            except Exception:   # noqa: BLE001
                pass
        for c in ctxs:
            try:
                c.close()
            except Exception:   # noqa: BLE001
                pass
        shutil.rmtree(root, ignore_errors=True)


INGEST_BATCH_ROWS = 256


def _ingest_batch(key, rows=INGEST_BATCH_ROWS, day=1):
    import numpy as np
    import pandas as pd
    return pd.DataFrame({
        "ts": pd.to_datetime(f"2024-01-{day:02d}"),
        "k": [key] * rows,
        "v": np.arange(rows, dtype=np.int64)})


def run_ingest(args):
    """Streaming-ingest benchmark (persist/wal.py group commit): T
    producer threads stream keyed batches into one WAL-backed
    datasource with group commit OFF (every ACK pays its own covering
    fsync, commits serialized under the build lock) then ON (one
    covering fsync amortized over every frame staged while the leader
    held the file). Reports rows/s, ACK p50/p99, fsyncs and
    frames-per-fsync, plus read-your-writes probes (an ACKed batch must
    be queryable immediately). Every leg is differentially checked —
    live keys/counts must be exactly the acked set, and a fresh context
    over the same root must recover identically. With --cluster N the
    same stream runs through an in-process broker over N historicals
    (push-on-ingest), timing ACK-to-visible staleness through the
    scatter path. Exit 0 needs zero mismatches, zero stale probes, and
    grouped throughput >= the serialized leg."""
    import os
    import shutil
    import tempfile
    import threading
    import numpy as np
    sys.path.insert(0, ".")
    import spark_druid_olap_tpu as sdot

    T = min(args.threads, 8)
    B = max(10, int(args.duration))     # batches per producer per leg
    rows = INGEST_BATCH_ROWS
    tmp = tempfile.mkdtemp(prefix="sdot-ingest-")
    failures = []
    q_keys = ("select k, count(*) as n from events "
              "group by k order by k")

    def pct(vals, p):
        return round(float(np.percentile(vals, p)) * 1000, 2) \
            if vals else None

    def produce(ctx, label):
        """T producers x B batches; returns (wall_s, ack_lat, ryw)."""
        lat, ryw, lock = [], [], threading.Lock()

        def producer(tid):
            for b in range(B):
                key = f"p{tid}b{b}"
                df = _ingest_batch(key, rows, day=(b % 27) + 1)
                t0 = time.perf_counter()
                ctx.stream_ingest("events", df, time_column="ts",
                                  target_rows=8192)
                dt = time.perf_counter() - t0
                probe = None
                if b % 4 == 0:
                    # read-your-writes: the ACK promises this key is
                    # queryable NOW; time to first *correct* answer is
                    # the staleness
                    t1 = time.perf_counter()
                    while True:
                        n = int(ctx.sql(
                            "select count(*) as n from events "
                            f"where k = '{key}'").data["n"][0])
                        if n == rows:
                            probe = (time.perf_counter() - t1, True)
                            break
                        if time.perf_counter() - t1 > 5.0:
                            probe = (time.perf_counter() - t1, False)
                            break
                with lock:
                    lat.append(dt)
                    if probe is not None:
                        ryw.append(probe)

        ths = [threading.Thread(target=producer, args=(t,))
               for t in range(T)]
        t0 = time.perf_counter()
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        return time.perf_counter() - t0, lat, ryw

    def check(root, label, got):
        """Differential: live answers == acked set == recovered."""
        want = sorted(f"p{t}b{b}" for t in range(T) for b in range(B))
        live_ok = (got["k"].tolist() == want
                   and bool((got["n"] == rows).all()))
        if not live_ok:
            failures.append(f"{label}: live differential")
        rec = sdot.Context({"sdot.persist.enabled": True,
                            "sdot.persist.path": root,
                            "sdot.cache.enabled": False})
        rec_ok = rec.sql(q_keys).to_pandas().equals(got)
        rec.close()
        if not rec_ok:
            failures.append(f"{label}: recovery differential")
        return live_ok and rec_ok

    def leg(label, group_on):
        root = os.path.join(tmp, label)
        ctx = sdot.Context({
            "sdot.persist.enabled": True, "sdot.persist.path": root,
            "sdot.persist.wal.group.commit": group_on,
            "sdot.cache.enabled": False})
        wall, lat, ryw = produce(ctx, label)
        got = ctx.sql(q_keys).to_pandas()
        st = ctx.persist.stats()
        gc, appends = st["groupCommit"], st["counters"]["wal_appends"]
        ctx.close()
        ok = check(root, label, got)
        stale = sum(1 for _, fresh in ryw if not fresh)
        if stale:
            failures.append(f"{label}: {stale} stale RYW probes")
        fsyncs = gc["commits"] if group_on else appends
        out = {"label": label, "acks": len(lat),
               "rows_s": round(T * B * rows / wall, 1),
               "acks_s": round(len(lat) / wall, 1),
               "ack_p50_ms": pct(lat, 50), "ack_p99_ms": pct(lat, 99),
               "fsyncs": fsyncs,
               "frames_per_fsync": round(
                   gc["frames"] / max(gc["commits"], 1), 2)
               if group_on else 1.0,
               "ryw_probe_p99_ms": pct([d for d, _ in ryw], 99),
               "stale_probes": stale, "differential_ok": ok}
        print(f"  [{label}] {json.dumps(out)}")
        return out

    def cluster_leg(n_nodes):
        from spark_druid_olap_tpu.cluster.historical import HistoricalNode
        root = os.path.join(tmp, "cluster")
        seeder = sdot.Context({"sdot.persist.path": root,
                               "sdot.cache.enabled": False})
        seeder.stream_ingest("events", _ingest_batch("seed", rows),
                             time_column="ts", target_rows=8192)
        seeder.checkpoint()
        seeder.close()
        addrs = [f"127.0.0.1:{_free_port()}" for _ in range(n_nodes)]
        common = {"sdot.persist.path": root,
                  "sdot.cluster.nodes": ",".join(addrs),
                  "sdot.cluster.shards": max(2, n_nodes),
                  "sdot.cluster.replication": min(2, n_nodes),
                  "sdot.cluster.retry.backoff.start.seconds": 0.01,
                  "sdot.cache.enabled": False}
        hists, broker = [], None
        try:
            for i in range(n_nodes):
                hists.append(HistoricalNode(dict(common),
                                            node_id=i).start())
            broker = sdot.Context({
                **common, "sdot.cluster.role": "broker",
                "sdot.cluster.probe.interval.seconds": 0.1})
            wall, lat, ryw = produce(broker, "cluster")
            got = broker.sql(q_keys).to_pandas()
            want = sorted(["seed"] + [f"p{t}b{b}" for t in range(T)
                                      for b in range(B)])
            if got["k"].tolist() != want \
                    or not bool((got["n"] == rows).all()):
                failures.append("cluster: live differential")
            ing = broker.cluster.stats()["ingest"]
            mode = (broker.engine.last_stats.get("cluster")
                    or {}).get("mode")
            stale = sum(1 for _, fresh in ryw if not fresh)
            if stale:
                failures.append(f"cluster: {stale} stale RYW probes")
            out = {"label": f"cluster-{n_nodes}", "acks": len(lat),
                   "rows_s": round(T * B * rows / wall, 1),
                   "ack_p50_ms": pct(lat, 50),
                   "ack_p99_ms": pct(lat, 99),
                   "ryw_staleness_p99_ms": pct([d for d, _ in ryw], 99),
                   "stale_probes": stale, "mode": mode,
                   "pushes": broker.cluster.counters.get(
                       "ingest_pushes", 0),
                   "push_enabled": ing.get("push_enabled")}
            print(f"  [cluster-{n_nodes}] {json.dumps(out)}")
            return out
        finally:
            for h in hists:
                h.stop()
            if broker is not None:
                broker.close()

    try:
        print(f"[ingest] {T} producers x {B} batches x {rows} rows "
              f"per leg")
        base = leg("serialized", False)
        grouped = leg("group-commit", True)
        cluster = cluster_leg(args.cluster) if args.cluster else None
        ratio = round(grouped["rows_s"] / max(base["rows_s"], 1e-9), 2)
        if ratio < 1.0:
            failures.append(
                f"group commit slower than serialized ({ratio}x)")
        out = {"mode": "ingest", "threads": T, "batches": T * B,
               "rows_per_batch": rows, "serialized": base,
               "grouped": grouped, "speedup": ratio,
               "cluster": cluster, "failures": failures}
        print(json.dumps(out))
        if failures:
            print(f"INGEST FAILED: {failures}")
            sys.exit(1)
        print(f"OK: group commit {ratio}x serialized rows/s "
              f"({grouped['frames_per_fsync']} frames/fsync vs 1.0), "
              f"zero differential mismatches, zero stale "
              f"read-your-writes probes")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_cluster(args):
    """Multi-process distributed-serving benchmark (cluster/): build +
    checkpoint a synthetic store, spawn N historical subprocesses over
    it (`python -m spark_druid_olap_tpu.cluster historical`), attach an
    in-process broker, and hammer the same query mix through the broker
    vs a single-process engine (result/plan caches off everywhere —
    every rep executes). Reports scatter fan-out, merge latency,
    per-node shared-scan coalesce rates, and the qps ratio; then a
    kill -9 failover leg: one historical dies mid-storm and every answer
    must still match the single-engine reference (zero mismatches)."""
    import os
    import shutil
    import signal
    import subprocess
    import tempfile
    sys.path.insert(0, ".")
    import spark_druid_olap_tpu as sdot

    n_nodes = args.cluster
    # micro-batch hold window for the historicals: subqueries for one
    # shard arrive tens of ms apart under a storm, so the in-process
    # default (8 ms) closes nearly every group solo. 25 ms is enough for
    # the queued-waiter handoff to fill groups once lanes serialize.
    window_ms = args.window if args.window is not None else 25.0
    root = tempfile.mkdtemp(prefix="sdot-cluster-bench-")
    caches_off = {"sdot.cache.enabled": False,
                  "sdot.plan.cache.enabled": False,
                  "sdot.cluster.subq.cache.enabled": False}
    procs, broker, single = [], None, None
    try:
        seed = sdot.Context({"sdot.persist.path": root})
        # enough rows that scan work dominates per-RPC overhead — the
        # regime the tier is for; small segments so every node gets real
        # shards to own
        df = _synthetic_sales(1_200_000)
        seed.ingest_dataframe("sales", df, time_column="ts",
                              target_rows=16384)
        seed.checkpoint()
        seed.close()

        ports = [_free_port() for _ in range(n_nodes)]
        nodes = ",".join(f"127.0.0.1:{p}" for p in ports)
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        for i in range(n_nodes):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "spark_druid_olap_tpu.cluster",
                 "historical", "--persist", root, "--nodes", nodes,
                 "--node-id", str(i),
                 "--set", "sdot.cache.enabled=false",
                 "--set", "sdot.plan.cache.enabled=false",
                 # the tier's designed configuration: each historical
                 # coalesces its own slice of the storm (concurrent
                 # subqueries on one node fuse into one scan), which is
                 # what lets N nodes multiply qps instead of merely
                 # splitting rows. Single-slot lanes serialize execution
                 # so every subquery that arrives while a fused dispatch
                 # runs queues — and the WLM handoff rides it into the
                 # NEXT group's micro-batch window instead of scanning
                 # solo.
                 "--set", "sdot.sharedscan.enabled=true",
                 "--set", "sdot.sharedscan.max.queries=64",
                 "--set", f"sdot.wlm.batch.window.ms={window_ms}",
                 "--set", "sdot.wlm.lanes=interactive:slots=1,queue=256;"
                          "reporting:slots=1,queue=64;"
                          "batch:slots=1,queue=32"],
                env=env, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL))
        print(f"[cluster] waiting for {n_nodes} historicals "
              f"(persist recovery + shard load) ...")
        t0 = time.monotonic()
        for p, proc in zip(ports, procs):
            _wait_ready(p, proc=proc)
        print(f"[cluster] ready in {time.monotonic() - t0:.1f}s "
              f"on ports {ports}")

        broker = sdot.Context({
            "sdot.persist.path": root, "sdot.cluster.nodes": nodes,
            "sdot.cluster.role": "broker",
            "sdot.cluster.probe.interval.seconds": 0.25,
            "sdot.cluster.retry.backoff.start.seconds": 0.01,
            # don't bottleneck the storm at the broker: every in-flight
            # query needs a scatter worker per shard, and the broker's
            # own admission must pass the full user count through so the
            # historicals see the real concurrency to coalesce
            "sdot.cluster.scatter.threads": args.threads * n_nodes,
            "sdot.wlm.lanes": (
                f"interactive:slots={max(args.threads, 8)},queue=512;"
                "reporting:slots=8,queue=64;batch:slots=4,queue=32"),
            **caches_off})
        single = sdot.Context({"sdot.persist.path": root, **caches_off})

        queries = args.sql or DEFAULT_QUERIES
        answers = {}
        for q in queries:                  # warm/compile both engines
            single.sql(q)
            answers[q] = single.sql(q).to_pandas()
            broker.sql(q)
            if not _frames_close(broker.sql(q).to_pandas(), answers[q]):
                print(f"[cluster] WARMUP MISMATCH: {q}")
                sys.exit(1)

        # concurrent warmup: each distinct combination of fused lanes is
        # its own compiled program on the historicals (identical specs
        # dedup into one lane, so the combo space is the subsets of the
        # query mix). A sequential pass never forms groups — storm the
        # broker untimed so the common combos are compiled before the
        # measured leg, matching the single engine whose programs the
        # gate above already compiled.
        print("[cluster] concurrent warmup (fused-group compile) ...")
        run(lambda: (lambda sql: broker.sql(sql)), queries,
            args.threads, 20.0)

        legs = {}
        print(f"\n=== single-process leg ({args.threads} threads x "
              f"{args.duration:.0f}s) ===")
        legs["single"] = _summarize(run(
            lambda: (lambda sql: single.sql(sql)), queries,
            args.threads, args.duration))
        c0 = dict(broker.cluster.counters)
        print(f"\n=== cluster leg ({n_nodes} historicals, {args.threads} "
              f"threads x {args.duration:.0f}s) ===")
        legs["cluster"] = _summarize(run(
            lambda: (lambda sql: broker.sql(sql)), queries,
            args.threads, args.duration))
        c1 = dict(broker.cluster.counters)
        dq = max(c1["queries"] - c0["queries"], 1)
        fanout = (c1["scatters"] - c0["scatters"]) / dq
        merge_ms = (c1["merge_ms"] - c0["merge_ms"]) / dq
        coalesce = {}
        for i, p in enumerate(ports):
            try:
                ss = get_json(f"http://127.0.0.1:{p}", "/metadata/sharedscan")
                served = max(ss.get("queries_coalesced", 0)
                             + ss.get("solo_groups", 0), 1)
                coalesce[str(i)] = round(
                    ss.get("queries_coalesced", 0) / served, 4)
            except Exception:   # noqa: BLE001 — introspection only
                coalesce[str(i)] = None
        speedup = legs["cluster"]["qps"] / max(legs["single"]["qps"], 1e-9)
        print(f"  scatter fan-out {fanout:.2f} shards/query, broker merge "
              f"{merge_ms:.2f}ms/query, per-node coalesce {coalesce}")
        print(f"  qps {legs['single']['qps']} -> {legs['cluster']['qps']} "
              f"({speedup:.2f}x)")

        # -- kill -9 failover leg ------------------------------------------
        print(f"\n=== failover leg: kill -9 node {n_nodes - 1} "
              f"mid-storm ===")
        mism, errs, post_kill = [], [0], []
        lock = threading.Lock()
        stop_at = time.monotonic() + max(6.0, args.duration / 3)
        t_kill = [None]

        def storm(tid):
            i = tid
            while time.monotonic() < stop_at:
                sql = queries[i % len(queries)]
                i += 1
                t0 = time.perf_counter()
                try:
                    got = broker.sql(sql).to_pandas()
                except Exception:   # noqa: BLE001 — counted + asserted
                    with lock:
                        errs[0] += 1
                    continue
                dt = (time.perf_counter() - t0) * 1000
                with lock:
                    if t_kill[0] is not None:
                        post_kill.append(dt)
                    if not _frames_close(got, answers[sql]):
                        mism.append(sql)

        workers = [threading.Thread(target=storm, args=(t,), daemon=True)
                   for t in range(args.threads)]
        for t in workers:
            t.start()
        time.sleep(1.0)
        victim = procs[-1]
        t_kill[0] = time.monotonic()
        victim.send_signal(signal.SIGKILL)
        for t in workers:
            t.join()
        # detection latency: kill -> broker marking the node down
        st = broker.cluster.stats()
        down_s = st["nodes"][n_nodes - 1].get("down_seconds")
        detect_ms = None if down_s is None else round(
            (time.monotonic() - t_kill[0] - down_s) * 1000, 1)
        pk = np.array(post_kill) if post_kill else np.array([0.0])
        print(f"  {len(post_kill)} queries answered after the kill; "
              f"mismatches={len(mism)} errors={errs[0]} "
              f"detect={detect_ms}ms post-kill "
              f"p99={np.percentile(pk, 99):.1f}ms")

        out = {"mode": "cluster", "nodes": n_nodes, "rows": len(df),
               "threads": args.threads, "duration_s": args.duration,
               "legs": legs, "qps_speedup": round(speedup, 2),
               "scatter_fanout": round(fanout, 2),
               "merge_ms_per_query": round(merge_ms, 3),
               "per_node_coalesce_rate": coalesce,
               "failover": {
                   "answered_after_kill": len(post_kill),
                   "mismatches": len(mism), "errors": errs[0],
                   "detect_ms": detect_ms,
                   "post_kill_p99_ms": round(float(
                       np.percentile(pk, 99)), 1)}}
        print("\n" + json.dumps(out))
        ok = (not mism and legs["cluster"]["n"] > 0
              and len(post_kill) > 0 and speedup >= 2.0)
        sys.exit(0 if ok else 1)
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        for ctx in (broker, single):
            if ctx is not None:
                ctx.close()
        shutil.rmtree(root, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--url", default="http://127.0.0.1:8082")
    ap.add_argument("--threads", type=int, default=None,
                    help="concurrent client threads (default 8; "
                    "--cluster defaults to 32 — a dashboard storm needs "
                    "more users than distinct queries for per-node "
                    "dedup to bite)")
    ap.add_argument("--duration", type=float, default=30.0)
    ap.add_argument("--sql", action="append", default=None,
                    help="query to run (repeatable); default: built-in mix")
    ap.add_argument("--flight", action="store_true",
                    help="drive the Arrow Flight SQL endpoint (the BI "
                    "wire path) instead of HTTP JSON")
    ap.add_argument("--selfcontained", action="store_true",
                    help="start an in-process server on a synthetic dataset")
    ap.add_argument("--tpch", type=float, default=None, metavar="SF",
                    help="serve the TPC-H store from the bench cache at "
                    "this scale factor and run a BI dashboard query mix "
                    "through BOTH HTTP and Flight on the same data, "
                    "reporting the two side by side (VERDICT r4 item 6)")
    ap.add_argument("--hotcold", type=int, default=0, metavar="N",
                    help="repeated-query result-cache loop: each query "
                    "once cold then N warm repeats; reports hit rate "
                    "(from /metadata/cache) and cold vs warm p50/p99 "
                    "(HTTP only; first cold run includes compile)")
    ap.add_argument("--rollup", type=int, default=0, metavar="N",
                    help="in-process base-vs-rollup comparison on a "
                    "synthetic dataset: N timed reps per query with the "
                    "planner rewrite off, then on (caches disabled); "
                    "reports rewrite hit rate and p50/p99 side by side")
    ap.add_argument("--coldtier", action="store_true",
                    help="in-process cold-tier comparison: checkpoint a "
                    "synthetic store, capture unbudgeted answers, then "
                    "replay the mix through a tiered recovery under "
                    "--budget bytes (cold pass + hot reps); reports "
                    "cold/hot p50/p99, hit rate, bytes faulted, and "
                    "prefetch overlap (differential mismatch -> exit 1)")
    ap.add_argument("--budget", type=int, default=1 << 20, metavar="BYTES",
                    help="hot-set byte budget for --coldtier/--encoded "
                    "(default 1 MiB — far under the synthetic store)")
    ap.add_argument("--encoded", action="store_true",
                    help="encoded-vs-raw differential: checkpoint the "
                    "synthetic store raw and with sdot.encode.enabled, "
                    "replay the mix through both tiered recoveries at "
                    "the same --budget, check every reply against "
                    "unbudgeted eager answers (mismatch -> exit 1); "
                    "reports compression ratio, bytes faulted, and "
                    "hot-set residency per leg")
    ap.add_argument("--coldstart", action="store_true",
                    help="warm vs cold startup-to-first-result: build + "
                    "checkpoint a synthetic store, then time a fresh "
                    "context's deep-storage recovery + first query "
                    "against the live context's first query "
                    "(differential: answers must match)")
    ap.add_argument("--sharedscan", action="store_true",
                    help="in-process shared-scan comparison: K client "
                    "threads replay the TPC-H dashboard mix (scale from "
                    "--tpch, default SF1) with query coalescing off then "
                    "on; reports qps/p50/p99, coalescing rate, and device "
                    "dispatches per leg; every reply is differentially "
                    "checked against sequential answers (mismatch -> "
                    "exit 1)")
    ap.add_argument("--window", type=float, default=None, metavar="MS",
                    help="sdot.wlm.batch.window.ms (micro-batch hold "
                    "window) for --sharedscan (default 8ms) and for the "
                    "historicals in --cluster (default 25ms)")
    ap.add_argument("--mesh", action="store_true",
                    help="in-process multi-chip mesh differential: replay "
                    "concurrent fused storms over a TPC-H flat subset "
                    "through a single-device engine and a mesh engine "
                    "sharding waves across every local device (needs >1 "
                    "device — set XLA_FLAGS=--xla_force_host_platform_"
                    "device_count=8 to emulate); every reply checked "
                    "against sequential answers (mismatch -> exit 1); "
                    "reports the scaling ratio and merge-collective "
                    "counters; with --cluster N also storms an in-process "
                    "broker over N meshed historical subprocesses")
    ap.add_argument("--joins", action="store_true",
                    help="device join-tier differential under storm: "
                    "star-unservable queries (fact-to-fact, self-join "
                    "funnel, non-equi range) through the broadcast tier, "
                    "every reply checked against the host pandas tier "
                    "and required to have engaged a join tier; with "
                    "--cluster N an in-process exchange leg forces the "
                    "partitioned tier and reports per-leg shuffle-bytes "
                    "counter deltas (exit 1 on any mismatch)")
    ap.add_argument("--windows", action="store_true",
                    help="window post-pass + KLL percentile differential "
                    "under storm: OVER(...) statements (ranks over a "
                    "GROUP BY base, moving frames / lag over row-level "
                    "scans) checked per-reply against exact pandas "
                    "references, percentile_approx checked against exact "
                    "order statistics within sdot.quantile.rank_bound; "
                    "with --cluster N the same storm runs through a "
                    "broker over N in-process historicals with scatter "
                    "required and broker percentile answers required "
                    "byte-identical to a single-process engine (exit 1 "
                    "on any mismatch or out-of-bound estimate)")
    ap.add_argument("--cluster", type=int, default=0, metavar="N",
                    help="multi-process distributed-serving benchmark: "
                    "checkpoint a synthetic store, spawn N historical "
                    "subprocesses over it, scatter the query mix through "
                    "an in-process broker vs a single-process engine "
                    "(caches off), then kill -9 one node mid-storm; "
                    "reports fan-out, merge latency, per-node coalesce "
                    "rates, failover detection, and the qps ratio "
                    "(exit 0 needs zero mismatches and >= 2x qps)")
    ap.add_argument("--ingest", action="store_true",
                    help="streaming-ingest benchmark: producer threads "
                    "stream keyed batches through the WAL with group "
                    "commit off then on (rows/s, ACK p50/p99, frames "
                    "per fsync, read-your-writes probes; every leg "
                    "differentially checked live and after recovery); "
                    "with --cluster N the stream also runs through an "
                    "in-process broker over N historicals, timing "
                    "ACK-to-visible staleness (exit 0 needs zero "
                    "mismatches and grouped >= serialized rows/s)")
    ap.add_argument("--chaos", action="store_true",
                    help="seeded fault-injection differential: an "
                    "in-process two-node cluster runs the dashboard mix "
                    "under a FaultPlan derived from --seed (RPC drops/"
                    "delays/corruption, breaker trips, hedges, a "
                    "replication-1 partial outage, torn WAL appends, a "
                    "cold-tier CRC flip, WLM shed); strict replies must "
                    "match a single-process reference, degraded replies "
                    "the reference restricted to surviving shards; "
                    "prints a seed-stable replay digest (exit 1 on any "
                    "mismatch)")
    ap.add_argument("--seed", type=int, default=42,
                    help="FaultPlan seed for --chaos: the same seed "
                    "replays the same fault schedule and digest")
    ap.add_argument("--wlm", action="store_true",
                    help="in-process overload comparison: interactive + "
                    "heavy query mix at 4x the interactive lane's "
                    "concurrency with workload management off then on; "
                    "reports per-class p50/p99 and shed rate (caches "
                    "off, fixed seed)")
    args = ap.parse_args()
    if args.threads is None:
        # the join legs measure the tier, not client fan-in: every
        # worker drives a full device build+probe (or a scatter), so a
        # dashboard-storm thread count would just queue on the device
        args.threads = 8 if (args.joins or args.windows) \
            else (32 if args.cluster else 8)

    if args.chaos:
        return run_chaos(args)
    if args.ingest:
        return run_ingest(args)
    if args.mesh:
        return run_mesh(args)
    if args.joins:
        return run_joins(args)
    if args.windows:
        return run_windows(args)
    if args.cluster:
        return run_cluster(args)
    if args.coldstart:
        return run_coldstart(args)
    if args.coldtier:
        return run_coldtier(args)
    if args.encoded:
        return run_encoded(args)
    if args.sharedscan:
        return run_sharedscan(args)
    if args.wlm:
        return run_wlm(args)
    if args.rollup:
        return run_rollup(args)
    if args.tpch is not None:
        return run_tpch_compare(args)

    queries = args.sql or DEFAULT_QUERIES
    server = None
    if args.selfcontained:
        sys.path.insert(0, ".")
        import spark_druid_olap_tpu as sdot
        from spark_druid_olap_tpu.server.http import SqlServer
        # statement (plan/cplan) caches off: measured reps must replan,
        # not replay a compiled-plan lookup (the result cache stays on —
        # --hotcold measures exactly that layer)
        ctx = sdot.Context({"sdot.plan.cache.enabled": False})
        ctx.ingest_dataframe("sales", _synthetic_sales(), time_column="ts")
        if args.flight:
            from spark_druid_olap_tpu.server.flight import SdotFlightServer
            # FlightServerBase serves from construction; .serve() would
            # just block this thread
            server = SdotFlightServer(ctx, "grpc://127.0.0.1:0")
            args.url = f"grpc://127.0.0.1:{server.port}"
        else:
            server = SqlServer(ctx, port=0)
            server.start()
            args.url = f"http://127.0.0.1:{server.port}"
        if not args.hotcold:
            warm = make_flight_caller(args.url) if args.flight \
                else make_http_caller(args.url)
            for q in queries:    # compile/warm before measuring
                warm(q)

    if args.hotcold:
        if args.flight:
            sys.exit("--hotcold drives the HTTP endpoint "
                     "(it reads /metadata/cache)")
        try:
            ok = run_hotcold(make_http_caller(args.url), queries,
                             args.url, iters=args.hotcold)
        finally:
            if server is not None:
                server.stop()
        sys.exit(0 if ok else 1)

    if args.flight:
        if args.url.startswith("http://"):
            # flight is gRPC; the HTTP default (or a pasted http URL)
            # would fail on the scheme in every worker thread
            args.url = "grpc://" + args.url[len("http://"):]
            print(f"[loadtest] --flight: using {args.url}")

        def make_caller(url=args.url):
            return make_flight_caller(url)
    else:
        def make_caller(url=args.url):
            return make_http_caller(url)

    try:
        total, errs, _, _ = run(make_caller, queries, args.threads,
                                args.duration)
    finally:
        if server is not None:
            try:
                server.stop()
            except Exception:   # noqa: BLE001 — flight server shutdown
                server.shutdown()
    sys.exit(1 if (total == 0 or errs > total * 0.01) else 0)


if __name__ == "__main__":
    main()
