"""Benchmark driver: full TPC-H 22-query suite on the star-schema index,
single chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Headline value: geometric-mean per-query WALL latency (ms) over the
22-query suite at SDOT_BENCH_SF. A dispatch-floor-adjusted geomean (the
fixed per-dispatch host<->device round trip, measured with a trivial
compiled device query and subtracted from engine-mode timings) is also
reported, clearly labelled, as "adjusted_geomean_ms".

vs_baseline: the reference's Druid-accelerated TPC-H SF10 numbers on a
4-node cluster (BASELINE.md / docs/benchmark/BenchMarkDetails.org:140-163)
for the five published full-table queries {Q1, Q3, Q5, Q7, Q8} — geomean
over those queries of (our lineitem-rows/sec) / (their 59,986,052 rows /
published ms), i.e. per-chip scan-throughput ratio at possibly different
scale factors. Computed from UNADJUSTED wall time, like the reference's
end-to-end latencies.

Backend: the one JAX gives. A measurement path that finds no chip fails:
the script exits non-zero unless that backend is a TPU, or
SDOT_BENCH_PLATFORM=cpu asked for the CPU by name (a functional run; its
JSON records "platform": "cpu"). A failed run prints a JSON line with an
"error" field AND exits non-zero.

Env knobs: SDOT_BENCH_SF (default 1.0), SDOT_BENCH_REPS (default 5),
SDOT_BENCH_QUERIES (comma list, default all 22), SDOT_BENCH_PLATFORM
(only "cpu" means anything). Per-query detail goes to stderr; stdout
carries only the JSON line.
"""

import json
import os
import sys
import time

import numpy as np


def log(msg):
    print(msg, file=sys.stderr, flush=True)


# -----------------------------------------------------------------------------
# backend: the one JAX gives (see module docstring)
# -----------------------------------------------------------------------------

def require_backend() -> str:
    """Platform of JAX's default backend; exits non-zero unless it is a
    TPU or SDOT_BENCH_PLATFORM=cpu asked for the CPU by name."""
    import jax
    want_cpu = os.environ.get("SDOT_BENCH_PLATFORM", "").strip() == "cpu"
    if want_cpu:
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    log(f"backend={dev.platform} kind={dev.device_kind} "
        f"devices={len(jax.devices())}")
    if dev.platform != "tpu" and not want_cpu:
        log(f"bench: JAX found no TPU (default device: {dev.platform}); "
            f"SDOT_BENCH_PLATFORM=cpu runs the suite on the CPU by name")
        sys.exit(2)
    return dev.platform


# reference Druid avg ms, TPC-H SF10 (BASELINE.md table 1)
BASELINE_MS = {"q1": 18340.0, "q3": 10669.0, "q5": 16722.0,
               "q7": 862.0, "q8": 20429.0}
BASELINE_ROWS = 59_986_052

DROP_COLS = [
    "l_comment", "o_comment", "c_comment", "s_comment", "ps_comment",
    "cn_comment", "cr_comment", "sn_comment", "sr_comment",
    "c_address", "s_address", "o_clerk",
]

ALL22 = ["q1", "q2", "q3", "q4", "q5", "q6", "q7", "q8", "q9", "q10",
         "q11", "q12", "q13", "q14", "q15", "q16", "q17", "q18", "q19",
         "q20", "q21", "q22"]


def cache_dir():
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     ".bench_cache")
    os.makedirs(d, exist_ok=True)
    return d


def _stream_sf() -> float:
    """Scale factor at/above which the flat index is built and ingested
    out-of-core (chunked flatten to Parquet + row-group streaming ingest)
    instead of through whole-frame pandas."""
    return float(os.environ.get("SDOT_BENCH_STREAM_SF", "3"))


def build_tables(sf: float):
    """Generate (or load cached) base tables + the flat index.

    Returns (tables, flat_df_or_None, flat_path, n_flat_rows): at/above
    SDOT_BENCH_STREAM_SF the flat index exists only as a Parquet file
    (flat_df is None) — the out-of-core regime.
    """
    import pandas as pd
    from spark_druid_olap_tpu.tools import tpch
    d = cache_dir()
    names = ["lineitem", "orders", "partsupp", "part", "supplier",
             "customer", "nation", "region"]
    paths = {n: os.path.join(d, f"tpch_{n}_sf{sf}.parquet") for n in names}
    flat_path = os.path.join(d, f"tpch_flat_sf{sf}.parquet")
    streaming = sf >= _stream_sf()
    if all(os.path.exists(p) for p in paths.values()) and \
            os.path.exists(flat_path):
        log(f"loading cached tables from {d}")
        tables = {n: pd.read_parquet(p) for n, p in paths.items()}
        if streaming:
            import pyarrow.parquet as pq
            n_flat = pq.ParquetFile(flat_path).metadata.num_rows
            return tables, None, flat_path, n_flat
        flat = pd.read_parquet(flat_path)
        return tables, flat, flat_path, len(flat)
    t0 = time.perf_counter()
    tables = tpch.generate(sf)
    log(f"generated SF{sf}: lineitem {len(tables['lineitem']):,} rows "
        f"in {time.perf_counter() - t0:.1f}s")
    li_path = paths["lineitem"]
    try:
        for n, p in paths.items():
            tables[n].to_parquet(p)
    except Exception as e:
        log(f"cache write failed ({e}); continuing")
        if streaming:
            # the streamed flatten reads lineitem back from Parquet; a
            # failed/partial cache write must not be silently reused
            import tempfile
            li_path = os.path.join(tempfile.mkdtemp(prefix="sdot_li_"),
                                   "lineitem.parquet")
            tables["lineitem"].to_parquet(li_path)
    if streaming:
        t0 = time.perf_counter()
        n_flat = tpch.flatten_stream(tables, li_path, flat_path,
                                     batch_rows=1 << 21,
                                     drop_columns=DROP_COLS)
        log(f"streamed flatten: {n_flat:,} rows in "
            f"{time.perf_counter() - t0:.1f}s")
        return tables, None, flat_path, n_flat
    flat = tpch.flatten(tables)
    flat = flat.drop(columns=[c for c in DROP_COLS if c in flat.columns])
    try:
        flat.to_parquet(flat_path)
    except Exception as e:
        log(f"cache write failed ({e}); continuing")
    return tables, flat, flat_path, len(flat)


def _bench_config():
    """Session config for MEASURED contexts: the semantic result cache and
    the compiled-statement (plan/cplan) caches would serve warm reps from
    memory, so the reported latency would measure the cache, not the
    engine. Set ONCE at context creation — toggling mid-run would change
    the config fingerprint and thrash the session result caches."""
    return {"sdot.cache.enabled": False,
            "sdot.plan.cache.enabled": False}


def setup(sf: float):
    import spark_druid_olap_tpu as sdot
    from spark_druid_olap_tpu.tools import tpch
    tables, flat, flat_path, n_rows = build_tables(sf)
    ctx = sdot.Context(_bench_config())
    t0 = time.perf_counter()
    if flat is None:
        ctx.ingest_parquet_stream("tpch_flat", flat_path,
                                  time_column="l_shipdate",
                                  target_rows=1 << 20,
                                  batch_rows=1 << 21)
    else:
        ctx.ingest_dataframe("tpch_flat", flat, time_column="l_shipdate",
                             target_rows=1 << 20)
    del flat
    for name, df in tables.items():
        if name in ("nation", "region"):
            continue
        tcol = {"lineitem": "l_shipdate", "orders": "o_orderdate"}.get(name)
        ctx.ingest_dataframe(name, df, time_column=tcol, target_rows=1 << 20)
    for name, df in tpch.nation_region_views(tables).items():
        ctx.ingest_dataframe(name, df)
    # second star at partsupp grain (q2/q11/q16/q20-class pushdown)
    ctx.ingest_dataframe("partsupp_flat", tpch.flatten_partsupp(tables),
                         target_rows=1 << 20)
    ctx.register_star_schema(tpch.partsupp_star_schema("partsupp_flat"))
    ctx.register_star_schema(tpch.star_schema("tpch_flat"))
    log(f"ingest: {time.perf_counter() - t0:.1f}s "
        f"({ctx.store.get('tpch_flat').num_segments} flat segments)")
    return ctx, n_rows


def measure_floor(ctx, reps: int) -> float:
    """Fixed per-dispatch overhead: a compiled trivial device query, timed
    end-to-end (dominated by the host<->device round trip)."""
    q = ("select count(*) as c from supplier where s_suppkey = 1"
         if "supplier" in ctx.store.names()
         else "select count(*) as c from lineorder where lo_orderkey = 1")
    ctx.sql(q)
    ts = []
    for _ in range(max(reps, 5)):
        t0 = time.perf_counter()
        ctx.sql(q)
        ts.append(time.perf_counter() - t0)
    floor = float(np.median(ts)) * 1000
    log(f"dispatch floor: {floor:.1f}ms")
    return floor


def setup_ssb(sf: float):
    """SSB suite (SDOT_BENCH_SUITE=ssb): 13 star-join queries on the
    denormalized lineorder index (BASELINE config 3). At/above
    SDOT_BENCH_STREAM_SF (SF30 = 180M rows) the lineorder fact and the
    flat index are generated and ingested out-of-core, cached in
    .bench_cache like the TPC-H SF10 path."""
    import spark_druid_olap_tpu as sdot
    from spark_druid_olap_tpu.tools import ssb
    ctx = sdot.Context(_bench_config())
    t0 = time.perf_counter()
    if sf >= _stream_sf():
        import pandas as pd
        import pyarrow.parquet as pq
        d = cache_dir()
        lo_path = os.path.join(d, f"ssb_lineorder_sf{sf}.parquet")
        flat_path = os.path.join(d, f"ssb_flat_sf{sf}.parquet")
        dim_names = ["date", "customer", "supplier", "part"]
        dim_paths = {n: os.path.join(d, f"ssb_{n}_sf{sf}.parquet")
                     for n in dim_names}
        cached = all(os.path.exists(p) for p in
                     [flat_path, *dim_paths.values()])
        if cached:
            log(f"loading cached SSB SF{sf} from {d}")
            dims = {n: pd.read_parquet(p) for n, p in dim_paths.items()}
        else:
            dims, n_lo = ssb.generate_stream(sf, lo_path)
            log(f"ssb SF{sf}: streamed {n_lo:,} lineorder rows in "
                f"{time.perf_counter() - t0:.1f}s")
            t1 = time.perf_counter()
            n_flat = ssb.flatten_stream(dims, lo_path, flat_path,
                                        batch_rows=1 << 21)
            log(f"streamed flatten: {n_flat:,} rows in "
                f"{time.perf_counter() - t1:.1f}s")
            try:
                for n, p in dim_paths.items():
                    dims[n].to_parquet(p)
            except Exception as e:   # noqa: BLE001
                log(f"dim cache write failed ({e}); continuing")
        n = pq.ParquetFile(flat_path).metadata.num_rows
        ctx.ingest_parquet_stream("ssb_flat", flat_path,
                                  time_column="lo_orderdate",
                                  target_rows=1 << 20,
                                  batch_rows=1 << 21)
        # base lineorder (raw 6M*sf fact) is NOT ingested in the
        # out-of-core regime: all 13 SSB queries are star joins that
        # collapse onto the flat index (bench asserts mode=engine)
        for name, df in dims.items():
            ctx.ingest_dataframe(name, df, target_rows=1 << 20)
        ctx.register_star_schema(ssb.star_schema("ssb_flat"))
    else:
        tables, flat = ssb.setup_context(ctx, sf=sf, target_rows=1 << 20)
        n = len(flat)
    log(f"ssb SF{sf}: {n:,} lineorder rows, ingest+gen "
        f"{time.perf_counter() - t0:.1f}s")
    return ctx, n, ssb.QUERIES


def metric_name(suite, sf):
    return f"{suite}_sf{sf}_geomean_latency_ms"


def fail_json(suite, sf, reason):
    """Emit a diagnosable JSON line, then exit non-zero."""
    out = {
        "metric": metric_name(suite, sf),
        "value": None,
        "unit": "ms",
        "vs_baseline": 0.0,
        "error": reason,
    }
    print(json.dumps(out), flush=True)
    sys.exit(1)


def numerics_check():
    """Integer-exactness differential check on the LIVE backend — proves the
    lane/limb aggregation routes are exact under real TPU dtypes (f64
    unsupported, i64 emulated): values past the f32 2^24 cliff, sums past
    2^32. Returns (ok, detail)."""
    import pandas as pd
    import spark_druid_olap_tpu as sdot
    r = np.random.default_rng(5)
    n = 200_000
    df = pd.DataFrame({
        "g": r.choice(["a", "b", "c"], n),
        "big": (r.integers(0, 1 << 30, n) + (1 << 24)).astype(np.int64),
        "sgn": r.integers(-(1 << 26), 1 << 26, n).astype(np.int64),
    })
    ctx = sdot.Context()
    ctx.ingest_dataframe("numcheck", df, target_rows=1 << 16)
    res = ctx.sql(
        "select g, sum(big) as sb, sum(sgn) as ss, min(big) as mb, "
        "max(big) as xb, count(*) as n from numcheck group by g"
    ).to_pandas().sort_values("g").reset_index(drop=True)
    mode = ctx.history.entries()[-1].stats.get("mode", "?")
    gb = df.groupby("g")
    want = pd.DataFrame({
        "sb": gb["big"].sum(), "ss": gb["sgn"].sum(),
        "mb": gb["big"].min(), "xb": gb["big"].max(), "n": gb.size(),
    }).reset_index()
    for c in ("sb", "ss", "mb", "xb", "n"):
        got = res[c].to_numpy().astype(np.int64)
        if not np.array_equal(got, want[c].to_numpy()):
            return False, f"{c}: got {got.tolist()} " \
                          f"want {want[c].tolist()} (mode={mode})"

    # device result-reduction epilogues on the LIVE backend: top-k
    # selection, HAVING compaction, correlated-lookup broadcast join
    df2 = pd.DataFrame({
        "k": r.integers(0, 20_000, n),
        "q": r.integers(1, 50, n).astype(np.int64),
    })
    ctx.ingest_dataframe("epicheck", df2, target_rows=1 << 16)
    g2 = df2.groupby("k")["q"].sum()
    topk = ctx.sql("select k, sum(q) as s from epicheck group by k "
                   "order by s desc limit 5").to_pandas()
    st = ctx.history.entries()[-1].stats
    want_top = g2.sort_values(ascending=False).head(5).to_numpy()
    if not np.array_equal(topk["s"].to_numpy().astype(np.int64), want_top):
        return False, f"topk: got {topk['s'].tolist()} " \
                      f"want {want_top.tolist()}"
    if not st.get("topk_device"):
        return False, f"topk epilogue did not engage ({st})"
    hav = ctx.sql("select k, sum(q) as s from epicheck group by k "
                  "having sum(q) > 400").to_pandas()
    want_h = g2[g2 > 400]
    if len(hav) != len(want_h) or \
            not np.array_equal(np.sort(hav["s"].to_numpy().astype(np.int64)),
                               np.sort(want_h.to_numpy())):
        return False, f"having: {len(hav)} rows want {len(want_h)}"
    corr = ctx.sql(
        "select count(*) as n from epicheck "
        "where q < (select 0.5 * avg(i_q) from "
        "  (select k as i_k, q as i_q from epicheck) i "
        "   where i_k = k)").to_pandas()
    thr = df2.groupby("k")["q"].mean() * 0.5
    want_c = int((df2.q < df2.k.map(thr)).sum())
    if int(corr["n"][0]) != want_c:
        return False, f"lookup: got {int(corr['n'][0])} want {want_c}"
    return True, f"exact incl. topk/having/lookup epilogues (mode={mode})"


def run_pallas_ab(reps: int = 3):
    """Pallas wave A-B on a canned 4-lane shared-scan storm.

    Runs the same fused wave through the jaxpr path (wave off) and the
    hand-scheduled pallas kernel (wave on), differentially checks the
    answers, and reports per-leg wall ms plus the wave counter deltas.
    Storms need concurrent queries, so this uses a small dedicated store
    rather than the suite context. On a plain-CPU backend without
    SDOT_PALLAS=interpret the wave never engages — records
    {"available": False}. In interpret mode the ON leg runs the kernel
    through the pallas interpreter (a correctness vehicle, not a fast
    one), so "speedup" below 1 there is expected and the "interpret"
    flag says so.
    """
    import threading

    from spark_druid_olap_tpu.ops import pallas_groupby as PG
    if not (os.environ.get("SDOT_PALLAS", "") == "interpret"
            or PG._tpu_backend()):
        return {"available": False}

    import pandas as pd
    from spark_druid_olap_tpu.ir import spec as S
    from spark_druid_olap_tpu.parallel.executor import QueryEngine
    from spark_druid_olap_tpu.segment.ingest import ingest_dataframe
    from spark_druid_olap_tpu.segment.store import SegmentStore
    from spark_druid_olap_tpu.utils.config import Config

    rng = np.random.default_rng(7)
    n = 40_000
    df = pd.DataFrame({
        "ts": pd.Timestamp("2015-01-01")
        + pd.to_timedelta(rng.integers(0, 365 * 24 * 3600, n), unit="s"),
        "region": rng.choice(["east", "west", "north", "south"], n),
        "product": rng.choice([f"p{i:03d}" for i in range(50)], n),
        "status": rng.choice(["O", "F"], n),
        "qty": rng.integers(1, 52, n).astype(np.int64),
        "price": rng.uniform(1.0, 100.0, n),
    })
    store = SegmentStore()
    store.register(ingest_dataframe("sales", df, time_column="ts",
                                    target_rows=4096))
    aggs = (S.AggregationSpec("doublesum", "revenue", field="price"),
            S.AggregationSpec("longsum", "units", field="qty"),
            S.AggregationSpec("count", "n"))
    shared = S.SelectorFilter("status", "O")
    specs = [
        S.GroupByQuerySpec("sales", (S.DimensionSpec("region", "region"),),
                           aggs, filter=shared),
        S.GroupByQuerySpec(
            "sales", (S.DimensionSpec("region", "region"),), aggs,
            filter=S.LogicalFilter("and", (
                shared, S.BoundFilter("qty", lower=10, numeric=True)))),
        S.TimeseriesQuerySpec("sales", aggs,
                              granularity=S.Granularity("month"),
                              filter=shared),
        S.TopNQuerySpec("sales", S.DimensionSpec("product", "product"),
                        "revenue", 7, aggs, filter=shared),
    ]
    eng = QueryEngine(store, config=Config({
        "sdot.sharedscan.enabled": True,
        "sdot.wlm.batch.window.ms": 500.0,
        "sdot.wlm.enabled": False,
        "sdot.pallas.wave.enabled": False,
    }))

    def run_batch():
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

    def leg(wave):
        eng.config.set("sdot.pallas.wave.enabled", bool(wave))
        p0 = eng.sharedscan.stats()["pallas"]
        run_batch()                     # warm: compile this leg's program
        frames, ts = None, []
        for _ in range(max(reps, 1)):
            t0 = time.perf_counter()
            frames = run_batch()
            ts.append(time.perf_counter() - t0)
        p1 = eng.sharedscan.stats()["pallas"]
        delta = {k: int(p1[k]) - int(p0[k])
                 for k in ("launches", "tiles", "fallbacks")}
        return frames, float(np.median(ts)) * 1000, delta

    off_frames, off_ms, off_delta = leg(False)
    on_frames, on_ms, on_delta = leg(True)

    match = True
    for a, b in zip(off_frames, on_frames):
        aa = a.reset_index(drop=True)
        bb = b.reset_index(drop=True)
        if list(aa.columns) != list(bb.columns) or len(aa) != len(bb):
            match = False
            continue
        for c in aa.columns:
            av, bv = aa[c].to_numpy(), bb[c].to_numpy()
            if av.dtype.kind in "fc":
                if not np.allclose(av.astype(float), bv.astype(float),
                                   rtol=1e-4, atol=1e-8, equal_nan=True):
                    match = False
            elif not np.array_equal(av, bv):
                match = False
    out = {"available": True, "lanes": len(specs),
           "interpret": bool(PG._interpret()),
           "off_ms": round(off_ms, 2), "on_ms": round(on_ms, 2),
           "speedup": round(off_ms / max(on_ms, 1e-9), 3),
           "pallas_off": off_delta, "pallas_on": on_delta,
           "answers_match": bool(match)}
    log(f"pallas A-B: off {off_ms:.1f}ms / on {on_ms:.1f}ms "
        f"(x{out['speedup']}, launches {on_delta['launches']}, "
        f"match={match})")
    return out


def run_mesh_ab(reps: int = 3):
    """Multi-chip mesh A-B: the same fused shared-scan storms at every
    power-of-two device count the process exposes.

    Two canned storms over a TPC-H flat subset run coalesced at
    n ∈ {1, 2, 4, 8} devices (1 = no mesh, the single-device baseline;
    the cost model is off so the mesh decision is unconditional).
    Reports per-device-count median wall ms and the geomean over the
    storm shapes, the merge-collective bytes the mesh tier statically
    accounts (ring convention: merged payload x (n-1) x waves), mesh
    dispatch counters, and an answers-match gate against the 1-device
    leg. On a real pod this measures ICI scaling; under
    ``--xla_force_host_platform_device_count=8`` (the CI recipe in
    docs/MESH.md) the wall numbers measure host-core contention, not
    interconnect — the accounting + match gate are the pinned part.
    """
    import threading

    import jax

    counts = [n for n in (1, 2, 4, 8) if n <= len(jax.devices())]
    if not counts or counts[-1] < 2:
        return {"available": False,
                "reason": "single-device process; set XLA_FLAGS="
                          "--xla_force_host_platform_device_count=8"}

    from spark_druid_olap_tpu.ir import spec as S
    from spark_druid_olap_tpu.parallel.executor import QueryEngine
    from spark_druid_olap_tpu.parallel.mesh import make_mesh
    from spark_druid_olap_tpu.tools import tpch
    from spark_druid_olap_tpu.utils.config import Config

    sf = float(os.environ.get("SDOT_BENCH_MESH_SF", "0.01"))
    import spark_druid_olap_tpu as sdot
    ctx = sdot.Context()
    tpch.setup_context(ctx, sf=sf, target_rows=2048, flat_only=True)
    store = ctx.store

    aggs = (S.AggregationSpec("doublesum", "rev", field="l_extendedprice"),
            S.AggregationSpec("longsum", "q", field="l_quantity"),
            S.AggregationSpec("count", "n"),
            S.AggregationSpec("doublemax", "mx", field="l_extendedprice"))
    storms = {
        "flag_status": [
            S.GroupByQuerySpec(
                "tpch_flat",
                (S.DimensionSpec("l_returnflag", "l_returnflag"),
                 S.DimensionSpec("l_linestatus", "l_linestatus")), aggs),
            S.GroupByQuerySpec(
                "tpch_flat", (S.DimensionSpec("l_shipmode", "l_shipmode"),),
                aggs, filter=S.SelectorFilter("l_returnflag", "N")),
            S.TimeseriesQuerySpec("tpch_flat", aggs,
                                  granularity=S.Granularity("month")),
        ],
        "sketch_mix": [
            S.GroupByQuerySpec(
                "tpch_flat", (S.DimensionSpec("l_shipmode", "l_shipmode"),),
                aggs + (S.AggregationSpec("cardinality", "uo",
                                          field="l_orderkey"),)),
            S.GroupByQuerySpec(
                "tpch_flat",
                (S.DimensionSpec("l_returnflag", "l_returnflag"),),
                aggs + (S.AggregationSpec("thetasketch", "sk",
                                          field="l_suppkey"),)),
        ],
    }

    def run_batch(eng, specs):
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

    def leg(n):
        eng = QueryEngine(store, config=Config({
            "sdot.sharedscan.enabled": True,
            "sdot.wlm.batch.window.ms": 500.0,
            "sdot.wlm.enabled": False,
            "sdot.querycostmodel.enabled": False,
        }), mesh=make_mesh(n) if n > 1 else None)
        frames, storm_ms = {}, {}
        for name, specs in storms.items():
            run_batch(eng, specs)       # warm: compile this leg's program
            ts = []
            for _ in range(max(reps, 1)):
                t0 = time.perf_counter()
                frames[name] = run_batch(eng, specs)
                ts.append(time.perf_counter() - t0)
            storm_ms[name] = float(np.median(ts)) * 1000
        mst = eng.sharedscan.stats()["mesh"]
        gm = float(np.exp(np.mean([np.log(max(v, 1e-9))
                                   for v in storm_ms.values()])))
        return frames, {
            "geomean_ms": round(gm, 2),
            "storm_ms": {k: round(v, 2) for k, v in storm_ms.items()},
            "collective_bytes": int(mst["collective_bytes"]),
            "mesh_dispatches": int(mst["dispatches"]),
            "mesh_groups": int(mst["groups"]),
            "fallbacks": dict(mst["fallbacks"]),
        }

    def frames_match(a, b):
        aa = a.reset_index(drop=True)
        bb = b.reset_index(drop=True)
        if list(aa.columns) != list(bb.columns) or len(aa) != len(bb):
            return False
        for c in aa.columns:
            av, bv = aa[c].to_numpy(), bb[c].to_numpy()
            if av.dtype.kind in "fc":
                if not np.allclose(av.astype(float), bv.astype(float),
                                   rtol=1e-9, atol=1e-12, equal_nan=True):
                    return False
            elif not np.array_equal(av, bv):
                return False
        return True

    base_frames, legs = None, {}
    match = True
    for n in counts:
        frames, stats = leg(n)
        legs[str(n)] = stats
        if base_frames is None:
            base_frames = frames
        else:
            for name in storms:
                for a, b in zip(base_frames[name], frames[name]):
                    match = match and frames_match(a, b)
    gm1 = legs[str(counts[0])]["geomean_ms"]
    gmN = legs[str(counts[-1])]["geomean_ms"]
    out = {"available": True, "device_counts": counts, "legs": legs,
           "scaling_vs_single": round(gm1 / max(gmN, 1e-9), 3),
           "answers_match": bool(match)}
    curve = ", ".join("%ddev %sms" % (n, legs[str(n)]["geomean_ms"])
                      for n in counts)
    log(f"mesh A-B: {curve} (x{out['scaling_vs_single']} at {counts[-1]} "
        f"devices, collective "
        f"{legs[str(counts[-1])]['collective_bytes']}B, match={match})")
    return out


def run_join_ab(reps: int = 3):
    """Device-join-tier A-B over star-unservable queries (join/).

    Three shapes the star rewrite cannot collapse onto the flat fact
    index — a fact-to-fact join, a self-join funnel, and an equi plus
    non-equi range join — run through the broadcast join tier and then
    through the host pandas tier over the SAME stores
    (``sdot.join.enabled`` toggled; the config fingerprint keys every
    cache, so both legs execute for real). Reports per-query median
    wall ms for both legs, the tier's own accounting (mode, build
    bytes, static match width, shuffle bytes), and two gates: every
    query must actually engage the tier (``last_stats["join"]``
    present — a silent host fallback would "pass" while measuring
    nothing) and must answer exactly like the host. The gates are the
    pinned part; on the CPU fallback backend the wall numbers measure
    host-core speed, not device bandwidth.
    """
    import pandas as pd

    import spark_druid_olap_tpu as sdot
    from spark_druid_olap_tpu.utils.config import JOIN_ENABLED

    rng = np.random.default_rng(18)
    n = int(os.environ.get("SDOT_BENCH_JOIN_ROWS", "20000"))
    regions = ["na", "emea", "apac", "latam"]
    orders = pd.DataFrame({
        "ts": (np.datetime64("2024-03-01")
               + rng.integers(0, 90, n).astype("timedelta64[D]")
               ).astype("datetime64[ns]"),
        "order_id": np.arange(n, dtype=np.int64),
        # ~5 orders per user: the self-join's widest build group stays
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
        # duplicate order_ids: some orders ship in several parcels
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

    queries = {
        # fact-to-fact: both sides are event tables, no star edge
        "fact_to_fact": """
            SELECT s.carrier AS c, count(*) AS n, sum(o.amount) AS amt
            FROM orders o JOIN shipments s ON o.order_id = s.order_id
            GROUP BY s.carrier ORDER BY c""",
        # self-join funnel: pairs of orders by the same user where the
        # second is bigger (alias scoping rewrites the legs)
        "self_join_funnel": """
            SELECT a.channel AS c, count(*) AS n
            FROM orders a JOIN orders b
              ON a.user_id = b.user_id AND a.amount < b.amount
            GROUP BY a.channel ORDER BY c""",
        # equi key (region) + non-equi range residual (amount banding)
        "non_equi_range": """
            SELECT r.band AS b, count(*) AS n, sum(o.amount) AS amt
            FROM orders o JOIN rates r
              ON o.region = r.region
             AND o.amount >= r.lo AND o.amount < r.hi
            GROUP BY r.band ORDER BY b""",
    }

    ctx = sdot.Context()
    try:
        ctx.ingest_dataframe("orders", orders, time_column="ts",
                             target_rows=2048)
        ctx.ingest_dataframe("shipments", shipments, time_column="ts",
                             target_rows=1024)
        ctx.ingest_dataframe("rates", rates, time_column="ts",
                             target_rows=64)

        def timed(q):
            ctx.sql(q)                    # warm: compile this leg
            ts = []
            df = None
            for _ in range(max(reps, 1)):
                t0 = time.perf_counter()
                df = ctx.sql(q).to_pandas()
                ts.append(time.perf_counter() - t0)
            return df, float(np.median(ts)) * 1000

        def frames_match(a, b):
            # float tolerance matches the repo's differential comparator
            # (tests/conftest.assert_frames_equal): metrics are stored
            # f32, so device accumulation order differs from the host's
            # f64 pandas sums at ~1e-5 relative on non-x64 backends
            aa = a.reset_index(drop=True)
            bb = b.reset_index(drop=True)
            if list(aa.columns) != list(bb.columns) or len(aa) != len(bb):
                return False
            for c in aa.columns:
                av, bv = aa[c].to_numpy(), bb[c].to_numpy()
                if av.dtype.kind in "fc":
                    if not np.allclose(av.astype(float), bv.astype(float),
                                       rtol=1e-4, atol=1e-6,
                                       equal_nan=True):
                        return False
                elif not np.array_equal(av, bv):
                    return False
            return True

        legs, match = {}, True
        for name, q in queries.items():
            dev, dev_ms = timed(q)
            js = dict(ctx.engine.last_stats.get("join") or {})
            ctx.config.set(JOIN_ENABLED.key, False)
            try:
                host, host_ms = timed(q)
            finally:
                ctx.config.set(JOIN_ENABLED.key, True)
            ok = frames_match(dev, host)
            engaged = bool(js)
            match = match and ok and engaged
            legs[name] = {
                "join_ms": round(dev_ms, 2),
                "host_ms": round(host_ms, 2),
                "speedup_vs_host": round(host_ms / max(dev_ms, 1e-9), 2),
                "mode": js.get("mode"),
                "build_bytes": js.get("build_bytes"),
                "match_width": js.get("match_width"),
                "shuffle_bytes": js.get("shuffle_bytes"),
                "rows": int(len(dev)),
                "tier_engaged": engaged,
                "answers_match": bool(ok),
            }
            log(f"join A-B {name}: {dev_ms:.1f}ms {js.get('mode')} vs "
                f"{host_ms:.1f}ms host (x{legs[name]['speedup_vs_host']}, "
                f"width={js.get('match_width')}, match={ok})")
    finally:
        ctx.close()
    return {"available": True, "n_rows": n, "queries": legs,
            "answers_match": bool(match)}


def run_encode_ab(reps: int = 3):
    """Encoded-vs-raw A-B over the cold tier (encode/ + tier/).

    Builds one synthetic store, checkpoints it twice — raw and with
    ``sdot.encode.enabled`` — then reopens each snapshot through the
    tiered path at the SAME byte budget and replays one aggregation
    mix. Reports the on-disk compression ratio, per-leg wall ms, the
    EFFECTIVE scan rate (LOGICAL bytes scanned per second — the encoded
    leg faults ratio× fewer physical bytes for the same logical scan),
    and the hot-set residency each leg ends with under the shared
    budget (the encoded leg should hold more segment-chunks resident).
    Differential: both legs must return identical frames.
    """
    import shutil
    import tempfile

    import pandas as pd
    import spark_druid_olap_tpu as sdot

    rng = np.random.default_rng(11)
    n = 200_000
    df = pd.DataFrame({
        "ts": pd.Timestamp("2015-01-01")
        + pd.to_timedelta(np.sort(rng.integers(0, 365 * 24 * 3600, n)),
                          unit="s"),
        "region": rng.choice(["east", "west", "north", "south"], n),
        "product": rng.choice([f"p{i:03d}" for i in range(100)], n),
        "status": rng.choice(["O", "F", "P"], n, p=[0.7, 0.2, 0.1]),
        "qty": rng.integers(1, 52, n).astype(np.int64),
        "price": rng.uniform(1.0, 100.0, n),
    })
    queries = [
        "select region, sum(price), sum(qty), count(*) from sales "
        "group by region",
        "select product, sum(price) from sales where status = 'O' "
        "group by product order by sum(price) desc limit 7",
        "select year(ts) y, month(ts) m, count(*) from sales "
        "group by year(ts), month(ts)",
    ]
    root = tempfile.mkdtemp(prefix="sdot-encab-")
    try:
        legs, frames = {}, {}
        budget = None
        for leg, enabled in (("raw", False), ("encoded", True)):
            sub = os.path.join(root, leg)
            seed = sdot.Context({"sdot.persist.path": sub,
                                 "sdot.encode.enabled": enabled})
            seed.ingest_dataframe("sales", df, time_column="ts",
                                  target_rows=8192)
            seed.checkpoint()
            col_bytes = sum(
                c["size"] for c in
                seed.store.get("sales").metadata()["columns"].values())
            seed.close()
            if budget is None:
                # sized off the RAW leg so both legs share one number:
                # raw must evict under it, encoded should mostly fit
                budget = max(1 << 20, int(col_bytes) // 3)
            ctx = sdot.Context({"sdot.persist.path": sub,
                                "sdot.cache.enabled": False,
                                "sdot.plan.cache.enabled": False,
                                "sdot.tier.enabled": True,
                                "sdot.tier.budget.bytes": budget,
                                "sdot.tier.wave.io.bytes": budget // 4})
            frames[leg] = {q: ctx.sql(q).to_pandas() for q in queries}
            ts, logical = [], 0
            for _ in range(max(reps, 1)):
                t0 = time.perf_counter()
                for q in queries:
                    ctx.sql(q)
                    st = ctx.history.entries()[-1].stats
                    logical += int(st.get("bytes_scanned", 0) or 0)
                ts.append(time.perf_counter() - t0)
            last = ctx.history.entries()[-1].stats
            tier_st = (ctx.persist.tier.stats_snapshot()
                       if ctx.persist.tier else {})
            enc_st = last.get("encoding") or {}
            ctx.close()
            ms = float(np.median(ts)) * 1000
            legs[leg] = {
                "wall_ms": round(ms, 2),
                "column_bytes": int(col_bytes),
                "bytes_faulted": int(tier_st.get("bytes_faulted", 0)),
                "hot_entries": int(tier_st.get("hot_entries", 0)),
                "hot_bytes": int(tier_st.get("hot_bytes", 0)),
                # effective = LOGICAL bytes the queries scanned per
                # second of wall; physical fault traffic is ratio× less
                # on the encoded leg
                "effective_scan_gbps": round(
                    (logical / max(len(ts), 1)) / max(ms / 1000, 1e-9)
                    / 1e9, 3),
            }
            if enc_st:
                legs[leg]["encoding"] = enc_st
        match = all(
            _frames_equal(frames["raw"][q], frames["encoded"][q])
            for q in queries)
        enc = legs["encoded"].get("encoding", {})
        out = {"available": True, "budget_bytes": int(budget),
               "ratio": enc.get("ratio"),
               "raw": legs["raw"], "encoded": legs["encoded"],
               "resident_gain": round(
                   legs["encoded"]["hot_entries"]
                   / max(legs["raw"]["hot_entries"], 1), 2),
               "answers_match": bool(match)}
        log(f"encode A-B: ratio {out['ratio']}x, raw "
            f"{legs['raw']['wall_ms']:.1f}ms / encoded "
            f"{legs['encoded']['wall_ms']:.1f}ms, resident "
            f"{legs['raw']['hot_entries']} -> "
            f"{legs['encoded']['hot_entries']} chunks (match={match})")
        return out
    finally:
        shutil.rmtree(root, ignore_errors=True)


def run_window_ab(reps: int = 3):
    """Window post-pass + KLL percentile A-B on a canned store.

    Leg 1 (windows): a storm of OVER(...) statements — ranks over a
    GROUP BY base, moving/cumulative frames and lag over a row-level
    scan base — runs through the device window post-pass, and every
    answer is differentially checked against an exact pandas
    computation of the same window. Leg 2 (percentile): each
    percentile_approx answer is gated against numpy's exact order
    statistics within the sketch's declared rank-error bound
    (sdot.quantile.rank_bound): the estimate must land between the
    exact values at rank (q - eps) and (q + eps). Both checks ship in
    the JSON as hard ok flags; timings compare the device post-pass
    wall against the exact host reference.
    """
    import pandas as pd
    from spark_druid_olap_tpu.context import Context
    from spark_druid_olap_tpu.ops import kll as KLL

    rng = np.random.default_rng(11)
    n = 30_000
    df = pd.DataFrame({
        "ts": pd.Timestamp("2015-01-01")
        + pd.to_timedelta(rng.integers(0, 365 * 24 * 3600, n), unit="s"),
        "id": np.arange(n, dtype=np.int64),   # unique ORDER BY key:
        "region": rng.choice(["east", "west", "north", "south"], n),
        "product": rng.choice([f"p{i:03d}" for i in range(20)], n),
        "qty": rng.integers(1, 52, n).astype(np.int64),
        "price": rng.uniform(1.0, 100.0, n),
    })                                        # ties would make moving
    ctx = Context({"sdot.cache.enabled": False})  # frames order-dependent
    ctx.ingest_dataframe("wsales", df, time_column="ts",
                         target_rows=4096)

    # -- exact pandas references ------------------------------------
    t0 = time.perf_counter()
    agg = (df.groupby(["region", "product"], as_index=False)
             .agg(units=("qty", "sum")))
    agg["r"] = (agg.groupby("region")["units"]
                .rank(method="min", ascending=False).astype(np.int64))
    flt = (df[df["qty"] > 25].sort_values(["region", "id"],
                                          kind="mergesort"))
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
    host_ms = (time.perf_counter() - t0) * 1000

    storm = [
        ("rank_over_groupby",
         "SELECT region, product, SUM(qty) AS units, "
         "RANK() OVER (PARTITION BY region ORDER BY SUM(qty) DESC) AS r "
         "FROM wsales GROUP BY region, product", agg),
        ("moving_sum_scan",
         "SELECT id, region, qty, SUM(qty) OVER (PARTITION BY region "
         "ORDER BY id ROWS BETWEEN 3 PRECEDING AND CURRENT ROW) AS mv "
         "FROM wsales WHERE qty > 25", mv),
        ("lag_scan",
         "SELECT id, region, price, LAG(price, 1) OVER "
         "(PARTITION BY region ORDER BY id) AS prev "
         "FROM wsales WHERE id < 2000", lg),
        ("cumulative_avg_rownum",
         "SELECT id, region, AVG(price) OVER (PARTITION BY region "
         "ORDER BY id) AS cavg, ROW_NUMBER() OVER "
         "(PARTITION BY region ORDER BY id) AS rn "
         "FROM wsales WHERE id < 2000", cum),
    ]

    mismatches = []
    for name, sql, ref in storm:            # cold + differential pass
        got = ctx.sql(sql).to_pandas()
        stats = ctx.history.entries()[-1].stats
        if "window" not in stats:
            mismatches.append(f"{name}: window post-pass did not engage "
                              f"(mode={stats.get('mode')})")
        elif not _frames_equal(got, ref.reset_index(drop=True)):
            mismatches.append(name)
    ts = []
    for _ in range(max(reps, 1)):           # warm: post-pass wall
        t0 = time.perf_counter()
        for _, sql, _ref in storm:
            ctx.sql(sql)
        ts.append(time.perf_counter() - t0)
    window_ms = float(np.median(ts)) * 1000

    # -- percentile leg: KLL vs exact order statistics ---------------
    eps = KLL.rank_bound(ctx.config)
    pct_fail = []
    for q in (0.5, 0.9):
        got = ctx.sql(
            f"SELECT region, PERCENTILE_APPROX(price, {q}) AS p "
            f"FROM wsales GROUP BY region").to_pandas()
        for _, row in got.iterrows():
            vals = np.sort(df.loc[df["region"] == row["region"],
                                  "price"].to_numpy())
            lo = vals[max(int(np.floor((q - eps) * len(vals))), 0)]
            hi = vals[min(int(np.ceil((q + eps) * len(vals))),
                          len(vals) - 1)]
            if not (lo <= float(row["p"]) <= hi):
                pct_fail.append(f"{row['region']}@q{q}: {row['p']:.4f} "
                                f"outside [{lo:.4f}, {hi:.4f}]")

    out = {"available": True, "n_rows": n, "n_statements": len(storm),
           "window_ms": round(window_ms, 2),
           "host_ref_ms": round(host_ms, 2),
           "windows_match": not mismatches,
           "percentile_rank_bound": eps,
           "percentile_within_bound": not pct_fail}
    if mismatches:
        out["window_mismatches"] = mismatches
    if pct_fail:
        out["percentile_failures"] = pct_fail
    log(f"window A-B: {len(storm)} statements {window_ms:.1f}ms device "
        f"post-pass vs {host_ms:.1f}ms host ref "
        f"(match={not mismatches}, percentile_ok={not pct_fail})")
    return out


def _frames_equal(a, b) -> bool:
    """Order-insensitive equality with float tolerance (shared by the
    encode A-B differential)."""
    cols = sorted(a.columns)
    if cols != sorted(b.columns) or len(a) != len(b):
        return False
    a = a[cols].sort_values(cols).reset_index(drop=True)
    b = b[cols].sort_values(cols).reset_index(drop=True)
    for c in cols:
        av, bv = a[c].to_numpy(), b[c].to_numpy()
        if av.dtype.kind in "fc":
            if not np.allclose(av.astype(float), bv.astype(float),
                               rtol=1e-4, atol=1e-8, equal_nan=True):
                return False
        elif not np.array_equal(av, bv):
            return False
    return True


def main():
    sf = float(os.environ.get("SDOT_BENCH_SF", "1.0"))
    reps = int(os.environ.get("SDOT_BENCH_REPS", "5"))
    suite = os.environ.get("SDOT_BENCH_SUITE", "tpch")
    qsel = os.environ.get("SDOT_BENCH_QUERIES", "")

    import jax
    platform = require_backend()
    # the persistent compile cache is placed by Context (utils/compile_cache)
    if platform == "cpu":
        # exact differential math when the CPU was asked for (tests' config)
        jax.config.update("jax_enable_x64", True)

    numerics = None
    if os.environ.get("SDOT_BENCH_CHECK", "1") != "0":
        try:
            ok, detail = numerics_check()
            numerics = {"exact": ok, "detail": detail}
            log(f"numerics check: {'OK' if ok else 'FAILED'} — {detail}")
        except Exception as e:
            numerics = {"exact": False,
                        "detail": f"{type(e).__name__}: {e}"}
            log(f"numerics check crashed: {e}")

    from spark_druid_olap_tpu.tools import tpch

    try:
        if suite == "ssb":
            ctx, n_rows, queries = setup_ssb(sf)
            names = [s.strip() for s in qsel.split(",") if s.strip()] \
                or list(queries)
        else:
            queries = tpch.QUERIES
            names = [s.strip() for s in qsel.split(",")
                     if s.strip()] or ALL22
            ctx, n_rows = setup(sf)
        floor_ms = measure_floor(ctx, reps)
    except Exception as e:
        fail_json(suite, sf,
                  f"setup/ingest failed on '{platform}': "
                  f"{type(e).__name__}: {e}")

    # measured unit costs (VERDICT r4 item 1: calibrate BEFORE bench).
    # SDOT_BENCH_UNIT_COSTS points at scripts/calibrate_chip.py output —
    # the perf gates (compaction, sorted-run, ffl ceiling) then run on
    # constants fit on THIS backend instead of the r3 probe defaults.
    unit_costs = None
    uc_path = os.environ.get("SDOT_BENCH_UNIT_COSTS", "").strip()
    if uc_path:
        try:
            with open(uc_path) as f:
                doc = json.load(f)
            # validate BEFORE the first config.set: a malformed entry must
            # not leave the session half-calibrated while the snapshot
            # claims defaults were used
            fitted = {k: float(v) for k, v in doc.get("fitted", {}).items()}
            if doc.get("backend") not in (None, jax.default_backend()):
                log(f"unit costs in {uc_path} were fit on "
                    f"'{doc.get('backend')}' but this run is "
                    f"'{jax.default_backend()}'; NOT applying")
            else:
                for k, v in fitted.items():
                    ctx.config.set(k, v)
                unit_costs = {"source": uc_path, "values": fitted}
                log(f"applied {len(fitted)} measured unit costs "
                    f"from {uc_path}")
        except Exception as e:   # noqa: BLE001 — calibration is optional
            log(f"unit-cost load failed ({type(e).__name__}: {e}); "
                f"continuing with per-backend defaults")

    # parallel prewarm (VERDICT r2 #10 compile diet): compile-heavy first
    # executions overlap across a thread pool — per-signature compile
    # ownership lets different programs compile concurrently, so the
    # cold suite pays max(compile) depth instead of sum(compile)
    prewarm_s = 0.0
    try:
        n_pre = int(os.environ.get("SDOT_BENCH_PREWARM", "0"))
    except ValueError:
        n_pre = 0
    if sf >= 10:
        # concurrent first binds at SF10+ can transiently exceed the
        # device-cache budget (eviction can't reclaim buffers still
        # referenced by in-flight programs)
        n_pre = min(n_pre, 2)
    if n_pre > 0:
        from concurrent.futures import ThreadPoolExecutor
        t0 = time.perf_counter()
        errs = {}
        with ThreadPoolExecutor(max_workers=n_pre) as pool:
            futs = {pool.submit(ctx.sql, queries[n]): n for n in names}
            for f, n in futs.items():
                try:
                    f.result()
                except Exception as e:   # noqa: BLE001 — timed loop reports
                    errs[n] = f"{type(e).__name__}: {e}"
        prewarm_s = time.perf_counter() - t0
        log(f"parallel prewarm ({n_pre} threads): {prewarm_s:.1f}s"
            + (f", {len(errs)} failed: {errs}" if errs else ""))

    wall_lat, adj_lat = {}, {}
    gbps = {}
    gbps_basis = {}
    ndisp = {}
    klaunch = {}
    zero_dispatch = []
    zero_dispatch_served = []
    fusion_fallback = []
    qphases = {}            # per-query stats["phases"] from the measured rep
    host_overhead = {}      # engine queries: wall minus device dispatch ms

    def _fusion_stats():
        # engine fusion-planner counters (0s until any engine query runs);
        # host-mode suites (numerics) have no sharedscan tier
        try:
            return dict(ctx.engine.sharedscan.stats().get("fusion") or {})
        except Exception:   # noqa: BLE001 — counters are advisory
            return {}

    def _pallas_stats():
        # engine wave-kernel counters (launches/tiles/fallbacks/vmem peak)
        try:
            return dict(ctx.engine.sharedscan.stats().get("pallas") or {})
        except Exception:   # noqa: BLE001 — counters are advisory
            return {}

    cold_total_s = 0.0
    n_engine = 0
    host_queries = []
    suite_t0 = time.perf_counter()
    try:
        budget_s = float(os.environ.get("SDOT_BENCH_TIME_BUDGET", "2400"))
    except ValueError:
        budget_s = 2400.0
    for name in names:
        # queries run as written over the base tables; the planner's
        # star-join collapse routes fact+dim joins onto the flat index
        sql = queries[name]
        fus0 = _fusion_stats()
        try:
            t0 = time.perf_counter()
            r = ctx.sql(sql)
            cold = time.perf_counter() - t0
        except Exception as e:
            log(f"{name}: FAILED ({type(e).__name__}: {e})")
            wall_lat[name] = adj_lat[name] = float("nan")
            continue
        cold_total_s += cold
        mode = ctx.history.entries()[-1].stats.get("mode", "?")
        n_engine += mode == "engine"
        if mode != "engine":
            host_queries.append(f"{name}:{mode}")
        over_budget = (time.perf_counter() - suite_t0) > budget_s
        if over_budget:
            # past the soft budget, the cold run (already paid) is the
            # only sample — wall for these queries includes compile
            log(f"{name}: over SDOT_BENCH_TIME_BUDGET, cold sample only")
        n_reps = 0 if over_budget else (1 if cold > 3.0 else reps)
        ts = [cold] if over_budget else []
        try:
            for _ in range(n_reps):
                t0 = time.perf_counter()
                ctx.sql(sql)
                ts.append(time.perf_counter() - t0)
        except Exception as e:
            # a transient failure mid-reps must not kill the run; time
            # from the surviving reps or cold time
            log(f"{name}: warm rep failed ({type(e).__name__}: {e}); "
                f"using {len(ts) or 'cold'} sample(s)")
            if not ts:
                ts = [cold]
        wall = float(np.median(ts)) * 1000
        adj = max(wall - floor_ms, 0.05) if mode == "engine" else wall
        wall_lat[name] = wall
        adj_lat[name] = adj
        # achieved scan bandwidth from the engine's own byte accounting
        # over the floor-adjusted WALL clock, marked so: a host reading
        # that can overshoot when the floor estimate does, and never a
        # peak claim. Device time comes from a profiler trace
        # (benchmarks/, docs/PROFILING.md).
        meas_stats = dict(ctx.history.entries()[-1].stats)
        # fusion-plan regression guard (extends the zero_dispatch pattern):
        # a plan_fallbacks advance during this query's reps means a fused
        # group silently reverted to the unfused (per-lane re-eval)
        # program — the single-pass win regressed without failing anything
        fus1 = _fusion_stats()
        if mode == "engine" and (int(fus1.get("plan_fallbacks", 0))
                                 > int(fus0.get("plan_fallbacks", 0))):
            fusion_fallback.append(name)
            log(f"{name}: WARNING fusion planner fell back to the unfused "
                f"program during this query's reps — fused dispatch is no "
                f"longer single-pass")
        bs = meas_stats.get("bytes_scanned")
        gb = ""
        if mode == "engine" and bs:
            gbps[name] = round(bs / (adj / 1000.0) / 1e9, 2)
            gbps_basis[name] = "adjusted_wall"
            gb = f", {gbps[name]:.1f}GB/s (wall-est)"
        nd = meas_stats.get("n_dispatch")
        nt = meas_stats.get("n_transfer")
        kl = meas_stats.get("kernel_launches")
        if kl:
            klaunch[name] = int(kl)
        dd = ""
        if nd is not None:
            ndisp[name] = int(nd)
            dd = f", {nd}+{nt}rt"   # program dispatches + host->dev transfers
            if mode == "engine" and int(nd) == 0:
                # an engine-mode query that reports zero device dispatches
                # measured a cache hit, not an execution (TPC-H q20
                # regression: the ungated subquery cache served its
                # decorrelated inners on warm reps). The session now
                # annotates LEGITIMATE cache service via "served_from"
                # (result cache, or the gated subquery cache serving every
                # scan leg of a decorrelated plan) — those are recorded in
                # a separate list so the guard itself can't silently rot:
                # an unannotated zero-dispatch engine query is always a
                # loud accounting bug.
                served = meas_stats.get("served_from")
                if not served:
                    # a sketch lane answered by a materialized rollup
                    # reaggregates STORED registers — host-side merge of
                    # persisted sketch state is a legitimate zero-dispatch
                    # answer, not a cache accident. Only sketch aggs get
                    # this exemption; a plain agg off a rollup still
                    # scans the rollup's segments on device.
                    roll = str(meas_stats.get("rollup", ""))
                    if roll.startswith("rollup:") and any(
                            fn in sql.lower() for fn in
                            ("percentile_approx", "approx_percentile",
                             "approx_count_distinct", "approx_distinct",
                             "theta_sketch")):
                        served = f"sketch-{roll}"
                if served:
                    zero_dispatch_served.append(
                        {"query": name, "served_from": str(served)})
                    log(f"{name}: zero device dispatches, served from "
                        f"{served} (annotated; exempt from the guard)")
                else:
                    zero_dispatch.append(name)
                    log(f"{name}: WARNING engine-mode query reported ZERO "
                        f"device dispatches — a cache is serving the "
                        f"measured rep")
        elif mode == "engine":
            # engine mode must always account its dispatches; a missing
            # counter would quietly disable the zero-dispatch guard
            zero_dispatch.append(name)
            log(f"{name}: WARNING engine-mode query is MISSING the "
                f"n_dispatch counter — the zero-dispatch guard cannot "
                f"audit it")
        cm = meas_stats.get("compact_m")
        if cm:
            dd += f", lm={cm}"      # late-materialization budget engaged
        if meas_stats.get("compact_overflow"):
            dd += ", lm-overflow"
        # host critical-path accounting from the always-on phase profiler:
        # host overhead is the measured wall minus the device-dispatch
        # phase — everything the host does around the actual execution
        # (parse/plan/admit/cache/bind/demux). Tracked per engine query so
        # the round-over-round guard below can flag host-side regressions
        # that adjusted geomean (dominated by dispatch) would hide.
        ph = meas_stats.get("phases")
        if isinstance(ph, dict) and ph:
            qphases[name] = {k: float(v) for k, v in ph.items()}
            if mode == "engine":
                host_overhead[name] = round(
                    max(wall - float(ph.get("dispatch", 0.0)), 0.0), 3)
        log(f"{name}: {wall:.1f}ms wall ({adj:.1f}ms floor-adjusted, cold "
            f"{cold:.2f}s, mode={mode}, {len(r)} rows{gb}{dd})")

    def geomean(d):
        vals = [max(v, 0.05) for v in d.values() if np.isfinite(v)]
        return float(np.exp(np.mean(np.log(vals)))) if vals else float("nan")

    ok_wall = {k: v for k, v in wall_lat.items() if np.isfinite(v)}
    gm_wall = geomean(wall_lat)
    gm_adj = geomean(adj_lat)
    n_fail = len(wall_lat) - len(ok_wall)
    log(f"geomean over {len(ok_wall)}/{len(wall_lat)} queries: "
        f"{gm_wall:.1f}ms wall / {gm_adj:.1f}ms adjusted"
        + (f" ({n_fail} FAILED)" if n_fail else ""))

    # vs_baseline: per-chip row-throughput ratio on the published queries,
    # from UNADJUSTED wall time (the reference's numbers are end-to-end)
    ratios = []
    for qn, base_ms in BASELINE_MS.items():
        if qn in ok_wall:
            ours = n_rows / max(ok_wall[qn], 0.05)     # rows/ms
            theirs = BASELINE_ROWS / base_ms
            ratios.append(ours / theirs)
            log(f"  vs_baseline {qn}: {ours / theirs:.1f}x (wall)")
    vs = float(np.exp(np.mean(np.log(ratios)))) if ratios else 0.0

    out = {
        "metric": metric_name(suite, sf),
        "value": round(gm_wall, 2) if np.isfinite(gm_wall) else None,
        "unit": "ms",
        "vs_baseline": round(vs, 3),
        "platform": platform,
        "adjusted_geomean_ms": round(gm_adj, 2) if np.isfinite(gm_adj)
        else None,
        "dispatch_floor_ms": round(floor_ms, 1),
        "n_queries": len(wall_lat),
        "n_engine_mode": n_engine,
        "host_queries": host_queries,
        "n_failed": n_fail,
        "rows": n_rows,
        "numerics": numerics,
        # compile-diet regression surface (VERDICT r2 #10): total cold
        # (first-execution, compile-inclusive) seconds across the suite
        # INCLUDING the parallel prewarm wall; the persistent XLA cache
        # makes repeat runs near-warm
        "cold_total_s": round(cold_total_s + prewarm_s, 1),
        "prewarm_s": round(prewarm_s, 1),
    }
    if unit_costs is not None:
        out["unit_costs"] = unit_costs
    if ndisp:
        # device round trips per query: each costs the dispatch floor,
        # made auditable (and the target of dispatch-reduction work)
        out["n_dispatch"] = ndisp
    if klaunch:
        # hand-scheduled wave-kernel launches per query (slot 2 of the
        # dispatch counter; nonzero only when the pallas wave path ran)
        out["kernel_launches"] = klaunch
    if zero_dispatch:
        out["zero_dispatch_engine"] = zero_dispatch
    if zero_dispatch_served:
        out["zero_dispatch_served"] = zero_dispatch_served
    if qphases:
        # suite-level host critical path: per-phase geomean (ms) over the
        # queries that reported the phase. Inclusive timers — parents
        # contain children — so rows are read individually, not summed.
        pnames = sorted({p for d in qphases.values() for p in d})
        out["phases"] = {
            p: round(geomean({q: d[p] for q, d in qphases.items()
                              if p in d}), 3)
            for p in pnames}
        log("host phases (geomean ms over reporting queries): "
            + ", ".join(f"{p}={v}" for p, v in out["phases"].items()))
    if host_overhead:
        out["host_overhead_ms"] = host_overhead
        # regression guard vs the previous BENCH round file (repo root):
        # flag engine queries whose host overhead grew >25% (and by at
        # least 1ms — sub-ms jitter is timer noise, not a regression).
        # Older rounds predate this counter; the guard stays inert until
        # a round with host_overhead_ms exists to compare against.
        prev = {}
        try:
            import glob as _glob
            rounds = sorted(_glob.glob(
                os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_r*.json")))
            if rounds:
                with open(rounds[-1], "r", encoding="utf-8") as f:
                    doc = json.load(f)
                doc = doc.get("parsed") or doc
                prev = dict(doc.get("host_overhead_ms") or {})
        except Exception:   # noqa: BLE001 — the guard is advisory
            prev = {}
        regressed = []
        for qn, cur in host_overhead.items():
            old = prev.get(qn)
            if old is None or float(old) <= 0:
                continue
            if cur > float(old) * 1.25 and cur - float(old) >= 1.0:
                regressed.append({"query": qn, "prev_ms": round(float(old), 3),
                                  "now_ms": cur})
                log(f"{qn}: WARNING host overhead regressed "
                    f"{float(old):.1f}ms -> {cur:.1f}ms (>25%)")
        if regressed:
            out["host_overhead_regressions"] = regressed
    fus_end = _fusion_stats()
    if fus_end:
        # deterministic CSE counters for the whole suite: how much
        # predicate work and column streaming the fusion planner removed
        out["fusion"] = fus_end
    if fusion_fallback:
        out["fusion_fallback_engine"] = fusion_fallback
    pal_end = _pallas_stats()
    if pal_end:
        out["pallas"] = pal_end
    try:
        out["pallas_ab"] = run_pallas_ab()
    except Exception as e:   # noqa: BLE001 — the A-B leg is advisory
        out["pallas_ab"] = {"available": False,
                            "error": f"{type(e).__name__}: {e}"}
    try:
        out["encode_ab"] = run_encode_ab()
    except Exception as e:   # noqa: BLE001 — the A-B leg is advisory
        out["encode_ab"] = {"available": False,
                            "error": f"{type(e).__name__}: {e}"}
    try:
        out["mesh_ab"] = run_mesh_ab()
    except Exception as e:   # noqa: BLE001 — the A-B leg is advisory
        out["mesh_ab"] = {"available": False,
                          "error": f"{type(e).__name__}: {e}"}
    try:
        out["join_ab"] = run_join_ab()
    except Exception as e:   # noqa: BLE001 — the A-B leg is advisory
        out["join_ab"] = {"available": False,
                          "error": f"{type(e).__name__}: {e}"}
    try:
        out["window_ab"] = run_window_ab()
    except Exception as e:   # noqa: BLE001 — the A-B leg is advisory
        out["window_ab"] = {"available": False,
                            "error": f"{type(e).__name__}: {e}"}
    if gbps:
        out["scan_gbps"] = gbps
        out["scan_gbps_basis"] = gbps_basis
    if n_fail == len(wall_lat) and wall_lat:
        out["error"] = "all queries failed; see stderr for per-query errors"
    print(json.dumps(out), flush=True)
    if "error" in out:
        sys.exit(1)


if __name__ == "__main__":
    main()
