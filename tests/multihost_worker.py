"""Worker process for the multi-host integration tests.

Each worker is one "host": it joins the ``jax.distributed`` runtime
(virtual 4-CPU-device backend — the multi-process extension of
conftest.py's 8-device single-process mesh), ingests ONLY its host's
segment rows (``n_hosts``/``host_id`` partial ingest), builds the global
mesh over all processes' devices, and runs the query list. Process 0
writes results JSON for the parent test to diff against a single-process
run of the same data.

Usage: python tests/multihost_worker.py <pid> <nproc> <port> <out.json>
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEVICES_PER_PROCESS = 4


def make_frame():
    import numpy as np
    import pandas as pd
    rng = np.random.default_rng(42)
    n = 60_000
    return pd.DataFrame({
        "ts": pd.Timestamp("2021-01-01")
        + pd.to_timedelta(rng.integers(0, 365, n), unit="D"),
        "region": rng.choice(["east", "west", "north", "south"], n),
        "sku": rng.integers(0, 2000, n).astype(str),     # high-card dim
        "qty": rng.integers(0, 50, n),
        "price": rng.normal(20.0, 5.0, n).round(3),
        "wide": rng.integers(-1_000_000, 1_000_000, n),
    })


QUERIES = {
    # dense group-by, filter, order
    "dense": ("select region, sum(qty) as q, count(*) as c, "
              "min(price) as mn, max(price) as mx from sales "
              "where qty > 10 group by region order by region"),
    # hashed tier: high-cardinality key
    "hashed": ("select sku, sum(qty) as q from sales "
               "where qty > 30 group by sku order by q desc, sku limit 25"),
    # time bucketing
    "timeseries": ("select date_trunc('month', ts) as m, sum(price) as p, "
                   "count(*) as c from sales group by 1 order by 1"),
    # avg decomposition + having epilogue
    "having": ("select region, avg(price) as ap from sales group by region "
               "having count(*) > 100 order by region"),
    # interval pruning (prunes whole hosts under contiguous assignment)
    "pruned": ("select region, count(*) as c from sales "
               "where ts >= timestamp '2021-10-01' group by region "
               "order by region"),
    # count distinct (HLL register merges across processes)
    "hll": ("select approx_count_distinct(sku) as d from sales"),
}


def run_queries(ctx, queries=None):
    out = {}
    for name, sql in (queries or QUERIES).items():
        r = ctx.sql(sql).to_pandas()
        st = ctx.history.entries()[-1].stats
        out[name] = {
            "columns": list(r.columns),
            "rows": json.loads(r.to_json(orient="values",
                                         date_format="iso")),
            "mode": st.get("mode", "engine"),
            "sharded": bool(st.get("sharded")),
            "waves": int(st.get("waves", 1)),
            # hashed-tier transfer accounting: compacted slots that
            # actually traveled vs table size (the multi-host diet proof)
            "hash_slots": st.get("hash_slots"),
            "hash_compact_k": st.get("hash_compact_k"),
            "topk_exchange": bool(st.get("topk_exchange")),
        }
    return out


CENSUS_SF = 0.02


def build_census_tpch(nproc: int, pid: int):
    """TPC-H store with the FACT indexes partial-ingested
    (n_hosts/host_id); dimension/base tables replicated. ``nproc=1``,
    ``pid=0`` builds the complete single-process oracle. Mirrors
    bench.setup (incl. the wide-column drop from the flat index)."""
    import spark_druid_olap_tpu as sdot
    from spark_druid_olap_tpu.parallel.mesh import make_mesh
    from spark_druid_olap_tpu.tools import tpch

    drop = ["l_comment", "o_comment", "c_comment", "s_comment",
            "ps_comment", "cn_comment", "cr_comment", "sn_comment",
            "sr_comment", "c_address", "s_address", "o_clerk"]
    part = {"n_hosts": nproc, "host_id": pid} if nproc > 1 else {}
    ctx = sdot.Context(mesh=make_mesh())
    tables = tpch.generate(CENSUS_SF)
    flat = tpch.flatten(tables)
    flat = flat.drop(columns=[c for c in drop if c in flat.columns])
    ctx.ingest_dataframe("tpch_flat", flat, time_column="l_shipdate",
                         target_rows=1 << 12, **part)
    for name, df in tables.items():
        if name in ("nation", "region"):
            continue
        tcol = {"lineitem": "l_shipdate",
                "orders": "o_orderdate"}.get(name)
        ctx.ingest_dataframe(name, df, time_column=tcol,
                             target_rows=1 << 14)
    for name, df in tpch.nation_region_views(tables).items():
        ctx.ingest_dataframe(name, df)
    ctx.ingest_dataframe("partsupp_flat", tpch.flatten_partsupp(tables),
                         target_rows=1 << 12, **part)
    ctx.register_star_schema(tpch.partsupp_star_schema("partsupp_flat"))
    ctx.register_star_schema(tpch.star_schema("tpch_flat"))

    # correlated-inequality outer dim: decorrelation can't lift it, so
    # the statement lands on the host tier and must GATHER the partial
    # flat store (Datasource.complete) — the fallback-serves-everything
    # contract (≈ DruidRelation.scala:111's Spark-side fallback scan)
    import pandas as pd
    ctx.ingest_dataframe("segdim", pd.DataFrame({
        "seg_name": ["AUTOMOBILE", "BUILDING", "FURNITURE"],
        "min_q": [10, 20, 30]}))
    # a 2-arg session Python function has no device compilation path, so
    # any statement using it demotes WHOLE to the host tier — the
    # guaranteed host-mode shape for the partial-store gather proof
    ctx.functions["hostfn"] = lambda a, b: float(a) * 2 + float(b)
    return ctx


def _rss_mb() -> float:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return round(int(line.split()[1]) / 1024.0, 1)
    return -1.0


def _store_mb(ds) -> float:
    """Exact column-array bytes of a datasource — the memory metric the
    partial-ingest guarantee is ABOUT (process RSS retains streamed-
    ingest pass-A transients under glibc and can't see the savings)."""
    tot = 0
    for d in ds.dims.values():
        tot += d.codes.nbytes + d.dictionary.nbytes
        if d.validity is not None:
            tot += d.validity.nbytes
    for m in ds.metrics.values():
        tot += m.values.nbytes
        if m.validity is not None:
            tot += m.validity.nbytes
    if ds.time is not None:
        tot += ds.time.days.nbytes + ds.time.ms_in_day.nbytes
    return round(tot / 2**20, 1)


def build_sf10_ctx(nproc: int, pid: int):
    """SF10 (60M-row) TPC-H store from the bench parquet cache with the
    flat index PARTIAL-ingested per host via the out-of-core streamer —
    the SF100 ingest mechanism rehearsed at a scale where mistakes show
    (VERDICT r4 item 4). Requires .bench_cache/tpch_flat_sf10.0.parquet
    (built by bench.py at SDOT_BENCH_SF=10)."""
    import pandas as pd

    import bench
    import spark_druid_olap_tpu as sdot
    from spark_druid_olap_tpu.parallel.mesh import make_mesh
    from spark_druid_olap_tpu.tools import tpch

    d = bench.cache_dir()
    flat_path = os.path.join(d, "tpch_flat_sf10.0.parquet")
    assert os.path.exists(flat_path), \
        "SF10 cache missing: run SDOT_BENCH_SF=10 bench.py once first"
    part = {"n_hosts": nproc, "host_id": pid} if nproc > 1 else {}
    ctx = sdot.Context(mesh=make_mesh())
    ctx.ingest_parquet_stream("tpch_flat", flat_path,
                              time_column="l_shipdate",
                              target_rows=1 << 20, batch_rows=1 << 21,
                              **part)
    rss_after_flat = _rss_mb()
    tables = {n: pd.read_parquet(
        os.path.join(d, f"tpch_{n}_sf10.0.parquet"))
        for n in ("lineitem", "orders", "partsupp", "part", "supplier",
                  "customer", "nation", "region")}
    for name, df in tables.items():
        if name in ("nation", "region"):
            continue
        tcol = {"lineitem": "l_shipdate",
                "orders": "o_orderdate"}.get(name)
        ctx.ingest_dataframe(name, df, time_column=tcol,
                             target_rows=1 << 20)
    for name, df in tpch.nation_region_views(tables).items():
        ctx.ingest_dataframe(name, df)
    ctx.ingest_dataframe("partsupp_flat", tpch.flatten_partsupp(tables),
                         target_rows=1 << 20, **part)
    del tables
    ctx.register_star_schema(tpch.partsupp_star_schema("partsupp_flat"))
    ctx.register_star_schema(tpch.star_schema("tpch_flat"))
    return ctx, rss_after_flat


# one query per engine mechanism at SF10 (the FULL 22+13 census is
# proven multi-host at tests/test_multihost.py census scale; at 60M
# rows x 2 processes x 1 shared core, 22 queries blow the wall-clock
# budget — these 10 cover dense/selective/star/outer-join/hashed/
# having/decorrelated/complex-predicate/partsupp-star/host shapes)
SF10_QUERIES = ("q1", "q3", "q6", "q11", "q13", "q14", "q18", "q19",
                "q21", "q22")


def run_sf10(ctx):
    """A per-mechanism TPC-H subset at SF10 with walls (the SSB side of
    the census is covered at census scale; SF10's flat cache is TPC-H)."""
    import time

    from spark_druid_olap_tpu.tools import tpch
    out = {}
    for name in SF10_QUERIES:
        t0 = time.time()
        r = ctx.sql(tpch.QUERIES[name]).to_pandas()
        st = ctx.history.entries()[-1].stats
        out[f"tpch_{name}"] = {
            "columns": list(r.columns),
            "rows": json.loads(r.to_json(orient="values",
                                         date_format="iso")),
            "mode": st.get("mode", "engine"),
            "sharded": bool(st.get("sharded")),
            "wall_ms": round((time.time() - t0) * 1000, 1),
        }
    return out


def build_census_ssb(nproc: int, pid: int):
    """SSB store (separate Context: SSB's customer/supplier/part share
    names with TPC-H's — one namespace per workload, like bench)."""
    import spark_druid_olap_tpu as sdot
    from spark_druid_olap_tpu.parallel.mesh import make_mesh
    from spark_druid_olap_tpu.tools import ssb

    part = {"n_hosts": nproc, "host_id": pid} if nproc > 1 else {}
    ctx = sdot.Context(mesh=make_mesh())
    stables = ssb.generate(CENSUS_SF)
    ctx.ingest_dataframe("ssb_flat", ssb.flatten(stables),
                         time_column="lo_orderdate",
                         target_rows=1 << 12, **part)
    for name, df in stables.items():
        tcol = {"lineorder": "lo_orderdate"}.get(name)
        ctx.ingest_dataframe(name, df, time_column=tcol,
                             target_rows=1 << 14)
    ctx.register_star_schema(ssb.star_schema("ssb_flat"))
    return ctx


def run_census(ctx, ctx_ssb):
    """The full TPC-H 22 + SSB 13 census plus the query shapes that need
    multi-host-specific routing: select paging, search, a forced-waves
    scan, and a host-tier residual over the partial store."""
    from spark_druid_olap_tpu.ir import spec as SP
    from spark_druid_olap_tpu.tools import ssb, tpch

    out = {}
    out.update({f"tpch_{n}": v for n, v in
                run_queries(ctx, tpch.QUERIES).items()})
    out.update({f"ssb_{n}": v for n, v in
                run_queries(ctx_ssb, ssb.QUERIES).items()})
    out.update(run_queries(ctx, {
        # decorrelated correlated-inequality (engine-served — proves the
        # decorrelation plane works over a partial store)
        "decorrelated": (
            "select seg_name from segdim where "
            "(select count(*) from tpch_flat where c_mktsegment = seg_name"
            " and l_quantity >= min_q) > 100 order by seg_name"),
        # session Python UDF: no device path, whole statement demotes to
        # the host tier, which must GATHER the partial flat store
        # (Datasource.complete) — fallback-serves-everything
        "host_gather": (
            "select l_returnflag, count(*) as n from tpch_flat "
            "where hostfn(l_quantity, l_discount) > 25 "
            "group by l_returnflag order by l_returnflag"),
    }))

    # forced waves on the partial store: the SF100 overflow valve must
    # compose with multi-host (VERDICT r4 item 2)
    from spark_druid_olap_tpu.utils.config import WAVE_MAX_BYTES
    prev = ctx.config.get(WAVE_MAX_BYTES)
    # below one segment's scan bytes: plan_waves floors at one segment
    # per device per wave, so the scan is forced into multiple waves
    ctx.config.set(WAVE_MAX_BYTES.key, 1 << 14)
    try:
        out.update({f"waved_{n}": v for n, v in run_queries(ctx, {
            "dense": ("select l_returnflag, sum(l_quantity) as q, "
                      "count(*) as c from tpch_flat group by l_returnflag "
                      "order by l_returnflag"),
            "hashed": ("select l_orderkey, sum(l_quantity) as q from "
                       "tpch_flat group by l_orderkey "
                       "order by q desc, l_orderkey limit 20"),
        }).items()})
    finally:
        ctx.config.set(WAVE_MAX_BYTES.key, prev)

    # select paging + search over the partial store (raw QuerySpecs)
    sel = ctx.execute(SP.SelectQuerySpec(
        datasource="tpch_flat",
        columns=("l_orderkey", "l_quantity", "l_shipmode", "c_mktsegment"),
        filter=SP.BoundFilter("l_quantity", lower=45.0, numeric=True),
        page_offset=7, page_size=40)).to_pandas()
    out["select_page"] = {
        "columns": list(sel.columns),
        "rows": json.loads(sel.to_json(orient="values",
                                       date_format="iso")),
        "mode": "select",
    }
    srch = ctx.execute(SP.SearchQuerySpec(
        datasource="tpch_flat",
        dimensions=("l_shipmode", "c_mktsegment"),
        query="AI")).to_pandas()
    out["search"] = {
        "columns": list(srch.columns),
        "rows": json.loads(srch.to_json(orient="values")),
        "mode": "search",
    }
    return out


def spawn_workers(n_processes: int, outpath: str,
                  devices_per_process: int = DEVICES_PER_PROCESS,
                  timeout_s: float = 600.0, mode: str = "basic"):
    """Run ``n_processes`` worker processes to completion (the shared rig
    for tests/test_multihost.py and __graft_entry__.dryrun_multiprocess).
    Returns the parsed results JSON; raises AssertionError with worker
    logs on failure."""
    import socket
    import subprocess

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    worker = os.path.abspath(__file__)
    procs = [subprocess.Popen(
        [sys.executable, worker, str(pid), str(n_processes), str(port),
         str(outpath), str(devices_per_process), mode],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for pid in range(n_processes)]
    logs = []
    try:
        for p in procs:
            stdout, _ = p.communicate(timeout=timeout_s)
            logs.append(stdout.decode(errors="replace"))
    finally:
        for p in procs:
            p.kill()
    assert all(p.returncode == 0 for p in procs), \
        "multihost worker failed:\n" + "\n====\n".join(logs)
    with open(outpath) as f:
        return json.load(f)


def main():
    pid, nproc = int(sys.argv[1]), int(sys.argv[2])
    port, outpath = sys.argv[3], sys.argv[4]
    devs = int(sys.argv[5]) if len(sys.argv) > 5 else DEVICES_PER_PROCESS
    mode = sys.argv[6] if len(sys.argv) > 6 else "basic"
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["TZ"] = "UTC"
    import jax
    jax.config.update("jax_platforms", "cpu")
    # persistent XLA cache: the census compiles ~50 programs per process;
    # repeat runs (and the single-process oracle) come back warm
    from spark_druid_olap_tpu.utils import compile_cache
    compile_cache.configure()
    from spark_druid_olap_tpu.parallel import multihost as MH
    MH.initialize(f"127.0.0.1:{port}", nproc, pid,
                  local_device_count=devs)
    assert jax.process_count() == nproc
    assert len(jax.devices()) == nproc * devs

    import spark_druid_olap_tpu as sdot
    from spark_druid_olap_tpu.parallel.mesh import make_mesh

    if mode == "probe":
        # capability probe: ONE cross-process collective, nothing else.
        # Succeeds only where the backend implements inter-process
        # collectives (TPU/GPU, or CPU builds with a cross-host
        # transport); environments without them fail/hang here instead
        # of 40 minutes into the census.
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from spark_druid_olap_tpu.parallel.mesh import (
            SEGMENT_AXIS, shard_map)
        mesh = make_mesh()
        n_dev = nproc * devs

        def body(x):
            return jax.lax.psum(x, SEGMENT_AXIS)

        got = jax.jit(shard_map(
            body, mesh=mesh, in_specs=P(SEGMENT_AXIS),
            out_specs=P(), check_vma=False))(
            jnp.ones((n_dev,), jnp.float32))
        assert float(got[0]) == float(n_dev), got
        if pid == 0:
            with open(outpath, "w") as f:
                json.dump({"ok": True, "devices": n_dev}, f)
        print(f"[worker {pid}] probe ok", flush=True)
        return

    if mode == "census":
        ctx = build_census_tpch(nproc, pid)
        ctx_ssb = build_census_ssb(nproc, pid)
        ds = ctx.store.get("tpch_flat")
        assert ds.is_partial
        n_local = len(ds.local_seg_ids)
        results = run_census(ctx, ctx_ssb)
    elif mode == "sf10":
        ctx, rss_flat = build_sf10_ctx(nproc, pid)
        ds = ctx.store.get("tpch_flat")
        # nproc == 1 is the like-for-like single-process RSS baseline
        assert ds.is_partial == (nproc > 1)
        n_local = len(ds.local_seg_ids) if ds.is_partial \
            else ds.num_segments
        results = run_sf10(ctx)
        results["_rss"] = {"after_flat_ingest_mb": rss_flat,
                           "after_queries_mb": _rss_mb(),
                           "flat_store_mb": _store_mb(ds),
                           "local_rows": int(ds.local_num_rows),
                           "total_rows": int(ds.num_rows)}
    else:
        ctx = sdot.Context(mesh=make_mesh())
        ds = ctx.ingest_dataframe("sales", make_frame(), time_column="ts",
                                  target_rows=4096, n_hosts=nproc,
                                  host_id=pid)
        assert ds.is_partial
        n_local = len(ds.local_seg_ids)
        assert 0 < n_local < ds.num_segments, \
            f"host {pid} holds {n_local}/{ds.num_segments} segments"
        results = run_queries(ctx)
    results["_meta"] = {
        "pid": pid, "n_local_segments": n_local,
        "n_segments": ds.num_segments,
        "devices": len(jax.devices()),
    }
    # every process computes replicated results; process 0 publishes
    if pid == 0:
        with open(outpath, "w") as f:
            json.dump(results, f, indent=1)
    print(f"[worker {pid}] done ({n_local}/{ds.num_segments} local "
          f"segments)", flush=True)


if __name__ == "__main__":
    main()
