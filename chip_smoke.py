#!/usr/bin/env python3
"""chip_smoke.py — TPC-H SF1 served from one TPU chip: the quickest proof
that the system still starts on the accelerator.

    python chip_smoke.py                 # one chip: load, serve, storm
    python chip_smoke.py --chips 4       # four chips: the mesh phase only
    python chip_smoke.py --params        # first answer for an unseen literal
    python chip_smoke.py --rehearse --sf 0.01    # CPU rehearsal, "ok": false

A SMOKE, not a benchmark: it proves the served path executes on the
device and answers correctly; its times are host-clock readings of a
handful of statements. One process (a chip belongs to one process); it
never selects a JAX platform; it exits non-zero at the first failed
check. Every phase prints one JSON object per line; the LAST line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

Phases (one chip):

- device  JAX's default device must be a TPU, or exit before any load.
- cache   where the persistent compile cache lives, entries before/after.
- load    ``tools.tpch.setup_context`` at ``--sf`` from ``--seed`` (what
          the server's own ``--tpch`` flag loads).
- serve   ``server.http.SqlServer`` in this process; ``POST /sql`` the
          published q1, q6, q3, q5, q12 and an ``approx_count_distinct``
          group-by, twice each; every statement must run ``engine`` mode
          with a device dispatch and match a plain reference (pandas over
          the generated tables; the host executor for the joins).
- storm   eight concurrent dashboard statements on ``tpch_flat`` (q6's
          ungrouped date-range shape among them) through the shared-scan
          tier: one fused Pallas wave kernel, no fallback, answers equal
          to the same statements served one by one.

``--params`` replaces serve and storm by the user's view of a new
literal: per TPC-H template (q1 q3 q5 q6 q12), draws of the spec's
substitution parameters (``tools.tpch.substitution_parameters``, from
``--seed``) are served until every year of the template has been seen
(each twice: the warm-up), then ONE draw that was never sent is served
once — its wall ms, its ``compile`` phase, the compile-cache entries it
added, whether its program's signature had been seen. A tree whose
records carry ``program`` fails the phase if that draw built a program
under a signature the warm-up had already run.

``--chips 4`` runs the storm through the mesh tier and q1 through the
solo sharded path on a four-device mesh, against a single-device context
over the same store in the same process.
"""

import argparse
import json
import os
import shutil
import sys
import threading
import time
import urllib.request

import numpy as np
import pandas as pd

import spark_druid_olap_tpu as sdot
from spark_druid_olap_tpu.tools import tpch

SERVED = ("q1", "q6", "q3", "q5", "q12", "acd")
ACD_SQL = ("select l_shipmode, approx_count_distinct(l_partkey) as parts, "
           "count(*) as n from lineitem group by l_shipmode")

# the storm: SQL twins of tests/test_sharedscan.py:_storm_batch (group-by,
# filtered group-by, yearly timeseries, topN over ONE shared predicate)
# plus q6's shape and three more KPI tiles. Every lane stays at or under
# the 64-key fused cap (sdot.engine.groupby.pallas.max.keys).
_SHARED = "c_mktsegment = 'BUILDING'"
_AGGS = ("sum(l_extendedprice) as revenue, sum(l_quantity) as units, "
         "count(*) as n")
STORM = {
    "groupby": f"select l_linestatus, {_AGGS} from tpch_flat "
               f"where {_SHARED} group by l_linestatus",
    "groupby_filtered": f"select l_shipmode, {_AGGS} from tpch_flat "
                        f"where {_SHARED} and l_returnflag = 'R' "
                        f"group by l_shipmode",
    "timeseries_year": f"select year(l_shipdate) as y, {_AGGS} "
                       f"from tpch_flat where {_SHARED} "
                       f"and l_quantity >= 10 group by year(l_shipdate)",
    "topn": f"select sn_name, {_AGGS} from tpch_flat where {_SHARED} "
            f"group by sn_name order by revenue desc limit 7",
    "q6_shape": "select sum(l_extendedprice * l_discount) as revenue "
                "from tpch_flat where l_shipdate >= date '1994-01-01' "
                "and l_shipdate < date '1995-01-01' "
                "and l_discount between 0.05 and 0.07 and l_quantity < 24",
    "kpi_total": f"select {_AGGS}, min(l_discount) as dmin, "
                 f"max(l_discount) as dmax from tpch_flat where {_SHARED}",
    "q1_shape": f"select l_returnflag, l_linestatus, {_AGGS}, "
                f"avg(l_discount) as avg_disc from tpch_flat "
                f"where l_shipdate <= date '1998-09-02' "
                f"group by l_returnflag, l_linestatus",
    "groupby_year_window": f"select o_orderpriority, {_AGGS} "
                           f"from tpch_flat "
                           f"where l_shipdate >= date '1995-01-01' "
                           f"and l_shipdate < date '1996-01-01' "
                           f"group by o_orderpriority",
}

# docs/DISTRIBUTED.md "serving configuration for storm traffic": the
# window is wide enough for HTTP arrival jitter (default 8 ms)
STORM_CONFIG = {"sdot.sharedscan.enabled": True,
                "sdot.wlm.batch.window.ms": 400.0}


# storm vs solo compares two f32 kernels with different block depths
# (wave 512 rows, dense 2048): each is held to 1e-6 of the truth, and on
# the chip they were 1.4e-6 apart on avg(l_discount) (PERF.md, PR 22)
STORM_RTOL = 1e-5


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


# -- references ---------------------------------------------------------------

def ref_q1(flat):
    d = flat[flat.l_shipdate <= pd.Timestamp("1998-12-01")
             - pd.Timedelta(days=90)]
    disc_price = d.l_extendedprice * (1 - d.l_discount)
    d = d.assign(_dp=disc_price, _ch=disc_price * (1 + d.l_tax))
    g = d.groupby(["l_returnflag", "l_linestatus"], sort=True)
    return pd.DataFrame({
        "sum_qty": g.l_quantity.sum().astype(np.int64),
        "sum_base_price": g.l_extendedprice.sum(),
        "sum_disc_price": g._dp.sum(), "sum_charge": g._ch.sum(),
        "avg_qty": g.l_quantity.mean(), "avg_price": g.l_extendedprice.mean(),
        "avg_disc": g.l_discount.mean(),
        "count_order": g.size().astype(np.int64)}).reset_index()


def ref_q6(flat):
    # the literals as the statement spells them; l_discount is two-decimal
    disc = flat.l_discount.round(2)
    d = flat[(flat.l_shipdate >= "1994-01-01")
             & (flat.l_shipdate < "1995-01-01")
             & (disc >= 0.05) & (disc <= 0.07) & (flat.l_quantity < 24)]
    return pd.DataFrame(
        {"revenue": [float((d.l_extendedprice * d.l_discount).sum())]})


def ref_acd(flat):
    g = flat.groupby("l_shipmode", sort=True)
    return pd.DataFrame({"parts": g.l_partkey.nunique().astype(np.int64),
                         "n": g.size().astype(np.int64)}).reset_index()


def ref_host(ctx, sql):
    """The host executor (pandas) on the same statement — the plain
    reference for the joins."""
    from spark_druid_olap_tpu.planner import host_exec
    from spark_druid_olap_tpu.sql.parser import parse_statement
    return host_exec.execute_select(ctx, parse_statement(sql))


def json_frame(df):
    """A frame as a client sees it: through the server's own JSON row
    encoder, so dates, nulls and integer widths compare like with like."""
    from spark_druid_olap_tpu.server.http import _df_to_json_rows
    body = json.loads(_df_to_json_rows(df))
    return pd.DataFrame(body["rows"], columns=body["columns"])


def check_frames(name, got, want, approx=(), rtol=1e-6):
    """Integers, counts and strings exact; float columns ``rtol``;
    ``approx`` columns (sketch estimates) within 5 %. Returns the
    largest relative error seen in a float column."""
    check(list(got.columns) == list(want.columns),
          f"{name}: columns {list(got.columns)} != {list(want.columns)}")
    check(len(got) == len(want), f"{name}: {len(got)} rows != {len(want)}")
    # JSON prints a whole-valued float as an integer: a column is a
    # float column if EITHER side parsed as one
    floats = {c for c in want.columns
              if "f" in (got[c].dtype.kind, want[c].dtype.kind)}
    keys = [c for c in want.columns if c not in floats and c not in approx]
    if keys:
        got = got.sort_values(keys).reset_index(drop=True)
        want = want.sort_values(keys).reset_index(drop=True)
    worst = 0.0
    for c in want.columns:
        g, w = got[c].to_numpy(), want[c].to_numpy()
        if c in approx:
            ok = np.allclose(g.astype(float), w.astype(float), rtol=0.05)
        elif c in floats:
            g, w = g.astype(float), w.astype(float)
            ok = np.allclose(g, w, rtol=rtol, atol=0.0, equal_nan=True)
            with np.errstate(divide="ignore", invalid="ignore"):
                rel = np.abs(g - w) / np.abs(w)
            worst = max(worst, float(np.nanmax(np.where(w == 0, 0, rel),
                                               initial=0.0)))
        else:
            ok = np.array_equal(g, w)
        check(ok, f"{name}: column {c!r} differs (rtol {rtol})\n"
                  f" got {g[:8]!r}\nwant {w[:8]!r}")
    return worst


# -- HTTP client --------------------------------------------------------------

def post_sql(port, sql, **extra):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/sql",
        data=json.dumps({"sql": sql, **extra}).encode(),
        headers={"Content-Type": "application/json"})
    t0 = time.perf_counter()
    with urllib.request.urlopen(req, timeout=900) as resp:
        body = json.loads(resp.read())
    ms = (time.perf_counter() - t0) * 1000
    return pd.DataFrame(body["rows"], columns=body["columns"]), ms


def get_json(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=60) as resp:
        return json.loads(resp.read())


def history_since(port, n0):
    return get_json(port, "/history")["history"][n0:]


_STAT_KEYS = ("mode", "n_dispatch", "n_transfer", "kernel_launches")


def engine_stats(name, rec, dispatched=True):
    """The statement ran on the device, not on a fallback."""
    st = {k: rec.get(k) for k in _STAT_KEYS}
    check(rec.get("mode") == "engine",
          f"{name}: mode {rec.get('mode')!r}, not 'engine'")
    check(not rec.get("backend_lost"), f"{name}: backend_lost")
    if dispatched:
        check((rec.get("n_dispatch") or 0) >= 1,
              f"{name}: no device dispatch ({st})")
    return st


# -- phases -------------------------------------------------------------------

def phase_device(args):
    import jax
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (default device: "
              f"{dev.platform} {dev.device_kind}); nothing was run",
              file=sys.stderr)
        sys.exit(2)
    check(info["count"] >= args.chips,
          f"--chips {args.chips} but JAX sees {info['count']} device(s)")
    stats = dev.memory_stats() or {}
    emit("device", **info, bytes_limit=stats.get("bytes_limit"),
         x64=bool(jax.config.jax_enable_x64), jax=jax.__version__,
         rehearse=bool(args.rehearse))
    return info


def native_encoder():
    """Build (or find) the native segment encoder; a build that was
    attempted and failed is a failure, a missing toolchain is not."""
    from spark_druid_olap_tpu.segment import native
    prebuilt = os.path.exists(native._SO) and \
        os.path.getmtime(native._SO) >= os.path.getmtime(native._SRC)
    if native.load() is not None:
        return "prebuilt" if prebuilt else "built"
    check(shutil.which("g++") is None,
          "native/segment_encoder.cpp failed to build or load")
    return "numpy (no g++)"


def phase_load(args, ctx, flat_only=False):
    native = native_encoder()
    t0 = time.perf_counter()
    tables, flat = tpch.setup_context(ctx, sf=args.sf, seed=args.seed,
                                      target_rows=args.target_rows,
                                      flat_only=flat_only)
    ds = ctx.store.get("tpch_flat")
    check(ds.num_rows == len(flat) and ds.num_rows > 0, "tpch_flat is empty")
    emit("load", sf=args.sf, seed=args.seed, rows=int(ds.num_rows),
         segments=int(ds.num_segments), padded_rows=int(ds.padded_rows),
         datasources=len(ctx.store.names()),
         seconds=round(time.perf_counter() - t0, 2), native_encoder=native)
    return flat


def device_bytes(ctx):
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return {"device_bytes_bound": int(ctx.engine._device_bytes),
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


def phase_serve(ctx, port, flat):
    sqls = {k: tpch.QUERIES[k] for k in SERVED if k != "acd"}
    sqls["acd"] = ACD_SQL
    refs = {"q1": lambda: ref_q1(flat), "q6": lambda: ref_q6(flat),
            "acd": lambda: ref_acd(flat)}
    for name in SERVED:
        sql = sqls[name]
        runs = []
        for _ in range(2):
            n0 = len(get_json(port, "/history")["history"])
            got, ms = post_sql(port, sql)
            rec = history_since(port, n0)[-1]
            check(rec["sql"] == sql, f"{name}: history out of step")
            runs.append((got, ms, engine_stats(name, rec),
                         rec.get("phases")))
        t0 = time.perf_counter()
        want = json_frame(refs[name]() if name in refs
                          else ref_host(ctx, sql))
        ref_s = time.perf_counter() - t0
        worst = max(check_frames(name, got, want,
                                 approx=("parts",) if name == "acd" else ())
                    for got, *_ in runs)
        emit("serve", statement=name, rows=len(want), max_rel_err=worst,
             cold_ms=round(runs[0][1], 1), warm_ms=round(runs[1][1], 1),
             cold=runs[0][2], warm=runs[1][2], warm_phases_ms=runs[1][3],
             reference="pandas" if name in refs else "host_exec",
             reference_s=round(ref_s, 2), correct=True)
    emit("serve_done", statements=len(SERVED), **device_bytes(ctx))


def _served(port, name, sql):
    """POST one statement; (frame, wall ms, its history record)."""
    n0 = len(get_json(port, "/history")["history"])
    got, ms = post_sql(port, sql)
    rec = history_since(port, n0)[-1]
    check(rec["sql"] == sql, f"{name}: history out of step")
    engine_stats(name, rec)
    return got, ms, rec


def phase_params(ctx, port, seed, cache_dir):
    """First answer for a literal nobody has sent: warm each template
    with drawn statements, then serve one unseen draw of it once."""
    import random
    from spark_druid_olap_tpu.utils import compile_cache
    rng = random.Random(seed)
    for t in tpch.TEMPLATES:
        years = {"q1": 1, "q3": 1}.get(t, 5)    # q5 q6 q12: DATE's year
        sent, sigs, seen_years = {}, set(), set()
        t0 = time.perf_counter()
        while len(sent) < 2 or len(seen_years) < years:
            params = tpch.substitution_parameters(t, rng)
            sql = tpch.render(t, params)
            if sql in sent:
                continue
            for _ in range(2):
                got, ms, rec = _served(port, t, sql)
            sent[sql] = (got, params)
            sigs.add((rec.get("program") or {}).get("sig"))
            seen_years.add(params["date"][:4] if years > 1 else "")
        warm_s = time.perf_counter() - t0
        sql0, (got0, _) = next(iter(sent.items()))
        check_frames(t + " (warm)", got0, json_frame(ref_host(ctx, sql0)))
        while True:
            params = tpch.substitution_parameters(t, rng)
            sql = tpch.render(t, params)
            if sql not in sent:
                break
        entries0 = compile_cache.entries(cache_dir)
        got, ms, rec = _served(port, t, sql)
        added = compile_cache.entries(cache_dir) - entries0
        worst = check_frames(t, got, json_frame(ref_host(ctx, sql)))
        prog = rec.get("program")
        compile_ms = (rec.get("phases") or {}).get("compile")
        shape_seen = None if prog is None else prog["sig"] in sigs
        emit("params", template=t, params=params, warm_texts=len(sent),
             warm_seconds=round(warm_s, 1), warm_programs=len(sigs),
             wall_ms=round(ms, 1), compile_ms=compile_ms,
             cache_entries_added=added, program=prog,
             shape_seen=shape_seen, n_transfer=rec.get("n_transfer"),
             phases_ms=rec.get("phases"), max_rel_err=worst, correct=True)
        if shape_seen:
            check(not prog["built"] and compile_ms is None and added == 0,
                  f"{t}: an unseen draw of a seen shape compiled "
                  f"(built {prog['built']}, compile {compile_ms} ms, "
                  f"{added} cache entries)")
    emit("params_done", templates=len(tpch.TEMPLATES), **device_bytes(ctx))


def run_storm(port):
    """Eight concurrent POST /sql on the interactive lane; returns
    {name: (frame, ms)}."""
    out, errs = {}, {}
    bar = threading.Barrier(len(STORM))

    def worker(name, sql):
        try:
            bar.wait()
            out[name] = post_sql(port, sql, lane="interactive")
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errs[name] = e

    threads = [threading.Thread(target=worker, args=kv)
               for kv in STORM.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    check(not errs, f"storm statements failed: {errs}")
    return out


def phase_storm(ctx, port, mesh_devices=1):
    """The storm through the shared-scan tier, then the same statements
    one by one with the tier off. Returns the coalescer's stats."""
    for k, v in STORM_CONFIG.items():
        ctx.config.set(k, v)
    passes = []
    for label in ("cold", "warm"):
        n0 = len(get_json(port, "/history")["history"])
        out = run_storm(port)
        recs = {r["sql"]: r for r in history_since(port, n0)}
        passes.append((label, out, recs))
    st = ctx.engine.sharedscan.stats()
    ctx.config.set("sdot.sharedscan.enabled", False)
    refs = {name: post_sql(port, sql)[0] for name, sql in STORM.items()}

    lanes = {}
    for label, out, recs in passes:
        for name, sql in STORM.items():
            rec = recs[sql]
            engine_stats(f"storm/{name}", rec, dispatched=False)
            ss = rec.get("sharedscan") or {}
            check(ss.get("pallas"),
                  f"storm/{name} ({label}): not served by a wave kernel "
                  f"({ss})")
            worst = check_frames(f"storm/{name} ({label})", out[name][0],
                                 refs[name], rtol=STORM_RTOL)
            lanes.setdefault(name, {})[label + "_ms"] = \
                round(out[name][1], 1)
            lanes[name]["max_rel_err_vs_solo"] = worst
            lanes[name]["pallas"] = ss["pallas"]
            lanes[name]["group"] = ss.get("group")
    for name, row in lanes.items():
        emit("storm_lane", statement=name, **row)

    p = st["pallas"]
    check(st["queries_coalesced"] >= len(STORM) * 2,
          f"storm did not coalesce: {st}")
    check(st["fallbacks"] == 0 and st["last_error"] is None,
          f"storm members fell back to solo: {st}")
    check(p["launches"] >= 1 and p["fallbacks"] == 0,
          f"wave kernel did not launch cleanly: {p}")
    m = st["mesh"]
    check(m["devices"] == mesh_devices, f"mesh devices: {m}")
    if mesh_devices > 1:
        check(m["groups"] >= 1 and not m["fallbacks"],
              f"storm did not shard across the mesh: {m}")
        check(p["launches"] == m["dispatches"] * mesh_devices,
              f"launches != waves x devices: {p} vs {m}")
    emit("storm", statements=len(STORM), passes=2,
         config=STORM_CONFIG, interpret=bool(
             os.environ.get("SDOT_PALLAS") == "interpret"),
         groups_coalesced=st["groups_coalesced"],
         queries_coalesced=st["queries_coalesced"],
         fallbacks=st["fallbacks"], pallas=p,
         mesh={k: m[k] for k in ("devices", "groups", "dispatches",
                                 "collective_bytes", "fallbacks")},
         fusion_shared_predicates=st["fusion"]["shared_predicates"],
         correct=True, **device_bytes(ctx))
    return st


def phase_mesh(args, cfg):
    """--chips 4: the storm through the mesh tier and q1 through the solo
    sharded path, against a single-device context over the same store."""
    from spark_druid_olap_tpu.server.http import SqlServer
    ctx = sdot.Context(cfg, auto_mesh=True)
    check(ctx.mesh is not None and ctx.mesh.devices.size == args.chips,
          f"auto_mesh built {ctx.mesh}")
    phase_load(args, ctx, flat_only=False)
    one = sdot.Context(cfg, mesh=None)
    for name in ctx.store.names():
        one.store.register(ctx.store.get(name))
    one.register_star_schema(tpch.partsupp_star_schema("partsupp_flat"))
    one.register_star_schema(tpch.star_schema("tpch_flat"))

    srv = SqlServer(ctx, "127.0.0.1", 0).start(background=True)
    srv_one = SqlServer(one, "127.0.0.1", 0).start(background=True)
    try:
        # q1 solo through QueryEngine._shard_wrap
        q1 = tpch.QUERIES["q1"]
        n0 = len(get_json(srv.port, "/history")["history"])
        got, cold_ms = post_sql(srv.port, q1)
        _, warm_ms = post_sql(srv.port, q1)
        rec = history_since(srv.port, n0)[0]
        st = engine_stats("mesh/q1", rec)
        check(rec.get("sharded") is True,
              f"mesh/q1 did not shard: {rec.get('shard_decision')}")
        want, _ = post_sql(srv_one.port, q1)
        check_frames("mesh/q1", got, want)
        emit("mesh_q1", cold_ms=round(cold_ms, 1), warm_ms=round(warm_ms, 1),
             shard_decision=rec.get("shard_decision"), **st, correct=True)

        # the storm through parallel/meshexec.py
        phase_storm(ctx, srv.port, mesh_devices=args.chips)
        for name, sql in STORM.items():
            got, _ = post_sql(srv.port, sql)        # sharedscan now off
            want, _ = post_sql(srv_one.port, sql)
            check_frames(f"mesh/{name} vs single-device", got, want)

        sharded = [a for k, a in ctx.engine._device_arrays.items() if k[4]]
        check(sharded, "no sharded array was bound")
        for a in sharded:
            check(len(a.sharding.device_set) == args.chips,
                  f"bound array on {len(a.sharding.device_set)} devices")
        emit("mesh_bind", sharded_arrays=len(sharded),
             device_set=args.chips, shape=list(sharded[0].shape),
             single_device_arrays=len(one.engine._device_arrays))
    finally:
        srv.stop()
        srv_one.stop()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--sf", type=float, default=1.0,
                    help="TPC-H scale factor (default 1: 6.0 M-row flat)")
    ap.add_argument("--seed", type=int, default=20260729)
    ap.add_argument("--target-rows", type=int, default=1 << 20,
                    help="rows per segment")
    ap.add_argument("--params", action="store_true",
                    help="instead of serve and storm: one never-sent draw "
                         "of each TPC-H template after a warm-up")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: skips ONLY the device assertion "
                         "and ends with \"ok\": false")
    args = ap.parse_args()
    check(args.rehearse or "SDOT_PALLAS" not in os.environ,
          "SDOT_PALLAS is set: the chip run takes the compiler's kernel")

    device = phase_device(args)
    from spark_druid_olap_tpu.utils import compile_cache
    cache_dir = compile_cache.configure()
    emit("cache", dir=cache_dir, entries=compile_cache.entries(cache_dir))
    # the result cache would answer every second run from host memory;
    # the smoke wants the device each time
    cfg = {"sdot.cache.enabled": False}
    t_all = time.perf_counter()
    if args.chips > 1:
        phase_mesh(args, cfg)
    else:
        from spark_druid_olap_tpu.server.http import SqlServer
        ctx = sdot.Context(cfg)
        flat = phase_load(args, ctx)
        srv = SqlServer(ctx, "127.0.0.1", 0).start(background=True)
        try:
            t0 = time.perf_counter()
            if args.params:
                phase_params(ctx, srv.port, args.seed, cache_dir)
                emit("seconds", params=round(time.perf_counter() - t0, 1))
            else:
                phase_serve(ctx, srv.port, flat)
                t1 = time.perf_counter()
                phase_storm(ctx, srv.port)
                emit("seconds", serve=round(t1 - t0, 1),
                     storm=round(time.perf_counter() - t1, 1))
        finally:
            srv.stop()
    emit("cache", dir=cache_dir, entries=compile_cache.entries(cache_dir),
         total_seconds=round(time.perf_counter() - t_all, 1))
    print(json.dumps({"ok": not args.rehearse,
                      "device": {"platform": device["platform"],
                                 "kind": device["kind"],
                                 "count": device["count"]}}), flush=True)


if __name__ == "__main__":
    main()
