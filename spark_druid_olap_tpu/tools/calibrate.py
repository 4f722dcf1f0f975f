"""Cost-model calibration from MEASURED wall times (VERDICT r2 item 9).

The reference validated its cost structure with a calibrated
``DruidQueryCostModelTest``; here the constants themselves are fit on
the live backend: run probe group-bys single-chip and mesh-sharded,
time the warm executions, and least-squares the model's terms —

    single  ~= rows * scan_c + groups * 16 * byte_c
    sharded ~= rows * scan_c / (n_dev * eff) + groups * n_aggs * merge_c
               + groups * 16 * byte_c

Units become SECONDS (the defaults are unit-free hand-set numbers).
``eff`` is the mesh's real parallel efficiency — ~1.0 on ICI-connected
chips, far lower on a virtual CPU mesh sharing host cores — which is
exactly what makes the single-vs-sharded decision transfer between
environments.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from spark_druid_olap_tpu.ir import spec as S
from spark_druid_olap_tpu.parallel.mesh import mesh_size
from spark_druid_olap_tpu.utils.config import (
    COST_COMPILE, COST_PER_BYTE_TRANSPORT, COST_PER_ROW_MERGE,
    COST_PER_ROW_SCAN, COST_SHARD_EFFICIENCY)


def default_shapes(datasource: str, ds) -> List[S.GroupByQuerySpec]:
    """Three probe shapes with distinct (rows x groups) profiles: a
    low-cardinality full scan, a filtered scan, and a high-cardinality
    group-by (merge-term heavy)."""
    dims = sorted(ds.dims, key=lambda d: ds.cardinality(d) or 0)
    if not dims:
        raise ValueError("calibration needs at least one dimension")
    lo = dims[0]
    hi = dims[-1]
    metric = next((m for m in ds.metrics), None)
    aggs = [S.AggregationSpec("count", "n")]
    if metric is not None:
        kind = "doublesum" if ds.column_kind(metric).name == "DOUBLE" \
            else "longsum"
        aggs.append(S.AggregationSpec(kind, "s", field=metric))
    aggs = tuple(aggs)
    filt = None
    d0 = ds.dims[lo]
    if len(d0.dictionary):
        filt = S.SelectorFilter(lo, str(d0.dictionary[0]))
    return [
        S.GroupByQuerySpec(datasource=datasource,
                           dimensions=(S.DimensionSpec(lo, lo),),
                           aggregations=aggs),
        S.GroupByQuerySpec(datasource=datasource,
                           dimensions=(S.DimensionSpec(lo, lo),),
                           aggregations=aggs, filter=filt),
        S.GroupByQuerySpec(datasource=datasource,
                           dimensions=(S.DimensionSpec(hi, hi),),
                           aggregations=aggs),
    ]


def _measure(engine, q, reps: int) -> Tuple[float, dict]:
    engine.execute(q)                       # warm (compile + upload)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        engine.execute(q)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)), dict(engine.last_stats)


def measure_samples(single_engine, mesh_engine, shapes,
                    reps: int = 3) -> List[dict]:
    """One sample per shape: measured single/sharded wall seconds plus
    the model's inputs (rows, groups, n_aggs)."""
    out = []
    for q in shapes:
        t1, st1 = _measure(single_engine, q, reps)
        sample = {"rows": int(st1.get("rows_scanned", 0)),
                  "groups": max(1, int(st1.get("groups", 1))),
                  "n_aggs": max(1, len(S.query_aggregations(q))),
                  "single_s": t1, "spec": q}
        if mesh_engine is not None:
            t8, st8 = _measure(mesh_engine, q, reps)
            sample["sharded_s"] = t8
            sample["sharded_really"] = bool(st8.get("sharded"))
        out.append(sample)
    return out


def fit(samples: List[dict], n_dev: int) -> Dict[str, float]:
    """Least-squares fit of the model constants (clamped positive)."""
    rows = np.array([s["rows"] for s in samples], dtype=np.float64)
    grp = np.array([s["groups"] for s in samples], dtype=np.float64)
    naggs = np.array([s["n_aggs"] for s in samples], dtype=np.float64)
    t1 = np.array([s["single_s"] for s in samples], dtype=np.float64)

    a1 = np.stack([rows, grp * 16.0], axis=1)
    (scan_c, byte_c), *_ = np.linalg.lstsq(a1, t1, rcond=None)
    scan_c = max(float(scan_c), 1e-12)
    byte_c = max(float(byte_c), 1e-13)

    out = {COST_PER_ROW_SCAN.key: scan_c,
           COST_PER_BYTE_TRANSPORT.key: byte_c,
           COST_COMPILE.key: 0.0}
    if any("sharded_s" in s for s in samples) and n_dev > 1:
        # only timings where the mesh engine REALLY sharded inform the
        # sharded-side terms (a cost-model single-chip run would fit
        # eff ~= 1/n_dev and a noise merge_c)
        t8 = np.array([s["sharded_s"]
                       if s.get("sharded_really", True)
                       and "sharded_s" in s else np.nan
                       for s in samples])
        ok = ~np.isnan(t8)
        if not ok.any():
            return out
        a8 = np.stack([rows[ok], grp[ok] * naggs[ok]], axis=1)
        resid = t8[ok] - grp[ok] * 16.0 * byte_c
        (alpha, merge_c), *_ = np.linalg.lstsq(a8, resid, rcond=None)
        merge_c = max(float(merge_c), 1e-13)
        # alpha = scan_c / (n_dev * eff)
        eff = scan_c / (max(float(alpha), 1e-15) * n_dev)
        out[COST_PER_ROW_MERGE.key] = merge_c
        out[COST_SHARD_EFFICIENCY.key] = float(np.clip(eff, 0.01, 1.0))
    return out


def _amortized_s(fn, args, reps: int = 4) -> float:
    """Median amortized seconds of one jitted program: chained dispatches
    between DATA-DEPENDENT syncs (a fetch cannot return before the
    dispatch that produces it retires)."""
    import jax
    import jax.numpy as jnp

    def sync(r):
        leaf = jax.tree_util.tree_leaves(r)[0]
        np.asarray(jnp.ravel(leaf)[:1])

    sync(fn(*args))                         # compile + warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        r = None
        for _ in range(4):
            r = fn(*args)
        sync(r)
        ts.append((time.perf_counter() - t0) / 4)
    return float(np.median(ts))


def calibrate_primitives(config, n_rows: int = 1 << 21,
                         apply: bool = True) -> Dict[str, float]:
    """Fit the per-backend UNIT costs the perf gates consume (VERDICT r3
    weak 6): 1-op sort s/row, extra-payload s/row, scatter s/update at an
    in-cache AND a past-cache table size, and 1D-gather s/probe. Applied
    to the session config, these drive `_plan_compact_m`, the sorted-run
    gate, and the ffl compaction ceiling from measurement instead of
    hand-tuned literals."""
    import jax
    import jax.numpy as jnp
    from spark_druid_olap_tpu.utils.config import (
        COST_GATHER_PROBE, COST_SCATTER_UPDATE, COST_SCATTER_UPDATE_BIG,
        COST_SORT_PAYLOAD_ROW, COST_SORT_ROW, COST_TABLE_CACHE_BYTES)

    n = int(n_rows)
    rng = np.random.default_rng(11)
    k1 = jnp.asarray(rng.integers(0, 1 << 30, n).astype(np.int32))
    k2 = jnp.asarray(rng.integers(0, 1 << 30, n).astype(np.int32))
    p1 = jnp.asarray(rng.integers(0, 100, n).astype(np.int32))
    p2 = jnp.asarray(rng.normal(size=n).astype(np.float32))

    # late materialization's position sort: one unique key, unstable
    sort1 = jax.jit(lambda a: jax.lax.sort(a, is_stable=False))
    t_sort1 = _amortized_s(sort1, (jnp.asarray(
        rng.permutation(n).astype(np.int32)),))
    sort2 = jax.jit(lambda a, b: jax.lax.sort((a, b), num_keys=2))
    sort4 = jax.jit(lambda a, b, c, d: jax.lax.sort((a, b, c, d),
                                                    num_keys=2))
    t_sort2 = _amortized_s(sort2, (k1, k2))
    t_sort4 = _amortized_s(sort4, (k1, k2, p1, p2))

    t_small = 1 << 15                   # ~128KB table: comfortably cached
    # big table: slots = cache-threshold BYTES, i.e. a 4x-past-threshold
    # f32 table, so the thrash regime (if this backend has one) is what
    # gets measured
    t_big = max(1 << 18, int(config.get(COST_TABLE_CACHE_BYTES)))

    def scat(tbl_slots):
        idx = jnp.asarray(rng.integers(0, tbl_slots, n).astype(np.int32))

        def f(v):
            return jnp.zeros(tbl_slots, jnp.float32).at[idx].add(v)
        return _amortized_s(jax.jit(f), (p2,))

    t_scat_small = scat(t_small)
    t_scat_big = scat(t_big)

    lut = jnp.asarray(rng.normal(size=t_small).astype(np.float32))
    gidx = jnp.asarray(rng.integers(0, t_small, n).astype(np.int32))
    t_gather = _amortized_s(jax.jit(lambda i: jnp.take(lut, i)), (gidx,))

    fitted = {
        COST_SORT_ROW.key: max(t_sort1 / n, 1e-13),
        COST_SORT_PAYLOAD_ROW.key: max((t_sort4 - t_sort2) / (2 * n),
                                       1e-13),
        COST_SCATTER_UPDATE.key: max(t_scat_small / n, 1e-13),
        COST_SCATTER_UPDATE_BIG.key: max(t_scat_big / n, 1e-13),
        COST_GATHER_PROBE.key: max(t_gather / n, 1e-13),
    }
    if apply:
        for k, v in fitted.items():
            config.set(k, v)
    return fitted


def calibrate(ctx, datasource: Optional[str] = None, reps: int = 3,
              mesh_ctx=None, apply: bool = True) -> Dict[str, float]:
    """Fit the cost constants on the LIVE backend and (optionally) apply
    them to the session config. ``mesh_ctx`` supplies the sharded side;
    without one, only the single-chip terms are fit."""
    datasource = datasource or sorted(ctx.store.names())[0]
    ds = ctx.store.get(datasource)
    shapes = default_shapes(datasource, ds)
    mesh_engine = mesh_ctx.engine if mesh_ctx is not None else None
    n_dev = mesh_size(mesh_engine.mesh) if mesh_engine is not None else 1
    from spark_druid_olap_tpu.utils.config import COST_MODEL_ENABLED
    prev_cm = None
    if mesh_ctx is not None:
        # the sharded probes must REALLY shard, whatever the current
        # (uncalibrated) model would decide
        prev_cm = mesh_ctx.config.get(COST_MODEL_ENABLED)
        mesh_ctx.config.set(COST_MODEL_ENABLED.key, False)
    try:
        samples = measure_samples(ctx.engine, mesh_engine, shapes, reps)
    finally:
        if mesh_ctx is not None:
            mesh_ctx.config.set(COST_MODEL_ENABLED.key, prev_cm)
    fitted = fit(samples, n_dev)
    if apply:
        for k, v in fitted.items():
            ctx.config.set(k, v)
            if mesh_ctx is not None:
                mesh_ctx.config.set(k, v)
    return fitted
