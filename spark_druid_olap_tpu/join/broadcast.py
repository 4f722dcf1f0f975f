"""Broadcast hash join: build once per node, probe in the wave loop.

The build side (already under ``sdot.join.broadcast.max.bytes`` by the
planner's estimate) materializes host-side, canonicalizes its keys, and
becomes one device-resident pytree — the open-addressing table from
``ops/hash_join.py`` plus payload/group columns. The probe side then
streams through the SAME segment wave loop the scan executor uses:
waves sized by ``parallel/cost.py:plan_waves``, arrays bound through
the engine's cached device bind (``_bind_arrays`` — so repeated join
queries never re-upload columns), cold-tier chunks pinned for the whole
join (``tier/store.py`` pin pair) and prefetched a wave ahead.

On a multi-chip mesh the table pytree replicates per device (in-spec
``P()``) while probe waves shard over the segment axis — each device
probes its slice and per-group partials merge on the interconnect with
the same register algebra the mesh scan tier uses
(``groupby.merge_partials``: psum sums/counts, pmin/pmax extrema).

Device residency of the build table is a checked acquire/release pair
(``BuildLedger`` — sdlint leaks resource ``join-build``), mirroring the
mesh tier's partial-buffer ledger: no decline/exception path may leave
phantom build bytes in the gauge.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
from jax.sharding import NamedSharding, PartitionSpec as P

from spark_druid_olap_tpu.ir import expr as E
from spark_druid_olap_tpu.ops import groupby as G
from spark_druid_olap_tpu.ops import hash_join as HJ
from spark_druid_olap_tpu.ops.hash_join import JoinUnsupported
from spark_druid_olap_tpu.ops.scan import (
    NULL_VALID_PREFIX,
    ROW_VALID_KEY,
    array_dtype,
    array_names,
)
from spark_druid_olap_tpu.parallel import cost as C
from spark_druid_olap_tpu.parallel.executor import (
    EngineFallback,
    _pad_segments,
)
from spark_druid_olap_tpu.parallel.mesh import (
    SEGMENT_AXIS,
    mesh_size,
    named_jit,
    shard_map,
)
from spark_druid_olap_tpu.utils import phases as PH
from spark_druid_olap_tpu.utils.config import (
    GROUPBY_MATMUL_MAX_KEYS,
    JOIN_MAX_MATCHES,
    MESH_ENABLED,
)

#: dense group-key ceiling for the join group-by (same order as the
#: engine's dense tier; a wider group space declines to the host)
MAX_GROUP_KEYS = 1 << 22


# =============================================================================
# build-table residency ledger (sdlint leaks pair: join-build)
# =============================================================================

class _BuildToken:
    __slots__ = ("nbytes", "released")

    def __init__(self, nbytes: int):
        self.nbytes = int(nbytes)
        self.released = False


class BuildLedger:
    """Device-byte accounting for broadcast build tables while a join
    holds them resident (table + payload pytree, per node — replicated
    copies on a mesh count once; the mesh replicates for free from the
    ledger's point of view, like a weight pytree)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.outstanding_bytes = 0
        self.peak_bytes = 0
        self.acquires = 0

    def acquire_build(self, nbytes: int) -> _BuildToken:
        tok = _BuildToken(nbytes)
        with self._lock:
            self.acquires += 1
            self.outstanding_bytes += tok.nbytes
            self.peak_bytes = max(self.peak_bytes, self.outstanding_bytes)
        return tok

    def release_build(self, tok: _BuildToken) -> None:
        with self._lock:
            if not tok.released:
                tok.released = True
                self.outstanding_bytes -= tok.nbytes

    def stats(self) -> dict:
        with self._lock:
            return {"outstanding_bytes": self.outstanding_bytes,
                    "peak_bytes": self.peak_bytes,
                    "acquires": self.acquires}


#: process-wide gauge (surfaced through stats["join"]["build_ledger"])
LEDGER = BuildLedger()


# =============================================================================
# host-side helpers shared with the partitioned tier's local exec
# =============================================================================

def null_mask(vals) -> np.ndarray:
    """NaN/None-coded null mask for a host column (pandas convention)."""
    return np.asarray(pd.isna(np.asarray(vals)), dtype=bool)


def factorize_group(vals: np.ndarray):
    """Host group-column factorization: sorted non-null uniques + codes
    with the null lane at ``len(uniques)``. Returns
    ``(codes int32, card_with_null, decoder)``."""
    vals = np.asarray(vals)
    nulls = null_mask(vals)
    nn = vals[~nulls]
    if nn.dtype == object or nn.dtype.kind in ("U", "S"):
        uniq = np.unique(nn.astype(str)) if len(nn) else \
            np.empty(0, dtype=object)
        pos = np.searchsorted(uniq, vals.astype(str)) if len(uniq) else \
            np.zeros(len(vals), dtype=np.int64)
    else:
        uniq = np.unique(nn)
        pos = np.searchsorted(uniq, np.where(nulls, uniq[0] if len(uniq)
                                             else 0, vals)) \
            if len(uniq) else np.zeros(len(vals), dtype=np.int64)
    card = len(uniq)
    codes = np.where(nulls, card, np.clip(pos, 0, max(0, card - 1))) \
        .astype(np.int32)

    def decode(cs: np.ndarray) -> np.ndarray:
        cs = np.asarray(cs, dtype=np.int64)
        isnull = cs >= card
        if uniq.dtype == object or uniq.dtype.kind in ("U", "S"):
            out = np.empty(len(cs), dtype=object)
            out[~isnull] = uniq[np.clip(cs[~isnull], 0,
                                        max(0, card - 1))].astype(str) \
                if card else None
            out[isnull] = None
            return out
        out = uniq[np.clip(cs, 0, max(0, card - 1))] if card else \
            np.zeros(len(cs))
        if isnull.any():
            out = out.astype(np.float64)
            out[isnull] = np.nan
        return out

    return codes, card + 1, decode


def numeric_payload(vals: np.ndarray, x64: bool):
    """Host agg/residual column -> (device value array, valid mask).
    Integers keep an exact integer route when the backend can carry it;
    strings decline (the planner should have caught them)."""
    vals = np.asarray(vals)
    nulls = null_mask(vals)
    if vals.dtype == object or vals.dtype.kind in ("U", "S"):
        raise JoinUnsupported("string column in a numeric join payload")
    if vals.dtype.kind in ("i", "u"):
        if x64:
            return vals.astype(np.int64), ~nulls
        a = vals.astype(np.float64)
        if len(a) and np.abs(a[~nulls]).max(initial=0) >= 2 ** 31:
            raise JoinUnsupported(
                "wide integer join payload on a 32-bit backend")
        return vals.astype(np.int32), ~nulls
    out = np.where(nulls, 0.0, vals).astype(
        np.float64 if x64 else np.float32)
    return out, ~nulls


def agg_is_int(arg: Optional[E.Expr], kindof) -> bool:
    """Static integer-route hint: a bare integer column aggregates on
    the exact integer route; any compound expression goes float."""
    return isinstance(arg, E.Column) and kindof(arg.name) == "int"


_F32_SENT = np.float32(3.4e38)
_SENTINELS = {
    ("f64", "min"): np.inf, ("f64", "max"): -np.inf,
    ("i64", "min"): G.I64_MAX, ("i64", "max"): G.I64_MIN,
    ("i32", "min"): G.I32_MAX, ("i32", "max"): G.I32_MIN,
    ("f32", "min"): _F32_SENT, ("f32", "max"): -_F32_SENT,
}


def sentinel_of(route: G.Route):
    return _SENTINELS.get((route.tag, route.kind))


def finalize_agg(spec_fn: str, out_name: str, acc: Dict[str, np.ndarray],
                 routes: Dict[str, G.Route]) -> np.ndarray:
    """One aggregation's exact cross-wave accumulator -> final column
    with SQL null semantics (empty-group sum/avg/min/max -> NULL)."""
    if spec_fn == "count":
        return np.asarray(acc[out_name], dtype=np.int64)
    if spec_fn in ("sum", "avg"):
        raw = np.asarray(acc[out_name])
        vc = np.asarray(acc["__vc__" + out_name], dtype=np.int64)
        if spec_fn == "avg":
            return np.where(vc > 0, raw / np.maximum(vc, 1), np.nan) \
                .astype(np.float64)
        if (vc == 0).any():
            return np.where(vc > 0, raw.astype(np.float64), np.nan)
        return raw
    # min / max: the route sentinel marks all-null groups
    val = np.asarray(acc[out_name])
    sent = sentinel_of(routes[out_name])
    if sent is not None and (val == sent).any():
        return np.where(val == sent, np.nan, val.astype(np.float64))
    return val


def combine_wave(acc: Dict[str, np.ndarray], wave_out: Dict[str, object],
                 routes: Dict[str, G.Route], n_keys: int) -> None:
    """Fold one wave's device outputs into the exact host accumulator
    (f64/i64 adds for sums/counts, sentinel-preserving elementwise
    min/max for extrema)."""
    np_out = {k: np.asarray(v) for k, v in wave_out.items()}
    for name, route in routes.items():
        arr = G.combine_route(route, np_out, n_keys)
        cur = acc.get(name)
        if cur is None:
            acc[name] = arr
        elif route.kind == "min":
            acc[name] = np.minimum(cur, arr)
        elif route.kind == "max":
            acc[name] = np.maximum(cur, arr)
        else:
            acc[name] = cur + arr


# =============================================================================
# the broadcast executor
# =============================================================================

def execute_broadcast(ctx, plan) -> Tuple[Dict[str, np.ndarray], dict]:
    """Run ``plan`` (planner/joinplan.JoinPlan) on the broadcast tier.

    Returns ``(grouped data, join stats dict)`` — group columns keyed by
    query name, agg columns keyed by output name, all finalized; the
    planner's shared epilogue does having/order/limit/projection."""
    eng = ctx.engine
    conf = ctx.config
    store = ctx.store
    x64 = G._x64()
    ds = store.get(plan.probe.ds)
    if getattr(ds, "is_partial", False):
        raise JoinUnsupported("probe side is a multi-host partial store")

    # ---- build side: materialize, filter, canonicalize ----------------------
    from spark_druid_olap_tpu.planner import host_exec
    from spark_druid_olap_tpu.utils import host_eval
    bcols = plan.build_cols()
    bdf = host_exec.datasource_frame(ctx, plan.build.ds, columns=bcols)
    if plan.build_filter is not None:
        env = {c: bdf[c].to_numpy() for c in bdf.columns}
        bdf = bdf[host_eval.eval_pred3(plan.build_filter, env)]
    bdf = bdf.reset_index(drop=True)

    key_pcols = [pc for pc, _ in plan.keys]
    key_bcols = [bc for _, bc in plan.keys]
    bvals = [bdf[c].to_numpy() for c in key_bcols]
    bvalid = [~null_mask(v) for v in bvals]
    uniques, comps, keep = HJ.build_key_components(bvals, bvalid)
    cards = [len(u) for u in uniques]
    if HJ.key_domain(cards) >= HJ.MAX_KEY_DOMAIN:
        raise JoinUnsupported(
            f"composite key domain {HJ.key_domain(cards)} exceeds int32")
    bdf = bdf[keep].reset_index(drop=True)
    fused = HJ.fuse_components(comps, cards)
    table = HJ.build_table(fused, conf.get(JOIN_MAX_MATCHES))
    n_build = table.n_build
    C_w = max(1, table.max_count)

    # probe-side key maps (dictionary LUT / numeric searchsorted)
    keymaps = []
    for pc, uniq in zip(key_pcols, uniques):
        dcol = ds.dims.get(pc)
        if dcol is not None:
            if uniq.dtype != object and uniq.dtype.kind not in ("U", "S"):
                raise JoinUnsupported(
                    f"join key {pc!r} is a dimension but the build side "
                    f"is numeric")
            keymaps.append(HJ.dim_keymap(dcol.dictionary, uniq))
        else:
            if uniq.dtype == object or uniq.dtype.kind in ("U", "S"):
                raise JoinUnsupported(
                    f"join key {pc!r} is numeric but the build side is "
                    f"a string column")
            keymaps.append(HJ.numeric_keymap(
                uniq, array_dtype(ds, pc)))

    # ---- build payload / group columns --------------------------------------
    build_used = plan.build_value_cols()
    pay, payv = {}, {}
    for c in build_used:
        pay[c], payv[c] = numeric_payload(bdf[c].to_numpy(), x64)
    bgrp: Dict[str, Tuple[np.ndarray, int, object]] = {}
    group_meta: List[Tuple[str, int, object]] = []
    probe_group_cols = []
    for g in plan.group_by:
        side, phys = plan.colside[g]
        if side == "build":
            codes, cardn, dec = factorize_group(bdf[phys].to_numpy())
            bgrp[g] = (codes, cardn, dec)
            group_meta.append((g, cardn, dec))
        else:
            dcol = ds.dims.get(phys)
            card = dcol.cardinality

            def dec_dim(cs, _d=dcol, _card=card):
                cs = np.asarray(cs, dtype=np.int64)
                out = np.empty(len(cs), dtype=object)
                nn = cs < _card
                out[nn] = _d.decode(cs[nn])
                out[~nn] = None
                return out

            probe_group_cols.append(phys)
            group_meta.append((g, card + 1, dec_dim))
    gcards = [m[1] for m in group_meta]
    n_keys = 1
    for c in gcards:
        n_keys *= c
    n_keys = max(1, n_keys)
    if n_keys > MAX_GROUP_KEYS:
        raise JoinUnsupported(
            f"join group-by cardinality {n_keys} exceeds the dense "
            f"tier's ceiling {MAX_GROUP_KEYS}")

    if n_build == 0:
        # an empty build side (after its filter) joins to nothing: skip
        # the device loop entirely — a gather over zero-length payload
        # arrays is ill-formed — and emit the empty grouped shape (or
        # the single global-aggregate zero row) directly
        data0: Dict[str, np.ndarray] = {}
        if group_meta:
            for g, _, dec in group_meta:
                data0[g] = dec(np.empty(0, dtype=np.int64))
            for spec in plan.aggs:
                data0[spec.out] = (np.zeros(0, dtype=np.int64)
                                   if spec.fn == "count"
                                   else np.zeros(0, dtype=np.float64))
        else:
            for spec in plan.aggs:
                data0[spec.out] = (np.zeros(1, dtype=np.int64)
                                   if spec.fn == "count"
                                   else np.full(1, np.nan))
        js0 = {"mode": "broadcast", "build_rows": 0, "build_bytes": 0,
               "table_slots": int(table.n_slots), "match_width": 0,
               "waves": 0, "segments_per_wave": 0, "devices": 0,
               "mesh": "empty-build", "groups": 0,
               "build_ledger": LEDGER.stats()}
        return data0, js0

    # ---- probe plan: columns, waves, mesh decision --------------------------
    pcols = sorted(plan.probe_cols())
    names = array_names(ds, pcols, need_time_ms=False)
    n_segments = ds.num_segments
    mesh_reason = "no-mesh"
    n_dev = 1
    if eng.mesh is not None and mesh_size(eng.mesh) > 1:
        n = mesh_size(eng.mesh)
        if not bool(conf.get(MESH_ENABLED)):
            mesh_reason = "disabled"
        elif jax.process_count() > 1:
            mesh_reason = "multihost"
        elif n_segments < n:
            mesh_reason = "few-segments"
        else:
            n_dev, mesh_reason = n, "sharded"
    seg_bytes = C.bytes_per_segment(ds, names)
    spw, n_waves = C.plan_waves(
        n_segments, n_dev, seg_bytes, C.wave_budget_bytes(conf), conf,
        output_groups=n_keys, n_aggs=len(plan.aggs),
        io_budget=C.tier_io_budget(ds, conf),
        io_seg_bytes=C.tier_io_seg_bytes(ds, names))

    # ---- routes -------------------------------------------------------------
    matmul_max = int(conf.get(GROUPBY_MATMUL_MAX_KEYS))
    Rrows = ds.padded_rows
    n_flat = spw * Rrows * C_w

    def kindof(qname: str) -> str:
        side, phys = plan.colside[qname]
        if side == "probe":
            if phys in ds.dims:
                return "dim"
            k = ds.column_kind(phys)
            return "int" if k.value == "long" else "float"
        v = pay.get(phys)
        if v is None:
            return "dim"
        return "int" if v.dtype.kind in ("i", "u") else "float"

    meta_inputs = [G.AggInput(ROW_VALID_KEY, "count")]
    for spec in plan.aggs:
        kind = "sum" if spec.fn == "avg" else spec.fn
        if kind == "count":
            meta_inputs.append(G.AggInput(spec.out, "count"))
        else:
            is_int = agg_is_int(spec.arg, kindof)
            meta_inputs.append(G.AggInput(spec.out, kind, is_int=is_int))
            if kind == "sum":
                meta_inputs.append(G.AggInput("__vc__" + spec.out,
                                              "count"))
    routes = G.plan_routes(meta_inputs, n_keys, matmul_max,
                           n_rows=n_flat)
    if n_dev > 1 and not all(r.merged for r in routes.values()):
        # unmerged Neumaier pairs want a per-chip host combine the
        # join's replicated out-spec doesn't carry — single-device
        n_dev, mesh_reason = 1, "unmerged-routes"
        spw, n_waves = C.plan_waves(
            n_segments, 1, seg_bytes, C.wave_budget_bytes(conf), conf,
            output_groups=n_keys, n_aggs=len(plan.aggs),
            io_budget=C.tier_io_budget(ds, conf),
            io_seg_bytes=C.tier_io_seg_bytes(ds, names))

    # ---- the jitted wave core ----------------------------------------------
    dimlk = ds.dims.get

    def jdim(qname: str):
        side, phys = plan.colside.get(qname, (None, None))
        return ds.dims.get(phys) if side == "probe" else None

    def core(arrays, tdev):
        rowv = arrays[ROW_VALID_KEY]

        def pget(phys):
            v = arrays[phys]
            if phys in ds.dims:
                v = v.astype(jnp.int32)
            nv = arrays.get(NULL_VALID_PREFIX + phys)
            valid = rowv if nv is None else jnp.logical_and(rowv, nv)
            return v, valid

        keep = rowv
        fm = HJ.pred_mask(plan.probe_filter, pget, dimlk)
        if fm is not None:
            keep = jnp.logical_and(keep, fm)
        kvals, kvalids = [], []
        for pc in key_pcols:
            v, ok = pget(pc)
            kvals.append(v)
            kvalids.append(jnp.logical_and(ok, keep))
        kdevs = [tdev["keys"][i] for i in range(len(keymaps))]
        key, kvalid = HJ.canonical_key(keymaps, kdevs, kvals, kvalids)
        key = key.reshape(-1)
        kvalid = kvalid.reshape(-1)
        start, count = HJ.probe(
            tdev["table"], key, kvalid, n_slots=table.n_slots,
            shift=table.shift, max_disp=table.max_disp)
        bidx, mvalid = HJ.expand(tdev["table"], start, count,
                                 width=C_w, n_build=n_build)
        N = key.shape[0]
        shape = (N, C_w)

        def jget(qname):
            side, phys = plan.colside[qname]
            if side == "probe":
                v, ok = pget(phys)
                return (v.reshape(-1)[:, None],
                        jnp.logical_and(ok.reshape(-1)[:, None], mvalid))
            return (tdev["pay"][phys][bidx],
                    jnp.logical_and(tdev["payv"][phys][bidx], mvalid))

        pairmask = mvalid
        if plan.residual is not None:
            pairmask = jnp.logical_and(
                pairmask, HJ.pred_mask(plan.residual, jget, jdim))

        gcodes = []
        for g in plan.group_by:
            side, phys = plan.colside[g]
            if side == "build":
                gcodes.append(tdev["bgrp"][g][bidx])
            else:
                code, ok = pget(phys)
                card = ds.dims[phys].cardinality
                gc = jnp.where(ok, code, jnp.int32(card))
                gcodes.append(jnp.broadcast_to(
                    gc.reshape(-1)[:, None], shape))
        if gcodes:
            gkey, _ = G.fuse_keys(gcodes, gcards)
        else:
            gkey = jnp.zeros(shape, dtype=jnp.int32)
        gkey = gkey.reshape(-1)
        flatmask = pairmask.reshape(-1)

        inputs = [G.AggInput(ROW_VALID_KEY, "count", mask=flatmask)]
        for spec in plan.aggs:
            kind = "sum" if spec.fn == "avg" else spec.fn
            if kind == "count":
                if spec.arg is None:
                    m = flatmask
                else:
                    _, ok = jget(_arg_col(spec.arg))
                    m = jnp.logical_and(pairmask, ok).reshape(-1)
                inputs.append(G.AggInput(spec.out, "count", mask=m))
                continue
            v, ok = HJ._num(spec.arg, jget, jdim)
            v = jnp.broadcast_to(v, shape).reshape(-1)
            m = jnp.logical_and(pairmask, ok).reshape(-1)
            is_int = agg_is_int(spec.arg, kindof)
            inputs.append(G.AggInput(spec.out, kind, values=v, mask=m,
                                     is_int=is_int))
            if kind == "sum":
                inputs.append(G.AggInput("__vc__" + spec.out, "count",
                                         mask=m))
        return G.dense_groupby(gkey, flatmask, n_keys, inputs, routes,
                               matmul_max)

    if n_dev > 1:
        def core_merged(arrays, tdev):
            out = core(arrays, tdev)
            return G.merge_partials(out, routes, SEGMENT_AXIS)

        smfn = shard_map(core_merged, mesh=eng.mesh,
                         in_specs=(P(SEGMENT_AXIS, None), P()),
                         out_specs=P(), check_vma=False)
        prog = named_jit("sdot_join_broadcast", smfn)
    else:
        prog = named_jit("sdot_join_broadcast", core)

    # ---- device residency + the wave loop -----------------------------------
    tree = {"table": table.device_tree(),
            "keys": {i: km.device_tree()
                     for i, km in enumerate(keymaps)},
            "pay": pay,
            "payv": payv,
            "bgrp": {g: codes for g, (codes, _, _) in bgrp.items()}}
    build_bytes = int(sum(a.nbytes for a in jax.tree_util.tree_leaves(
        tree)))
    sharding = NamedSharding(eng.mesh, P()) if n_dev > 1 else None
    tiers, pins = [], []
    for name in {plan.probe.ds, plan.build.ds}:
        t = getattr(store._datasources.get(name), "tier", None)
        if t is not None:
            tiers.append(t)
    acc: Dict[str, np.ndarray] = {}
    btok = LEDGER.acquire_build(build_bytes)
    try:
        pins = [t.acquire_pins() for t in tiers]
        eng._tick(1, len(jax.tree_util.tree_leaves(tree)))
        tdev = jax.device_put(tree, sharding) if sharding is not None \
            else jax.device_put(tree)
        seg_idx = np.arange(n_segments, dtype=np.int64)
        s_pad = spw if n_waves > 1 else _pad_segments(n_segments, n_dev)
        waves = [seg_idx[i: i + s_pad]
                 for i in range(0, n_segments, s_pad)]
        try:
            for i, w in enumerate(waves):
                arrays = eng._bind_arrays(ds, names, w, s_pad, n_dev > 1)
                eng._tier_prefetch(ds, names, waves, i + 1)
                eng._tick()
                with PH.phase("dispatch"):
                    out = eng._wait(eng._launch(
                        lambda a: prog(a, tdev), arrays))
                    eng._tick(1)
                    eng._fetch(lambda o: combine_wave(acc, o, routes,
                                                      n_keys), out)
        except EngineFallback as e:
            raise JoinUnsupported(str(e)) from e
    finally:
        try:
            for t, tok in zip(tiers, pins):
                t.release_pins(tok)
        finally:
            LEDGER.release_build(btok)

    # ---- finalize -----------------------------------------------------------
    rows = np.asarray(acc[ROW_VALID_KEY], dtype=np.int64)
    idx = np.nonzero(rows > 0)[0]
    if not group_meta:
        idx = np.arange(1)     # global aggregate: always one row

    codes = G.unfuse_key(idx, gcards) if group_meta else []
    data: Dict[str, np.ndarray] = {}
    for (g, _, dec), cs in zip(group_meta, codes):
        data[g] = dec(cs)
    for spec in plan.aggs:
        data[spec.out] = finalize_agg(spec.fn, spec.out, acc,
                                      routes)[idx]
    js = {
        "mode": "broadcast",
        "build_rows": int(n_build),
        "build_bytes": build_bytes,
        "table_slots": int(table.n_slots),
        "match_width": int(C_w),
        "waves": int(n_waves),
        "segments_per_wave": int(spw),
        "devices": int(n_dev),
        "mesh": mesh_reason,
        "groups": int(len(idx)),
        "build_ledger": LEDGER.stats(),
    }
    return data, js


def _arg_col(e: E.Expr) -> str:
    if isinstance(e, E.Column):
        return e.name
    raise JoinUnsupported("count() over a compound expression")
