"""Query executor: lowers a QuerySpec onto compiled XLA scan programs and runs
them single-chip or sharded over a device mesh.

This layer merges three reference components, re-seamed for TPU:

- ``DruidRDD`` (``DruidRDD.scala:152-277``): partitioning the scan across
  historicals/segments -> here, the segment axis of the stacked tensors,
  sharded over the mesh by ``shard_map``;
- the broker/historical scatter-gather + Spark-side final aggregate
  (``DruidStrategy.scala:349-360``, ``PostAggregate``): -> ICI collectives
  (psum/pmin/pmax) inside the compiled program;
- result-row materialization (``DruidRDD.scala:235-241`` value transforms):
  -> host-side group decoding through the global dictionaries.

Compile model: one XLA program per (query structure, padded shapes) — cached,
so repeated dashboard-style queries hit a warm executable (the reference's
analog is Druid's own query planning being stateless but fast; our compile
cost is front-loaded and amortized, tracked by the cost model's compile-cost
knob).
"""

from __future__ import annotations

import dataclasses
import hashlib
import time as _time
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_druid_olap_tpu.ir import expr as E
from spark_druid_olap_tpu.ir import spec as S
from spark_druid_olap_tpu.parallel import multihost as MH
from spark_druid_olap_tpu.ops import expr_compile as EC
from spark_druid_olap_tpu.ops import filters as F
from spark_druid_olap_tpu.ops import groupby as G
from spark_druid_olap_tpu.ops import hash_groupby as H
from spark_druid_olap_tpu.ops import hll as HLL
from spark_druid_olap_tpu.ops import kll as KLL
from spark_druid_olap_tpu.ops import literals as L
from spark_druid_olap_tpu.ops import pallas_groupby as PG_tpu
from spark_druid_olap_tpu.ops import sorted_groupby as SG
from spark_druid_olap_tpu.ops import theta as TH
from spark_druid_olap_tpu.ops import time_ops as T
from spark_druid_olap_tpu.ops import timezone as TZ
from spark_druid_olap_tpu.ops.scan import (
    Compaction,
    ScanContext,
    compact_scan,
    array_dtype,
    array_names,
    build_array,
    build_array_blocks,
    ROW_VALID_KEY,
    NULL_VALID_PREFIX,
    TIME_MS_KEY,
)
from spark_druid_olap_tpu.parallel import cost as C
from spark_druid_olap_tpu.parallel.mesh import (SEGMENT_AXIS, mesh_size,
                                                 named_jit, shard_map)
from spark_druid_olap_tpu.planner import fusion as FU
from spark_druid_olap_tpu.result import QueryResult
from spark_druid_olap_tpu.segment.column import ColumnKind
from spark_druid_olap_tpu.segment.store import (Datasource, Segment,
                                                SegmentStore)
from spark_druid_olap_tpu.utils import host_eval
from spark_druid_olap_tpu.utils import phases as PH
from spark_druid_olap_tpu.utils.config import (
    Config,
    TZ_ID,
    BACKEND_RETRY_SECONDS,
    DEVICE_CACHE_BYTES,
    ENCODE_ENABLED,
    GROUPBY_DENSE_MAX_KEYS,
    SCAN_COMPACT,
    SCAN_COMPACT_MIN_ROWS,
    GROUPBY_HASH_COMPACT_MIN,
    GROUPBY_HASH_MAX_SLOTS,
    GROUPBY_HASH_SORTED,
    GROUPBY_HASH_SLOTS,
    GROUPBY_MATMUL_MAX_KEYS,
    GROUPBY_PALLAS_MAX_KEYS,
    HAVING_DEVICE_MIN_KEYS,
    HLL_LOG2M,
    QUANTILE_LANES,
    SELECT_DEVICE_MIN_ROWS,
    SHAREDSCAN_FUSION_ENABLED,
    TOPN_DEVICE_MIN_KEYS,
)


class EngineFallback(Exception):
    """Query (or part) can't run on the device path; planner must evaluate a
    host residual instead. ≈ the reference leaving unpushable predicates
    above the Druid scan (``ProjectFilterTransfom.addUnpushedAttributes``)."""


class QueryCancelled(RuntimeError):
    """Raised when a registered query id is cancelled mid-flight.

    ≈ the reference's cooperative cancellation: Spark task interruption
    relayed to abort the in-flight Druid HTTP call (``TaskCancelHandler``
    ``DruidRDD.scala:428-491``, ``CancellableHolder``
    ``DruidClient.scala:82-124``). A dispatched XLA program itself is not
    interruptible (neither was Druid's in-progress segment scan) — the check
    fires at stage boundaries: before dispatch, after the device round-trip,
    and per select page."""


class QueryTimeout(RuntimeError):
    """Raised when QueryContext.timeout_millis elapses at a stage boundary."""


# =============================================================================
# dimension planning (host side; card/decode known before tracing)
# =============================================================================

@dataclasses.dataclass
class DimPlan:
    output_name: str
    card: int
    build: object            # ctx -> int32 codes in [0, card)
    decode: object           # np.ndarray[int] -> np.ndarray of output values
    source_cols: tuple
    # cardinality and buckets come from the SELECTED segments' day range,
    # so the range is part of the program's signature
    reads_days: bool = False


def _with_null_slot(build, decode, card, name, nullable):
    """Nullable grouping columns get slot 0 = the null group (Druid emits a
    null group for null dimension values); non-null codes shift by one."""
    if not nullable:
        return build, decode, card

    def build2(ctx):
        nv = ctx.null_valid(name)
        codes = build(ctx)
        if nv is None:
            return codes + 1
        return jnp.where(nv, codes + 1, 0)

    def decode2(idx):
        idx = np.asarray(idx, np.int64)
        vals = decode(np.maximum(idx - 1, 0))
        out = np.empty(len(idx), dtype=object)
        out[:] = [None if i == 0 else v for i, v in zip(idx, vals)]
        return out

    return build2, decode2, card + 1


def _plan_plain(name: str, ds: Datasource, out: str, min_day, max_day) -> DimPlan:
    kind = ds.column_kind(name)
    if kind == ColumnKind.DIM:
        col = ds.dims[name]
        build, decode, card = _with_null_slot(
            lambda ctx: ctx.col(name),
            lambda idx: col.dictionary[np.asarray(idx, np.int64)],
            col.cardinality, name, col.validity is not None)
        return DimPlan(out, card, build, decode, (name,))
    if kind == ColumnKind.DATE:
        m = ds.metrics[name]
        lo = int(m.min) if m.min is not None else 0
        hi = int(m.max) if m.max is not None else 0
        build, decode, card = _with_null_slot(
            lambda ctx: ctx.col(name) - lo,
            lambda idx: (np.asarray(idx, np.int64) + lo)
            .astype("datetime64[D]"),
            hi - lo + 1, name, m.validity is not None)
        return DimPlan(out, card, build, decode, (name,))
    if kind == ColumnKind.LONG:
        m = ds.metrics[name]
        lo = int(m.min) if m.min is not None else 0
        hi = int(m.max) if m.max is not None else 0
        if hi - lo + 1 >= H.PART_LIMIT:
            # beyond one int32 key part even alone; hashed path can't pack it
            raise EngineFallback(f"grouping on wide-range long {name}")
        build, decode, card = _with_null_slot(
            lambda ctx: ctx.col(name) - lo,
            lambda idx: np.asarray(idx, np.int64) + lo,
            hi - lo + 1, name, m.validity is not None)
        return DimPlan(out, card, build, decode, (name,))
    if kind == ColumnKind.TIME:
        # raw-time grouping only supported at day grain via extraction
        raise EngineFallback("group by raw time column; use an extraction")
    raise EngineFallback(f"group by {kind}")


_FIELD_CARDS = {"month": (1, 12), "quarter": (1, 4), "day": (1, 31),
                "dow": (1, 7), "doy": (1, 366), "hour": (0, 23),
                "minute": (0, 59), "second": (0, 59)}


def _plan_time_extraction(dspec: S.DimensionSpec, ds: Datasource,
                          min_day: int, max_day: int,
                          tz: str = "UTC") -> DimPlan:
    ex = dspec.extraction
    assert isinstance(ex, S.TimeExtraction)
    name = dspec.dimension
    kind = ds.column_kind(name)
    if kind not in (ColumnKind.TIME, ColumnKind.DATE, ColumnKind.DIM):
        raise EngineFallback(f"time extraction over {kind}")
    if kind == ColumnKind.DIM:
        # date-string dim: convert through host LUT then treat as days
        # (calendar dates — timezone-independent)
        col = ds.dims[name]
        lut = np.array([T.date_literal_to_days(s) if s else 0
                        for s in col.dictionary], dtype=np.int32)
        day_build = lambda ctx: EC._take_lut(lut, ctx.col(name))
        lo_day, hi_day = int(lut.min()), int(lut.max())
    elif kind == ColumnKind.DATE:
        # calendar dates — timezone-independent
        m = ds.metrics[name]
        lo_day = int(m.min) if m.min is not None else 0
        hi_day = int(m.max) if m.max is not None else 0
        day_build = lambda ctx: ctx.col(name)
    elif not TZ.is_utc(tz):
        # instants: shift to session-local wall-clock before extraction
        lo_day, hi_day = min_day - 1, max_day + 1
        _tzlut = TZ.day_offset_lut(tz, lo_day, hi_day)

        def dt_build(ctx):
            return TZ.shift_days_ms(ctx.col(name), ctx.time_ms(), _tzlut,
                                    lo_day)

        day_build = lambda ctx: dt_build(ctx)[0]
    else:
        lo_day, hi_day = min_day, max_day
        day_build = lambda ctx: ctx.col(name)
    if kind == ColumnKind.TIME and not TZ.is_utc(tz):
        ms_build = lambda ctx: dt_build(ctx)[1]
    elif kind == ColumnKind.TIME:
        ms_build = lambda ctx: ctx.time_ms()
    else:
        ms_build = lambda ctx: None

    field = ex.field
    reads_days = kind == ColumnKind.TIME
    if field.startswith("trunc_"):
        grain = field[len("trunc_"):]
        def build(ctx, grain=grain):
            days = day_build(ctx)
            b, _, _ = T.bucket_and_cardinality(grain, days, ms_build(ctx),
                                               lo_day, hi_day)
            return b
        _, card, decode1 = T.bucket_and_cardinality(
            grain, np.zeros(1, np.int32), np.zeros(1, np.int32),
            lo_day, hi_day)
        decode = lambda idx: np.array([decode1(i) for i in np.asarray(idx)],
                                      dtype="datetime64[ms]")
        return DimPlan(dspec.output_name, card, build, decode, (name,),
                       reads_days)
    if field == "year":
        y_lo = host_eval._civil(np.array([lo_day]))[0][0]
        y_hi = host_eval._civil(np.array([hi_day]))[0][0]
        card = int(y_hi - y_lo + 1)
        def build(ctx):
            days = day_build(ctx)
            return T.extract_field("year", days) - int(y_lo)
        return DimPlan(dspec.output_name, card, build,
                       lambda idx: np.asarray(idx, np.int64) + int(y_lo),
                       (name,), reads_days)
    if field == "week":
        lo = (lo_day + 3) // 7
        hi = (hi_day + 3) // 7
        def build(ctx):
            return T.extract_field("week", day_build(ctx)) - lo
        return DimPlan(dspec.output_name, hi - lo + 1, build,
                       lambda idx: ((np.asarray(idx, np.int64) + lo) * 7 - 3)
                       .astype("datetime64[D]"), (name,), reads_days)
    if field in _FIELD_CARDS:
        f_lo, f_hi = _FIELD_CARDS[field]
        needs_ms = field in ("hour", "minute", "second")
        if needs_ms and kind != ColumnKind.TIME:
            raise EngineFallback(f"{field} of a date column")
        def build(ctx, field=field, f_lo=f_lo):
            return T.extract_field(field, day_build(ctx),
                                   ms_build(ctx)) - f_lo
        return DimPlan(dspec.output_name, f_hi - f_lo + 1, build,
                       lambda idx: np.asarray(idx, np.int64) + f_lo, (name,),
                       reads_days)
    raise EngineFallback(f"time extraction field {field}")


def plan_granularity_dim(gran: S.Granularity, ds: Datasource, min_day: int,
                         max_day: int, tz: str = "UTC") -> DimPlan:
    """Granularity bucketing as a leading group dimension named 'timestamp'
    (Druid result rows' timestamp field). Uses absolute time buckets for
    every grain incl. hour/minute/duration. Non-UTC sessions bucket in
    LOCAL wall-clock time and label buckets with their local start."""
    if ds.time is None:
        raise EngineFallback("granularity on time-less datasource")
    tname = ds.time.name
    kind = gran.kind
    if kind == "none":
        raise EngineFallback("'none' granularity (row-grain) on agg path")
    shift = not TZ.is_utc(tz)
    lo_day, hi_day = (min_day - 1, max_day + 1) if shift \
        else (min_day, max_day)
    tzlut = TZ.day_offset_lut(tz, lo_day, hi_day) if shift else None
    try:
        _, card, decode1 = T.bucket_and_cardinality(
            kind, np.zeros(1, np.int32), np.zeros(1, np.int32),
            lo_day, hi_day, gran.duration_millis)
    except ValueError as e:
        raise EngineFallback(str(e))

    def build(ctx):
        days, ms = ctx.col(tname), ctx.time_ms()
        if shift:
            days, ms = TZ.shift_days_ms(days, ms, tzlut, lo_day)
        b, _, _ = T.bucket_and_cardinality(
            kind, days, ms, lo_day, hi_day, gran.duration_millis)
        return b

    decode = lambda idx: np.array([decode1(i) for i in np.asarray(idx)],
                                  dtype="datetime64[ms]")
    return DimPlan("timestamp", card, build, decode, (tname,),
                   reads_days=True)


def _plan_expr_extraction(dspec: S.DimensionSpec, ds: Datasource,
                          min_day: int, max_day: int) -> DimPlan:
    ex = dspec.extraction
    assert isinstance(ex, S.ExprExtraction)
    cols = sorted(E.columns_in(ex.expr))
    # single string-dim expression: evaluate over the dictionary domain on
    # host, factorize, remap codes through a LUT (dictionary-functional path)
    if len(cols) == 1 and cols[0] in ds.dims:
        dim = ds.dims[cols[0]]
        try:
            vals = host_eval.eval_expr(ex.expr, {cols[0]: dim.dictionary})
        except host_eval.HostEvalError as e:
            raise EngineFallback(str(e))
        vals = np.asarray(vals)
        if vals.shape != dim.dictionary.shape:
            raise EngineFallback("non-elementwise dim expression")
        uniq, remap = np.unique(vals.astype(object) if vals.dtype == object
                                else vals, return_inverse=True)
        lut = remap.astype(np.int32)
        name = cols[0]
        return DimPlan(dspec.output_name, len(uniq),
                       lambda ctx: EC._take_lut(lut, ctx.col(name)),
                       lambda idx: uniq[np.asarray(idx, np.int64)],
                       (name,))
    # general expression: compile to device; needs a declared or derivable
    # small integer range
    card = ex.cardinality
    if card is None:
        raise EngineFallback(
            "expression dimension without cardinality bound "
            f"({E.to_sql(ex.expr)})")

    def build(ctx):
        v = EC.compile_expr(ex.expr, ctx)
        if isinstance(v, EC.BoolValue):
            return v.arr.astype(jnp.int32)
        if isinstance(v, EC.NumValue) and not v.is_float:
            return jnp.clip(v.arr, 0, card - 1)
        raise EC.Unsupported("expression dimension must be int/bool")

    return DimPlan(dspec.output_name, card, build,
                   lambda idx: np.asarray(idx, np.int64), tuple(cols))


def _plan_dict_transform(dspec: S.DimensionSpec, ds: Datasource,
                         vals_fn) -> DimPlan:
    """Dictionary-functional extraction: apply ``vals_fn`` to the dim's
    dictionary on host (may yield None entries = null), factorize, and remap
    codes through a constant LUT on device. Null output (and null input
    rows) land in slot 0."""
    name = dspec.dimension
    if ds.column_kind(name) != ColumnKind.DIM:
        raise EngineFallback("lookup/regex extraction over non-string column")
    dim = ds.dims[name]
    vals = vals_fn(dim.dictionary)
    null_mask = np.array([v is None for v in vals], dtype=bool)
    uniq = np.unique(np.asarray(
        [str(v) for v, nm in zip(vals, null_mask) if not nm], dtype=object)) \
        if (~null_mask).any() else np.empty(0, dtype=object)
    pos = {v: j for j, v in enumerate(uniq)}
    lut = np.array([0 if nm else 1 + pos[str(v)]
                    for v, nm in zip(vals, null_mask)], dtype=np.int32)
    has_nulls = dim.validity is not None

    def build(ctx):
        mapped = EC._take_lut(lut, ctx.col(name))
        if has_nulls:
            nv = ctx.null_valid(name)
            mapped = jnp.where(nv, mapped, 0)
        return mapped

    def decode(idx):
        idx = np.asarray(idx, np.int64)
        out = np.empty(len(idx), dtype=object)
        out[:] = [None if i == 0 else uniq[i - 1] for i in idx]
        return out

    return DimPlan(dspec.output_name, len(uniq) + 1, build, decode, (name,))


def _lookup_vals_fn(ex: S.LookupExtraction):
    table = dict(ex.lookup)

    def vals_fn(dictionary):
        out = []
        for s in dictionary:
            if s in table:
                out.append(table[s])
            elif ex.retain_missing:
                out.append(s)
            else:
                out.append(ex.replace_missing_with)
        return out
    return vals_fn


def _regex_vals_fn(ex: S.RegexExtraction):
    import re as _re
    rx = _re.compile(ex.pattern)

    def vals_fn(dictionary):
        out = []
        for s in dictionary:
            m = rx.search(s) if s is not None else None
            if m is not None:
                out.append(m.group(ex.index))
            elif ex.replace_missing:
                out.append(ex.replace_missing_with)
            else:
                out.append(s)
        return out
    return vals_fn


def plan_dimension(dspec: S.DimensionSpec, ds: Datasource, min_day: int,
                   max_day: int, tz: str = "UTC") -> DimPlan:
    try:
        if dspec.extraction is None:
            return _plan_plain(dspec.dimension, ds, dspec.output_name,
                               min_day, max_day)
        if isinstance(dspec.extraction, S.TimeExtraction):
            return _plan_time_extraction(dspec, ds, min_day, max_day, tz)
        if isinstance(dspec.extraction, S.LookupExtraction):
            return _plan_dict_transform(dspec, ds,
                                        _lookup_vals_fn(dspec.extraction))
        if isinstance(dspec.extraction, S.RegexExtraction):
            return _plan_dict_transform(dspec, ds,
                                        _regex_vals_fn(dspec.extraction))
        if isinstance(dspec.extraction, S.ExprExtraction):
            return _plan_expr_extraction(dspec, ds, min_day, max_day)
    except EC.Unsupported as e:
        raise EngineFallback(str(e))
    raise EngineFallback(f"extraction {type(dspec.extraction).__name__}")


# =============================================================================
# aggregation planning
# =============================================================================

@dataclasses.dataclass
class AggPlan:
    spec: S.AggregationSpec
    kind: str                    # 'count'|'sum'|'min'|'max'|'hll'
    out_dtype: object
    source_cols: tuple
    is_int: bool = False         # integer-exact device lanes (i32 storage)
    maxabs: Optional[float] = None   # static |value| bound (col metadata)
    dim_codes: bool = False      # min/max over a NON-numeric string dim:
    #   aggregate the dictionary CODES (the global dictionary is sorted
    #   ascending, segment/column.py:46, so code order IS lexicographic
    #   order) and decode the extremum code to its string at output

    def build_values(self, ctx: ScanContext):
        a = self.spec
        if a.kind == "anyvalue":
            # FD-demoted grouping column: any row's value works (max); dims
            # contribute their dictionary code, decoded at output
            return ctx.col(a.field)
        if a.field is not None:
            k = ctx.kind(a.field)
            if self.kind in ("hll", "theta"):
                if k == ColumnKind.DIM:
                    return ctx.col(a.field)
                if k in (ColumnKind.LONG, ColumnKind.DATE):
                    return ctx.col(a.field)
                if k == ColumnKind.DOUBLE:
                    return ctx.col(a.field).view(jnp.int32) \
                        if hasattr(ctx.col(a.field), "view") else \
                        jax.lax.bitcast_convert_type(ctx.col(a.field),
                                                     jnp.int32)
                raise EngineFallback(f"cardinality over {k}")
            if self.kind == "kll":
                # quantile domain: the actual numeric values (canonical
                # f32 inside kll_registers so every tier sees one bit
                # pattern per value)
                if k in (ColumnKind.LONG, ColumnKind.DOUBLE):
                    return ctx.col(a.field)
                raise EngineFallback(f"quantile over {k}")
            if k in (ColumnKind.LONG, ColumnKind.DOUBLE, ColumnKind.DATE):
                return ctx.col(a.field)
            if k == ColumnKind.DIM and self.dim_codes:
                return ctx.col(a.field)          # sorted-dict codes
            if k == ColumnKind.DIM and self.kind in ("min", "max", "sum"):
                # numeric-parsed dim (Druid coerces); host LUT
                lut = np.array([host_eval_try_float(s)
                                for s in ctx.dictionary(a.field)],
                               dtype=np.float32)
                return EC._take_lut(lut, ctx.col(a.field))
            raise EngineFallback(f"aggregate {a.kind} over {k}")
        if a.expr is not None:
            v = EC.compile_expr(a.expr, ctx)
            n = EC._as_num(v, ctx)
            return n.arr
        return None

    def build_mask(self, ctx: ScanContext, cse=None):
        """``cse`` (planner.fusion.CSECache, bound to ``ctx``) memoizes
        the filter lowering so aggregation filters repeated within a
        query — or across fused shared-scan lanes — lower once."""
        a = self.spec
        masks = []
        if a.filter is not None:
            m = cse.lower(a.filter) if cse is not None \
                else F.lower_filter(a.filter, ctx)
            if m is not None:
                masks.append(m)
        if a.field is not None:
            nv = ctx.null_valid(a.field)
            if nv is not None:
                masks.append(nv)
        if a.expr is not None:
            for c in E.columns_in(a.expr):
                nv = ctx.null_valid(c)
                if nv is not None:
                    masks.append(nv)
        if not masks:
            return None
        out = masks[0]
        for m in masks[1:]:
            out = out & m
        return out


def host_eval_try_float(s):
    try:
        return float(s)
    except (TypeError, ValueError):
        return np.nan


_AGG_KIND = {"count": ("count", np.int64), "longsum": ("sum", np.int64),
             "doublesum": ("sum", np.float64), "longmin": ("min", np.int64),
             "longmax": ("max", np.int64), "doublemin": ("min", np.float64),
             "doublemax": ("max", np.float64),
             "cardinality": ("hll", np.int64),
             "thetasketch": ("theta", np.int64),
             "quantile": ("kll", np.float64),
             "anyvalue": ("max", np.float64)}


def _identity_row(kinds_by_name) -> Dict[str, np.ndarray]:
    """The one identity row of a GLOBAL aggregate over zero rows — SQL
    semantics (and Druid's default timeseries behavior, minus its sum-is-0
    quirk): count/hll -> 0, sum/min/max -> NULL."""
    return {name: (np.array([0], dtype=np.int64)
                   if kind in ("count", "hll", "theta")
                   else np.array([np.nan]))
            for name, kind in kinds_by_name.items()}


def _col_bounds(ds: Datasource, name: str):
    """(is_int, maxabs) of a column's device representation (i32 codes/days/
    longs are integer-exact; DOUBLE is f32)."""
    kind = ds.column_kind(name)
    if kind == ColumnKind.DIM:
        return True, float(max(ds.dims[name].cardinality, 1))
    m = ds.metrics.get(name)
    if m is None:
        if ds.time is not None and name == ds.time.name:
            return True, float(2**31)
        return False, None
    lo = float(m.min) if m.min is not None else None
    hi = float(m.max) if m.max is not None else None
    maxabs = max(abs(lo), abs(hi)) if lo is not None and hi is not None \
        else None
    return kind in (ColumnKind.LONG, ColumnKind.DATE), maxabs


def _expr_bounds(e: E.Expr, ds: Datasource):
    """Conservative static (is_int, maxabs) of an expression's compiled
    device value — drives the exact-integer route for pushed-down
    ``sum(case when ...)``-style aggregates. Returns (False, None) when it
    can't tell."""
    if isinstance(e, E.Literal):
        v = e.value
        if isinstance(v, bool):
            return True, 1.0
        if isinstance(v, int):
            return True, float(abs(v))
        if isinstance(v, float):
            return False, float(abs(v))
        return False, None
    if isinstance(e, E.Column):
        # DIM columns lower to f32 parsed-LUT values in expressions (codes
        # are only integer-exact on the direct anyvalue/field path)
        if ds.column_kind(e.name) == ColumnKind.DIM:
            return False, None
        return _col_bounds(ds, e.name)
    if isinstance(e, E.Cast):
        i, m = _expr_bounds(e.child, ds)
        if e.to in ("int", "long", "integer", "bigint"):
            return True, m
        return i, m
    if isinstance(e, E.BinaryOp):
        li, lm = _expr_bounds(e.left, ds)
        ri, rm = _expr_bounds(e.right, ds)
        both = lm is not None and rm is not None
        if e.op in ("+", "-"):
            return li and ri, (lm + rm) if both else None
        if e.op == "*":
            return li and ri, (lm * rm) if both else None
        return False, None
    if isinstance(e, E.Case):
        is_int, maxabs = True, 0.0
        branches = [v for _, v in e.branches] + \
            ([e.otherwise] if e.otherwise is not None else [])
        for b in branches:
            bi, bm = _expr_bounds(b, ds)
            is_int &= bi
            if bm is None or maxabs is None:
                maxabs = None
            else:
                maxabs = max(maxabs, bm)
        return is_int, maxabs
    if isinstance(e, (E.Comparison, E.And, E.Or, E.Not, E.IsNull, E.InList,
                      E.Between, E.Like)):
        return True, 1.0
    return False, None


def plan_aggregation(a: S.AggregationSpec, ds: Datasource) -> AggPlan:
    if a.kind not in _AGG_KIND:
        raise EngineFallback(f"aggregation kind {a.kind}")
    kind, dtype = _AGG_KIND[a.kind]
    cols = set()
    is_int, maxabs = False, None
    if a.kind == "count":
        is_int, maxabs = True, 1.0
    elif a.field is not None:
        cols.add(a.field)
        ck = ds.column_kind(a.field)
        if kind == "kll" and ds.time is not None:
            cols.add(ds.time.name)   # content salt for the sampled set
        if a.kind == "anyvalue" or kind in ("hll", "theta", "kll"):
            is_int, maxabs = _col_bounds(ds, a.field)
            if ck == ColumnKind.DOUBLE:
                is_int = False
        elif ck == ColumnKind.DIM:
            if kind in ("min", "max") and not _dim_parses_numeric(
                    ds, a.field):
                # lexicographic min/max of a string dim = min/max of its
                # sorted-dictionary codes, decoded at output
                is_int, maxabs = _col_bounds(ds, a.field)
                cols |= F.columns_of_filter(a.filter)
                return AggPlan(a, kind, dtype, tuple(sorted(cols)),
                               is_int, maxabs, dim_codes=True)
            # numeric-parsed dim rides an f32 LUT
            is_int, maxabs = False, None
        else:
            is_int, maxabs = _col_bounds(ds, a.field)
    if a.expr is not None:
        cols |= E.columns_in(a.expr)
        is_int, maxabs = _expr_bounds(a.expr, ds)
    cols |= F.columns_of_filter(a.filter)
    return AggPlan(a, kind, dtype, tuple(sorted(cols)), is_int, maxabs)


def _dim_parses_numeric(ds: Datasource, field: str) -> bool:
    """Whether EVERY dictionary entry of a string dim parses as a number
    (then Druid's numeric-coercion semantics apply to min/max/sum over
    it); cached per datasource column — dictionaries can be large."""
    cache = getattr(ds, "_dim_numeric_cache", None)
    if cache is None:
        try:
            cache = ds._dim_numeric_cache = {}
        except AttributeError:           # frozen datasource: no cache
            cache = {}
    r = cache.get(field)
    if r is None:
        d = ds.dims[field].dictionary
        r = bool(len(d)) and not np.isnan(np.array(
            [host_eval_try_float(s) for s in d], dtype=np.float64)).any()
        cache[field] = r
    return r


# =============================================================================
# the engine
# =============================================================================

class HashLayout(NamedTuple):
    """``QueryEngine._hash_layout``: a statement's layout in the hashed
    tier — the key's parts, the table's slots and their ceiling, whether
    and over how many chips the scan is sharded, a segment's bytes,
    segments a wave and waves."""

    parts: List[List[int]]
    T: int
    max_slots: int
    sharded: bool
    n_dev: int
    seg_bytes: int
    spw: int
    n_waves: int

    @property
    def one_table(self) -> bool:
        """Every row of a group reaches ONE table: one wave on one chip."""
        return self.n_dev == 1 and self.n_waves == 1


class QueryEngine:
    def __init__(self, store: SegmentStore, config: Optional[Config] = None,
                 mesh: Optional[Mesh] = None):
        self.store = store
        self.config = config or Config()
        self.mesh = mesh
        self._programs: Dict[tuple, object] = {}   # compile cache
        self._compiling: Dict[tuple, object] = {}  # sig -> in-flight Event
        # shape -> (most survivors a compacting program of it reported,
        # the budget that count holds the shape to): _note_survivors
        self._compact_seen: Dict[tuple, tuple] = {}
        self._literal_plans: Dict[tuple, tuple] = {}  # spec -> its literals
        self._device_arrays: Dict[tuple, object] = {}
        self._device_bytes = 0
        self._cancel_flags: Dict[str, object] = {}
        self._cancel_refs: Dict[str, int] = {}
        self._cancel_lock = __import__("threading").Lock()
        # concurrency: queries execute in parallel (threading server); only
        # compile-cache population is serialized, and per-query stats are
        # thread-local so concurrent sessions don't trample each other
        self._compile_lock = __import__("threading").RLock()
        self._tls = __import__("threading").local()
        # device-loss state (≈ the reference's ZK-watch topology
        # invalidation, CuratorConnection.scala:77-136): when the backend
        # dies mid-session, statements demote to the host tier and a
        # bounded re-attach probe runs at most once per cooldown window
        self._backend_lost_at: Optional[float] = None
        self._backend_retry_at: float = 0.0
        # semantic result cache (cache/): exact + subsumption reuse of
        # materialized aggregate results, keyed on the per-datasource
        # ingest version (structural invalidation, no TTL)
        from spark_druid_olap_tpu.cache.result_cache import SemanticResultCache
        self.result_cache = SemanticResultCache(self.config)
        # workload management (wlm/): lane admission + tenant quotas in
        # front of every spec this engine executes; shed queries raise
        # AdmissionRejected here and never reach planning/dispatch
        from spark_druid_olap_tpu.metadata.history import InflightRegistry
        from spark_druid_olap_tpu.wlm.admit import WorkloadManager
        self.wlm = WorkloadManager(self.config)
        self.inflight = InflightRegistry()
        # shared-scan tier (parallel/sharedscan.py): concurrent eligible
        # queries on one datasource coalesce into a single fused program
        # with a shared column-union bind; gated by
        # sdot.sharedscan.enabled (off by default)
        from spark_druid_olap_tpu.parallel.sharedscan import (
            SharedScanCoalescer)
        self.sharedscan = SharedScanCoalescer(self)
        self.wlm.sharedscan = self.sharedscan
        # deterministic fault injection (fault/, docs/CHAOS.md): None
        # unless sdot.fault.plan is set, and every site guards on None
        # so the un-injected hot path pays nothing. The WLM site is
        # wired here; broker / persist / tier pick the injector up from
        # this attribute in their own constructors.
        from spark_druid_olap_tpu.fault import FaultInjector
        self.fault = FaultInjector.from_config(self.config)
        self.wlm.fault = self.fault
        # distributed serving tier (cluster/): on a broker this is the
        # scatter/merge client (cluster/broker.py:ClusterClient) wired
        # in by Context; None on single-process engines and historicals
        self.cluster = None
        # historical-node mode (cluster/historical.py): sketch
        # aggregates emit RAW register blocks instead of finalized
        # estimates, so the broker can merge registers across shards
        # and finalize the estimate exactly once
        self.partial_sketches = False

    @property
    def last_stats(self) -> Dict[str, object]:
        d = getattr(self._tls, "stats", None)
        if d is None:
            d = self._tls.stats = {}
        return d

    @property
    def dispatch_counts(self):
        """Thread-local MONOTONE [program_dispatches, host_transfers,
        wave_kernel_launches, fetched_bytes] counters (never reset by
        execute);
        statement layers diff them around a statement to report device
        round trips — each costs a launch plus a host sync, so this is
        the per-query round-trip budget made visible. Slot 2 counts
        hand-scheduled Pallas wave mega-kernel launches
        (parallel/sharedscan.py wave path) — a
        subset-annotation of slot 0, surfaced as ``kernel_launches`` in
        statement stats. Slot 3 sums the bytes the ``dispatch.fetch``
        spans copied back (``fetch_bytes``)."""
        c = getattr(self._tls, "dcount", None)
        if c is None:
            c = self._tls.dcount = [0, 0, 0, 0]
        return c

    def _tick(self, kind: int = 0, n: int = 1):
        self.dispatch_counts[kind] += n

    def _launch(self, prog, args, fetch=True):
        """``dispatch.launch``: the call of the compiled program, which
        enqueues it and returns device buffers that are not ready yet.
        With ``fetch`` the device->host copies of everything it returns
        are enqueued behind it, so they overlap the compute and each
        other and ``dispatch.fetch`` finds them under way; without, the
        outputs stay on the device (a table a later program reads)."""
        with PH.phase("dispatch.launch"):
            out = prog(args)
            if fetch:
                for buf in jax.tree_util.tree_leaves(out):
                    buf.copy_to_host_async()
            return out

    @staticmethod
    def _wait(bufs):
        """``dispatch.wait``: until the device has written ``bufs`` — the
        host's view of device time."""
        with PH.phase("dispatch.wait"):
            return jax.block_until_ready(bufs)

    def _fetch(self, unpack, bufs):
        """``dispatch.fetch``: device->host copy and host reshaping of
        ready buffers; their bytes count as ``fetch_bytes``."""
        with PH.phase("dispatch.fetch"):
            self._tick(3, sum(int(getattr(b, "nbytes", 0))
                              for b in jax.tree_util.tree_leaves(bufs)))
            return unpack(bufs)

    def _run_program(self, prog, args, unpack):
        """One dispatch of a program whose operands are on the device
        already (a table's second program, a row mask)."""
        self._tick()
        with PH.phase("dispatch"):
            return self._fetch(unpack, self._wait(self._launch(prog, args)))

    def _waves(self, q, t0, ds, names, wave_segs, s_pad, sharded, lits,
               prog, unpack=None):
        """The wave pipeline every tier runs, double-buffered; yields
        each wave's fetched result after its ``dispatch`` span closed, so
        what the consumer does with it — merge, a second program, stop —
        is no part of that span. In the span, in this order: the launch;
        behind it wave i+2's cold chunks start loading and wave i+1
        binds (a ``bind`` span inside wave i's ``dispatch``: the transfer
        overlaps the compute); the wait; the fetch through ``unpack``.
        Without ``unpack`` the program returns a table that stays on the
        device for a second program: only its ``__stats__`` travel, and
        the wave yields (table, stats). One wave binds through the
        device cache (``_bind_arrays``: the arrays stay resident between
        statements); several bind uncached, wave mode existing because
        the scan exceeds the device budget."""
        sharding = NamedSharding(self.mesh, P(SEGMENT_AXIS, None)) \
            if sharded else None
        multihost = sharded and MH.is_multihost()

        def bind(i):
            return self._bind_wave(ds, names, wave_segs[i], s_pad, sharding,
                                   multihost, lits)

        # cold tier: wave 1's chunks load while wave 0 binds + computes
        self._tier_prefetch(ds, names, wave_segs, 1)
        cur = self._bind_arrays(ds, names, wave_segs[0], s_pad, sharded,
                                lits) if len(wave_segs) == 1 else bind(0)
        for i in range(len(wave_segs)):
            if t0 is not None:
                self._stage_check(q, t0)     # per-wave boundary
            self._tick()
            with PH.phase("dispatch"):
                bufs = self._launch(prog, cur, fetch=unpack is not None)
                self._tier_prefetch(ds, names, wave_segs, i + 2)
                cur = bind(i + 1) if i + 1 < len(wave_segs) else None
                if unpack is not None:
                    out = self._fetch(unpack, self._wait(bufs))
                else:
                    table = self._wait(dict(bufs))
                    out = table, self._fetch(np.asarray,
                                             table.pop("__stats__"))
            yield out

    # -- cancellation / timeout ----------------------------------------------
    def register_query(self, query_id: str) -> None:
        """Register a cancellable id BEFORE planning starts, so a cancel
        arriving at any point in the query's life is honored (≈ the
        reference registering the Druid query id with TaskCancelHandler
        before the HTTP call, DruidRDD.scala:175). Registrations are
        refcounted: statements sharing an id (one cancel scope, like
        Druid's queryId) stay cancellable until the LAST one releases."""
        import threading
        with self._cancel_lock:
            self._cancel_flags.setdefault(query_id, threading.Event())
            self._cancel_refs[query_id] = \
                self._cancel_refs.get(query_id, 0) + 1

    def release_query(self, query_id: str) -> None:
        with self._cancel_lock:
            n = self._cancel_refs.get(query_id, 1) - 1
            if n <= 0:
                self._cancel_refs.pop(query_id, None)
                self._cancel_flags.pop(query_id, None)
            else:
                self._cancel_refs[query_id] = n

    def cancel(self, query_id: str) -> bool:
        """Mark a registered query id cancelled (cooperative; takes effect at
        the next stage boundary)."""
        ev = self._cancel_flags.get(query_id)
        if ev is None:
            return False
        ev.set()
        return True

    def _stage_check(self, q, t0: float):
        ctxq = getattr(q, "context", None)
        if ctxq is None:
            return
        if ctxq.query_id is not None:
            ev = self._cancel_flags.get(ctxq.query_id)
            if ev is not None and ev.is_set():
                raise QueryCancelled(f"query {ctxq.query_id} cancelled")
        if ctxq.timeout_millis is not None:
            if (_time.perf_counter() - t0) * 1000 > ctxq.timeout_millis:
                raise QueryTimeout(
                    f"query exceeded {ctxq.timeout_millis}ms")

    # -- public ---------------------------------------------------------------
    def execute(self, q: S.QuerySpec) -> QueryResult:
        t0 = _time.perf_counter()
        self.last_stats.clear()   # per-thread; no cross-query leakage
        qid = getattr(getattr(q, "context", None), "query_id", None)
        if qid is not None:
            # refcounted: session-registered ids (and ids shared by
            # concurrent statements) stay cancellable until the LAST
            # holder releases
            self.register_query(qid)
        tier = pin_tok = None
        try:
            # tiered cold storage: pin every hot chunk this query faults
            # for its whole lifetime — eviction under budget pressure
            # must never pull a column out from under an in-flight scan.
            # acquire/release is a checked pair (sdlint leaks registry,
            # "tier-pin").
            tier_ds = self.store._datasources.get(
                getattr(q, "datasource", None))
            tier = getattr(tier_ds, "tier", None)
            pin_tok = tier.acquire_pins() if tier is not None else None
            tok = self.inflight.begin(qid, getattr(q, "datasource", None),
                                      type(q).__name__)
            try:
                # visible to the shared-scan coalescer (joined on this
                # thread): the group leader annotates every constituent's
                # sys_queries row with the coalesced-group id
                self._tls.inflight_tok = tok
                ticket = None
                try:
                    if self.wlm.enabled:
                        # admission BEFORE any planning/cache/dispatch
                        # work: a shed query must cost nothing, and queue
                        # wait counts against the deadline (t0 is already
                        # ticking). Specs of one statement admit
                        # sequentially (never hold-and-wait), so nested
                        # plans cannot deadlock on lane slots.
                        cancel_ev = self._cancel_flags.get(qid) \
                            if qid is not None else None
                        ticket = self.wlm.admit(self, q, t0, cancel_ev)
                        if ticket.timeout_millis is not None \
                                and getattr(q.context, "timeout_millis",
                                            None) is None:
                            # lane default timeout rides the spec so every
                            # downstream _stage_check honors it (context
                            # is stripped from cache keys and compile
                            # signatures, so the replace is cache-neutral)
                            import dataclasses as _dc
                            q = _dc.replace(q, context=_dc.replace(
                                q.context or S.QueryContext(),
                                timeout_millis=ticket.timeout_millis))
                        self.last_stats["wlm"] = ticket.stats()
                        self.inflight.running(tok, lane=ticket.lane,
                                              tenant=ticket.tenant,
                                              queued_ms=ticket.queued_ms)
                    else:
                        self.inflight.running(tok)
                    return self._execute_admitted(q, t0)
                finally:
                    self._tls.inflight_tok = None
                    if ticket is not None:
                        self.wlm.release(ticket)
            finally:
                self.inflight.done(tok)
        finally:
            try:
                if pin_tok is not None:
                    tier.release_pins(pin_tok)
                    self.last_stats["tier"] = tier.stats_snapshot()
                    enc_info = getattr(tier_ds, "encoding_info", None)
                    if enc_info is not None:
                        self.last_stats["encoding"] = enc_info()
            finally:
                if qid is not None:
                    self.release_query(qid)
            # after the releases: a failing stats snapshot must not be
            # able to strand the pin or the cancel flag
            if self.fault is not None:
                self.last_stats["fault"] = self.fault.stats()

    def _execute_admitted(self, q: S.QuerySpec, t0: float) -> QueryResult:
        try:
            pinfo = self.store.recovery_info.get(
                getattr(q, "datasource", None))
            if pinfo is not None:
                # the datasource was rebuilt from deep storage this
                # session — surface where it came from (snapshot / wal /
                # both) and what checksum verification cost
                self.last_stats["persist"] = dict(pinfo)
            cache = self.result_cache
            use_cache = cache.enabled and cache.cacheable(q)
            if use_cache:
                # lookup precedes the backend-loss gate on purpose: a
                # cached answer needs no device, so hits keep serving at
                # full speed while the host tier covers the misses
                ds_version = self.store.datasource_version(q.datasource)
                served, status = cache.lookup(q, ds_version)
                if served is not None:
                    self.last_stats["cache"] = status
                    self.last_stats["datasource"] = q.datasource
                    self.last_stats["total_ms"] = \
                        (_time.perf_counter() - t0) * 1000
                    return served
            if self.cluster is not None and self.cluster.should_distribute(q):
                # broker path: scatter per-shard subqueries to the
                # historicals and merge partials. Sits UNDER the cache
                # (hits never leave this process) and ABOVE the
                # backend-loss gate (the scatter needs no local device).
                # None = the client declined mid-flight (serde gap, node
                # EngineFallback, replicas exhausted with local fallback
                # enabled) — fall through to ordinary local execution.
                r = self.cluster.execute(q, t0)
                if r is not None:
                    # degraded (partial-results) answers must NEVER enter
                    # the result cache: a later healthy run would serve
                    # the hole forever
                    if use_cache and r.degraded is None:
                        cache.put(q, ds_version, r)
                        self.last_stats["cache"] = "miss"
                    return r
            if self._backend_lost_at is not None \
                    and not self._try_reattach():
                self.last_stats["backend_lost"] = True
                raise EngineFallback(
                    "backend_lost (device unreachable; host tier serving)")
            if self.sharedscan.should_try(q):
                # coalesce with concurrent eligible queries on the same
                # datasource; sits UNDER the cache layer so each
                # constituent still populates its own canonical key
                r = self.sharedscan.run(q, t0)
            else:
                r = self._execute_inner(q, t0)
            if use_cache:
                cache.put(q, ds_version, r)
                self.last_stats["cache"] = "miss"
            return r
        except EC.Unsupported as e:
            # expression/filter compilation is lazy (trace time), so an
            # unsupported node can surface only here — demote it to the
            # fallback signal the session layer handles
            raise EngineFallback(str(e)) from e
        except Exception as e:  # noqa: BLE001 — classify device loss
            if _is_backend_loss(e):
                self._mark_backend_lost()
                raise EngineFallback(
                    f"backend_lost ({type(e).__name__}: "
                    f"{str(e)[:120]})") from e
            raise

    def _mark_backend_lost(self):
        """Invalidate everything referencing dead device buffers; the
        host tier serves until a re-attach probe succeeds."""
        with self._compile_lock:
            self._backend_lost_at = _time.time()
            self._backend_retry_at = self._backend_lost_at \
                + float(self.config.get(BACKEND_RETRY_SECONDS))
            self._programs.clear()
            self._device_arrays.clear()
            self._device_bytes = 0
        self.last_stats["backend_lost"] = True

    def _try_reattach(self) -> bool:
        """At most one bounded device probe per cooldown window. The probe
        runs in a daemon thread with a hard deadline — a dispatch to a
        dead device can hang, and an in-process hang would otherwise take
        the session down with it.

        A successful re-attach RESHARDS onto the now-live device set when
        its size changed (chips lost or restored) — the analog of the
        reference re-planning against ZooKeeper's changed server list
        (``CuratorConnection.scala:77-136``) instead of requiring the
        original topology back."""
        now = _time.time()
        with self._compile_lock:
            if now < self._backend_retry_at:
                return False
            # claim this window under the lock so concurrent statements
            # don't pile probes onto a dead backend
            self._backend_retry_at = now \
                + float(self.config.get(BACKEND_RETRY_SECONDS))
        if _probe_device_alive():
            with self._compile_lock:
                self._backend_lost_at = None
            if self.mesh is not None:
                try:
                    live = len(jax.devices())
                except Exception:   # noqa: BLE001 — treat as still down
                    return True
                if live != mesh_size(self.mesh):
                    self.reshard()
            return True
        return False

    def reshard(self, devices=None) -> None:
        """Rebuild the segment mesh over the CURRENTLY live devices (or an
        explicit subset) and drop every mesh-shaped artifact: compiled
        programs (their s_pad/shard split encodes the old device count)
        and device-resident arrays (their sharding references old
        devices). The store itself is host-resident, so the next
        statement re-binds onto the new mesh — segments re-spread the way
        Druid re-balances onto the surviving historicals."""
        from spark_druid_olap_tpu.parallel.mesh import make_mesh
        devs = list(devices) if devices is not None else jax.devices()
        with self._compile_lock:
            self.mesh = make_mesh(devices=devs) if len(devs) > 1 else None
            self._programs.clear()
            self._compact_seen.clear()
            self._device_arrays.clear()
            self._device_bytes = 0
        self.last_stats["resharded_to"] = len(devs)

    def _execute_inner(self, q: S.QuerySpec, t0: float) -> QueryResult:
        self._stage_check(q, t0)
        shape = _agg_shape(q)
        if shape is not None:
            dims, having, limit = shape
            r = self._run_agg(q, dims, q.aggregations, q.post_aggregations,
                              having, limit, q.granularity, q.filter,
                              q.intervals, t0)
        elif isinstance(q, S.SelectQuerySpec):
            r = self._run_select(q)
        elif isinstance(q, S.SearchQuerySpec):
            r = self._run_search(q)
        else:
            raise EngineFallback(f"query type {type(q).__name__}")
        self.last_stats["total_ms"] = (_time.perf_counter() - t0) * 1000
        return r

    # -- aggregation path -----------------------------------------------------
    def _run_agg(self, q, dimensions: List[S.DimensionSpec], aggregations,
                 post_aggregations, having, limit, granularity, filter_spec,
                 intervals, t0: Optional[float] = None,
                 no_topk: bool = False) -> QueryResult:
        ds = self.store.get(q.datasource)
        seg_idx = ds.prune_segments(intervals, filter_spec)
        gran_kind = granularity.kind if granularity else "all"

        if ds.num_rows == 0 or len(seg_idx) == 0:
            names = (["timestamp"] if gran_kind != "all" else [])
            names += [d.output_name for d in dimensions]
            names += [a.name for a in aggregations]
            names += [p.name for p in post_aggregations]
            if not dimensions and gran_kind == "all":
                # global aggregate over an empty/pruned scan still yields the
                # one identity row (same semantics as the global_empty path
                # below)
                data = _identity_row(
                    {a.name: _AGG_KIND.get(a.kind, ("sum", None))[0]
                     for a in aggregations})
                for p in post_aggregations:
                    v = np.asarray(host_eval.eval_expr(p.expr, data))
                    data[p.name] = np.broadcast_to(v, (1,)) if v.ndim == 0 \
                        else v
                if having is not None:
                    keep = host_eval.eval_pred3(having.expr, data)
                    data = {k: v[keep] for k, v in data.items()}
                self.last_stats.update({
                    "datasource": ds.name, "segments": 0, "sharded": False,
                    "groups": int(len(next(iter(data.values()))))
                    if data else 0, "rows_scanned": 0})
                return QueryResult(names, data)
            return QueryResult.empty(names)

        with PH.phase("plan.engine"):
            all_dim_plans, agg_plans, min_day, max_day, n_keys, names, \
                routes = self._plan_agg(ds, seg_idx, dimensions,
                                        aggregations, granularity,
                                        filter_spec, intervals)
        lits, days = self._plan_literals(q, ds, all_dim_plans, min_day,
                                         max_day)

        if bool(self.config.get(SHAREDSCAN_FUSION_ENABLED)):
            # solo-path CSE accounting, at PLAN time so warm program-
            # cache runs still tick the deterministic counters (the
            # trace-time cache in _make_core/_hash_core does the actual
            # sharing; this mirrors its hit count)
            try:
                tot, distinct = FU.analyze_query(
                    filter_spec, intervals,
                    [a.filter for a in aggregations])
                if tot > distinct:
                    self.sharedscan.note_solo_cse(tot - distinct, tot)
                elif tot:
                    self.sharedscan.note_solo_cse(0, tot)
            except Exception:  # noqa: BLE001 — accounting never fails a query
                pass

        route_hashed = n_keys > self.config.get(GROUPBY_DENSE_MAX_KEYS)
        if not route_hashed:
            # medium-K reroute (VERDICT r3 item 3): at K past the onehot
            # crossover, the sorted-run tier's one sort + payload scans
            # beat the dense matmul's N*K HBM onehot traffic — the SAME
            # gate as the sorted-run tier itself (its 'off' kill-switch
            # must kill the reroute too, or medium-K queries would land
            # on the hashed SCATTER tier the reroute exists to avoid)
            from spark_druid_olap_tpu.utils import config as CF
            min_k = int(self.config.get(CF.GROUPBY_SORTED_MIN_KEYS))
            if min_k > 0 and n_keys >= min_k \
                    and not any(p.kind in ("theta", "kll")
                                for p in agg_plans):
                if any(p.kind == "hll" for p in agg_plans):
                    # an HLL sketch goes where its registers' form runs:
                    # the sparse one in the hashed tier (the sorted-run
                    # core has the rows sorted by group already, the
                    # scatter core sorts them by slot), where it is the
                    # cheapest — or the block past what a send may fetch
                    # — AND that tier would scan in one wave on one chip:
                    # an estimate is final, and several waves' or chips'
                    # registers only a dense form can merge
                    route_hashed = self._hll_form(
                        agg_plans, ds, len(seg_idx), n_keys,
                        self._hash_layout(
                            q, ds, seg_idx, all_dim_plans, agg_plans,
                            names).one_table) == "sparse"
                else:
                    route_hashed = self._sorted_run_wanted()
        if route_hashed:
            return self._run_agg_hashed(
                q, ds, seg_idx, all_dim_plans, agg_plans, names, min_day,
                max_day, post_aggregations, having, limit, filter_spec,
                intervals, t0, no_topk=no_topk, lits=lits, days=days)

        sharded = self._should_shard(q, ds, seg_idx)
        n_dev = mesh_size(self.mesh) if sharded else 1
        seg_bytes = C.bytes_per_segment(ds, names)
        spw, n_waves = C.plan_waves(
            len(seg_idx), n_dev, seg_bytes,
            C.wave_budget_bytes(self.config), self.config, n_keys,
            len(agg_plans),
            io_budget=C.tier_io_budget(ds, self.config),
            io_seg_bytes=C.tier_io_seg_bytes(ds, names))
        s_pad = spw if n_waves > 1 else _pad_segments(len(seg_idx), n_dev)
        n_seg_sel = len(seg_idx)
        multihost = sharded and MH.is_multihost()
        if multihost:
            seg_idx, s_pad, spw, n_waves = self._multihost_layout(
                ds, seg_idx, n_waves, seg_bytes)
        sketch_plans = [p for p in agg_plans
                        if p.kind in ("hll", "theta", "kll")]
        topk = self._plan_device_topk(limit, having, agg_plans, n_keys) \
            if n_waves == 1 and not no_topk else None
        having_dev = self._plan_device_having(having, routes, agg_plans,
                                              n_keys, topk, n_waves) \
            if not multihost else None
        # (multi-host: the having/table-resident two-dispatch path keeps
        # finals per-chip — the host HAVING epilogue over the replicated
        # merge is correct and cheap; revisit if profiling says otherwise)
        n_out = topk[1] if topk else n_keys

        top_idx = None
        # the statement's SHAPE, not its literal values; the selected
        # segments' day range only where the program is built from it
        hll_costs = self._hll_costs(agg_plans)
        base_sig = (self._sig_base(ds), lits.shape, s_pad, days, sharded,
                    n_dev, tuple(names), hll_costs)
        if having_dev:
            # two dispatches: finals stay device-resident, only the mask
            # count then the passing groups travel
            sigA = ("aggtable", base_sig, having_dev)
            progA = self._cached_program(
                sigA, lambda: self._build_agg_table_program(
                    ds, all_dim_plans, agg_plans, filter_spec, intervals,
                    days, n_keys, sharded, routes, having_dev, lits,
                    hll_costs))
            # one wave (_plan_device_having): the table stays on the
            # device, only its count travels
            (table, stats), = self._waves(q, t0, ds, names, [seg_idx],
                                          s_pad, sharded, lits, progA)
            cnt = int(stats[0])
            n_out = min(n_keys,
                        1 << max(6, (max(cnt, 1) - 1).bit_length()))
            # most groups pass: the [n_keys] top_k sort costs more than
            # the transfer it saves — take the sort-free full gather
            full = n_out * 2 >= n_keys
            if full:
                n_out = n_keys
            gfn, unpackB = self._cached_program(
                (sigA, "gather", n_out, full),
                lambda: self._build_agg_gather_program(
                    agg_plans, routes, n_out, n_keys, sharded, full=full))
            out = self._run_program(gfn, table, unpackB)
            if sketch_plans:
                self.last_stats.update({
                    "sketch_fetch_bytes":
                        4 * _sketch_words(out, sketch_plans),
                    "sketch_groups": int(n_out)})
            finals = _finals_from_out(out, routes, n_out, sketch_plans)
            if not full:
                top_idx = np.asarray(out["__topk_idx__"]) \
                    .astype(np.int64)
            # full mode: rows travel in key order — decode's identity
            # path (top_idx None) already maps sel -> key ids
        else:
            # late materialization: the compact block runs INSIDE each
            # wave's program under a per-wave survivor budget (the first
            # wave's rows stand in for all — waves are equal-sized
            # splits), priced from the compaction mask only: staged
            # gather-heavy conjuncts apply after compaction and don't
            # shrink what the prefix must hold
            cheap_f0, _ = _compaction_filters(filter_spec)
            shape = self._compact_shape("agg", ds, lits, s_pad, days,
                                        sharded, n_dev, names)

            def plan():
                return self._plan_compact_m(
                    ds, seg_idx[:spw], cheap_f0, sharded, routes=routes,
                    n_dev=n_dev, allow_sharded=True, n_keys=n_keys,
                    shape=shape)

            def run(late):
                prog_fn, unpack, compact, notes = self._cached_program(
                    ("agg", base_sig, topk, late),
                    lambda: self._build_agg_program(
                        ds, all_dim_plans, agg_plans, filter_spec,
                        intervals, days, n_keys, sharded,
                        routes, topk=topk, late=late, hll_costs=hll_costs,
                        lits=lits))
                finals, n_live, out = self._run_waves(
                    q, ds, names, seg_idx, s_pad, sharded, prog_fn, unpack,
                    routes, n_out, sketch_plans, t0, lits,
                    budget=compact and compact.m)
                if notes:
                    self.last_stats.update({
                        "hll_form": notes["hll_form"],
                        "hll_slots": notes["hll_slots"]})
                return (finals, out), n_live, compact

            finals, out = self._run_budgeted(
                shape, plan, run,
                count=None if sharded or n_waves > 1 else
                lambda: self._count_survivors(
                    q, t0, ds, names, seg_idx, s_pad, lits, cheap_f0,
                    intervals, days), staged=_all_staged(cheap_f0))
            if topk:
                top_idx = np.asarray(out["__topk_idx__"]).astype(np.int64)
        if t0 is not None:
            self._stage_check(q, t0)      # post-device boundary

        with PH.phase("decode"):
            result, n_groups = self._decode_dense(
                ds, all_dim_plans, agg_plans, routes, finals, gran_kind,
                post_aggregations, having, limit, top_idx)
        if topk and not isinstance(q, S.TopNQuerySpec):
            # exact-contract GroupBy: the candidate selection is
            # f32-approximate — prove the boundary row clears the cutoff
            # or re-run with the full-table transfer (ADVICE r2)
            scores = np.asarray(out["__topk_score__"], np.float64)
            if not _topk_selection_exact(limit, topk, routes[topk[0]],
                                         scores, result.data):
                return self._run_agg(q, dimensions, aggregations,
                                     post_aggregations, having, limit,
                                     granularity, filter_spec, intervals,
                                     t0, no_topk=True)

        self.last_stats.update({
            "datasource": ds.name, "segments": int(n_seg_sel),
            "sharded": sharded, "groups": n_groups,
            "rows_scanned": int(ds.num_rows), "waves": int(n_waves),
            "segments_per_wave": int(spw),
            "bytes_scanned": int(seg_bytes) * int(n_seg_sel),
            "topk_device": int(topk[1]) if topk else 0,
            "having_device": int(n_out) if having_dev else 0})
        return result

    def _decode_dense(self, ds, dim_plans, agg_plans, routes, finals,
                      gran_kind, post_aggregations, having, limit,
                      top_idx=None):
        """Dense finals -> (QueryResult, groups selected): group
        selection, dictionary decode, sketch estimates, the global
        aggregate's identity row, the host epilogue. The caller names the
        span: ``decode`` for a solo statement, ``demux`` for a lane of a
        fused group. ``top_idx`` maps a device top-k's rows to key ids;
        None where rows travel in key order."""
        sel = np.nonzero(finals["__rows__"] > 0)[0]
        # a GLOBAL aggregate (no dims, no time bucketing) over zero
        # matching rows yields ONE identity row — SQL semantics (and
        # Druid's default timeseries behavior, minus its sum-is-0
        # quirk: we emit NULL sums)
        global_empty = (not dim_plans and gran_kind == "all"
                        and len(sel) == 0)
        if global_empty:
            sel = np.zeros(1, dtype=np.int64)
        data: Dict[str, np.ndarray] = {}
        columns: List[str] = []
        if dim_plans:
            key_ids = top_idx[sel] if top_idx is not None else sel
            code_lists = G.unfuse_key(key_ids, [p.card for p in dim_plans])
            for p, codes in zip(dim_plans, code_lists):
                data[p.output_name] = p.decode(codes)
                columns.append(p.output_name)
        for p in agg_plans:
            name = p.spec.name
            columns.append(name)
            if p.kind not in ("hll", "theta", "kll"):
                data[name] = _decode_agg_value(ds, p, routes[name],
                                               finals[name][sel])
            elif self.partial_sketches:
                # cluster historical mode: ship the raw [G, m] register
                # block; the broker merges registers across shards
                # (max/min/minsum) and finalizes the estimate once
                # (cluster/merge.py) — that is what makes the distributed
                # estimate EQUAL the single-engine one, not merely close
                data[name] = np.asarray(finals[name])[sel]
            else:
                with PH.phase("sketch"):
                    data[name] = _sketch_column(p, finals[name], sel)
        if global_empty:
            data.update(_identity_row(
                {p.spec.name: p.kind for p in agg_plans
                 if p.kind in ("sum", "min", "max")}))
        data = self._agg_epilogue(data, columns, post_aggregations, having,
                                  limit)
        return QueryResult(columns, data), len(sel)

    def _plan_literals(self, q, ds, dim_plans, min_day, max_day):
        """(the statement's literal plan, the day range its program's
        signature carries or None). Filter literals are resolved on the
        host here (``bind.operands``) and reach the program as an
        operand, so the signature holds the statement's shape
        (``lits.shape``) and every draw of a template is one program.
        The selected segments' day range stays in the signature only
        where something traced is built from it: a time-derived
        dimension, or any session that is not UTC (instants shift
        through a per-day offset table)."""
        tz = self.config.get(TZ_ID)
        with PH.phase("bind"), PH.phase("bind.operands"):
            # a repeated text reaches here as the statement memo's plan
            # (the same objects under a new per-request context), so its
            # resolved literals, shape and packed words are kept with it
            key = _spec_identity(q)
            held = (q, ds, self.store.datasource_version(ds.name), tz,
                    min_day, max_day)
            hit = self._literal_plans.get(key)
            if hit is not None and hit[0][1] is ds \
                    and hit[0][2:] == held[2:]:
                lits = hit[1]
            else:
                lits = L.LiteralPlan(ds, q.filter, q.aggregations,
                                     q.intervals, tz, min_day, max_day)
                lits.shape = lits.shape_repr(q)
                if key is not None:
                    _memo_put_bounded(self._literal_plans, key,
                                      (held, lits), _LITERAL_PLANS_MAX)
        days = (min_day, max_day) if not TZ.is_utc(tz) \
            or any(p.reads_days for p in dim_plans) else None
        return lits, days

    @staticmethod
    def _split_filter_staged(f):
        """(cheap, expensive) for staged filter evaluation under
        compaction: top-level AND conjuncts whose lowering must GATHER
        (large frozen-int membership, keyed-lookup expressions — the
        decorrelated-EXISTS machinery) evaluate after compaction, on the
        survivors of the cheap conjuncts only. A 6M-probe gather costs
        ~40ms on v5e; post-compaction it costs ~M/6M of that."""
        def expr_has_gather(e):
            found = [False]

            def visit(n):
                if isinstance(n, (E.KeyedLookup, E.KeyedLookup2)):
                    found[0] = True
                if isinstance(n, E.InList) \
                        and isinstance(n.values, E.FrozenIntSet) \
                        and not EC.int_set_lowers_to_chain(n.values.array):
                    found[0] = True
                return n
            E.transform(e, visit)
            return found[0]

        def is_expensive(x):
            if isinstance(x, S.InFilter) \
                    and isinstance(x.values, E.FrozenIntSet) \
                    and not EC.int_set_lowers_to_chain(x.values.array):
                return True
            if isinstance(x, S.ExprFilter):
                return expr_has_gather(x.expr)
            if isinstance(x, S.LogicalFilter) and x.op == "not":
                return is_expensive(x.fields[0])
            return False

        if f is None:
            return None, None
        conj = list(f.fields) if isinstance(f, S.LogicalFilter) \
            and f.op == "and" else [f]
        cheap = [x for x in conj if not is_expensive(x)]
        exp = [x for x in conj if is_expensive(x)]
        if not exp:
            return f, None

        def rejoin(parts):
            if not parts:
                return None
            if len(parts) == 1:
                return parts[0]
            return S.LogicalFilter("and", tuple(parts))

        return rejoin(cheap), rejoin(exp)

    def _plan_compact_m(self, ds, seg_idx, filter_spec, sharded,
                        routes=None, n_dev=1, allow_sharded=False,
                        n_keys=None, n_ops=None, shape=None):
        """Static survivor budget for late materialization (None = don't
        compact). Where a compacting program of this ``shape`` (or the
        filter-only count, ``_count_survivors``) has reported how many
        rows survive, the budget is the one that count holds the shape
        to (``_note_survivors``: twice the most seen, kept while it
        holds). At the first sight of a shape it is the cost model's
        filter-selectivity estimate, which multiplies conjuncts as if
        independent, with the same 2x margin. Either way a budget the
        survivors exceed is caught by the program's '__live__' output
        and the statement run again (``_run_budgeted``). Sharded (dense
        path only): the budget is PER SHARD — the compact block runs on
        each shard's local arrays under shard_map, and the largest
        shard's count travels.

        Gate (VERDICT r3 weak 6 — calibrated constants, not literals): the
        compaction sort costs ``rows * sort_c``; it saves the downstream
        per-row aggregation work — scatter updates (or the fused kernel's
        streamed pass under an 'ffl' route) on the rows it removes — and
        re-buys, per touched column, ``m`` gather probes or one more sort
        operand over ``rows``, whichever is less. All unit costs are
        per-backend measurements (``cost.unit_cost``; tools/calibrate.py
        refits them on the live backend). On TPU sort ≈ scatter/30 so the
        gate engages for any selective filter; on the CPU fallback the
        x64 sort only pays once the un-compacted table would scatter in
        the past-LLC thrash regime (the measured SF10 crossover).
        ``min.rows == 0`` is the explicit test/config override."""
        if filter_spec is None or (sharded and not allow_sharded):
            return None
        if not self.config.get(SCAN_COMPACT):
            return None
        min_rows = int(self.config.get(SCAN_COMPACT_MIN_ROWS))
        rows = int(sum(ds.segments[int(si)].num_rows for si in seg_idx
                       if si >= 0))   # -1 = multihost padding slot
        rows //= max(int(n_dev) if sharded else 1, 1)   # per-shard budget
        if min_rows > 0 and rows < min_rows:
            return None                  # small scans: the sort wins nothing
        seen = self._compact_seen.get(shape)
        if seen is None:        # a blind estimate leaves it to the count
            m = _budget_for(0 if _estimate_blind(filter_spec, ds) else
                            rows * C._filter_selectivity(filter_spec, ds))
        else:
            m = seen[1]
        m = max(m, 1 << 15) if rows >= (1 << 21) else m
        if m > rows // 2:
            return None                  # unselective: nothing to remove
        if min_rows > 0:
            from spark_druid_olap_tpu.utils import config as CF
            if n_ops is None:
                n_ops = max(1, len(routes)) if routes is not None else 4
            n_ops = min(int(n_ops), 8)
            sort_s = rows * C.unit_cost(self.config, CF.COST_SORT_ROW)
            # each touched column reaches the prefix the cheaper way, as
            # the program will (ops.scan.carries_by_sort)
            gather_s = n_ops * min(
                rows * C.unit_cost(self.config, CF.COST_SORT_PAYLOAD_ROW),
                m * C.unit_cost(self.config, CF.COST_GATHER_PROBE))
            if routes is not None and any(
                    getattr(r, "tag", None) == "ffl"
                    for r in routes.values()):
                # fused single streamed pass: the only saving is the
                # kernel's per-row cost on removed rows
                saved = (rows - m) * C.unit_cost(self.config,
                                                 CF.COST_FUSED_ROW)
            else:
                per_key = 4 * (sum(
                    sz for r in routes.values()
                    for _, sz, _ in r.outputs(1)) if routes else n_ops)
                tbl_bytes = (int(n_keys) if n_keys else 1 << 16) * per_key
                big = tbl_bytes > int(self.config.get(
                    CF.COST_TABLE_CACHE_BYTES))
                sc = C.unit_cost(
                    self.config, CF.COST_SCATTER_UPDATE_BIG if big
                    else CF.COST_SCATTER_UPDATE)
                saved = (rows - m) * sc * n_ops
            if sort_s + gather_s >= saved:
                return None
        return int(m)

    def _compact_shape(self, tier, ds, lits, s_pad, days, sharded, n_dev,
                       names):
        """What a survivor count is remembered under: the scan program's
        signature without its budget and what hangs on it (the table's
        width, the device epilogues chosen by that width). Like the
        signature it holds the statement's shape and never a literal's
        value, so every draw of a template shares one entry; unlike a
        plan cache's key it holds no text, so a deployment that turns
        every memory of a text off keeps it."""
        return (tier, self._sig_base(ds), lits.shape, s_pad, days, sharded,
                n_dev, tuple(names))

    def _late(self, compact_m):
        """What a compacting program is built under and cached by: the
        budget and the two unit costs that choose, with the traced
        shapes, how the survivors' arrays reach the prefix
        (``ops.scan.carries_by_sort``). None without a budget."""
        if not compact_m:
            return None
        from spark_druid_olap_tpu.utils import config as CF
        return (int(compact_m),
                C.unit_cost(self.config, CF.COST_SORT_PAYLOAD_ROW),
                C.unit_cost(self.config, CF.COST_GATHER_PROBE))

    def _run_budgeted(self, shape, plan, run, count=None, staged=False):
        """Late materialization's budget protocol, the dense and the
        hashed tier's alike. ``plan()`` is the tier's ``_plan_compact_m``
        for ``shape`` — the program's signature without its budget and
        table width, never a literal's value: every draw of a template
        shares one entry — and ``run(late)`` runs the scan under the
        budget ``late`` (``_late``; None: uncompacted) and returns
        (result, survivors the program counted, its Compaction).

        The first sight of a shape whose estimate says "compact" takes
        the count from ``count()``, a filter-only program, so the
        estimate's program is never built (None — sharded or several
        waves, which no deployment runs compacted — observes with the
        estimate's program instead). Every compacting run reports its
        count to the shape; one the budget did not hold (the prefix
        dropped rows: ``compact_overflow``) is run again under the
        budget the count asks for, or uncompacted where
        ``_plan_compact_m``'s exits say so, and the SHAPE goes straight
        there from then on. The record says how full the budget ran, from
        where, on which mask (``compact_live``, ``_from``, ``_mask``)."""
        with PH.phase("plan.engine"):
            m = plan()
            observed = shape in self._compact_seen
        if m and not observed and count is not None:
            self._note_survivors(shape, m, count())
            m, observed = plan(), True
        while True:
            result, n_live, compact = run(self._late(m))
            if not m:
                return result
            self._note_survivors(shape, m, n_live)
            if n_live <= m:
                break
            self.last_stats["compact_overflow"] = n_live - m
            m, observed = plan(), True
        self.last_stats.update({
            "compact_m": compact.m, "compact_carry": compact.carry,
            "compact_cols": compact.cols, "compact_live": n_live,
            "compact_from": "observed" if observed else "estimate",
            "compact_mask": "staged" if staged else "cheap"})
        return result

    def _note_survivors(self, shape, m, n_live):
        """A program of ``shape`` counted ``n_live`` survivors under the
        budget ``m``. The shape keeps the most it has seen and the budget
        that count holds it to — sticky: the budget in force stays while
        the count fits it and fills over an eighth of it, so draws that
        straddle a power of two do not flip the shape between two
        programs; it is sized anew, at twice the count, only where rows
        were dropped or a quarter of it would do."""
        seen = self._compact_seen.get(shape)
        if seen is not None and n_live <= seen[0]:
            return                      # nothing the shape has not seen
        with self._compile_lock:
            seen = self._compact_seen.get(shape)
            if seen is not None:
                n_live, m = max(n_live, seen[0]), seen[1]
            want = _budget_for(n_live)
            if n_live > m or want * 4 <= m:
                m = want
            _memo_put_bounded(self._compact_seen, shape, (n_live, m),
                              _COMPACT_SEEN_MAX)

    def _count_survivors(self, q, t0, ds, names, seg_idx, s_pad, lits,
                         cheap_f, intervals, days):
        """How many rows the compaction mask keeps, by a program doing
        nothing else (no sort: seconds to compile, where a compacting one
        takes minutes): once a shape a process, at its first sight. One
        chip, one wave; binds the arrays its scan program then finds."""
        min_day, max_day = days or (None, None)

        def build():
            def run(arrays):
                ctx = ScanContext(ds, arrays, min_day, max_day,
                                  tz=self.config.get(TZ_ID),
                                  operands=_operands(lits, arrays))
                base = _survivor_mask(ctx, cheap_f, intervals)
                return jnp.sum(base.astype(jnp.int32)).reshape(1)
            return named_jit("sdot_count_survivors", run)

        prog = self._cached_program(
            ("count", self._sig_base(ds), lits.shape, s_pad, days,
             tuple(names)), build)
        n_live, = self._waves(q, t0, ds, names, [seg_idx], s_pad, False,
                              lits, prog,
                              lambda buf: int(np.asarray(buf)[0]))
        # the record names the scan program, which follows
        del self.last_stats["program"]
        return n_live

    def _plan_device_topk(self, limit, having, agg_plans, n_keys):
        """Decide whether the ordered-limit epilogue can run on device:
        select ``k_sel`` candidate keys by an f32 score over the merged
        partials (ops.groupby.route_score) and transfer only those rows.
        ≈ Druid's topN engine (per-key-space top-k on the data node instead
        of shipping the full groupBy result to the broker). Returns
        (metric, k_sel, ascending) or None.

        The candidate *selection* is f32-approximate with ``k_sel - limit``
        slack; the final ordering of candidates is exact (host combine).
        NULL-metric groups: min/max sentinels are detected on device and
        ranked after every real score (nulls-last, matching the host
        epilogue); a NULL *sum* scores as 0 (indistinguishable from a true
        zero), so it can displace a candidate only when the true top-k
        sits below 0 AND >slack NULL-sum groups exist — still tighter
        than Druid's documented topN approximation.
        Skipped under HAVING (it may filter an unbounded prefix) and in
        wave mode (waves merge by key; candidate sets differ per wave)."""
        if having is not None or limit is None or limit.limit is None:
            return None
        if not limit.columns:
            return None
        if n_keys < self.config.get(TOPN_DEVICE_MIN_KEYS):
            return None
        oc = limit.columns[0]
        mplan = next((p for p in agg_plans if p.spec.name == oc.name), None)
        if mplan is None or mplan.kind in ("hll", "theta", "kll"):
            return None
        if mplan.dim_codes:
            # string min/max decodes to text: the exactness proof can't
            # score it (float(str)), so the epilogue would always re-run
            return None
        k_sel = min(n_keys, _topk_slack(limit))
        if k_sel * 4 >= n_keys:
            return None              # full transfer is already cheap
        return (oc.name, k_sel, bool(oc.ascending))

    def _agg_epilogue(self, data, columns, post_aggregations, having, limit):
        """Host epilogue shared by the dense and hashed agg paths: post
        aggregations, HAVING, ORDER BY + LIMIT (≈ the Spark-side Project /
        Filter / Sort the reference leaves above the Druid scan)."""
        for pa in post_aggregations:
            data[pa.name] = np.asarray(host_eval.eval_expr(pa.expr, data))
            columns.append(pa.name)
        if having is not None:
            keep = host_eval.eval_pred3(having.expr, data)
            data = {k: v[keep] for k, v in data.items()}
        if limit is not None and limit.columns:
            order_keys = []
            for oc in reversed(limit.columns):
                k = data[oc.name]
                if k.dtype == object and all(
                        v is None or isinstance(v, (int, np.integer))
                        for v in k):
                    # wide-int min/max columns with empty groups: exact
                    # int64 sort (f64 would collapse values past 2^53),
                    # nulls last via a more-significant null flag
                    nulls = np.array([v is None for v in k])
                    vals = np.array([0 if v is None else int(v) for v in k],
                                    dtype=np.int64)
                    order_keys.append(vals if oc.ascending else -vals)
                    order_keys.append(nulls)
                    continue
                if k.dtype == object:
                    k = k.astype(str)
                order_keys.append(k if oc.ascending else _neg_key(k))
            idx = np.lexsort(order_keys)
            if limit.limit is not None:
                idx = idx[: limit.limit]
            data = {k: v[idx] for k, v in data.items()}
        elif limit is not None and limit.limit is not None:
            data = {k: v[: limit.limit] for k, v in data.items()}
        return data

    # -- hashed high-cardinality aggregation path -----------------------------
    def _hash_layout(self, q, ds, seg_idx, dim_plans, agg_plans, names):
        """How the hashed tier would lay a statement out, before anything
        is built: key parts, table slots and their ceiling, chips, waves
        (``HashLayout``). ``_run_agg`` reads it to decide an HLL
        statement's tier ONCE; ``_run_agg_hashed`` runs by it."""
        cards = [p.card for p in dim_plans]
        try:
            parts = H.split_parts(cards)
        except H.KeySpaceTooWide as e:
            raise EngineFallback(str(e)) from e

        # EXACT selected-row count: initial_slots sizes the table straight
        # to min(key space, rows), which is only a true upper bound on the
        # group count when this is not an average-based estimate (a skewed
        # segment selection could undershoot an average and trigger a
        # spurious 4x-retry recompile)
        rows_sel = int(sum(ds.segments[int(si)].num_rows
                           for si in seg_idx))
        max_slots = int(self.config.get(GROUPBY_HASH_MAX_SLOTS))
        if not PG_tpu._tpu_backend():
            # the 16M-slot ceiling is TPU economics (400MB of HBM table
            # buffers, ~sort+scatter in hundreds of ms); on the CPU
            # fallback x64 scatters into a 16M-slot table thrash cache so
            # badly that the host pandas tier is ~3x faster (measured
            # q18-inner SF10: 530s engine vs 193s host) — CPU gets its
            # own configurable ceiling (default 8M, from that measurement)
            from spark_druid_olap_tpu.utils.config import (
                GROUPBY_HASH_MAX_SLOTS_CPU)
            max_slots = min(max_slots, int(self.config.get(
                GROUPBY_HASH_MAX_SLOTS_CPU)))
        n_keys_total = 1
        for c in cards:
            n_keys_total *= int(c)
        T = int(self.config.get(GROUPBY_HASH_SLOTS)) or H.initial_slots(
            min(n_keys_total, rows_sel), hi=max_slots)

        sharded = self._should_shard(q, ds, seg_idx)
        n_dev = mesh_size(self.mesh) if sharded else 1
        seg_bytes = C.bytes_per_segment(ds, names)
        spw, n_waves = C.plan_waves(
            len(seg_idx), n_dev, seg_bytes,
            C.wave_budget_bytes(self.config), self.config,
            min(rows_sel, T), len(agg_plans),
            io_budget=C.tier_io_budget(ds, self.config),
            io_seg_bytes=C.tier_io_seg_bytes(ds, names))
        return HashLayout(parts, T, max_slots, sharded, n_dev, seg_bytes,
                          spw, n_waves)

    def _run_agg_hashed(self, q, ds, seg_idx, dim_plans, agg_plans, names,
                        min_day, max_day, post_aggregations, having, limit,
                        filter_spec, intervals, t0, no_topk: bool = False,
                        *, lits, days):
        """Group-by above the dense key-space ceiling: fixed-size device hash
        table per chip/wave (ops/hash_groupby.py), partials merged by *key*
        on host. Table overflow retries at 4x slots, then falls back.
        ≈ Druid groupBy v2 never refusing on cardinality
        (DruidQuerySpec.scala:558-571).

        An HLL sketch runs here in the sparse form only (a table of T
        slots has no ``[T, 2^log2m]`` block to give): one finished
        estimate a slot, which no merge by key can combine, so the scan
        must be one wave on one chip and no historical's. The medium-K
        reroute sends no other here (``_run_agg``); a statement past the
        dense tier's key space has no other tier and falls back."""
        if any(p.kind in ("theta", "kll") for p in agg_plans):
            raise EngineFallback(
                "theta / kll sketch aggregation over hashed group-by")
        has_hll = any(p.kind == "hll" for p in agg_plans)
        if has_hll and self.partial_sketches:
            # a historical ships raw registers for its broker to merge
            # (_decode_dense): only the dense forms have them
            raise EngineFallback(
                "partial HLL registers over hashed group-by")
        cards = [p.card for p in dim_plans]
        lay = self._hash_layout(q, ds, seg_idx, dim_plans, agg_plans, names)
        if has_hll and not lay.one_table:
            raise EngineFallback(
                "HLL sketch over a hashed group-by of several waves "
                "or chips")
        parts, T, max_slots, sharded, n_dev, seg_bytes, spw, n_waves = lay
        s_pad = spw if n_waves > 1 else _pad_segments(len(seg_idx), n_dev)
        n_seg_sel = len(seg_idx)
        multihost = sharded and MH.is_multihost()
        if multihost:
            seg_idx, s_pad, spw, n_waves = self._multihost_layout(
                ds, seg_idx, n_waves, seg_bytes)
        wave_segs = [seg_idx[i: i + s_pad]
                     for i in range(0, len(seg_idx), s_pad)]
        log2m = self.config.get(HLL_LOG2M)

        # no '__rows__' occupancy count here: occupied slots are read off
        # the key table (khi != EMPTY) directly
        metas = [G.AggInput(p.spec.name, p.kind, is_int=p.is_int,
                            maxabs=p.maxabs, log2m=log2m)
                 for p in agg_plans]
        topk_plan = self._plan_device_topk_hashed(limit, having, agg_plans,
                                                  n_dev, n_waves) \
            if not no_topk else None
        exch_plan = None
        if topk_plan is None and n_dev > 1 and n_waves == 1:
            # multi-host included: the exchange is pure in-mesh
            # collectives (candidate all_gather + psum/pmin/pmax); its
            # O(k_sel) output replicates for cross-process fetch
            exch_plan = self._plan_hash_topk_exchange(q, limit, having,
                                                      agg_plans)

        # late materialization (shared with the dense path): the key
        # build + aggregation shrink to O(survivors) under the shape's
        # survivor budget (_run_budgeted), and at most that many rows
        # reach the table, so the budget bounds the table as truly as
        # min(key space, selected rows) does
        cheap_f0, _ = _compaction_filters(filter_spec)
        shape = self._compact_shape("hashagg", ds, lits, s_pad, days,
                                    sharded, n_dev, names)
        T_full, fixed_T = T, bool(self.config.get(GROUPBY_HASH_SLOTS))

        def plan():
            return self._plan_compact_m(
                ds, seg_idx, cheap_f0, sharded, n_keys=T_full,
                n_ops=len(agg_plans) + 2, shape=shape) \
                if n_waves == 1 else None

        def scan(T, late_key):
            """One scan into a table of ``T`` slots under the budget
            ``late_key`` -> (rows the table did not resolve, survivors
            counted, what the epilogue reads)."""
            # k_sel*4 <= T also bounds k_sel < T, so no clamp is needed
            topk = topk_plan if topk_plan and topk_plan[1] * 4 <= T \
                else None
            exch = exch_plan if exch_plan and exch_plan[1] * 4 <= T \
                else None
            compact = (topk is None and exch is None
                       and T >= self.config.get(GROUPBY_HASH_COMPACT_MIN))
            # multi-host: the [T] slot tables stay DEVICE-RESIDENT
            # sharded between the two dispatches (_shard_wrap
            # gather_only) — only '__stats__' and the kg compacted slots
            # cross hosts, O(groups-out) instead of O(T x n_aggs)
            # (VERDICT r4 item 3)
            k_out = topk[1] if topk else T
            n_rows_dev = int(ds.padded_rows) * int(ds.num_segments)
            sorted_run = False
            if self._sorted_run_wanted():
                sroutes = SG.plan_sorted_routes(metas, n_rows=n_rows_dev)
                if sroutes is not None:
                    routes = sroutes
                    sorted_run = True
            if not sorted_run:
                routes = G.plan_routes(
                    [a for a in metas if a.kind != "hll"], T,
                    self.config.get(GROUPBY_MATMUL_MAX_KEYS),
                    n_rows=n_rows_dev)
                # (plan_routes knows the dense tier's kinds; in
                # agg_plans' order, which the packers follow)
                routes = {a.name: routes.get(a.name)
                          or G.Route(a.name, "hll", "i32") for a in metas}
            # HAVING on the device-resident table (the dense tier's
            # transfer filter, _plan_device_having): one chip, one wave
            # — a partial table's totals are not the group's — and an
            # aggregate the table holds as ONE exact integer column
            having_dev = self._plan_device_having(
                having, routes, agg_plans, T, None, n_waves) \
                if compact and n_dev == 1 else None
            if having_dev and routes[having_dev[0]].tag not in ("i32",
                                                                "i64"):
                having_dev = None
            sig = ("hashagg", self._sig_base(ds), lits.shape, s_pad, days,
                   sharded, n_dev, T, tuple(names), topk, compact,
                   late_key, sorted_run, having_dev)

            def build():
                # the program and, under a budget, its Compaction: the
                # record reads the form it ran in from there
                late = Compaction(*late_key) if late_key else None
                if compact or exch:
                    return self._build_hash_table_program(
                        ds, dim_plans, parts, agg_plans, filter_spec,
                        intervals, days, T, sharded, routes,
                        compact=late, sorted_run=sorted_run,
                        having_dev=having_dev, lits=lits), late
                return self._build_hash_program(
                    ds, dim_plans, parts, agg_plans, filter_spec,
                    intervals, days, T, sharded, routes,
                    topk=topk, compact=late, sorted_run=sorted_run,
                    lits=lits), late

            prog, late = self._cached_program(sig, build)

            partials, unresolved, kg_used, tk_scores = [], 0, 0, None
            sk_words = 0    # sketch estimates among what was copied back
            # (without a budget no survivors are counted)
            n_live, budget = 0, late.m if late else 0
            # the table form's program has no unpack: its table stays on
            # the device and a wave lands as (table, stats)
            prog_fn, unpack = (prog, None) if compact or exch else prog
            for landed in self._waves(q, t0, ds, names, wave_segs, s_pad,
                                      sharded, lits, prog_fn, unpack):
                if unpack is not None:
                    raw = landed
                    unresolved += int(raw.pop("__unres__").sum())
                    if late:
                        n_live = int(raw.pop("__live__")[0])
                    if unresolved or n_live > budget:
                        break
                    if topk:
                        tk_scores = raw.pop("__topk_score__")
                    sk_words += _sketch_words(raw, agg_plans)
                    partials.extend(
                        _hash_chip_partials(raw, routes, k_out, n_dev))
                    continue
                # the table's second program, a dispatch of its own: the
                # exchanged top-k candidates or the occupied slots.
                # '__stats__' a chip: unresolved, (survivors under a
                # budget,) occupied slots (, those that pass the HAVING)
                table, stats = landed[0], landed[1].reshape(
                    -1, 2 + bool(late) + bool(having_dev))
                unresolved += int(stats[:, 0].sum())
                if late:
                    n_live = int(stats[0, 1])
                if unresolved or n_live > budget:
                    break
                if exch:
                    metric, k_sel, ascending = exch
                    # sums need wider per-chip candidate lists (a
                    # key large in total can rank lower locally);
                    # min/max are exact with k_sel alone
                    mplan = next(p for p in agg_plans
                                 if p.spec.name == metric)
                    k_cand = k_sel if mplan.kind in ("min", "max") \
                        else min(T, max(4 * k_sel, 1024))
                    kg = k_sel
                    gfn, unpackB = self._cached_program(
                        (sig, "exchange", exch, k_cand),
                        lambda: self._build_hash_topk_exchange_program(
                            agg_plans, routes, metric, ascending,
                            k_cand, k_sel, T))
                else:
                    # the slots that travel: the occupied ones, or those
                    # of them that pass the HAVING
                    occ_max = max(1, int(stats[:, -1].max()))
                    kg = min(T, 1 << max(6, (occ_max - 1).bit_length()))
                    gfn, unpackB = self._cached_program(
                        (sig, "gather", kg),
                        lambda kg=kg: self._build_hash_gather_program(
                            agg_plans, routes, kg, T, sharded,
                            having_dev))
                kg_used = max(kg_used, kg)
                raw = self._run_program(gfn, table, unpackB)
                sk_words += _sketch_words(raw, agg_plans)
                partials.extend(_hash_chip_partials(raw, routes, kg, n_dev))
            return unresolved, n_live, (
                T, routes, topk, exch, having_dev, sorted_run, partials,
                kg_used, tk_scores, sk_words, late)

        def run(late_key):
            # the table follows the budget; its overflow retries at 4x
            # slots behind it (a budget's overflow is the protocol's)
            T = T_full if fixed_T or not late_key else min(
                T_full, H.initial_slots(late_key[0], hi=max_slots))
            while True:
                unresolved, n_live, tier = scan(T, late_key)
                if late_key and n_live > late_key[0]:
                    return None, n_live, None    # the budget's overflow
                if not unresolved:
                    return tier, n_live, tier[-1]
                T *= 4
                if T > max_slots:
                    raise EngineFallback(
                        f"hashed group-by exceeded {max_slots} table slots")

        (T, routes, topk, exch, having_dev, sorted_run, partials, kg_used,
         tk_scores, sk_words, late) = self._run_budgeted(
            shape, plan, run,
            count=None if sharded or n_waves > 1 else
            lambda: self._count_survivors(
                q, t0, ds, names, seg_idx, s_pad, lits, cheap_f0,
                intervals, days), staged=_all_staged(cheap_f0))
        if t0 is not None:
            self._stage_check(q, t0)

        with PH.phase("merge"):
            keys, merged = _merge_hash_partials(partials, routes)
        data: Dict[str, np.ndarray] = {}
        columns: List[str] = []
        with PH.phase("decode"):
            khi, klo = H.unpack_key(keys)
            part_vals = [khi, klo]
            dim_codes: Dict[int, np.ndarray] = {}
            for pi, idxs in enumerate(parts):
                for i, c in zip(idxs,
                                H.unfuse_part(part_vals[pi], cards, idxs)):
                    dim_codes[i] = c
            for i, p in enumerate(dim_plans):
                data[p.output_name] = p.decode(dim_codes[i])
                columns.append(p.output_name)
            for p in agg_plans:
                name = p.spec.name
                if p.kind == "hll":
                    # one partial (one chip, one wave): merged as it came
                    with PH.phase("sketch"):
                        data[name] = _sketch_column(p, merged[name],
                                                    slice(None))
                else:
                    data[name] = _decode_agg_value(ds, p, routes[name],
                                                   merged[name])
                columns.append(name)
            data = self._agg_epilogue(data, columns, post_aggregations,
                                      having, limit)
        if has_hll:
            # live (group, register) pairs the table can hold: a row each
            rows = late.m if late \
                else int(s_pad // n_dev) * int(ds.padded_rows)
            self.last_stats.update({
                "hll_form": "sparse",
                "hll_slots": min(rows, T << log2m),
                "sketch_fetch_bytes": 4 * sk_words,
                "sketch_groups": sk_words // sum(
                    p.kind == "hll" for p in agg_plans)})

        if topk and tk_scores is not None \
                and not isinstance(q, S.TopNQuerySpec):
            # exact-contract GroupBy over the hashed tier: same proof as
            # the dense epilogue (ADVICE r2); single-chip single-wave by
            # _plan_device_topk_hashed, so the slot scores are global
            scores = np.sort(np.asarray(tk_scores, np.float64))[::-1]
            if not _topk_selection_exact(limit, topk, routes[topk[0]],
                                         scores, data):
                return self._run_agg_hashed(
                    q, ds, seg_idx, dim_plans, agg_plans, names, min_day,
                    max_day, post_aggregations, having, limit, filter_spec,
                    intervals, t0, no_topk=True, lits=lits, days=days)

        self.last_stats.update({
            "datasource": ds.name, "segments": int(n_seg_sel),
            "sharded": sharded, "groups": int(len(keys)),
            "rows_scanned": int(ds.num_rows), "waves": int(len(wave_segs)),
            "bytes_scanned": int(seg_bytes) * int(n_seg_sel),
            "segments_per_wave": int(s_pad), "hashed": True,
            "hash_slots": int(T), "hash_compact_k": int(kg_used),
            # the mechanism that ran: sorted-run core or scatter, and the
            # rows a chip's table was built from (per wave)
            "sorted_run": bool(sorted_run),
            "hash_rows": late.m if late
            else int(s_pad // n_dev) * int(ds.padded_rows),
            "topk_device": int(topk[1]) if topk
            else (int(exch[1]) if exch else 0),
            "topk_exchange": bool(exch),
            "having_device": int(kg_used) if having_dev else 0})
        return QueryResult(columns, data)

    def _plan_device_topk_hashed(self, limit, having, agg_plans, n_dev,
                                 n_waves):
        """Device top-k over the hash table: transfer only the best
        ``k_sel`` SLOTS per chip/wave instead of the full [T] table.

        Single-chip single-wave ONLY: there the table is complete, so
        per-slot scores are global and selection is exact (modulo the f32
        score + slack, like the dense epilogue). Multi-chip/wave a key's
        partials are split across per-chip tables — per-chip top-k both
        misses globally-large keys AND under-counts any key selected on
        one chip but not another (Druid's topN accepts exactly this
        skew; we keep the full-table key-wise merge instead and stay
        exact)."""
        if having is not None or limit is None or limit.limit is None:
            return None
        if not limit.columns:
            return None
        oc = limit.columns[0]
        mplan = next((p for p in agg_plans if p.spec.name == oc.name), None)
        if mplan is None or mplan.dim_codes or mplan.kind == "hll":
            return None
        if n_dev != 1 or n_waves != 1:
            return None
        return (oc.name, _topk_slack(limit), bool(oc.ascending))

    def _compacted(self, ctx, base, compact, tail, exp_f, fuse_cse):
        """Late materialization between a core's cheap filter and its
        ``tail(ctx, base, cse)``: the survivors of ``base`` come to a
        static [compact.m] prefix (``ops.scan.compact_scan``: as payloads
        of the compaction sort or by a gather an array, whichever the
        shapes price lower) and the tail runs at O(survivors). Returns
        (the tail's outputs, the survivors counted) and notes on
        ``compact`` the form that ran. The position sort is 0.7ms/M rows
        on a v5e and each column it carries 0.5-0.7 — far below one
        6M-row scatter (~40ms)."""
        def staged(cctx, live):
            # the compacted context changes every mask's shape: the
            # full-width CSE entries must never leak past this point
            cse = FU.CSECache(cctx) if fuse_cse else None
            if exp_f is not None:
                # staged: gather-heavy conjuncts (membership sets,
                # keyed lookups) evaluate on the survivors only
                em = cse.lower(exp_f) if cse is not None \
                    else F.lower_filter(exp_f, cctx)
                if em is not None:
                    live = live & em
            return tail(cctx, live, cse)

        cctx, live, n_live = compact_scan(
            ctx, base, compact.m, staged, compact.payload_row_s,
            compact.probe_s)
        out = staged(cctx, live)
        compact.carry, compact.cols = cctx.carried()
        return out, n_live

    def _hash_core(self, ds, dim_plans, parts, agg_plans, filter_spec,
                   intervals, days, T, routes,
                   compact=None, sorted_run=False, *, lits):
        """The shared hash scan body: scan -> filter -> per-dim codes ->
        two-part key -> slot claim -> exact scatter aggregation into [T]
        buffers. Returns the raw out dict incl. '__tkhi__'/'__tklo__' key
        tables and '__unres__' (shape [1]). With ``compact`` (a
        ``Compaction``), late materialization (same machinery as the dense
        path) runs the key build + aggregation at O(survivors) and the
        survivor count travels as '__live__' (shape [1]) beside
        '__unres__', which stays the table's own."""
        matmul_max = self.config.get(GROUPBY_MATMUL_MAX_KEYS)
        log2m = self.config.get(HLL_LOG2M)
        cards = [p.card for p in dim_plans]
        cheap_f, exp_f = (_compaction_filters(filter_spec)
                          if compact else (filter_spec, None))
        fuse_cse = bool(self.config.get(SHAREDSCAN_FUSION_ENABLED))
        min_day, max_day = days or (None, None)

        @jax.named_scope("sdot_hashed_groupby")
        def core(arrays):
            operands = _operands(lits, arrays)
            ctx = ScanContext(ds, arrays, min_day, max_day,
                              tz=self.config.get(TZ_ID), operands=operands)
            # same trace-time predicate CSE as the dense core
            cse = FU.CSECache(ctx) if fuse_cse else None
            base = _survivor_mask(ctx, cheap_f, intervals, cse)
            if not compact:
                return tail(ctx, base, cse)
            out, n_live = self._compacted(ctx, base, compact, tail, exp_f,
                                          fuse_cse)
            out["__live__"] = n_live.reshape(1)
            return out

        def tail(ctx, base, cse):
            codes = [p.build(ctx) for p in dim_plans]
            khi = H.fuse_part(codes, cards, parts[0])
            klo = H.fuse_part(codes, cards, parts[1]) if len(parts) > 1 \
                else jnp.zeros_like(khi)
            inputs = []
            for p in agg_plans:
                mask = p.build_mask(ctx, cse=cse)
                # a filtered aggregation's masked rows hold the sentinel,
                # not the group's value: its rows do not agree
                inputs.append(G.AggInput(
                    p.spec.name, p.kind, p.build_values(ctx), mask,
                    is_int=p.is_int, maxabs=p.maxabs,
                    same_in_group=p.spec.kind == "anyvalue"
                    and mask is None, log2m=log2m))
            if sorted_run:
                # sorted-run tier: the slot sort rides the agg values as
                # payloads; prefix scans + run-boundary reads replace
                # every per-agg scatter (ops/sorted_groupby.py)
                return SG.sorted_hash_groupby(khi, klo, base, T, inputs,
                                              routes)
            slot, tk_hi, tk_lo, unresolved = H.build_slots(
                khi, klo, base, T)
            out = G.dense_groupby(
                slot, base, T, [a for a in inputs if a.kind != "hll"],
                routes, matmul_max)
            for a in inputs:
                if a.kind == "hll":
                    # the slot is a dense group key: the dense tier's
                    # sparse form, T groups wide
                    live = base & (slot < T)
                    out[a.name] = HLL.hll_estimates(
                        slot, live if a.mask is None else live & a.mask,
                        a.values, T, log2m)
            out["__tkhi__"] = tk_hi
            out["__tklo__"] = tk_lo
            out["__unres__"] = unresolved.reshape(1)
            return out

        return core

    def _hash_packers(self, agg_plans, routes, k_out, with_unres: bool,
                      with_score: bool = False, with_live: bool = False):
        """(pack, unpack) over the hash outputs: ONE flat buffer — every
        device->host transfer is its own sync, so the table must not
        travel as 8-10 separate arrays (same packing contract as the
        dense path)."""
        x64 = G._x64()
        meta = ([("__unres__", 1, "i32")] if with_unres else []) \
            + ([("__live__", 1, "i32")] if with_live else []) \
            + [("__tkhi__", k_out, "i32"), ("__tklo__", k_out, "i32")]
        if with_score:
            meta.append(("__topk_score__", k_out, "f64" if x64 else "f32"))
        for p in agg_plans:
            meta.extend(routes[p.spec.name].outputs(k_out))
        total = sum(m[1] for m in meta)

        def pack(out):
            return jnp.concatenate([_encode_buf(out[oname], dt, x64)
                                    for oname, _, dt in meta])

        def unpack(buf):
            """-> {name: [n_chips*size] chip-major} (incl. '__unres__')."""
            flat = np.asarray(buf)
            chips = flat.reshape(-1, total)
            out = {}
            off = 0
            for oname, size, dt in meta:
                chunk = np.ascontiguousarray(
                    chips[:, off: off + size]).reshape(-1)
                off += size
                out[oname] = _decode_buf(chunk, dt, x64)
            return out

        return pack, unpack

    def _sorted_run_wanted(self) -> bool:
        """The ONE gate for the sorted-run tier (and the medium-K
        reroute onto it): config 'on'/'off' wins; 'auto' engages when
        riding a payload through the already-paid slot sort beats one
        scatter pass — per-backend calibrated constants, true on TPU,
        false on the CPU fallback unless calibration says otherwise."""
        sr_mode = str(self.config.get(GROUPBY_HASH_SORTED))
        if sr_mode == "off":
            return False
        if sr_mode == "on":
            return True
        from spark_druid_olap_tpu.utils import config as CF
        return C.unit_cost(self.config, CF.COST_SORT_PAYLOAD_ROW) \
            < C.unit_cost(self.config, CF.COST_SCATTER_UPDATE)

    def _multihost_layout(self, ds, seg_idx, n_waves, seg_bytes: int = 0):
        """Re-order a (pruned) segment selection into per-host blocks so
        each host's devices scan exactly the segments that host stores
        (parallel/multihost.layout_segments). Returns the executor-shape
        tuple ``(ordered_seg_idx, s_pad, spw, n_waves)`` — ordered may
        contain ``-1`` padding slots (zero rows, validity False). With
        ``n_waves > 1`` each contiguous ``spw``-slice of the returned
        layout is itself host-blocked (multihost.layout_segments_waves),
        so the wave loops compose with multi-host unchanged — SF100's
        overflow valve works on partial stores (VERDICT r4 item 2)."""
        n_hosts, dph = MH.host_blocks(self.mesh)
        assignment = ds.host_assignment
        if assignment is None:
            # complete (replicated) datasource: derive the same contiguous
            # row-balanced split every process computes from metadata
            rows = np.array([s.num_rows for s in ds.segments], np.int64)
            assignment = MH.assign_segments_to_hosts(rows, n_hosts)
        if n_waves > 1:
            # pass the byte budget down so a skewed assignment (one host
            # owning most of the pruned segments) cannot overshoot the
            # per-device wave budget the caller's n_waves assumed
            ordered, spw = MH.layout_segments_waves(
                assignment, seg_idx, n_hosts, dph, n_waves,
                seg_bytes=int(seg_bytes),
                wave_budget=int(C.wave_budget_bytes(self.config) or 0))
            return ordered, spw, spw, len(ordered) // spw
        ordered, _ = MH.layout_segments(assignment, seg_idx, n_hosts, dph)
        return ordered, len(ordered), len(ordered), 1

    def _shard_wrap(self, name, fn, in_spec, out_spec, gather_only=None):
        """``name`` is the program's (``named_jit``). ``gather_only``: multi-host, dict-shaped outputs — all_gather
        (replicate for host fetch) ONLY these keys; the rest stay
        per-chip DEVICE-RESIDENT sharded arrays (the hashed tier's [T]
        slot tables, consumed by the gather dispatch without ever
        crossing hosts — VERDICT r4 item 3's transfer diet)."""
        if self.mesh is None:
            return named_jit(name, fn)
        if MH.is_multihost() and out_spec == P(SEGMENT_AXIS):
            inner = fn
            if gather_only is None:
                # per-chip outputs are not fetchable across processes: an
                # in-mesh all_gather replicates them (chips-major, exactly
                # the layout the host-side key-wise merge already expects)
                def fn(x):
                    out = inner(x)
                    return jax.tree.map(
                        lambda y: jax.lax.all_gather(y, SEGMENT_AXIS,
                                                     tiled=True), out)
                out_spec = P()
            else:
                def fn2(x):
                    out = dict(inner(x))
                    gathered = {k: jax.lax.all_gather(
                        out.pop(k), SEGMENT_AXIS, tiled=True)
                        for k in tuple(gather_only) if k in out}
                    return gathered, out
                smfn = shard_map(
                    fn2, mesh=self.mesh, in_specs=(in_spec,),
                    out_specs=(P(), P(SEGMENT_AXIS)), check_vma=False)
                jfn = named_jit(name, smfn)

                def wrapped(x):
                    g, rest = jfn(x)
                    return {**g, **rest}
                return wrapped
        smfn = shard_map(fn, mesh=self.mesh, in_specs=(in_spec,),
                             out_specs=out_spec, check_vma=False)
        return named_jit(name, smfn)

    def _build_hash_program(self, ds, dim_plans, parts, agg_plans,
                            filter_spec, intervals, days, T,
                            sharded, routes, topk=None, compact=None,
                            sorted_run=False, *, lits):
        """Single-dispatch hash program (full-table or topk-gathered
        transfer). Outputs stay per-chip in sharded mode (slot layouts
        differ per chip; the key-wise merge is host-side). With ``topk``
        only the top-scored ``k_sel`` slots per chip travel (see
        _plan_device_topk_hashed)."""
        core = self._hash_core(ds, dim_plans, parts, agg_plans, filter_spec,
                               intervals, days, T, routes,
                               compact=compact, sorted_run=sorted_run,
                               lits=lits)
        k_out = topk[1] if topk else T
        pack, unpack = self._hash_packers(agg_plans, routes, k_out, True,
                                          with_score=bool(topk),
                                          with_live=bool(compact))

        def run(arrays):
            out = core(arrays)
            if topk:
                counts = {k: out.pop(k) for k in ("__unres__", "__live__")
                          if k in out}
                out = _hash_topk_gather(out, routes, topk, T)
                out.update(counts)
            return pack(out)

        if not sharded:
            return named_jit("sdot_agg_hashed", run), unpack
        return self._shard_wrap("sdot_agg_hashed", run,
                                P(SEGMENT_AXIS, None),
                                P(SEGMENT_AXIS)), unpack

    def _build_hash_table_program(self, ds, dim_plans, parts, agg_plans,
                                  filter_spec, intervals, days,
                                  T, sharded, routes, compact=None,
                                  sorted_run=False, having_dev=None, *,
                                  lits):
        """Compaction dispatch 1 of 2: build the table, leave it DEVICE-
        RESIDENT, transfer only '__stats__' = [unresolved, occupied] per
        chip — under a ``compact`` budget the survivors counted between
        the two, with ``having_dev`` also how many occupied slots pass
        the HAVING. The host sizes the gather dispatch from the last."""
        core = self._hash_core(ds, dim_plans, parts, agg_plans, filter_spec,
                               intervals, days, T, routes,
                               compact=compact, sorted_run=sorted_run,
                               lits=lits)

        def run(arrays):
            out = core(arrays)
            head = [out.pop("__unres__")] \
                + ([out.pop("__live__")] if compact else [])
            counts = [out["__tkhi__"] != H.EMPTY]
            if having_dev:
                counts.append(_hash_having_mask(having_dev, out, routes))
            out["__stats__"] = jnp.concatenate(
                [c.astype(jnp.int32) for c in head]
                + [jnp.sum(m).astype(jnp.int32).reshape(1) for m in counts])
            return out

        if not sharded:
            return named_jit("sdot_agg_hashed_table", run)
        return self._shard_wrap("sdot_agg_hashed_table", run,
                                P(SEGMENT_AXIS, None), P(SEGMENT_AXIS),
                                gather_only=("__stats__",))

    def _plan_hash_topk_exchange(self, q, limit, having, agg_plans):
        """Gate for the multi-chip candidate-exchange ordered limit (see
        _build_hash_topk_exchange_program). min/max metrics are EXACT under
        the exchange; sum/count metrics carry Druid's topN union skew, so
        they engage only for TopNQuerySpec (whose contract is approximate)
        — exact GroupBy keeps the full-table key-wise merge."""
        if having is not None or limit is None or limit.limit is None:
            return None
        if not limit.columns:
            return None
        oc = limit.columns[0]
        plan = next((p for p in agg_plans if p.spec.name == oc.name), None)
        if plan is None:
            return None
        if plan.kind not in ("min", "max") \
                and not isinstance(q, S.TopNQuerySpec):
            return None
        return (oc.name, _topk_slack(limit), bool(oc.ascending))

    def _build_hash_topk_exchange_program(self, agg_plans, routes, metric,
                                          ascending, k_cand, k_sel, T):
        """Multi-chip hashed ordered-limit WITHOUT shipping the tables:
        each chip nominates its local top-``k_cand`` keys, the candidate
        lists all_gather over ICI, every chip probes its OWN table for
        every candidate, and the per-chip metric contributions combine
        with psum/pmin/pmax into EXACT global scores. The global
        top-``k_sel`` candidates' rows then travel per chip (a key a chip
        doesn't hold contributes an EMPTY row the host merge drops).

        Exact for min/max metrics (a global top-k key's global extremum
        is attained on some chip, where it ranks locally at least as high
        — the candidate union must contain it, given slack for ties).
        For sum metrics the union can miss a key that is mediocre on
        every chip yet large in total — Druid's topN accepts exactly this
        skew, and values here are still exact for every returned key
        (never under-counted, unlike Druid's merge)."""
        pack, unpack = self._hash_packers(agg_plans, routes, k_sel, False)
        r = routes[metric]

        def run(table):
            table = dict(table)
            table.pop("__stats__", None)
            tkhi = table["__tkhi__"]
            tklo = table["__tklo__"]
            occ = tkhi != H.EMPTY
            local_sc = _topk_score(r, table, T, ascending, occ)
            _, lidx = jax.lax.top_k(local_sc, k_cand)
            cand_hi = jnp.where(occ[lidx], tkhi[lidx], H.EMPTY)
            cand_lo = jnp.where(occ[lidx], tklo[lidx], H.EMPTY)
            cand_hi = jax.lax.all_gather(cand_hi, SEGMENT_AXIS,
                                         tiled=True)
            cand_lo = jax.lax.all_gather(cand_lo, SEGMENT_AXIS,
                                         tiled=True)
            C = cand_hi.shape[0]
            slot, found = H.probe_slots(tkhi, tklo, cand_hi, cand_lo)
            # exact global metric per candidate from per-chip
            # contributions (identity where this chip lacks the key)
            mvals = {}
            for oname, _, _ in r.outputs(1):
                flat = table[oname].reshape(-1)
                width = flat.shape[0] // T
                if width == 1:
                    mvals[oname] = flat[slot]
                else:
                    mvals[oname] = flat.reshape(T, width)[slot] \
                        .reshape(-1)
            v = G.route_score(r, mvals, C)
            if r.kind == "min":
                # +/-inf identity: strictly above every value AND every
                # NULL sentinel (f64's sentinel IS inf), so absent chips
                # can never mask a NULL-metric group's nulls-last rank
                v = jnp.where(found, v, jnp.asarray(jnp.inf, v.dtype))
                v = jax.lax.pmin(v, SEGMENT_AXIS)
            elif r.kind == "max":
                v = jnp.where(found, v, jnp.asarray(-jnp.inf, v.dtype))
                v = jax.lax.pmax(v, SEGMENT_AXIS)
            else:
                v = jnp.where(found, v, jnp.zeros_like(v))
                v = jax.lax.psum(v, SEGMENT_AXIS)
            sc = -v if ascending else v
            big = jnp.finfo(sc.dtype).max
            if r.kind in ("min", "max"):
                # NULL group = every chip HOLDING the key has the
                # sentinel, detected on the RAW per-chip values BEFORE
                # the float cast (a legitimate i32/i64 extremum within
                # one f32 ulp of the sentinel must not be misclassified
                # as NULL — ADVICE r2), combined across chips
                local_null = G.route_null_mask(r, mvals)
                has_real = jax.lax.psum(
                    (found & jnp.logical_not(local_null))
                    .astype(jnp.int32), SEGMENT_AXIS) > 0
                sc = jnp.where(has_real, sc, jnp.asarray(-big, sc.dtype))
            # duplicates (one key nominated by several chips) keep only
            # their first occurrence; padding/absent keys rank last
            order = jnp.lexsort((cand_lo, cand_hi))
            sh = cand_hi[order]
            sl = cand_lo[order]
            dup_sorted = jnp.concatenate(
                [jnp.zeros((1,), bool),
                 (sh[1:] == sh[:-1]) & (sl[1:] == sl[:-1])])
            dup = jnp.zeros_like(dup_sorted).at[order].set(dup_sorted)
            exists = jax.lax.psum(found.astype(jnp.int32),
                                  SEGMENT_AXIS) > 0
            sc = jnp.where(dup | ~exists | (cand_hi == H.EMPTY),
                           jnp.asarray(-jnp.inf, sc.dtype), sc)
            _, cidx = jax.lax.top_k(sc, k_sel)
            sel_slot = slot[cidx]
            sel_found = found[cidx]
            out = {}
            for name, arr in table.items():
                flat = arr.reshape(-1)
                width = flat.shape[0] // T
                if width == 1:
                    out[name] = flat[sel_slot]
                else:
                    out[name] = flat.reshape(T, width)[sel_slot] \
                        .reshape(-1)
            # a chip without the key contributes an EMPTY row (dropped by
            # the host occupancy filter), so absent values never pollute
            # the key-wise merge
            out["__tkhi__"] = jnp.where(sel_found, cand_hi[cidx], H.EMPTY)
            out["__tklo__"] = jnp.where(sel_found, cand_lo[cidx], H.EMPTY)
            return pack(out)

        in_specs = {"__tkhi__": P(SEGMENT_AXIS),
                    "__tklo__": P(SEGMENT_AXIS)}
        for p in agg_plans:
            for oname, _, _ in routes[p.spec.name].outputs(1):
                in_specs[oname] = P(SEGMENT_AXIS)
        out_spec = P(SEGMENT_AXIS)
        if MH.is_multihost():
            # per-chip candidate rows replicate in-mesh so every process
            # fetches the same O(k_sel) buffer — the tables never move
            inner_run = run

            def run(table):   # noqa: F811 — multihost wrapper
                return jax.tree.map(
                    lambda y: jax.lax.all_gather(y, SEGMENT_AXIS,
                                                 tiled=True),
                    inner_run(table))
            out_spec = P()
        smfn = shard_map(run, mesh=self.mesh, in_specs=(in_specs,),
                             out_specs=out_spec, check_vma=False)
        return named_jit("sdot_hashed_topk_exchange", smfn), unpack

    def _build_hash_gather_program(self, agg_plans, routes, k_gather, T,
                                   sharded, having_dev=None):
        """Compaction dispatch 2 of 2: gather the ``k_gather`` occupied
        slots from the resident table (per chip) and pack them into one
        transfer buffer — transfer scales with the ACTUAL group count, not
        the table size (a conservatively-sized table costs HBM, not
        wire). With ``having_dev`` the slots that pass the HAVING come
        first: of 1.5 M groups the few that pass travel (the host's
        epilogue applies the HAVING again, so a slot too many is dropped
        there)."""
        pack, unpack = self._hash_packers(agg_plans, routes, k_gather,
                                          False)

        def run(table):
            keep = _hash_having_mask(having_dev, table, routes) \
                if having_dev \
                else table["__tkhi__"] != H.EMPTY
            _, idx = jax.lax.top_k(keep.astype(jnp.float32), k_gather)
            return pack(_gather_rows(table, idx, T))

        if not sharded:
            return named_jit("sdot_hashed_gather", run), unpack
        return self._shard_wrap("sdot_hashed_gather", run, P(SEGMENT_AXIS),
                                P(SEGMENT_AXIS)), unpack

    def _run_waves(self, q, ds, names, seg_idx, s_pad, sharded, prog_fn,
                   unpack, routes, n_out, sketch_plans, t0, lits,
                   budget=None):
        """The dense tier's consumer of the wave pipeline: each wave's
        [n_out] finals merge on the host. Returns (finals, the most
        survivors a wave (and, sharded, a shard) counted under the
        compaction ``budget`` — 0 without one —, the last wave's unpacked
        outputs); a wave over its budget stops the scan, which the
        caller re-runs. ≈ the reference's cost-model "waves" of
        segments-per-query bounding per-historical work
        (DruidQueryCostModel.scala:309-314,444)."""
        wave_segs = [seg_idx[i: i + s_pad]
                     for i in range(0, len(seg_idx), s_pad)]
        finals, n_live, sk_words = None, 0, 0
        for out in self._waves(q, t0, ds, names, wave_segs, s_pad, sharded,
                               lits, prog_fn, unpack):
            if sketch_plans:
                # (a wave's groups travel again with every wave)
                sk_words += _sketch_words(out, sketch_plans)
                self.last_stats.update({
                    "sketch_fetch_bytes": 4 * sk_words,
                    "sketch_groups": int(n_out)})
            if budget:
                n_live = max(n_live, int(
                    np.asarray(out.pop("__live__")).reshape(-1)[0]))
                if n_live > budget:
                    return None, n_live, out
            f = _finals_from_out(out, routes, n_out, sketch_plans)
            finals = f if finals is None \
                else _merge_wave_finals(finals, f, routes, sketch_plans)
        return finals, n_live, out

    def _plan_agg(self, ds, seg_idx, dimensions, aggregations, granularity,
                  filter_spec, intervals):
        """Shared planning for agg queries (used by both the execution path
        and build_core). Raises EngineFallback on unsupported/oversized.
        Returns (dim_plans incl. granularity, agg_plans, min_day, max_day,
        n_keys, array names)."""
        gran_kind = granularity.kind if granularity else "all"
        if len(seg_idx) == 0 or ds.num_rows == 0:
            raise EngineFallback("no segments match the query intervals")
        mins, maxs = ds.segment_time_bounds()
        min_day = int(mins[seg_idx].min() // T.MILLIS_PER_DAY)
        max_day = int(maxs[seg_idx].max() // T.MILLIS_PER_DAY)
        tz = self.config.get(TZ_ID)
        dim_plans = [plan_dimension(d, ds, min_day, max_day, tz)
                     for d in dimensions]
        if gran_kind != "all":
            dim_plans = [plan_granularity_dim(granularity, ds, min_day,
                                              max_day, tz)] + dim_plans
        agg_plans = [plan_aggregation(a, ds) for a in aggregations]
        n_keys = 1
        for p in dim_plans:
            n_keys *= p.card
        # no cap here: callers route n_keys above the dense limit to the
        # hashed path (build_core enforces its own dense-only cap)
        needed = set()
        for p in dim_plans:
            needed |= set(p.source_cols)
        for p in agg_plans:
            needed |= set(p.source_cols)
        needed |= F.columns_of_filter(filter_spec)
        time_in_play = ds.time is not None and (
            intervals is not None or gran_kind != "all"
            or ds.time.name in needed)
        if time_in_play:
            needed.add(ds.time.name)
        names = array_names(ds, sorted(needed), time_in_play)
        routes = self._plan_routes(agg_plans, n_keys, ds)
        return dim_plans, agg_plans, min_day, max_day, n_keys, names, routes

    def _plan_routes(self, agg_plans, n_keys, ds):
        """Static numeric routes for the dense (non-HLL) aggregations plus
        the '__rows__' group-occupancy count."""
        metas = [G.AggInput(p.spec.name, p.kind, is_int=p.is_int,
                            maxabs=p.maxabs)
                 for p in agg_plans if p.kind not in ("hll", "theta", "kll")]
        metas.append(G.AggInput("__rows__", "count", is_int=True, maxabs=1.0))
        return G.plan_routes(
            metas, n_keys, self.config.get(GROUPBY_MATMUL_MAX_KEYS),
            pallas_max=self.config.get(GROUPBY_PALLAS_MAX_KEYS),
            n_rows=int(ds.padded_rows) * int(ds.num_segments))

    def build_core(self, q: S.QuerySpec):
        """Build the *unjitted* scan-aggregate program for an agg query plus
        its input arrays — the compile-check surface (flagship forward step).
        Returns (fn, arrays) with fn pure and jittable."""
        if isinstance(q, S.TimeseriesQuerySpec):
            dims, aggs, gran = [], q.aggregations, q.granularity
        elif isinstance(q, S.GroupByQuerySpec):
            dims, aggs, gran = list(q.dimensions), q.aggregations, \
                q.granularity
        else:
            raise EngineFallback("core build supports groupby/timeseries")
        ds = self.store.get(q.datasource)
        seg_idx = ds.prune_segments(q.intervals, q.filter)
        dim_plans, agg_plans, min_day, max_day, n_keys, names, routes = \
            self._plan_agg(ds, seg_idx, dims, aggs, gran, q.filter,
                           q.intervals)
        if n_keys > self.config.get(GROUPBY_DENSE_MAX_KEYS):
            raise EngineFallback(
                f"core build is dense-only (key cardinality {n_keys})")
        lits, days = self._plan_literals(q, ds, dim_plans, min_day, max_day)
        n_dev = mesh_size(self.mesh)
        s_pad = _pad_segments(len(seg_idx), n_dev)
        arrays = {k: _build_array_checked(ds, k, seg_idx, s_pad)
                  for k in names}
        if lits.count:
            arrays[L.LITERALS_KEY] = lits.pack()
        fn = self._make_core(ds, dim_plans, agg_plans, q.filter, q.intervals,
                             days, n_keys, routes,
                             hll_costs=self._hll_costs(agg_plans), lits=lits)
        return fn, arrays

    def _make_core(self, ds, dim_plans, agg_plans, filter_spec,
                   intervals, days, n_keys, routes,
                   compact=None, hll_costs=None, notes=None, *, lits):
        """``days``: the selected segments' (min_day, max_day), or None
        where the signature does not carry them — then nothing traced
        may read them. ``lits``: the building statement's literal plan;
        the filters read their literals from the operand bound under
        ``L.LITERALS_KEY``. ``compact``: the program's ``Compaction`` —
        late materialization between the cheap filter and everything
        after it; its survivor count surfaces as '__live__' and the host
        runs a statement whose budget it exceeds again. ``hll_costs``:
        the unit costs an HLL aggregation's registers choose their dense
        form under (``_hll_costs``; the sparse form is the hashed
        tier's, ``_hash_core``).
        ``notes``: a dict the trace fills with what the statement record
        says of the program's sketch epilogue (``hll_form``,
        ``hll_slots``)."""
        min_day, max_day = days or (None, None)
        matmul_max = self.config.get(GROUPBY_MATMUL_MAX_KEYS)
        log2m = self.config.get(HLL_LOG2M)
        kll_lanes = self.config.get(QUANTILE_LANES)
        hll_plans = [p for p in agg_plans if p.kind == "hll"]
        theta_plans = [p for p in agg_plans if p.kind == "theta"]
        kll_plans = [p for p in agg_plans if p.kind == "kll"]
        dense_plans = [p for p in agg_plans
                       if p.kind not in ("hll", "theta", "kll")]

        cheap_f, exp_f = (_compaction_filters(filter_spec)
                          if compact else (filter_spec, None))
        fuse_cse = bool(self.config.get(SHAREDSCAN_FUSION_ENABLED))

        def core(arrays):
            operands = _operands(lits, arrays)
            ctx = ScanContext(ds, arrays, min_day, max_day,
                              tz=self.config.get(TZ_ID), operands=operands)
            # trace-time predicate CSE: one query's tree can repeat
            # sub-predicates (OR-of-bounds over one column, a selector
            # shared by every filtered aggregation) — memoized lowering
            # emits each distinct sub-mask once, bit-identically
            cse = FU.CSECache(ctx) if fuse_cse else None
            base = _survivor_mask(ctx, cheap_f, intervals, cse)
            if not compact:
                return tail(ctx, base, cse)
            out, n_live = self._compacted(ctx, base, compact, tail, exp_f,
                                          fuse_cse)
            out["__live__"] = n_live.reshape(1)
            return out

        def tail(ctx, base, cse):
            if dim_plans:
                codes = [p.build(ctx) for p in dim_plans]
                key, _ = G.fuse_keys(codes, [p.card for p in dim_plans])
            else:
                key = jnp.zeros_like(base, dtype=jnp.int32)
            inputs = []
            for p in dense_plans:
                inputs.append(G.AggInput(p.spec.name, p.kind,
                                         p.build_values(ctx),
                                         p.build_mask(ctx, cse=cse),
                                         is_int=p.is_int, maxabs=p.maxabs))
            inputs.append(G.AggInput("__rows__", "count", is_int=True,
                                     maxabs=1.0))
            out = G.dense_groupby(key, base, n_keys, inputs, routes,
                                  matmul_max)
            for p in hll_plans:
                vals = p.build_values(ctx)
                am = p.build_mask(ctx, cse=cse)
                m = base if am is None else (base & am)
                out[p.spec.name] = HLL.hll_registers(
                    key, m, vals, n_keys, log2m, hll_costs)
            if hll_plans and notes is not None:
                notes.update(
                    hll_form=HLL.register_form(key.size, n_keys, log2m,
                                               hll_costs),
                    hll_slots=(n_keys + 1) << log2m)
            for p in theta_plans:
                vals = p.build_values(ctx)
                am = p.build_mask(ctx, cse=cse)
                m = base if am is None else (base & am)
                out[p.spec.name] = TH.theta_registers(key, m, vals, n_keys)
            for p in kll_plans:
                vals = p.build_values(ctx)
                am = p.build_mask(ctx, cse=cse)
                m = base if am is None else (base & am)
                # the time column joins the content salt so duplicate
                # values in distinct rows keep distinct survivor draws
                tcol = ctx.col(ds.time.name) if ds.time is not None else None
                out[p.spec.name] = KLL.kll_registers(
                    key, m, vals, tcol, n_keys, kll_lanes)
            return out

        return core

    def _build_agg_program(self, ds, dim_plans, agg_plans, filter_spec,
                           intervals, days, n_keys, sharded,
                           routes, topk=None, late=None, hll_costs=None,
                           *, lits):
        """Returns (jit_fn, unpack, compact, notes): ``compact`` is the
        program's ``Compaction`` under a ``late`` budget (``_late``; the
        statement record reads the form it ran in from there), else None;
        ``notes`` what the first trace says of its sketch epilogue
        (``_make_core``).

        The program packs outputs into TWO flat device buffers so the host
        pays at most two device->host transfers (each buffer is its own
        sync): one for collective-merged outputs
        (limbs/min/max/HLL — replicated across chips), one for per-chip
        ff/lanes partial pairs (sharded along the segment axis; combined
        exactly in f64 on host, ≈ the reference's historical-mode
        Spark-side final aggregate). Packing is dtype-faithful: on f32
        backends floats travel bitcast inside an i32 buffer, never rounded.

        With ``topk=(metric, k_sel, ascending)`` a device top-k epilogue
        runs after the merge: candidate keys are selected by f32 score
        (``ops.groupby.route_score``), every output is gathered at those
        indices, and only ``[k_sel]``-sized buffers (plus the index map
        ``__topk_idx__``) travel to host — the TPU analog of Druid's topN
        engine answering from the data node instead of shipping the full
        groupBy result (reference rewrite gate:
        ``QuerySpecTransforms.scala`` topN + ``DruidQueryCostModel``
        topN threshold).
        """
        compact = Compaction(*late) if late else None
        notes = {}
        core = self._make_core(ds, dim_plans, agg_plans, filter_spec,
                               intervals, days, n_keys, routes,
                               compact=compact, hll_costs=hll_costs,
                               notes=notes, lits=lits)
        hll_plans = [p for p in agg_plans if p.kind == "hll"]
        theta_plans = [p for p in agg_plans if p.kind == "theta"]
        kll_plans = [p for p in agg_plans if p.kind == "kll"]
        pack, unpack = self._agg_meta_packers(
            agg_plans, routes, topk[1] if topk else n_keys,
            with_idx=bool(topk), with_score=bool(topk),
            with_live=bool(late))

        def topk_gather(out, axis_name=None):
            """Select k_sel candidate keys by score, gather every output."""
            metric, k_sel, ascending = topk
            rows_sc = G.route_score(routes["__rows__"], out, n_keys,
                                    axis_name)
            sc = _topk_score(routes[metric], out, n_keys, ascending,
                             rows_sc > 0.5, axis_name)
            vals, idx = jax.lax.top_k(sc, k_sel)
            idx = idx.astype(jnp.int32)
            g = _gather_rows(out, idx, n_keys)
            g["__topk_idx__"] = idx
            g["__topk_score__"] = vals
            return g

        if not sharded:
            def plain(arrays):
                out = core(arrays)
                if topk:
                    live = out.pop("__live__", None)
                    out = topk_gather(out)
                    if live is not None:
                        out["__live__"] = live
                return pack(out)

            fn = named_jit("sdot_agg_dense", plain)
        else:
            mesh = self.mesh

            sketch_kinds = {p.spec.name: "hll" for p in hll_plans}
            sketch_kinds.update(
                {p.spec.name: "theta" for p in theta_plans})
            sketch_kinds.update(
                {p.spec.name: "kll" for p in kll_plans})

            def sharded_core(arrays):
                out = core(arrays)
                live = out.pop("__live__", None)
                # ONE mergeable-partial layout for every sharded program
                # (solo cores here, the fused mesh tier in
                # parallel/meshexec.py): psum / pmin / pmax per route
                # algebra, sketch registers per AGG_CLOSURE.merge
                merged = G.merge_lane_partials(out, routes, sketch_kinds,
                                               SEGMENT_AXIS)
                if topk:
                    merged = topk_gather(merged, SEGMENT_AXIS)
                if live is not None:
                    # the budget is a shard's: any shard over it
                    # invalidates the run (those rows were dropped), so
                    # every chip's replicated buffer carries the largest
                    # shard's count
                    merged["__live__"] = jax.lax.pmax(live, SEGMENT_AXIS)
                return pack(merged)

            if MH.is_multihost():
                # the per-chip partials buffer (ff/lanes pairs, host-side
                # lane combine) must replicate so every process can fetch;
                # fully-merged programs emit a ZERO-length one (all_gather
                # rejects zero-size dims — leave it, it decodes to nothing)
                inner_core = sharded_core

                def sharded_core(arrays):
                    rep, per_chip = inner_core(arrays)
                    if per_chip.size:
                        per_chip = jax.lax.all_gather(
                            per_chip, SEGMENT_AXIS, tiled=True)
                    return rep, per_chip
                out_specs = (P(), P())
            else:
                out_specs = (P(), P(SEGMENT_AXIS))
            smfn = shard_map(sharded_core, mesh=mesh,
                                 in_specs=(P(SEGMENT_AXIS, None),),
                                 out_specs=out_specs,
                                 check_vma=False)
            fn = named_jit("sdot_agg_dense", smfn)

        return fn, unpack, compact, notes

    def _sig_base(self, ds):
        """What every program signature starts from: the store the
        program is traced over and the environment it is traced in —
        the config keys a build reads whatever the tier, the backend and
        its integer width. The statement's shape follows at each site."""
        return (ds.name, id(ds), ds.padded_rows,
                self.config.get(TZ_ID),
                self.config.get(GROUPBY_MATMUL_MAX_KEYS),
                self.config.get(HLL_LOG2M),
                self.config.get(QUANTILE_LANES),
                bool(self.config.get(ENCODE_ENABLED)),
                jax.default_backend(), bool(jax.config.jax_enable_x64),
                bool(self.config.get(SHAREDSCAN_FUSION_ENABLED)))

    def _hll_costs(self, agg_plans):
        """What a program with an HLL aggregation is built under and
        cached by: the backend's unit costs ``ops.hll.hll_registers``
        chooses its form with, beside the traced shapes
        (``ops.hll.register_form``). None without one."""
        if not any(p.kind == "hll" for p in agg_plans):
            return None
        from spark_druid_olap_tpu.utils import config as CF
        return HLL.RegisterCosts(
            C.unit_cost(self.config, CF.COST_SORT_ROW),
            C.unit_cost(self.config, CF.COST_GATHER_PROBE),
            C.unit_cost(self.config, CF.COST_SCATTER_UPDATE))

    def _hll_form(self, agg_plans, ds, n_seg, n_keys, one_table):
        """The form a statement of ``n_keys`` groups over ``n_seg``
        segments should take its HLL registers in
        (``ops.hll.register_form``), None without an HLL aggregation:
        what the medium-K reroute asks, the hashed tier being where the
        sparse form runs. ``one_table``: every row of a group would
        reach ONE table there (``HashLayout.one_table``) — what the
        sparse form needs, its estimates being final; a historical's
        partial registers never are. Priced on the scanned rows,
        whatever late materialization leaves of them."""
        costs = self._hll_costs(agg_plans)
        if costs is None:
            return None
        return HLL.register_form(
            int(n_seg) * int(ds.padded_rows), n_keys,
            self.config.get(HLL_LOG2M), costs,
            sparse_ok=one_table and not self.partial_sketches)

    def _cached_program(self, sig, build):
        """Program-cache fetch with PER-SIGNATURE compile ownership: warm
        queries never touch a lock, and two different programs compile
        CONCURRENTLY (XLA releases the GIL during compilation, so a
        threaded prewarm overlaps what a single lock would serialize).
        A second thread wanting the SAME signature waits on the owner's
        event instead of compiling twice."""
        prog = self._programs.get(sig)
        built = False
        while prog is None:
            with self._compile_lock:
                prog = self._programs.get(sig)
                if prog is not None:
                    break
                ev = self._compiling.get(sig)
                owner = ev is None
                if owner:
                    ev = self._compiling[sig] = \
                        __import__("threading").Event()
            if owner:
                try:
                    with PH.phase("compile"):
                        prog = build()
                    with self._compile_lock:
                        self._programs[sig] = prog
                    built = True
                finally:
                    with self._compile_lock:
                        self._compiling.pop(sig, None)
                    ev.set()
                break
            ev.wait()
            prog = self._programs.get(sig)
            # owner failed (exception): loop claims ownership and retries
        # the statement's record names its (first, scan) program by the
        # signature's digest and says whether this statement built any
        st = self.last_stats.get("program")
        if st is None:
            st = self.last_stats["program"] = {
                "sig": hashlib.blake2s(repr(sig).encode(),
                                       digest_size=4).hexdigest(),
                "operands": 0, "built": False}
        st["built"] = st["built"] or built
        return prog

    def _plan_device_having(self, having, routes, agg_plans, n_keys,
                            topk, n_waves):
        """(agg_name, op, int_literal) when HAVING is a single comparison
        of an EXACT-on-device aggregate against an integer literal and the
        key space is big enough that shipping only passing groups pays
        (two dispatches: finals + having mask + count, then gather).
        Exactness: limb sums compare lexicographically at any magnitude;
        i32/i64/f64 min/max compare in their own domain. The host epilogue
        re-applies HAVING over the exact finals, so this is a transfer
        filter, never the source of truth."""
        if having is None or topk is not None or n_waves != 1:
            return None
        if n_keys < self.config.get(HAVING_DEVICE_MIN_KEYS):
            return None
        e = having.expr
        if not isinstance(e, E.Comparison):
            return None
        for a, b, op in ((e.left, e.right, e.op),
                         (e.right, e.left, E.FLIP_CMP.get(e.op, e.op))):
            if isinstance(a, E.Column) and isinstance(b, E.Literal) \
                    and isinstance(b.value, (int, np.integer)) \
                    and not isinstance(b.value, bool):
                r = routes.get(a.name)
                if r is None:
                    continue
                lit = int(b.value)
                # the literal must fit the route's comparable domain:
                # out-of-range casts would wrap/raise on device
                if r.tag == "i32" and not -2**31 <= lit < 2**31:
                    continue
                if r.tag in ("i64", "limbs") \
                        and not -2**62 <= lit < 2**62:
                    continue
                if r.tag in ("limbs", "i32", "i64", "f64"):
                    return (a.name, "!=" if op == "<>" else op, lit)
        return None

    def _having_mask(self, having_dev, out, routes, n_keys, axis_name):
        """Device bool [n_keys]: group occupied AND HAVING passes (exact;
        see _plan_device_having)."""
        name, op, lit = having_dev
        r = routes[name]
        rows_sc = G.route_score(routes["__rows__"], out, n_keys, axis_name)
        occ = rows_sc > 0.5
        if r.tag == "limbs":
            limbs = out[name + ".limbs"].reshape(n_keys, G.N_LIMBS)
            m = G.limbs_compare(limbs, lit, op)
        else:
            m = _having_passes(r, out, op, lit)
        return m & occ

    def _build_agg_table_program(self, ds, dim_plans, agg_plans,
                                 filter_spec, intervals, days,
                                 n_keys, sharded, routes, having_dev,
                                 lits, hll_costs=None):
        """HAVING-compaction dispatch 1 of 2: scan + merge, leave the
        finals DEVICE-RESIDENT, compute the exact having mask and transfer
        only its count. ≈ Druid evaluating HavingSpec on the data node
        instead of shipping every group to the broker."""
        core = self._make_core(ds, dim_plans, agg_plans, filter_spec,
                               intervals, days, n_keys, routes,
                               hll_costs=hll_costs, lits=lits)
        hll_plans = [p for p in agg_plans if p.kind == "hll"]
        theta_plans = [p for p in agg_plans if p.kind == "theta"]
        kll_plans = [p for p in agg_plans if p.kind == "kll"]

        def finish(out, axis_name=None):
            out = dict(out)
            out["__hmask__"] = self._having_mask(having_dev, out, routes,
                                                 n_keys, axis_name)
            out["__stats__"] = jnp.sum(out["__hmask__"]) \
                .astype(jnp.int32).reshape(1)
            return out

        if not sharded:
            return named_jit("sdot_agg_table",
                             lambda arrays: finish(core(arrays)))
        mesh = self.mesh

        sketch_kinds = {p.spec.name: "hll" for p in hll_plans}
        sketch_kinds.update({p.spec.name: "theta" for p in theta_plans})
        sketch_kinds.update({p.spec.name: "kll" for p in kll_plans})

        def sharded_core(arrays):
            out = core(arrays)
            # shared mergeable-partial layout (ops/groupby.py) — same
            # register algebra as the fused mesh tier
            merged = G.merge_lane_partials(out, routes, sketch_kinds,
                                           SEGMENT_AXIS)
            return finish(merged, SEGMENT_AXIS)

        out_specs = self._agg_out_specs(agg_plans, routes)
        smfn = shard_map(sharded_core, mesh=mesh,
                             in_specs=(P(SEGMENT_AXIS, None),),
                             out_specs=out_specs, check_vma=False)
        return named_jit("sdot_agg_table", smfn)

    def _agg_out_specs(self, agg_plans, routes, with_stats=True):
        """Per-leaf shard specs of the post-merge finals dict: merged
        routes and sketches are replicated, ff/lanes partial pairs stay
        per-chip along the segment axis."""
        specs = {}
        for p in agg_plans:
            if p.kind in ("hll", "theta", "kll"):
                specs[p.spec.name] = P()
                continue
            r = routes[p.spec.name]
            for oname, _, _ in r.outputs(1):
                specs[oname] = P() if r.merged else P(SEGMENT_AXIS)
        r = routes["__rows__"]
        for oname, _, _ in r.outputs(1):
            specs[oname] = P() if r.merged else P(SEGMENT_AXIS)
        if with_stats:
            specs["__hmask__"] = P()
            specs["__stats__"] = P()
        return specs

    def _build_agg_gather_program(self, agg_plans, routes, k, n_keys,
                                  sharded, full=False):
        """HAVING-compaction dispatch 2 of 2: gather the passing groups
        (device mask from dispatch 1) and pack into the standard
        two-buffer transfer, sized [k] instead of [n_keys].

        ``full``: when the mask passes MOST groups, top_k compaction
        buys (n_keys - k) rows of transfer at the price of a [n_keys]
        sort — a measured 3.5s outlier at 1.5M keys on the CPU backend
        (VERDICT r4 weak 3). Instead the whole table travels in key
        order (no index map — decode's identity path applies) and the
        failing groups' occupancy counts are zeroed so the standard
        rows>0 decode drops them — no sort, same answer."""
        pack, unpack = self._agg_meta_packers(agg_plans, routes, k,
                                              with_idx=not full)

        def gather(table):
            table = dict(table)
            table.pop("__stats__", None)
            mask = table.pop("__hmask__")
            if full:
                idx = jnp.arange(n_keys, dtype=jnp.int32)
                g = _gather_rows(table, idx, n_keys)
                for oname, _, _ in routes["__rows__"].outputs(1):
                    flat = g[oname]
                    width = flat.shape[0] // n_keys
                    m = mask.astype(flat.dtype)
                    g[oname] = (flat.reshape(n_keys, width)
                                * m[:, None]).reshape(-1)
            else:
                _, idx = jax.lax.top_k(mask.astype(jnp.float32), k)
                idx = idx.astype(jnp.int32)
                g = _gather_rows(table, idx, n_keys)
                g["__topk_idx__"] = idx
            return pack(g)

        if not sharded:
            return named_jit("sdot_gather", gather), unpack
        # '__stats__' was already popped host-side after dispatch 1
        in_specs = self._agg_out_specs(agg_plans, routes, with_stats=False)
        in_specs["__hmask__"] = P()
        smfn = shard_map(gather, mesh=self.mesh, in_specs=(in_specs,),
                             out_specs=(P(), P(SEGMENT_AXIS)),
                             check_vma=False)
        return named_jit("sdot_gather", smfn), unpack

    def _agg_meta_packers(self, agg_plans, routes, n_out, with_idx,
                          with_score=False, with_live=False):
        """(pack, unpack) for the dense path's TWO-buffer transfer:
        collective-merged outputs in one replicated buffer, per-chip
        ff/lanes partial pairs in one segment-sharded buffer. ``n_out``
        is the per-key output length (n_keys, or the gather size when a
        top-k/having epilogue selected rows; then ``with_idx`` appends
        the '__topk_idx__' key map)."""
        hll_plans = [p for p in agg_plans if p.kind == "hll"]
        theta_plans = [p for p in agg_plans if p.kind == "theta"]
        kll_plans = [p for p in agg_plans if p.kind == "kll"]
        dense_plans = [p for p in agg_plans
                       if p.kind not in ("hll", "theta", "kll")]
        m = 1 << self.config.get(HLL_LOG2M)
        kll_w = KLL.width(self.config.get(QUANTILE_LANES))
        x64 = G._x64()
        # (out_name, flat_len, dtype_str, merged)
        meta = []
        for p in dense_plans:
            r = routes[p.spec.name]
            for oname, size, dt in r.outputs(n_out):
                meta.append((oname, size, dt, r.merged))
        r = routes["__rows__"]
        for oname, size, dt in r.outputs(n_out):
            meta.append((oname, size, dt, r.merged))
        meta += [(p.spec.name, n_out * m, "i32", True) for p in hll_plans]
        meta += [(p.spec.name, n_out * TH.K_LANES,
                  "f64" if x64 else "f32", True) for p in theta_plans]
        meta += [(p.spec.name, n_out * kll_w, "i32", True)
                 for p in kll_plans]
        if with_idx:
            meta.append(("__topk_idx__", n_out, "i32", True))
        if with_score:
            meta.append(("__topk_score__", n_out, "f64" if x64 else "f32",
                         True))
        if with_live:
            meta.append(("__live__", 1, "i32", True))
        merged_meta = [t for t in meta if t[3]]
        perchip_meta = [t for t in meta if not t[3]]
        buf_dtype = jnp.int64 if x64 else jnp.int32
        perchip_len = sum(t[1] for t in perchip_meta)

        def pack_group(out, metas):
            parts = [_encode_buf(out[oname], dt, x64)
                     for oname, _, dt, _ in metas]
            if not parts:
                return jnp.zeros((0,), buf_dtype)
            return jnp.concatenate(parts)

        def pack(out):
            return pack_group(out, merged_meta), \
                pack_group(out, perchip_meta)

        def unpack(bufs) -> Dict[str, np.ndarray]:
            # both copies are under way since ``_launch`` enqueued them
            mflat = np.asarray(bufs[0])
            uflat = np.asarray(bufs[1])
            out = {}
            off = 0
            for oname, size, dt, _ in merged_meta:
                chunk = _decode_buf(mflat[off: off + size], dt, x64)
                off += size
                if any(oname == p.spec.name for p in hll_plans):
                    chunk = np.rint(chunk).astype(np.int32) \
                        .reshape(n_out, m)
                elif any(oname == p.spec.name for p in theta_plans):
                    chunk = np.asarray(chunk, np.float32) \
                        .reshape(n_out, TH.K_LANES)
                elif any(oname == p.spec.name for p in kll_plans):
                    chunk = np.rint(chunk).astype(np.int32) \
                        .reshape(n_out, kll_w)
                out[oname] = chunk
            if perchip_len:
                chips = uflat.reshape(-1, perchip_len)
                off = 0
                for oname, size, dt, _ in perchip_meta:
                    # [n_chips, size] -> flat chip-major (combine_route
                    # reshapes back)
                    out[oname] = _decode_buf(
                        np.ascontiguousarray(chips[:, off: off + size])
                        .reshape(-1), dt, x64)
                    off += size
            return out

        return pack, unpack

    # -- select path ----------------------------------------------------------
    def _run_select(self, q: S.SelectQuerySpec) -> QueryResult:
        ds = self.store.get(q.datasource)
        if ds.is_partial:
            # partial store: per-host mask + survivor/page exchange —
            # O(survivors + page) transfer, never the columns
            return self._run_select_multihost(q, ds)
        cols = list(q.columns) or ds.column_names()
        seg_idx = ds.prune_segments(q.intervals, q.filter)
        if len(seg_idx) == 0:
            return QueryResult.empty(cols)
        # filter on device when the scan is big enough to beat the
        # dispatch floor (compiled mask program, bit-packed transfer);
        # page materialization stays host-side — select is IO-bound
        # (≈ Druid Select paged through the broker)
        mask = None
        if (q.filter is not None or q.intervals is not None) \
                and ds.num_rows >= self.config.get(SELECT_DEVICE_MIN_ROWS):
            mask = self._device_mask(ds, q.filter, q.intervals, seg_idx)
        if mask is None:
            self.last_stats["select_filter"] = "host"
            mask = self._host_mask(ds, q.filter, q.intervals)
        idx = np.nonzero(mask)[0]
        if q.descending:
            idx = idx[::-1]
        page = idx[q.page_offset: q.page_offset + q.page_size]
        data = {}
        for c in cols:
            data[c] = _host_column_values(ds, c, page)
        self.last_stats.update({"datasource": ds.name,
                                "rows": int(len(page)),
                                "rows_scanned": int(ds.num_rows)})
        if self.last_stats.get("select_filter") != "host":
            # the device pass reads only the MASK's inputs (filter
            # columns); the page gather is host-side — sizing from the
            # output columns would overstate the roofline by orders
            mask_cols = set(F.columns_of_filter(q.filter))
            if q.intervals and ds.time is not None:
                mask_cols.add(ds.time.name)
            if mask_cols:
                self.last_stats["bytes_scanned"] = \
                    int(C.bytes_per_segment(ds, sorted(mask_cols))) \
                    * int(len(seg_idx))
        return QueryResult(cols, data)

    def _run_select_multihost(self, q: S.SelectQuerySpec,
                              ds: Datasource) -> QueryResult:
        """Select paging on a multi-host partial store (VERDICT r4
        item 2): every process runs the same query (SPMD); each host
        evaluates the filter over ITS local rows, hosts exchange the
        surviving GLOBAL row ids (O(survivors)), the page slice is
        computed identically everywhere, and only the page's raw values
        travel — dimensions as dictionary codes, decoded against the
        replicated global dictionary. ≈ Druid Select paging through the
        broker across historicals (the reference's paged select,
        ``DruidQuerySpec.scala`` SelectSpec result contract)."""
        import dataclasses as _dc
        if not MH.is_multihost():
            # single-process partial store (test rig): no peers to
            # exchange with — a local-only answer would be silently wrong
            ds.require_complete("select scan")
        cols = list(q.columns) or ds.column_names()
        seg_idx = ds.prune_segments(q.intervals, q.filter)
        if len(seg_idx) == 0:
            # metadata-deterministic: every process bails together
            return QueryResult.empty(cols)
        mask_local = self._host_mask(ds, q.filter, q.intervals,
                                     local=True)
        self.last_stats["select_filter"] = "host-local"
        gsur = ds.local_to_global_rows()[np.nonzero(mask_local)[0]]
        all_ids = np.concatenate(MH.exchange_block(gsur))
        all_ids.sort()
        if q.descending:
            all_ids = all_ids[::-1]
        page = all_ids[q.page_offset: q.page_offset + q.page_size]
        owner = ds.owner_of_rows(page)
        mine = np.nonzero(owner == ds.host_id)[0].astype(np.int64)
        lidx = ds.global_to_local_rows(page[mine])
        n_page = len(page)
        pos_blocks = MH.exchange_block(mine)

        def assemble(local_vals):
            """Exchange each host's page rows; place at page positions."""
            blocks = MH.exchange_block(local_vals)
            out = np.zeros((n_page,) + local_vals.shape[1:],
                           local_vals.dtype)
            for pb, blk in zip(pos_blocks, blocks):
                out[pb] = blk
            return out

        # a page-sized COMPLETE datasource clone: raw storage arrays are
        # exchanged (numeric only), then the standard host decode runs
        # unchanged (_host_column_values semantics cannot diverge)
        dims, mets = {}, {}
        time = None
        for c in cols:
            if c in ds.dims:
                col = ds.dims[c]
                dims[c] = _dc.replace(
                    col, codes=assemble(col.codes[lidx]),
                    validity=(assemble(col.validity[lidx])
                              if col.validity is not None else None))
            elif c in ds.metrics:
                m = ds.metrics[c]
                mm = _dc.replace(
                    m, values=assemble(m.values[lidx]),
                    validity=(assemble(m.validity[lidx])
                              if m.validity is not None else None))
                mm._bounds_cache = (m.min, m.max)
                mets[c] = mm
            elif ds.time is not None and c == ds.time.name:
                time = _dc.replace(ds.time,
                                   days=assemble(ds.time.days[lidx]),
                                   ms_in_day=assemble(
                                       ds.time.ms_in_day[lidx]))
        page_ds = Datasource(name=ds.name, time=time, dims=dims,
                             metrics=mets,
                             segments=[Segment("page", 0, n_page, 0, 0)])
        data = {c: _host_column_values(page_ds, c, None) for c in cols}
        self.last_stats.update({"datasource": ds.name,
                                "rows": int(n_page),
                                "rows_scanned": int(ds.num_rows),
                                "n_transfer": int(len(all_ids) + n_page)})
        return QueryResult(cols, data)

    def _run_search(self, q: S.SearchQuerySpec) -> QueryResult:
        ds = self.store.get(q.datasource)
        # host-side dictionary-occurrence counting; on a partial store
        # each host counts ITS rows and the per-code counts are summed
        # across processes (O(cardinality) transfer, never the columns)
        partial = ds.is_partial
        if partial and not MH.is_multihost():
            ds.require_complete("search scan")
        mask = self._host_mask(ds, q.filter, q.intervals, local=partial)
        needle = q.query if q.case_sensitive else q.query.lower()
        dims_out, vals_out, counts_out = [], [], []
        for dname in q.dimensions:
            dim = ds.dims[dname]
            cand = [i for i, s in enumerate(dim.dictionary)
                    if needle in (s if q.case_sensitive else s.lower())]
            if not cand:
                continue
            codes = dim.codes
            eff = mask if mask is not None \
                else np.ones(len(codes), dtype=bool)
            if dim.validity is not None:
                # NULL rows are encoded at code 0; they are not occurrences
                # of dictionary[0]
                eff = eff & dim.validity
            sub = codes[eff]
            counts = np.bincount(sub, minlength=dim.cardinality)
            if partial:
                counts = np.sum(MH.exchange_block(
                    counts.astype(np.int64)), axis=0)
            for c in cand:
                if counts[c] > 0:
                    dims_out.append(dname)
                    vals_out.append(dim.dictionary[c])
                    counts_out.append(int(counts[c]))
        if q.limit is not None:
            dims_out = dims_out[: q.limit]
            vals_out = vals_out[: q.limit]
            counts_out = counts_out[: q.limit]
        self.last_stats.update({"datasource": ds.name,
                                "search_values": len(vals_out)})
        if q.value_output is not None:
            # rewritten from a group-by: project to its output shape
            return QueryResult(
                [q.value_output, q.count_output],
                {q.value_output: np.array(vals_out, dtype=object),
                 q.count_output: np.array(counts_out, dtype=np.int64)})
        return QueryResult(
            ["dimension", "value", "count"],
            {"dimension": np.array(dims_out, dtype=object),
             "value": np.array(vals_out, dtype=object),
             "count": np.array(counts_out, dtype=np.int64)})

    # -- helpers --------------------------------------------------------------
    def _device_mask(self, ds: Datasource, filter_spec, intervals,
                     seg_idx) -> Optional[np.ndarray]:
        """Evaluate the select filter on device: one compiled program
        lowers the filter + interval mask over the pruned stacked scan and
        returns a 32x bit-packed word array ([S, R/32] uint32) — the same
        compiled filter tier aggregations use (dictionary compares, spatial,
        regex-via-dictionary, compiled expressions), so select filters can
        never diverge from aggregate filters. Returns the global [num_rows]
        bool mask, or None when the filter doesn't lower (host fallback)."""
        mins, maxs = ds.segment_time_bounds()
        if len(seg_idx) == 0 or ds.time is None:
            min_day = max_day = 0
        else:
            min_day = int(mins[seg_idx].min() // T.MILLIS_PER_DAY)
            max_day = int(maxs[seg_idx].max() // T.MILLIS_PER_DAY)
        needed = F.columns_of_filter(filter_spec)
        time_in_play = ds.time is not None and (
            intervals is not None or ds.time.name in needed)
        if time_in_play:
            needed.add(ds.time.name)
        names = array_names(ds, sorted(needed), time_in_play)
        # pad like the single-device agg path so the bound arrays SHARE
        # the device cache entries aggregations already made resident
        s_pad = _pad_segments(len(seg_idx), 1)
        sig = ("selmask", ds.name, id(ds), repr(filter_spec),
               repr(intervals), s_pad, ds.padded_rows, min_day, max_day,
               tuple(names), self.config.get(TZ_ID),
               jax.default_backend())
        prog = self._programs.get(sig)
        if prog is None:
            R = ds.padded_rows

            def core(arrays):
                ctx = ScanContext(ds, arrays, min_day, max_day,
                                  tz=self.config.get(TZ_ID))
                base = ctx.row_valid()
                fm = F.lower_filter(filter_spec, ctx)
                if fm is not None:
                    base = base & fm
                im = F.interval_mask(intervals, ctx)
                if im is not None:
                    base = base & im
                bits = base.reshape(s_pad, R // 32, 32).astype(jnp.uint32)
                weights = jnp.left_shift(
                    jnp.uint32(1), jnp.arange(32, dtype=jnp.uint32))
                return (bits * weights[None, None, :]).sum(
                    axis=-1, dtype=jnp.uint32)

            with self._compile_lock:
                prog = self._programs.get(sig)
                if prog is None:
                    prog = named_jit("sdot_select", core)
                    self._programs[sig] = prog
        try:
            # cached device bindings: a repeated (dashboard/paging) select
            # re-runs the mask program against resident arrays instead of
            # re-uploading the filter columns every call
            arrays = self._bind_arrays(ds, names, seg_idx, s_pad, False)
            words = self._run_program(prog, arrays, np.asarray)
        except (EngineFallback, EC.Unsupported):
            return None
        shifts = np.arange(32, dtype=np.uint32)
        bits = ((words[:, :, None] >> shifts) & 1).astype(bool) \
            .reshape(s_pad, ds.padded_rows)
        mask = np.zeros(ds.num_rows, dtype=bool)
        for i, si in enumerate(seg_idx):
            s = ds.segments[int(si)]
            mask[s.start_row: s.end_row] = bits[i, : s.num_rows]
        self.last_stats["select_filter"] = "device"
        return mask

    def _host_mask(self, ds: Datasource, filter_spec, intervals,
                   local: bool = False):
        """Row mask evaluated host-side. ``local=True`` evaluates over
        THIS host's rows only (a partial store's local arrays) — the
        multi-host select/search paths merge per-host results instead of
        gathering columns."""
        n = ds.local_num_rows if local else ds.num_rows
        mask = np.ones(n, dtype=bool)
        if intervals is not None and ds.time is not None:
            ms = ds.time.millis if local \
                else ds.complete(columns=()).time.millis
            im = np.zeros(n, dtype=bool)
            for lo, hi in intervals:
                im |= (ms >= lo) & (ms < hi)
            mask &= im
        if filter_spec is not None:
            env = {}
            # SORTED: on a partial store each column gathers via a
            # cross-process collective — set iteration order differs
            # per process (hash randomization) and would deadlock
            for c in sorted(_filter_columns_all(filter_spec)):
                env[c] = _host_column_values(ds, c, None, local_ok=local)
            expr = filter_to_expr(filter_spec)
            mask &= host_eval.eval_pred3(expr, env)
        return mask

    def _should_shard(self, q, ds, seg_idx) -> bool:
        if ds.is_partial:
            # a partial store's data exists only across the pod: the
            # sharded path is the ONLY path (host/single-device would
            # need remote rows)
            if self.mesh is None or mesh_size(self.mesh) <= 1:
                raise RuntimeError(
                    f"partial datasource {ds.name!r} requires a multi-host "
                    f"mesh (engine has {mesh_size(self.mesh)} device(s))")
            self.last_stats["shard_decision"] = "partial-store"
            return True
        if self.mesh is None or mesh_size(self.mesh) <= 1:
            return False
        pref = q.context.prefer_sharded if hasattr(q, "context") else None
        if pref is not None:
            self.last_stats["shard_decision"] = "context"
            return bool(pref)
        try:
            est = C.estimate(self, q)
        except Exception:   # noqa: BLE001 — cost must never fail a query
            self.last_stats["shard_decision"] = "default"
            return len(seg_idx) >= 1
        self.last_stats["shard_decision"] = (
            f"cost:{'sharded' if est.recommend_sharded else 'single'}")
        self.last_stats["cost_single"] = est.single_cost
        self.last_stats["cost_sharded"] = est.sharded_cost
        return est.recommend_sharded

    def _bind_wave(self, ds, names, w, s_pad, sharding, multihost,
                   lits=None):
        """Uncached per-wave bind (wave mode exists because the scan
        exceeds the device budget). Multi-host: each process provides only
        the shards its devices own — the wave layout is host-blocked
        (multihost.layout_segments_waves), so a block's non-local segment
        ids never reach this process's builder."""
        with PH.phase("bind"):
            self._tick(1, len(names))
            if multihost:
                out = {}
                for k in names:
                    dt = array_dtype(ds, k)
                    if dt == np.int64 and not G._x64():
                        raise EngineFallback(
                            f"wide integer column {k!r} on a 32-bit backend")
                    out[k] = MH.put_sharded_blocks(
                        lambda ids, k=k: build_array_blocks(ds, k, ids),
                        w, ds.padded_rows, dt, sharding)
            else:
                out = {k: _device_put_retry(
                    _build_array_checked(ds, k, w, s_pad), sharding)
                    for k in names}
            return self._bind_literals(out, lits, sharding, multihost)

    def _bind_literals(self, out, lits, sharding, multihost):
        """``bind.operands``: the statement's literal operand, packed and
        put beside the columns. On one device the packed words go to the
        program call as they are and its launch uploads them (+0.12 ms of
        ``dispatch.launch`` on a v5e; a ``jax.device_put`` of their own
        costs 0.25 ms of host time and 0.53 ms until ready — PERF.md §6,
        PR 28). A mesh gets the row once per shard, placed like the
        columns, so their partition spec fits it too. A few dozen bytes;
        not one of the column uploads ``n_transfer`` counts."""
        if lits is not None and lits.count:
            with PH.phase("bind.operands"):
                words = lits.pack()
                if sharding is not None:
                    rows = np.tile(words, (mesh_size(self.mesh), 1))
                    words = jax.make_array_from_callback(
                        rows.shape, sharding, lambda idx: rows[idx]) \
                        if multihost else _device_put_retry(rows, sharding)
                out[L.LITERALS_KEY] = words
            self.last_stats["program"]["operands"] = lits.count
        return out

    def _bind_arrays(self, ds, names, seg_idx, s_pad, sharded, lits=None):
        """Fetch-or-build the device arrays a program binds. Cached per
        (datasource, array, segment selection, layout) so repeated dashboard
        queries never re-upload host data (≈ segments staying resident on
        Druid historicals between queries).

        Multi-host: ``seg_idx`` is the per-host block layout (global ids
        with -1 padding) and each process provides only the shards its
        devices own — ``jax.make_array_from_callback`` invokes the block
        builder per locally-addressable device, so no process ever
        materializes (or ships) another host's rows."""
        with PH.phase("bind"):
            out = self._bind_arrays_inner(ds, names, seg_idx, s_pad,
                                          sharded)
            return self._bind_literals(
                out, lits, NamedSharding(self.mesh, P(SEGMENT_AXIS, None))
                if sharded else None, sharded and MH.is_multihost())

    def _bind_arrays_inner(self, ds, names, seg_idx, s_pad, sharded):
        sharding = NamedSharding(self.mesh, P(SEGMENT_AXIS, None)) \
            if sharded else None
        multihost = sharded and MH.is_multihost()
        seg_sig = (len(seg_idx), hash(seg_idx.tobytes()))
        out = {}
        for k in names:
            key = (id(ds), k, s_pad, seg_sig, bool(sharded), multihost)
            dev = self._device_arrays.get(key)   # lock-free warm path
            if dev is None:
                with self._compile_lock:
                    dev = self._device_arrays.get(key)
                    if dev is None:
                        if multihost:
                            dt = array_dtype(ds, k)
                            if dt == np.int64 and not G._x64():
                                raise EngineFallback(
                                    f"wide integer column {k!r} on a "
                                    f"32-bit backend")
                            # account what THIS process holds (its own
                            # devices' shards), not the global array
                            nbytes = len(seg_idx) * ds.padded_rows \
                                * np.dtype(dt).itemsize \
                                // max(jax.process_count(), 1)
                            host = None
                        else:
                            host = _build_array_checked(ds, k, seg_idx,
                                                        s_pad)
                            nbytes = int(host.nbytes)
                        # bound device residency: distinct segment
                        # selections (paged selects, shifting intervals)
                        # would otherwise pin fresh copies until OOM.
                        # Evict BEFORE the upload so peak residency never
                        # exceeds cap + one array.
                        cap = int(self.config.get(DEVICE_CACHE_BYTES))
                        if self._device_bytes + nbytes > cap \
                                and self._device_arrays:
                            self._device_arrays.clear()
                            self._device_bytes = 0
                        self._tick(1)
                        if multihost:
                            dev = MH.put_sharded_blocks(
                                lambda ids, k=k: build_array_blocks(
                                    ds, k, ids),
                                seg_idx, ds.padded_rows, dt, sharding)
                        else:
                            dev = _device_put_retry(host, sharding)
                        self._device_arrays[key] = dev
                        self._device_bytes += nbytes
            out[k] = dev
        return out

    def _tier_prefetch(self, ds, names, wave_segs, i):
        """Enqueue wave ``i``'s cold-tier chunks on the prefetcher so
        they load behind the current wave's device compute. No-op on
        in-memory datasources or past the last wave."""
        pf = getattr(ds, "tier_prefetch", None)
        if pf is not None and i < len(wave_segs):
            pf(names, wave_segs[i])

    def clear_caches(self):
        # under the compile lock: the backend-lost recovery thread calls
        # this concurrently with query threads populating the same dicts
        # in _cached_program/_device_tables (sdlint locks/unguarded-write)
        with self._compile_lock:
            self._programs.clear()
            self._compact_seen.clear()
            self._literal_plans.clear()
            self._device_arrays.clear()
            self._device_bytes = 0
        self.result_cache.clear()


_LOST_MARKERS = ("unavailable", "deadline_exceeded", "deadline exceeded",
                 "connection", "socket", "transport", "unreachable",
                 "device or resource busy", "premature end")


def _agg_shape(q):
    """(dimensions, having, limit) an aggregation spec runs under — a
    Timeseries has none, a TopN implies ORDER BY its metric DESC LIMIT
    its threshold — or None for a spec that is no aggregation."""
    if isinstance(q, S.GroupByQuerySpec):
        return list(q.dimensions), q.having, q.limit
    if isinstance(q, S.TimeseriesQuerySpec):
        return [], None, None
    if isinstance(q, S.TopNQuerySpec):
        return [q.dimension], None, S.topn_limit(q)
    return None


def _cache_repr(q) -> str:
    """repr(q) with the per-request QueryContext stripped: query_id /
    timeout never shape the compiled program, and leaving them in the
    signature would recompile EVERY server statement (each request
    carries a fresh query id — a 3-45s compile per request on a TPU)."""
    try:
        return repr(dataclasses.replace(q, context=None))
    except Exception:  # noqa: BLE001 — non-dataclass/frozen edge
        return repr(q)


_LITERAL_PLANS_MAX = 512     # specs whose literal plan is kept (a few KB each)
_COMPACT_SEEN_MAX = 512      # shapes whose survivor count is kept


def _spec_identity(q) -> Optional[tuple]:
    """Which spec this is, apart from its per-request context: the
    session stamps a query id on a copy of the memoised plan, and the
    copy's other fields are the plan's own objects. An entry that holds
    ``q`` keeps them alive, so an id names one object for as long as it
    is a key."""
    try:
        return (type(q),) + tuple(
            id(getattr(q, f.name)) for f in dataclasses.fields(q)
            if f.name != "context")
    except TypeError:        # not a dataclass: resolve every time
        return None


def _budget_for(n_live) -> int:
    """The power-of-two survivor budget for a count of ``n_live``: the
    2x margin before a run overflows, 64 rows at least."""
    return 1 << max(6, int(np.ceil(np.log2(max(n_live * 2.0, 1.0)))))


def _memo_put_bounded(memo: dict, key, value, bound: int) -> None:
    """Insert, dropping the oldest entries past ``bound``. Lock-free:
    statements run in parallel and two may evict at once."""
    memo[key] = value
    while len(memo) > bound:
        try:
            memo.pop(next(iter(memo)), None)
        except (StopIteration, RuntimeError):   # emptied / resized under us
            break


def _survivor_mask(ctx, cheap_f, intervals, cse=None):
    """The rows a scan keeps before anything is compacted or aggregated:
    valid, inside the intervals, passing the cheap filter (all of it
    where the program does not compact)."""
    base = ctx.row_valid()
    fm = cse.lower(cheap_f) if cse is not None \
        else F.lower_filter(cheap_f, ctx)
    if fm is not None:
        base = base & fm
    im = F.interval_mask(intervals, ctx)
    if im is not None:
        base = base & im
    return base


def _operands(lits, arrays):
    """The trace-time reader of a program's literal operand, or None
    where the statement has no slotted literal."""
    if not lits.count:
        return None
    return L.Operands(lits, arrays[L.LITERALS_KEY])


_COMPILE_MARKERS = ("mosaic", "compil")


def _is_backend_loss(e: BaseException) -> bool:
    """Heuristic classification of a permanently-dead device backend
    (transfers/dispatches raise UNAVAILABLE / connection errors after
    _device_put_retry exhausts its backoff). A compiler's refusal
    (Mosaic, XLA, a ``WaveCompileError`` — each names the compiler) is
    never device loss, whatever other words its text happens to carry:
    it must reach the client, not demote the session to the host tier."""
    if isinstance(e, EngineFallback) \
            or not isinstance(e, (RuntimeError, OSError)):
        return False
    from spark_druid_olap_tpu.cluster.broker import ClusterError
    if isinstance(e, ClusterError):
        # a shard unreachable over the NETWORK says nothing about the
        # local device backend — strict mode must surface it, not demote
        # it to a host fallback
        return False
    s = str(e).lower()
    if any(m in s for m in _COMPILE_MARKERS):
        return False
    return any(m in s for m in _LOST_MARKERS)


def _probe_device_alive(timeout_s: float = 10.0) -> bool:
    """Whether the default backend answers a trivial dispatch within the
    deadline, probed from a daemon thread (a hung dispatch must never
    hang the session)."""
    result = []

    def work():
        try:
            r = jax.device_put(np.arange(8, dtype=np.int32))
            result.append(int(jnp.sum(r)) == 28)
        except Exception:  # noqa: BLE001
            result.append(False)

    th = __import__("threading").Thread(target=work, daemon=True)
    th.start()
    th.join(timeout_s)
    return bool(result and result[0])


def _device_put_retry(host, sharding=None):
    """device_put with backoff on transient backend errors — a transfer
    can hiccup with UNAVAILABLE (≈ the reference wrapping Druid HTTP
    calls in RetryUtils.retryOnError)."""
    from spark_druid_olap_tpu.utils.retry import retry_on_error

    def transient(e):
        s = str(e)
        return "UNAVAILABLE" in s or "DEADLINE_EXCEEDED" in s \
            or "RESOURCE_EXHAUSTED" in s

    return retry_on_error(lambda: jax.device_put(host, sharding),
                          tries=3, start=0.5, retryable=transient)


def _build_array_checked(ds, key, seg_idx, s_pad) -> np.ndarray:
    """build_array + the wide-integer gate: a 32-bit device backend cannot
    carry int64 values without silently wrapping, so queries binding a wide
    LONG column demote to the host tier there (x64 backends carry them in
    f64 routes, exact to 2^53)."""
    arr = build_array(ds, key, seg_idx, s_pad)
    if arr.dtype == np.int64 and not G._x64():
        raise EngineFallback(
            f"wide integer column {key!r} on a 32-bit backend")
    return arr


def _decode_agg_value(ds, p, r, v) -> np.ndarray:
    """Final per-group route values -> output column (dtype-faithful; min/max
    empty-group sentinels become nulls, like Druid)."""
    if p.kind in ("min", "max"):
        if r.tag == "i32":
            sent = G.I32_MAX if p.kind == "min" else G.I32_MIN
            empty = v == np.int64(sent)
        elif r.tag == "i64":
            sent = G.I64_MAX if p.kind == "min" else G.I64_MIN
            empty = v == sent
        else:
            empty = np.abs(v) >= 3.0e38
        if p.spec.kind == "anyvalue":
            return _decode_anyvalue(ds, p.spec.field, v, empty)
        if p.dim_codes:
            # extremum CODE of the sorted dictionary -> its string (the
            # same decode contract as FD-demoted grouping columns)
            return _decode_anyvalue(ds, p.spec.field, v, empty)
        if empty.any():
            if r.tag == "i64" and \
                    np.abs(np.where(empty, 0, v)).max(initial=0) >= 2**53:
                # f64 NaN-nulls would round these; keep exact ints + None
                out = v.astype(object)
                out[empty] = None
                return out
            return np.where(empty, np.nan, v).astype(np.float64)
        if np.issubdtype(p.out_dtype, np.integer) and r.tag in ("i32", "i64"):
            return v.astype(np.int64)
        if np.issubdtype(p.out_dtype, np.integer):
            return np.round(v).astype(np.int64)
        return v.astype(np.float64)
    if np.issubdtype(p.out_dtype, np.integer):
        # sum/count int routes combine exactly (lanes/limbs/ff/i64);
        # np.rint would detour int64 through f64 and round past 2^53
        if np.issubdtype(v.dtype, np.integer):
            return v.astype(np.int64)
        return np.rint(v).astype(np.int64)
    return v.astype(np.float64)


def _encode_buf(a, dt: str, x64: bool):
    """Dtype-faithful packing of one flat program output into the int lane
    of the single transfer buffer: floats travel BITCAST inside the int
    buffer, never rounded (the packing contract shared by the dense and
    hashed programs)."""
    a = a.reshape(-1)
    if x64:
        if dt == "f64":
            return jax.lax.bitcast_convert_type(
                a.astype(jnp.float64), jnp.int64)
        if dt == "f32":
            # ffl pairs are f32 even on x64 backends: bitcast into the
            # low lane (astype would TRUNCATE the fraction)
            return jax.lax.bitcast_convert_type(
                a.astype(jnp.float32), jnp.int32).astype(jnp.int64)
        return a.astype(jnp.int64)
    if dt == "f32":
        return jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.int32)
    return a.astype(jnp.int32)


def _decode_buf(chunk: np.ndarray, dt: str, x64: bool) -> np.ndarray:
    """Host inverse of _encode_buf (chunk must be contiguous for the
    bitcast view)."""
    if x64 and dt == "f64":
        return chunk.view(np.float64)
    if dt == "f32":
        if x64:
            return chunk.astype(np.int32).view(np.float32)
        return chunk.view(np.float32)
    return chunk


_HAVING_CMP = {"=": jnp.equal, "!=": jnp.not_equal, "<": jnp.less,
               "<=": jnp.less_equal, ">": jnp.greater,
               ">=": jnp.greater_equal}


def _having_passes(route, out, op, lit):
    """Device bool per key: the aggregate ``route`` holds as ONE exact
    column passes ``op lit`` (``_plan_device_having``); a NULL min/max
    (its sentinel survived) is UNKNOWN and drops."""
    v = out[route.name]
    m = _HAVING_CMP[op](v, jnp.asarray(lit, v.dtype))
    nm = G.route_null_mask(route, out)
    return m if nm is None else m & ~nm


def _hash_having_mask(having_dev, table, routes):
    """Device bool [T] over a hashed tier's resident table: slot occupied
    AND its HAVING passes — ``having_dev`` = (aggregation, op, integer
    literal) over a route the table holds as one exact integer column
    (tags i32 / i64; the dense tier's ``_having_mask`` shares the
    comparison)."""
    name, op, lit = having_dev
    return _having_passes(routes[name], table, op, lit) \
        & (table["__tkhi__"] != H.EMPTY)


def _gather_rows(out, idx, n_keys):
    """Gather every per-key output at ``idx``: each output is flat
    [n_keys*width] key-major; rows of the [n_keys, width] view are kept."""
    g = {}
    for name, arr in out.items():
        flat = arr.reshape(-1)
        width = flat.shape[0] // n_keys
        if width == 1:
            g[name] = flat[idx]
        else:
            g[name] = flat.reshape(n_keys, width)[idx].reshape(-1)
    return g


def _topk_score(route, out, n_keys, ascending, valid, axis_name=None):
    """The shared selection-score pipeline of the dense and hashed top-k
    epilogues. Rank order must match the host epilogue's: real scores,
    then occupied groups whose metric is NULL (min/max sentinel — under
    ascending negation it would otherwise rank FIRST), then invalid
    (unoccupied) keys at -inf so NULL-metric groups still fill an
    under-subscribed LIMIT (nulls-last)."""
    sc = G.route_score(route, out, n_keys, axis_name)
    if ascending:
        sc = -sc
    nm = G.route_null_mask(route, out)
    if nm is not None:
        big = jnp.finfo(sc.dtype).max
        sc = jnp.where(nm, jnp.asarray(-big, sc.dtype), sc)
    return jnp.where(valid, sc, jnp.asarray(-jnp.inf, sc.dtype))


def _score_cast_exact(route, x64: bool, vlo: float, vhi: float) -> bool:
    """True when route_score is bit-exact for every metric value in
    [vlo, vhi] AND no value OUTSIDE that range can round onto a value
    inside it (so a boundary tie in score space is a true value tie).
    Bounds are therefore STRICT: at an inclusive 2^24 cutoff, an
    excluded i32 key at 2^24+1 rounds ties-to-even DOWN onto the
    cutoff and a tie-accept would certify a wrong result."""
    t = route.tag
    if t == "f32":
        return True                  # the score IS the device value
    if t == "f64":
        return x64
    if t == "i64":
        return x64 and -(2.0 ** 53) < vlo and vhi < 2.0 ** 53
    if t == "i32":
        return -(2.0 ** 24) < vlo and vhi < 2.0 ** 24
    if t in ("limbs", "lanes"):
        # nonnegative values below the first carry boundary reconstruct
        # as a sum of two exactly-representable f32 terms; values past
        # 2^24 round by at most 1 ulp and cannot reach below 2^23
        return 0.0 <= vlo and vhi < 2.0 ** 23
    return False                     # ff compensated pairs


def _topk_selection_exact(limit, topk, route, scores, data) -> bool:
    """True when the f32-approximate device candidate selection PROVABLY
    contains the exact ordered-limit result. Exact-contract GroupBy
    re-runs without the device epilogue when this returns False;
    TopNQuerySpec never checks (its contract is approximate, like
    Druid's topN engine — reference TopNQuerySpec semantics,
    DruidQuerySpec.scala:767-822).

    Soundness: the device transfers the best ``k_sel`` keys by a
    possibly-rounded score; every non-transferred key's device score is
    <= the k_sel-th best ("cutoff"), and its EXACT value can exceed its
    own device score only by the score-reconstruction error. So the
    result is exact whenever the LIMIT-th emitted row's exact value
    clears the cutoff by more than that error bound — excluded keys
    then cannot rank above (or tie with) any emitted row, which also
    makes secondary ORDER BY columns moot at the boundary."""
    metric, k_sel, ascending = topk
    cutoff = float(scores[-1]) if len(scores) else float("-inf")
    if cutoff != cutoff:
        return False                       # NaN scores: cannot reason
    if cutoff == float("-inf"):
        # an unoccupied (-inf) slot made the candidate set: every
        # occupied key was transferred, so the selection is complete
        return True
    n = int(limit.limit)
    if n <= 0:
        return True
    vals = data.get(metric)
    if vals is None:
        return False
    vals = np.asarray(vals)
    if len(vals) < n:
        # occupied keys were excluded (finite cutoff) yet the LIMIT is
        # under-subscribed — an excluded key might belong in the result
        return False
    v_k = vals[n - 1]
    if v_k is None or (isinstance(v_k, float) and v_k != v_k):
        return False      # NULL boundary row: excluded NULLs could tie
    try:
        s_k = float(v_k)
    except (TypeError, ValueError):
        return False
    if ascending:
        s_k = -s_k
    x64 = bool(jax.config.jax_enable_x64)
    c_val = -cutoff if ascending else cutoff        # cutoff in VALUE domain
    vlo = min(s_k if not ascending else -s_k, c_val)
    vhi = max(s_k if not ascending else -s_k, c_val)
    if _score_cast_exact(route, x64, vlo, vhi):
        # scores near the boundary are bit-exact: strictly-better is
        # always safe, and an exact TIE is safe when the primary metric
        # is the only order column (excluded tying keys are
        # interchangeable answers under SQL's unspecified tie order)
        return s_k > cutoff \
            or (s_k == cutoff and len(limit.columns) == 1)
    # Error bound for an excluded key's route_score reconstruction: a
    # few ulps relative to the magnitudes involved. The split integer
    # routes (limbs/lanes) renormalize through ~2^48-scale positive
    # intermediates that cancel for negative values, so near a
    # non-positive value the ABSOLUTE error is that scale's ulp.
    base = max(abs(cutoff), abs(s_k), 1.0)
    if route.tag in ("limbs", "lanes") and vlo <= 0:
        base = max(base, float(2 ** 50))
    f32_score = route.tag in ("limbs", "lanes", "ff", "ffl", "i32",
                              "f32") or not x64
    eps = float(np.spacing(np.float32(base))) if f32_score \
        else float(np.spacing(np.float64(base)))
    return (s_k - cutoff) > 64.0 * eps


def _topk_slack(limit: S.LimitSpec) -> int:
    """Candidate count for a device top-k selection. Secondary order
    columns (e.g. TPC-H q3/q18 'ORDER BY revenue DESC, o_orderdate') only
    reorder ties in the PRIMARY metric, so they widen the slack (selection
    stays exact unless >slack keys tie exactly at the cutoff value);
    single-column selection errors additionally require f32 rounding to
    cross a gap at the cutoff."""
    if len(limit.columns) == 1:
        return int(max(2 * limit.limit, limit.limit + 64))
    return int(max(4 * limit.limit, limit.limit + 256))


def _hash_topk_gather(out, routes, topk, T):
    """Per-chip top-k over hash-table slots: score occupied slots, keep the
    best k_sel (unoccupied slots at -inf fill any remainder and are
    dropped by the host occupancy filter)."""
    metric, k_sel, ascending = topk
    occ = out["__tkhi__"] != H.EMPTY
    sc = _topk_score(routes[metric], out, T, ascending, occ)
    vals, idx = jax.lax.top_k(sc, k_sel)
    g = _gather_rows(out, idx, T)
    g["__topk_score__"] = vals
    return g


def _hash_chip_partials(raw, routes, T, n_dev):
    """Split a hash program's stacked outputs into per-chip (packed-key,
    finals) partials, dropping unoccupied slots."""
    parts = []
    for c in range(n_dev):
        out_c = {}
        for name, arr in raw.items():
            if name == "__unres__":
                continue
            size = arr.size // n_dev
            out_c[name] = arr[c * size: (c + 1) * size]
        khi = out_c.pop("__tkhi__")
        klo = out_c.pop("__tklo__")
        occ = khi != H.EMPTY
        if not occ.any():
            continue
        finals = {name: np.asarray(G.combine_route(r, out_c, T))[occ]
                  for name, r in routes.items()}
        parts.append((H.pack_key(khi[occ], klo[occ]), finals))
    return parts


def _merge_hash_partials(parts, routes):
    """Merge per-chip/per-wave hash-table partials by key on host (≈ the
    broker-side merge of historical partials). Sums/counts add exactly
    (i64/f64 finals), min/max keep sentinels. An 'hll' route's
    estimates are final and arrive in ONE partial (_run_agg_hashed
    refuses more): a key's sum is its one value."""
    if not parts:
        empty = {name: np.zeros(0, np.float64) for name in routes}
        return np.zeros(0, np.int64), empty
    keys = np.concatenate([k for k, _ in parts])
    uniq, inv = np.unique(keys, return_inverse=True)
    merged = {}
    for name, r in routes.items():
        segs = np.concatenate([f[name] for _, f in parts])
        int_tag = r.tag in ("i32", "i64")
        if r.kind == "min":
            sent = {"i32": np.int64(G.I32_MAX),
                    "i64": G.I64_MAX}.get(r.tag, np.float64(np.inf))
            acc = np.full(len(uniq), sent,
                          dtype=np.int64 if int_tag else np.float64)
            np.minimum.at(acc, inv, segs)
        elif r.kind == "max":
            sent = {"i32": np.int64(G.I32_MIN),
                    "i64": G.I64_MIN}.get(r.tag, np.float64(-np.inf))
            acc = np.full(len(uniq), sent,
                          dtype=np.int64 if int_tag else np.float64)
            np.maximum.at(acc, inv, segs)
        else:
            dt = np.int64 if segs.dtype == np.int64 else np.float64
            acc = np.zeros(len(uniq), dtype=dt)
            np.add.at(acc, inv, segs.astype(dt))
        merged[name] = acc
    return uniq, merged


def _sketch_words(out, agg_plans):
    """The 4-byte words of sketch state — registers, or the sparse
    form's estimates — among one program's unpacked outputs ``out``:
    counted where they were copied back, as ``fetch_bytes`` is."""
    return sum(int(np.size(out[p.spec.name])) for p in agg_plans
               if p.kind in ("hll", "theta", "kll"))


def _sketch_column(p, state, sel):
    """What is left of a sketch on the host, the ``sketch`` span: the
    selected groups' estimates from the state a program shipped — a
    dense ``[groups, width]`` register block, estimated here in float64,
    or, from a program that took its HLL registers in the sparse form
    (``ops.hll.hll_estimates``), the finished estimates, one a group."""
    if p.kind == "kll":
        return KLL.estimate(state, p.spec.fraction or 0.5)[sel]
    if state.ndim == 1:
        return state[sel].astype(np.int64)
    est = (HLL.estimate(state) if p.kind == "hll"
           else TH.estimate(state))[sel]
    return np.round(est).astype(np.int64)


def _finals_from_out(out, routes, n_keys, sketch_plans):
    """Route outputs -> exact final [n_keys] arrays per aggregation (plus
    raw sketch registers), the unit that waves merge over."""
    finals = {name: np.asarray(G.combine_route(r, out, n_keys))
              for name, r in routes.items()}
    for p in sketch_plans:
        finals[p.spec.name] = np.asarray(out[p.spec.name])
    return finals


def _merge_wave_finals(acc, new, routes, sketch_plans=()):
    """Cross-wave merge: sums/counts add exactly (i64 or f64 finals), min/max
    keep their empty-group sentinels, sketch registers take their union
    (HLL: elementwise max; theta k-mins: elementwise min; KLL: lex-min
    survivor + exact count sum — ops/kll.py merge)."""
    theta_names = {p.spec.name for p in sketch_plans
                   if p.kind == "theta"}
    kll_names = {p.spec.name for p in sketch_plans if p.kind == "kll"}
    for name, v in new.items():
        r = routes.get(name)
        if r is None:                       # sketch registers
            if name in kll_names:
                acc[name] = KLL.merge(acc[name], v)
            else:
                acc[name] = np.minimum(acc[name], v) \
                    if name in theta_names else np.maximum(acc[name], v)
        elif r.kind == "min":
            acc[name] = np.minimum(acc[name], v)
        elif r.kind == "max":
            acc[name] = np.maximum(acc[name], v)
        else:
            acc[name] = acc[name] + v
    return acc


def _decode_anyvalue(ds: Datasource, field: str, v: np.ndarray,
                     empty: np.ndarray) -> np.ndarray:
    """Decode an FD-demoted grouping column from its max-aggregated device
    representation (dictionary code for dims, days for dates — exact i32
    lanes, never an f32 round-trip)."""
    kind = ds.column_kind(field)
    if kind == ColumnKind.DIM:
        codes = np.where(empty, 0, v).astype(np.int64)
        vals = ds.dims[field].dictionary[
            np.clip(codes, 0, max(ds.dims[field].cardinality - 1, 0))]
        if empty.any():
            vals = np.where(empty, None, vals)
        return vals
    if kind == ColumnKind.DATE:
        days = np.where(empty, 0, v).astype(np.int64)
        out = days.astype("datetime64[D]")
        if empty.any():
            out = np.where(empty, np.datetime64("NaT"), out)
        return out
    if kind == ColumnKind.LONG:
        if empty.any():
            return np.where(empty, np.nan, v).astype(np.float64)
        return np.rint(v).astype(np.int64)
    return np.where(empty, np.nan, v).astype(np.float64)


def _neg_key(k: np.ndarray):
    if np.issubdtype(k.dtype, np.number):
        return -k
    if np.issubdtype(k.dtype, np.datetime64):
        return -(k.astype(np.int64))
    # descending strings: invert via negated rank
    uniq, inv = np.unique(k, return_inverse=True)
    return -inv


def _pad_segments(s: int, n_dev: int) -> int:
    p = 1
    while p < s:
        p <<= 1
    p = max(p, n_dev)
    if p % n_dev:
        p = -(-p // n_dev) * n_dev
    return p


def _host_column_values(ds: Datasource, name: str,
                        idx: Optional[np.ndarray], *,
                        local_ok: bool = False):
    """Decoded host values of a column (optionally row-subset).

    On a multi-host partial store the columns are assembled by a
    cross-process gather (``Datasource.complete``) — the host fallback
    tier then serves any query shape, at O(table) transfer once.
    ``local_ok`` reads THIS host's rows only (local row indices) — the
    multi-host select/search paths that exchange results instead of
    columns."""
    if not local_ok:
        ds = ds.complete(columns=(name,))
    if name in ds.dims:
        col = ds.dims[name]
        codes = col.codes if idx is None else col.codes[idx]
        vals = col.dictionary[codes.astype(np.int64)]
        if col.validity is not None:
            v = col.validity if idx is None else col.validity[idx]
            vals = np.where(v, vals, None)
        return vals
    if name in ds.metrics:
        m = ds.metrics[name]
        vals = m.values if idx is None else m.values[idx]
        if m.kind == ColumnKind.DATE:
            return vals.astype("datetime64[D]")
        if m.kind == ColumnKind.LONG:
            out = vals.astype(np.int64)
            if m.validity is not None:
                v = m.validity if idx is None else m.validity[idx]
                out = np.where(v, out.astype(np.float64), np.nan)
            return out
        # keep f32 (storage dtype): python-float literals then compare
        # under NumPy weak promotion in f32, matching the device path's
        # comparison semantics at representation boundaries (e.g.
        # x >= 0.05 over a stored f32 0.05); np.nan fill preserves f32
        out = vals
        if m.validity is not None:
            v = m.validity if idx is None else m.validity[idx]
            out = np.where(v, out, np.float32(np.nan))
        return out
    if ds.time is not None and name == ds.time.name:
        ms = ds.time.millis if idx is None else ds.time.millis[idx]
        return ms.astype("datetime64[ms]")
    raise KeyError(name)


def _filter_columns_all(f: S.FilterSpec):
    return F.columns_of_filter(f)


def filter_to_expr(f: S.FilterSpec) -> E.Expr:
    """FilterSpec -> Expr (for host-side evaluation)."""
    if isinstance(f, S.SelectorFilter):
        if f.value is None:
            return E.IsNull(E.Column(f.dimension))
        return E.Comparison("=", E.Column(f.dimension), E.Literal(f.value))
    if isinstance(f, S.BoundFilter):
        parts = []
        c = E.Column(f.dimension)
        if f.lower is not None:
            parts.append(E.Comparison(">" if f.lower_strict else ">=", c,
                                      E.Literal(f.lower)))
        if f.upper is not None:
            parts.append(E.Comparison("<" if f.upper_strict else "<=", c,
                                      E.Literal(f.upper)))
        return E.And(tuple(parts)) if len(parts) != 1 else parts[0]
    if isinstance(f, S.InFilter):
        return E.InList(E.Column(f.dimension), tuple(f.values))
    if isinstance(f, S.PatternFilter):
        if f.kind == "like":
            return E.Like(E.Column(f.dimension), f.pattern)
        if f.kind == "contains":
            return E.Like(E.Column(f.dimension), f"%{f.pattern}%")
        raise EngineFallback("regex filter on host path")
    if isinstance(f, S.NullFilter):
        return E.IsNull(E.Column(f.dimension), negated=f.negated)
    if isinstance(f, S.LogicalFilter):
        subs = tuple(filter_to_expr(x) for x in f.fields)
        if f.op == "and":
            return E.And(subs) if subs else E.Literal(True)
        if f.op == "or":
            return E.Or(subs)
        return E.Not(subs[0])
    if isinstance(f, S.ExprFilter):
        return f.expr
    if isinstance(f, S.SpatialFilter):
        import math
        parts = []
        for ax, lo, hi in zip(f.axes, f.min_coords, f.max_coords):
            c = E.Column(ax)
            if lo is not None and math.isfinite(lo):
                parts.append(E.Comparison(">=", c, E.Literal(lo)))
            if hi is not None and math.isfinite(hi):
                parts.append(E.Comparison("<=", c, E.Literal(hi)))
        return E.And(tuple(parts)) if len(parts) != 1 else (
            parts[0] if parts else E.Literal(True))
    raise EngineFallback(f"filter {type(f).__name__}")


def _all_staged(f) -> bool:
    """Whether ``f`` is a filter of staged conjuncts alone
    (``QueryEngine._split_filter_staged`` leaves no cheap part)."""
    return f is not None and QueryEngine._split_filter_staged(f)[0] is None


def _compaction_filters(f):
    """(the mask late materialization compacts on, the conjuncts applied
    to its survivors after it). The cheap conjuncts are the mask and the
    staged ones follow (``QueryEngine._split_filter_staged``); a filter
    whose conjuncts are ALL staged (an executed IN subquery's keys, past
    1,024 of them a sorted set) is its own mask — it is evaluated over
    every row with or without compaction, and nothing is left to follow."""
    if _all_staged(f):
        return f, None
    return QueryEngine._split_filter_staged(f)


def _estimate_blind(f, ds) -> bool:
    """Whether the selectivity estimate has nothing to price the
    compaction mask ``f`` by, so that its first sight asks the count
    (``_plan_compact_m``): a mask of staged conjuncts alone, or one with
    a value list over a column that has no dictionary — where
    ``cost._filter_selectivity`` guesses 100 distinct values, and calls
    TPC-H q18's 56-71 order keys (of 1.5 M orders at SF1) unselective."""
    if _all_staged(f):
        return True
    conj = f.fields if isinstance(f, S.LogicalFilter) and f.op == "and" \
        else (f,)
    return any(isinstance(x, S.InFilter)
               and ds.cardinality(x.dimension) is None for x in conj)
