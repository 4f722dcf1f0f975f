"""FilterSpec -> vectorized device predicate masks.

The in-tree replacement for Druid's filter evaluation engine (the reference
only *models* filters — ``FilterSpec`` hierarchy ``DruidQuerySpec.scala:152-281``
— and ships them to Druid). Every filter lowers to a bool [S, R] mask over the
stacked segment tensors:

- selector  -> one integer compare on dictionary codes
- bound     -> two integer compares (sorted global dictionary ⇒ lexicographic
               bounds are code ranges; numeric bounds compare values directly)
- in        -> host ``np.isin`` over the dictionary -> constant code-mask gather
               (a short list whose literals arrive as an operand: one
               code compare per value)
- like/regex/contains -> host regex over the dictionary -> code-mask gather
- expr      -> compiled XLA predicate (replaces the JavaScript filter)
- and/or/not, is-null, time-interval masks

The string->code rewrites live in ``encode/predicates.py``: they are the
dictionary-predicate half of the compressed columnar subsystem (the code
tests evaluate identically on plain or bit-packed codes, so an encoded
store filters without ever decoding a string — or even a code — on
host). This module owns only the device-mask lowering around them.

A leaf's literals are never converted here: the lowering asks its scan
context (``ctx.literals(f)``, ``ctx.interval_literals``) and gets either
Python constants (the wave path) or scalars read from the program's
literal operand (the solo path) — ``ops/literals.py`` has the one
conversion and says which literals take a slot. A shortcut that needs
to KNOW a value (an absent dictionary value -> no scan at all) is taken
only for a constant; the operand form compares against a code that
matches no row, so every draw of a statement's shape is one program.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from spark_druid_olap_tpu.encode import predicates as P
from spark_druid_olap_tpu.ir import expr as E
from spark_druid_olap_tpu.ir import spec as S
from spark_druid_olap_tpu.ops import expr_compile as EC
from spark_druid_olap_tpu.ops.scan import ScanContext
from spark_druid_olap_tpu.segment.column import ColumnKind


def lower_filter(f: Optional[S.FilterSpec], ctx: ScanContext):
    """Lower a FilterSpec to a bool mask (None -> None, meaning all-true)."""
    if f is None:
        return None
    if isinstance(f, S.SelectorFilter):
        return _selector(f, ctx)
    if isinstance(f, S.BoundFilter):
        return _bound(f, ctx)
    if isinstance(f, S.InFilter):
        return _in(f, ctx)
    if isinstance(f, S.PatternFilter):
        return _pattern(f, ctx)
    if isinstance(f, S.NullFilter):
        nv = ctx.null_valid(f.dimension)
        valid = ctx.row_valid() if nv is None else nv
        return valid if f.negated else ~valid
    if isinstance(f, S.LogicalFilter):
        return _logical(f, ctx)
    if isinstance(f, S.ExprFilter):
        v = EC.compile_expr(f.expr, ctx)
        return EC._as_bool(v)
    if isinstance(f, S.SpatialFilter):
        return _spatial(f, ctx)
    raise EC.Unsupported(f"filter {type(f).__name__}")


def _false(ctx):
    return jnp.zeros_like(ctx.row_valid())


def _static(v) -> bool:
    """A Python constant, not a value read from the literal operand."""
    return isinstance(v, (int, float))


def _nullsafe(mask, name: str, ctx: ScanContext):
    nv = ctx.null_valid(name)
    return mask if nv is None else (mask & nv)


def _selector(f: S.SelectorFilter, ctx):
    kind = ctx.kind(f.dimension)
    if f.value is None:
        nv = ctx.null_valid(f.dimension)
        return ~nv if nv is not None else _false(ctx)
    if kind == ColumnKind.DIM:
        code, = ctx.literals(f)
        if _static(code) and code < 0:
            return _false(ctx)
        return _nullsafe(ctx.col(f.dimension) == code, f.dimension, ctx)
    if kind in (ColumnKind.LONG, ColumnKind.DOUBLE):
        v, = ctx.literals(f)
        return _nullsafe(ctx.col(f.dimension) == v, f.dimension, ctx)
    if kind == ColumnKind.DATE:
        day, = ctx.literals(f)
        return ctx.col(f.dimension) == day
    if kind == ColumnKind.TIME:
        # same literal policy as _time_bound: naive literals are
        # session-local, zoned ones absolute
        day, rem = ctx.literals(f)
        return (ctx.col(f.dimension) == day) & (ctx.time_ms() == rem)
    raise EC.Unsupported(f"selector on {kind}")


def _bound(f: S.BoundFilter, ctx):
    kind = ctx.kind(f.dimension)
    if kind == ColumnKind.DIM and not f.numeric:
        lo, hi = ctx.literals(f)        # the half-open code interval
        codes = ctx.col(f.dimension)
        if not _static(lo):
            return _nullsafe((codes >= lo) & (codes < hi), f.dimension, ctx)
        if lo >= hi:
            return _false(ctx)
        mask = None
        if lo > 0:
            mask = codes >= lo
        if hi < ctx.ds.dims[f.dimension].cardinality:
            m2 = codes < hi
            mask = m2 if mask is None else (mask & m2)
        if mask is None:
            nv = ctx.null_valid(f.dimension)
            return nv if nv is not None else ctx.row_valid()
        return _nullsafe(mask, f.dimension, ctx)
    if kind == ColumnKind.DIM and f.numeric:
        # numeric ordering over string dictionary: host-parse to LUT
        vals = ctx.dictionary(f.dimension)
        lut = np.array([_try_float(s) for s in vals], dtype=np.float32)
        arr = EC._take_lut(lut, ctx.col(f.dimension))
        return _nullsafe(_range_mask(arr, f, ctx), f.dimension, ctx)
    if kind in (ColumnKind.LONG, ColumnKind.DOUBLE):
        return _nullsafe(_range_mask(ctx.col(f.dimension), f, ctx),
                         f.dimension, ctx)
    if kind == ColumnKind.DATE:
        return _range_mask(ctx.col(f.dimension), f, ctx)
    if kind == ColumnKind.TIME:
        return _time_bound(f, ctx)
    raise EC.Unsupported(f"bound on {kind}")


def _try_float(s):
    try:
        return float(s)
    except (TypeError, ValueError):
        return np.nan


def _range_mask(arr, f: S.BoundFilter, ctx):
    lo, hi = ctx.literals(f)
    mask = None
    if lo is not None:
        m = (arr > lo) if f.lower_strict else (arr >= lo)
        mask = m
    if hi is not None:
        m = (arr < hi) if f.upper_strict else (arr <= hi)
        mask = m if mask is None else (mask & m)
    return mask if mask is not None else (arr == arr)


def _time_bound(f: S.BoundFilter, ctx):
    days = ctx.col(f.dimension)
    ms = ctx.time_ms()
    dlo, rlo, dhi, rhi = ctx.literals(f)
    mask = None

    if dlo is not None:
        d, r = dlo, rlo
        cmp = (ms > r) if f.lower_strict else (ms >= r)
        m = (days > d) | ((days == d) & cmp)
        mask = m
    if dhi is not None:
        d, r = dhi, rhi
        cmp = (ms < r) if f.upper_strict else (ms <= r)
        m = (days < d) | ((days == d) & cmp)
        mask = m if mask is None else (mask & m)
    return mask if mask is not None else ctx.row_valid()


def _in(f: S.InFilter, ctx):
    kind = ctx.kind(f.dimension)
    if isinstance(f.values, E.FrozenIntSet):
        # semi-join-scale membership: dense spans hit a packed-bitmap
        # gather, wide spans binary-search the sorted constant (shared
        # lowering, EC.int_set_membership)
        if kind not in (ColumnKind.LONG, ColumnKind.DATE):
            raise EC.Unsupported("large integer IN set over non-integer")
        vals = f.values.array
        if len(vals) == 0:
            return _false(ctx)
        arr = ctx.col(f.dimension)
        if arr.dtype != jnp.int64 and (
                int(vals[0]) < -(2**31) or int(vals[-1]) >= 2**31):
            raise EC.Unsupported("IN-set values exceed 32-bit column range")
        return _nullsafe(EC.int_set_membership(arr, vals),
                         f.dimension, ctx)
    lits = ctx.literals(f)      # codes, days or numbers; absent code: -1
    if kind == ColumnKind.DIM and all(map(_static, lits)):
        mask = P.in_code_mask(ctx.dictionary(f.dimension), f.values)
        return _nullsafe(EC._take_mask(mask, ctx.col(f.dimension)),
                         f.dimension, ctx)
    arr = ctx.col(f.dimension)
    out = None
    for v in lits:
        b = arr == v
        out = b if out is None else (out | b)
    return _nullsafe(out if out is not None else _false(ctx),
                     f.dimension, ctx)


def _pattern(f: S.PatternFilter, ctx):
    if ctx.kind(f.dimension) != ColumnKind.DIM:
        raise EC.Unsupported("pattern filter on non-string column")
    try:
        mask = P.pattern_code_mask(ctx.dictionary(f.dimension), f.kind,
                                   f.pattern,
                                   like_to_regex=EC.like_to_regex)
    except ValueError:
        raise EC.Unsupported(f"pattern kind {f.kind}") from None
    return _nullsafe(EC._take_mask(mask, ctx.col(f.dimension)),
                     f.dimension, ctx)


def _spatial(f: S.SpatialFilter, ctx):
    """Rectangular bound over the spatial dim's axis columns: fused per-axis
    inclusive range compares (the row-mask half; segment bounding-box
    pruning happens host-side in ``Datasource.prune_segments``)."""
    out = None
    for ax, lo, hi in zip(f.axes, f.min_coords, f.max_coords):
        arr = ctx.col(ax)
        m = None
        if lo is not None and np.isfinite(lo):
            m = arr >= lo
        if hi is not None and np.isfinite(hi):
            m2 = arr <= hi
            m = m2 if m is None else (m & m2)
        if m is not None:
            m = _nullsafe(m, ax, ctx)
            out = m if out is None else (out & m)
    return out if out is not None else ctx.row_valid()


def _logical(f: S.LogicalFilter, ctx):
    if f.op == "not":
        # BOOLEAN not (planner-generated wrappers — EXISTS encodings —
        # rely on it; SQL-level NOT gets its Kleene null guards added by
        # the builder at construction, builder._kleene_not)
        inner = lower_filter(f.fields[0], ctx)
        return ctx.row_valid() if inner is None else ~inner
    masks = [lower_filter(x, ctx) for x in f.fields]
    if f.op == "or":
        # an all-true (None) operand makes the whole OR all-true
        if not masks or any(m is None for m in masks):
            return None
    else:
        masks = [m for m in masks if m is not None]
        if not masks:
            return None
    out = masks[0]
    for m in masks[1:]:
        out = (out & m) if f.op == "and" else (out | m)
    return out


def interval_mask(intervals, ctx: ScanContext):
    """Residual device mask for time intervals (after host-side segment
    pruning; segments straddling an interval edge need the row-level mask).

    ≈ the reference's ``QueryIntervals`` constraints that Druid applies
    per-segment."""
    if not intervals or ctx.ds.time is None:
        return None
    days = ctx.col(ctx.ds.time.name)
    ms = ctx.time_ms()
    out = None
    for dlo, rlo, dhi, rhi in ctx.interval_literals(intervals):
        m_lo = (days > dlo) | ((days == dlo) & (ms >= rlo))
        m_hi = (days < dhi) | ((days == dhi) & (ms < rhi))
        m = m_lo & m_hi
        out = m if out is None else (out | m)
    return out


def columns_of_filter(f: Optional[S.FilterSpec]):
    """Source columns a filter touches (for array binding)."""
    if f is None:
        return set()
    if isinstance(f, (S.SelectorFilter, S.BoundFilter, S.InFilter,
                      S.PatternFilter, S.NullFilter)):
        return {f.dimension}
    if isinstance(f, S.SpatialFilter):
        return set(f.axes)
    if isinstance(f, S.LogicalFilter):
        out = set()
        for x in f.fields:
            out |= columns_of_filter(x)
        return out
    if isinstance(f, S.ExprFilter):
        from spark_druid_olap_tpu.ir import expr as E
        return E.columns_in(f.expr)
    return set()
