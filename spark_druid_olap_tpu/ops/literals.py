"""Filter literals as a program's operand.

A statement template is re-issued with other literals — another date,
segment, region, ship mode (TPC-H's substitution parameters; a dashboard
moving its window). A literal that is traced into the program as a
Python constant makes every such draw a program and an XLA compile of
its own; a literal that the program *receives* makes them one.

``leaf_literals`` is the ONE host-side conversion of a leaf filter's
literals to what the device compares against (dictionary value -> code,
date -> days, time -> (day, ms), number -> the column's compare dtype).
``ops/filters.py`` asks its scan context for them (``ScanContext
.literals``); the context hands out either these Python constants (the
wave path: a Pallas kernel cannot close over device values) or, on the
solo path, scalars read from one small int32 operand that the engine
packs per statement (``LiteralPlan.pack``) and binds beside the columns
under ``LITERALS_KEY``.

The program cache then keys on a statement's *shape*
(``LiteralPlan.shape_repr``): its query spec with every slotted literal
replaced by ``$<offset>:<dtype>``. What shapes the traced program stays
in the shape: columns, operators, strictness, which bound is absent, the
arity of an IN list, and which leaves are EQUAL (two equal leaves share
their slots — the trace-time predicate CSE of ``planner/fusion.py``
shares their mask, so a draw in which they differ must be another
program). Literals that stay constants, and so stay in the shape with
their values: NULL selectors, patterns (their mask is a function of the
whole dictionary), frozen integer sets, IN lists longer than
``IN_OPERAND_MAX``, spatial bounds, and everything inside an expression
(``ops/expr_compile.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from spark_druid_olap_tpu.encode import predicates as P
from spark_druid_olap_tpu.ir import expr as E
from spark_druid_olap_tpu.ir import spec as S
from spark_druid_olap_tpu.ops import time_ops
from spark_druid_olap_tpu.segment.column import ColumnKind

LITERALS_KEY = "__literals__"
IN_OPERAND_MAX = 16      # a longer IN list keeps its dictionary-mask gather

_I32 = np.dtype(np.int32)
_F32 = np.dtype(np.float32)


def _compare_dtype(dt) -> np.dtype:
    """The dtype a stored column is compared in: ``ScanContext.col``
    widens narrow integers to i32 on read."""
    dt = np.dtype(dt)
    return _I32 if dt.kind == "i" and dt.itemsize < 4 else dt


def _metric_dtype(ds, name) -> np.dtype:
    return _compare_dtype(ds.metrics[name].data_dtype())


def _number(kind, value):
    return float(value) if kind == ColumnKind.DOUBLE else int(float(value))


def leaf_literals(f, ds, tz) -> Optional[tuple]:
    """((value, dtype) | None, ...) for a leaf filter whose literals are
    scalars the device compares against — ``None`` entries are absent
    bounds — or None for a leaf that lowers some other way. Values are
    Python ints and floats, converted exactly as the lowering in
    ``ops/filters.py`` always has."""
    if isinstance(f, S.SelectorFilter):
        if f.value is None:
            return None
        kind = ds.column_kind(f.dimension)
        if kind == ColumnKind.DIM:
            return ((P.selector_code(ds.dims[f.dimension], f.value), _I32),)
        if kind in (ColumnKind.LONG, ColumnKind.DOUBLE):
            return ((_number(kind, f.value),
                     _metric_dtype(ds, f.dimension)),)
        if kind == ColumnKind.DATE:
            return ((time_ops.date_literal_to_days(f.value),
                     _metric_dtype(ds, f.dimension)),)
        if kind == ColumnKind.TIME:
            return _time_pair(f.value, ds, tz)
        return None
    if isinstance(f, S.BoundFilter):
        kind = ds.column_kind(f.dimension)
        if kind == ColumnKind.DIM and not f.numeric:
            lo, hi = P.bound_code_range(
                ds.dims[f.dimension], f.lower, f.upper,
                f.lower_strict, f.upper_strict)
            return ((lo, _I32), (hi, _I32))
        if kind == ColumnKind.DIM:
            return _pair(f, float, _F32)     # compared in a float32 LUT
        if kind in (ColumnKind.LONG, ColumnKind.DOUBLE):
            return _pair(f, lambda v: _number(kind, v),
                         _metric_dtype(ds, f.dimension))
        if kind == ColumnKind.DATE:
            return _pair(f, time_ops.date_literal_to_days,
                         _metric_dtype(ds, f.dimension))
        if kind == ColumnKind.TIME:
            lo = _time_pair(f.lower, ds, tz) if f.lower is not None \
                else (None, None)
            hi = _time_pair(f.upper, ds, tz) if f.upper is not None \
                else (None, None)
            return lo + hi
        return None
    if isinstance(f, S.InFilter):
        if isinstance(f.values, E.FrozenIntSet):
            return None
        kind = ds.column_kind(f.dimension)
        if kind == ColumnKind.DIM:
            dim = ds.dims[f.dimension]
            return tuple((P.selector_code(dim, v), _I32) for v in f.values)
        if kind == ColumnKind.TIME:
            dt = _compare_dtype(ds.time.data_dtype())
        else:
            dt = _metric_dtype(ds, f.dimension)
        if kind == ColumnKind.DATE:
            return tuple((time_ops.date_literal_to_days(v), dt)
                         for v in f.values)
        return tuple((_number(kind, v), dt) for v in f.values)
    return None


def _pair(f: S.BoundFilter, conv, dt) -> tuple:
    return (None if f.lower is None else (conv(f.lower), dt),
            None if f.upper is None else (conv(f.upper), dt))


def _time_pair(value, ds, tz) -> tuple:
    # naive literals are session-local, zoned ones absolute
    ms = time_ops.literal_to_utc_millis(value, tz)
    day, rem = divmod(ms, time_ops.MILLIS_PER_DAY)
    return ((day, _compare_dtype(ds.time.data_dtype())),
            (rem, _compare_dtype(ds.time.ms_dtype())))


def interval_literals(intervals, ds, min_day, max_day) -> List[tuple]:
    """[(dlo, rlo, dhi, rhi)] of ``ops.filters.interval_mask``. Open-ended
    interval bounds carry +-2^63-scale ms; their day numbers overflow the
    i32 lanes on a 32-bit backend. Scanned days all lie in [min_day,
    max_day], so clamping one day past that range preserves the mask
    exactly."""
    out = []
    for lo, hi in intervals:
        dlo, rlo, dhi, rhi = time_ops.interval_day_range(lo, hi)
        dlo = min(max(dlo, min_day - 1), max_day + 1)
        dhi = min(max(dhi, min_day - 1), max_day + 1)
        out.append((dlo, rlo, dhi, rhi))
    return out


class _Slot:
    """A literal's place in a statement's shape."""
    __slots__ = ("text",)

    def __init__(self, offset: int, dt: np.dtype):
        self.text = f"${offset}:{dt.name}"

    def __repr__(self):
        return self.text


def _leaves(f):
    if isinstance(f, S.LogicalFilter):
        for x in f.fields:
            yield from _leaves(x)
    elif f is not None:
        yield f


class LiteralPlan:
    """One statement's slotted literals: their values, where each sits in
    the packed operand, and the statement's shape without them."""

    _packed: Optional[np.ndarray] = None    # pack()'s result, made once

    def __init__(self, ds, filter_spec, aggregations, intervals, tz,
                 min_day, max_day):
        self.shape: Optional[str] = None    # the engine's: shape_repr(q)
        self._values: List[tuple] = []      # (python value, dtype)
        self._words = 0
        # leaf repr -> ((offset, dtype) | None, ...); equal leaves share
        self.slots: Dict[str, tuple] = {}
        for root in [filter_spec] + [a.filter for a in aggregations]:
            for leaf in _leaves(root):
                key = repr(leaf)
                if key in self.slots:
                    continue
                lits = leaf_literals(leaf, ds, tz)
                if lits is not None and len(lits) <= IN_OPERAND_MAX:
                    self.slots[key] = tuple(
                        None if x is None else self._add(*x) for x in lits)
        self.intervals: Optional[List[tuple]] = None
        if intervals and ds.time is not None:
            dd = _compare_dtype(ds.time.data_dtype())
            md = _compare_dtype(ds.time.ms_dtype())
            self.intervals = [
                (self._add(dlo, dd), self._add(rlo, md),
                 self._add(dhi, dd), self._add(rhi, md))
                for dlo, rlo, dhi, rhi in interval_literals(
                    intervals, ds, min_day, max_day)]

    def _add(self, value, dt) -> Tuple[int, np.dtype]:
        if dt.kind == "i":
            info = np.iinfo(dt)
            if not info.min <= value <= info.max:
                # what tracing the Python constant would have raised
                raise OverflowError(
                    f"Python integer {value} out of bounds for {dt.name}")
        at = (self._words, dt)
        self._values.append((value, dt))
        self._words += dt.itemsize // 4
        return at

    @property
    def count(self) -> int:
        return len(self._values)

    def pack(self) -> np.ndarray:
        """The operand: every slotted value in its own dtype, viewed as
        int32 words, one row ([1, words]; a mesh tiles the row per
        shard). Packed once and read-only: the plan outlives its
        statement where the engine keeps it for a repeated text."""
        if self._packed is None:
            words = np.concatenate(
                [np.array([v], dt).view(np.int32) for v, dt in self._values]
            ).reshape(1, -1) if self._values else np.zeros((1, 0), np.int32)
            words.flags.writeable = False
            self._packed = words
        return self._packed

    # -- the shape ------------------------------------------------------------
    def _shape_filter(self, f):
        if f is None:
            return None
        if isinstance(f, S.LogicalFilter):
            return dataclasses.replace(
                f, fields=tuple(self._shape_filter(x) for x in f.fields))
        at = self.slots.get(repr(f))
        if at is None:
            return f
        slots = [None if x is None else _Slot(*x) for x in at]
        if isinstance(f, S.SelectorFilter):
            if len(slots) == 1:
                return dataclasses.replace(f, value=slots[0])
            return dataclasses.replace(f, value=tuple(slots))
        if isinstance(f, S.InFilter):
            return dataclasses.replace(f, values=tuple(slots))
        if len(slots) == 2:
            return dataclasses.replace(f, lower=slots[0], upper=slots[1])
        return dataclasses.replace(      # a TIME bound: (day, ms) pairs
            f, lower=None if slots[0] is None else tuple(slots[:2]),
            upper=None if slots[2] is None else tuple(slots[2:]))

    def shape_repr(self, q) -> str:
        """``repr`` of the query spec with the per-request context
        stripped and every slotted literal replaced by its slot."""
        try:
            changes = dict(
                context=None, filter=self._shape_filter(q.filter),
                aggregations=tuple(
                    a if a.filter is None else dataclasses.replace(
                        a, filter=self._shape_filter(a.filter))
                    for a in q.aggregations))
            if self.intervals is not None:
                changes["intervals"] = ("slotted", len(self.intervals))
            return repr(dataclasses.replace(q, **changes))
        except Exception:  # noqa: BLE001 — non-dataclass/frozen edge
            return repr(q)


class Operands:
    """Trace-time reader of the packed operand for the program that the
    plan's statement builds; later statements of the same shape bind
    their own values at the same offsets."""

    def __init__(self, plan: LiteralPlan, words):
        self.plan = plan
        self.words = words.reshape(-1)        # [1, W] per shard -> [W]

    def _read(self, at):
        if at is None:
            return None
        offset, dt = at
        n = dt.itemsize // 4
        w = jax.lax.slice_in_dim(self.words, offset, offset + n)
        return jax.lax.bitcast_convert_type(w[0] if n == 1 else w, dt)

    def of(self, f) -> Optional[tuple]:
        at = self.plan.slots.get(repr(f))
        return None if at is None else tuple(self._read(x) for x in at)

    def of_intervals(self) -> Optional[List[tuple]]:
        if self.plan.intervals is None:
            return None
        return [tuple(self._read(x) for x in iv)
                for iv in self.plan.intervals]
