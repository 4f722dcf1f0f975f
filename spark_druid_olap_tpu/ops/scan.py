"""Scan context: the bridge between host-side metadata (dictionaries, column
kinds) and the traced device arrays inside a compiled query program.

A ``ScanContext`` is constructed inside the jitted query function: the device
arrays it holds are **tracers** (function inputs), while the dictionaries and
cardinalities it consults are host constants — so dictionary-derived predicate
masks become small embedded constants in the compiled executable, and no
string ever reaches the device.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from spark_druid_olap_tpu.ops import literals as L
from spark_druid_olap_tpu.segment.column import ColumnKind
from spark_druid_olap_tpu.segment.store import Datasource

TIME_MS_KEY = "__time_ms__"
ROW_VALID_KEY = "__rows__"
NULL_VALID_PREFIX = "__nulls__"


@dataclasses.dataclass
class ScanContext:
    """Host metadata + traced device arrays for one scan program."""

    ds: Datasource
    arrays: Dict[str, object]          # name -> traced [S, R] array
    min_day: int                       # over the selected segments
    max_day: int
    tz: str = "UTC"                    # session timezone (instants shift)
    # a program that takes its filter literals as an operand
    # (ops/literals.py) reads them here; without, they are constants
    operands: Optional[L.Operands] = None

    # -- filter literals ------------------------------------------------------
    def literals(self, f):
        """What leaf filter ``f`` compares against, converted on the host
        (``literals.leaf_literals``): one entry per literal, None for an
        absent bound; None when the leaf has no scalar literals. Traced
        scalars where the program takes them as an operand, else Python
        constants."""
        if self.operands is not None:
            got = self.operands.of(f)
            if got is not None:
                return got
        lits = L.leaf_literals(f, self.ds, self.tz)
        return None if lits is None else tuple(
            None if x is None else x[0] for x in lits)

    def interval_literals(self, intervals):
        """[(day_lo, ms_lo, day_hi, ms_hi)] of the residual time mask."""
        if self.operands is not None:
            got = self.operands.of_intervals()
            if got is not None:
                return got
        return L.interval_literals(intervals, self.ds, self.min_day,
                                   self.max_day)

    # -- device array access --------------------------------------------------
    def col(self, name: str):
        if name not in self.arrays:
            raise KeyError(
                f"column {name!r} not bound into this scan program "
                f"(bound: {sorted(self.arrays)})")
        arr = self.arrays[name]
        dt = getattr(arr, "dtype", None)
        if dt is not None and dt.kind == "i" and dt.itemsize < 4:
            # narrow storage (i8/i16 codes and small longs) widens on
            # read: HBM holds the narrow bytes, kernels see i32
            arr = arr.astype(jnp.int32)
        return arr

    def row_valid(self):
        return self.arrays[ROW_VALID_KEY]

    def time_ms(self):
        return self.arrays.get(TIME_MS_KEY)

    def null_valid(self, name: str):
        """Validity mask for a nullable column, or None if non-nullable."""
        return self.arrays.get(NULL_VALID_PREFIX + name)

    # -- host metadata --------------------------------------------------------
    def kind(self, name: str) -> ColumnKind:
        return self.ds.column_kind(name)

    def is_time(self, name: str) -> bool:
        return self.ds.time is not None and name == self.ds.time.name

    def dictionary(self, name: str) -> np.ndarray:
        return self.ds.dims[name].dictionary

    def date_bounds(self, name: str):
        """(min_day, max_day) for a TIME or DATE column — bounds any
        granularity/extraction bucket cardinality."""
        if self.is_time(name):
            return self.min_day, self.max_day
        m = self.ds.metrics[name]
        lo, hi = m.min, m.max
        return int(lo if lo is not None else 0), int(hi if hi is not None else 0)


@dataclasses.dataclass
class CompactScanContext(ScanContext):
    """Late-materialization view over a parent scan: after the filter
    mask is evaluated on the full [S, R] arrays, the survivors are brought
    to a static [M] prefix in ascending row order (``compact_scan``) and
    every later column access reads that prefix — so group-key building,
    value derivation, and aggregation all run at O(M) instead of O(N).
    This is the columnar-engine move Druid's historicals make with
    bitmap-index row lists; the TPU form keeps shapes static via a
    planner-chosen budget with on-device overflow detection (the
    survivor count travels; the host re-runs under a budget that holds).

    Two forms serve a read, one per context, chosen by ``carries_by_sort``
    from the static shapes:

    - ``taken``: the arrays rode the compaction sort as payloads; a read
      is the first M entries of its sorted operand. An array that did not
      ride raises — there is no silent gather behind it.
    - ``keep``: int32 [M] flat row positions; each array is gathered
      through it on first read (one 1D [M]-probe gather an array, ~8 ns a
      probe on a v5e: the form for a budget far under the scanned rows).

    Both hold the same rows in the same order, so whatever is computed
    from them is bit-identical."""

    keep: object = None                # int32 [M] flat row positions
    taken: Optional[Dict[str, object]] = None   # name -> [M] sorted prefix

    def __post_init__(self):
        self._cache = {}

    def _gather(self, name: str, arr):
        if self.taken is not None:
            if name not in self.taken:
                raise LookupError(
                    f"{name!r} is read after compaction but did not ride "
                    f"the compaction sort (carried: {sorted(self.taken)})")
            return self.taken[name]
        hit = self._cache.get(name)
        if hit is None:
            flat = arr.reshape(-1)
            hit = self._cache[name] = flat[self.keep]
        return hit

    def carried(self):
        """(form, arrays brought to the prefix so far) for the statement
        record's ``compact_carry`` / ``compact_cols``."""
        if self.taken is not None:
            return "sort", len(self.taken)
        return "gather", len(self._cache)

    def col(self, name: str):
        return self._gather(name, super().col(name))

    def row_valid(self):
        return self._gather(ROW_VALID_KEY, super().row_valid())

    def time_ms(self):
        t = super().time_ms()
        return None if t is None else self._gather(TIME_MS_KEY, t)

    def null_valid(self, name: str):
        nv = super().null_valid(name)
        return None if nv is None else self._gather(
            NULL_VALID_PREFIX + name, nv)


@dataclasses.dataclass
class Compaction:
    """One program's late materialization: the survivor budget and unit
    costs it was built under (``carries_by_sort``) and, once it has been
    traced, how the survivors' arrays reached the prefix — what the
    statement record says of it."""

    m: int
    payload_row_s: float
    probe_s: float
    carry: Optional[str] = None        # "sort" | "gather"
    cols: int = 0                      # arrays that rode or were gathered


@dataclasses.dataclass
class _ReadRecorder(CompactScanContext):
    """Stands in for the compacted context while the post-compaction body
    is traced once under ``jax.eval_shape``: remembers the key of every
    array the body reads, in order, and answers with [M] zeros of the
    dtype ``_gather`` is handed."""

    m: int = 0

    def __post_init__(self):
        self.reads: Dict[str, None] = {}

    def _gather(self, name: str, arr):
        self.reads[name] = None
        return jnp.zeros((self.m,), arr.dtype)


def _full_width(ctx: ScanContext, key: str):
    """The array under ``key`` as the compacted accessors hand it to
    ``_gather`` (narrow codes widened: decoding stays where it is)."""
    if key == ROW_VALID_KEY:
        return ctx.row_valid()
    if key == TIME_MS_KEY:
        return ctx.time_ms()
    if key.startswith(NULL_VALID_PREFIX):
        return ctx.null_valid(key[len(NULL_VALID_PREFIX):])
    return ctx.col(key)


def carries_by_sort(n_rows: int, m: int, payload_row_s: float,
                    probe_s: float) -> bool:
    """Which form brings one array's survivors to the [M] prefix cheaper:
    riding the compaction sort over all ``n_rows`` scanned rows (a further
    sort operand, ``sort.payload.seconds.per.row``) or a gather of ``m``
    probes (``gather.seconds.per.probe``). Static shapes and the backend's
    unit costs only — on a v5e's constants the sort carries where
    ``m * 13 > n_rows`` (measured there, PR 29: q3's five columns out of
    4.0M rows into 2^20 as payloads 12.2 ms, gathered 57; into 2^15 out of
    6.0M 25.2 against 5.9); on the CPU fallback's, where
    ``m > n_rows * 50``: never, for a budget is at most half the rows."""
    return n_rows * payload_row_s < m * probe_s


def compact_scan(ctx: ScanContext, mask, compact_m: int, body,
                 payload_row_s: float, probe_s: float):
    """The ONE late-materialization stanza. ``mask`` is the survivor mask
    over ``ctx``'s full-width arrays, ``body(cctx, base)`` what the
    program goes on to compute from the compacted context. Returns
    ``(cctx, base, n_live)``: the compacted context, the [M] mask of its
    live rows, and how many rows survived ``mask`` (the caller reports
    it: over ``compact_m`` the prefix dropped rows and the host runs the
    statement again under a budget that holds them; under it, the count
    is what the shape's next budget is sized from).

    One int32 key, ``row + N * dead``, sorts the survivors first and each
    side in ascending row order. It is unique, so the sort needs no
    stability — a stable sort rides a hidden index operand, 5.8 against
    2.6 ms over 4.0M rows on a v5e — and it is its own row index. In the
    payload form (``carries_by_sort``) ``body`` is traced once against a
    recorder — under ``jax.eval_shape``, so that pass leaves nothing in
    the program — to find the arrays that ride: what only the cheap
    filter read stays behind. Booleans ride as int8."""
    flat = mask.reshape(-1)
    n, m = flat.shape[0], int(compact_m)
    if 2 * n > np.iinfo(np.int32).max:
        raise ValueError(f"late materialization over {n} rows a shard: "
                         f"the int32 sort key holds 2^30")
    okey = jnp.arange(n, dtype=jnp.int32) \
        + jnp.where(flat, jnp.int32(0), jnp.int32(n))
    n_live = jnp.sum(flat.astype(jnp.int32))
    # survivors sort first, so which prefix rows are live needs no read
    base = jnp.arange(m, dtype=jnp.int32) < n_live
    parent = (ctx.ds, ctx.arrays, ctx.min_day, ctx.max_day, ctx.tz,
              ctx.operands)
    if not carries_by_sort(n, m, payload_row_s, probe_s):
        sidx = jax.lax.slice_in_dim(
            jax.lax.sort(okey, is_stable=False), 0, m)
        keep = jnp.where(sidx >= n, sidx - n, sidx)
        return CompactScanContext(*parent, keep=keep), base, n_live
    rec = _ReadRecorder(*parent, m=m)
    jax.eval_shape(lambda: body(rec, jnp.zeros((m,), jnp.bool_)))
    ride = [_full_width(ctx, key).reshape(-1) for key in rec.reads]
    _, *rode = jax.lax.sort(
        (okey, *(a.astype(jnp.int8) if a.dtype == jnp.bool_ else a
                 for a in ride)), num_keys=1, is_stable=False)
    taken = {}
    for key, a, s in zip(rec.reads, ride, rode):
        s = jax.lax.slice_in_dim(s, 0, m)
        taken[key] = s != 0 if a.dtype == jnp.bool_ else s
    return CompactScanContext(*parent, taken=taken), base, n_live


def array_names(ds: Datasource, columns, need_time_ms: bool):
    """The array keys a scan program over ``columns`` binds."""
    names = list(columns)
    for name in columns:
        # metadata-only nulls check: building the stacked validity here
        # (the old spelling) would fault whole columns on a tiered store
        # just to PLAN the array list
        col = ds.dims.get(name) or ds.metrics.get(name)
        if col is not None and col.has_nulls():
            names.append(NULL_VALID_PREFIX + name)
    if need_time_ms and ds.time is not None:
        names.append(TIME_MS_KEY)
    names.append(ROW_VALID_KEY)
    return names


def array_dtype(ds: Datasource, key: str):
    """Host dtype of one stacked array (shape-only program tracing)."""
    if key == ROW_VALID_KEY or key.startswith(NULL_VALID_PREFIX):
        return np.bool_
    if key == TIME_MS_KEY:
        return ds.time.ms_dtype()
    if key in ds.dims:
        return ds.dims[key].data_dtype()
    if key in ds.metrics:
        return ds.metrics[key].data_dtype()
    if ds.time is not None and key == ds.time.name:
        return ds.time.data_dtype()
    return np.int32


def _stacked_by_key(ds: Datasource, key: str) -> np.ndarray:
    """The [S, R] stacked tensor behind one array key (S = local segments
    on a multi-host partial store)."""
    if key == ROW_VALID_KEY:
        return ds.stacked_row_validity()
    if key == TIME_MS_KEY:
        return ds.stacked_time_ms()
    if key.startswith(NULL_VALID_PREFIX):
        return ds.stacked_null_validity(key[len(NULL_VALID_PREFIX):])
    return ds.stacked(key)


def build_array(ds: Datasource, key: str,
                segment_indices: Optional[np.ndarray] = None,
                pad_segments_to: Optional[int] = None) -> np.ndarray:
    """Materialize one host-side stacked array by key.

    ``segment_indices`` selects (pruned) segments; ``pad_segments_to`` pads
    the segment axis with empty segments so the compiled program shape is
    stable across prunings (compile-cache friendliness) and divisible by the
    mesh size.
    """
    tb = getattr(ds, "_tier_build", None)
    if tb is not None:
        # tiered store: fault only the requested segments' chunks into
        # the stacked layout (tier/handles.py). Encoded chunks decode
        # inside the fault (tier/store.py), so this path returns
        # logical-dtype rows either way — the device never sees packed
        # bytes. None means the key is metadata-only (row validity) —
        # fall through to the base path.
        out = tb(key, segment_indices, pad_segments_to)
        if out is not None:
            return out
    if ds.is_partial:
        # global segment ids -> local block (only this host's segments may
        # be requested; the multi-host layout guarantees that). The
        # "all segments" default means the LOCAL set here — the only set
        # this process can materialize.
        idx = ds.local_seg_ids if segment_indices is None \
            else np.asarray(segment_indices, np.int64)
        arr = build_array_blocks(ds, key, idx)
    else:
        arr = _stacked_by_key(ds, key)
        if segment_indices is not None and (
                len(segment_indices) != ds.num_segments
                or not np.array_equal(segment_indices,
                                      np.arange(ds.num_segments))):
            arr = arr[segment_indices]
    if pad_segments_to is not None and arr.shape[0] < pad_segments_to:
        pad = np.zeros((pad_segments_to - arr.shape[0],) + arr.shape[1:],
                       dtype=arr.dtype)
        arr = np.concatenate([arr, pad], axis=0)
    return arr


def build_array_blocks(ds: Datasource, key: str,
                       seg_ids: np.ndarray) -> np.ndarray:
    """[len(seg_ids), R] host block for a multi-host layout slice: global
    segment ids; ``-1`` entries are padding (zero rows, row-validity
    False). On a partial store, a non-padding id not held locally is a
    layout bug and raises (the callback must never fabricate remote
    data)."""
    seg_ids = np.asarray(seg_ids, np.int64)
    arr = _stacked_by_key(ds, key)
    if ds.is_partial:
        pos = np.where(
            seg_ids >= 0,
            ds._local_pos[np.clip(seg_ids, 0, ds.num_segments - 1)], -1)
        missing = (seg_ids >= 0) & (pos < 0)
        if missing.any():
            raise RuntimeError(
                f"host {ds.host_id} asked for non-local segments "
                f"{seg_ids[missing][:8].tolist()} of {ds.name!r}")
    else:
        pos = seg_ids
    out = np.zeros((len(seg_ids),) + arr.shape[1:], dtype=arr.dtype)
    ok = pos >= 0
    if ok.any():
        out[ok] = arr[pos[ok]]
    return out


def required_arrays(ds: Datasource, columns, need_time_ms: bool,
                    segment_indices: Optional[np.ndarray] = None,
                    pad_segments_to: Optional[int] = None) -> Dict[str, np.ndarray]:
    """Materialize every host-side stacked array a program needs."""
    return {k: build_array(ds, k, segment_indices, pad_segments_to)
            for k in array_names(ds, columns, need_time_ms)}
