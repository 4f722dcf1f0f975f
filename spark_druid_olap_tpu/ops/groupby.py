"""Dense group-by aggregation kernels with TPU-exact integer numerics.

The compute heart of the engine — the in-tree replacement for Druid's
historical-node groupBy/timeseries engine (the reference ships
``GroupByQuerySpec``/``TimeSeriesQuerySpec`` JSON to Druid,
``DruidQuerySpec.scala:638-744``; the actual scan/aggregate loop was never in
the repo. Here it is). Druid's aggregators are exact longs/doubles
(``DruidQuerySpec.scala:283-377``); matching that on a TPU — where f64 is
unsupported and i64 is emulated — is the point of the routing below.

Design (TPU-first):

- Group keys are **fused dictionary codes**: ``key = ((c0*card1)+c1)*card2+...``
  — dense in ``[0, K)`` because dictionaries are global and sorted. No hashing,
  no dynamic shapes.
- For small/medium K the kernel is a **blocked one-hot matmul**: scan over row
  blocks, ``acc += onehot(key).T @ values`` — sums/counts ride the MXU at f32
  throughput. min/max use masked VPU reductions per block.
- For large K it falls back to XLA ``segment_sum`` (scatter-add).
- Filtered-out rows get the sentinel key ``K`` which one-hot-misses every
  column (matmul path) / lands in a dropped overflow slot (scatter path):
  filtering is free, never a compaction.
- The output is a fixed-shape ``[K]``-family partial per chip — the shape ICI
  collectives want (replacing the reference's historical->broker HTTP merge,
  ``DruidStrategy.scala:349-360`` + ``PostAggregate.aggOp``).

Numeric routes (planned statically per aggregation by :func:`plan_route`):

- ``f64``   — CPU with x64: plain f64 accumulation, exact. One output array.
- ``ff``    — f32 backend (TPU): per-block sums + **compensated (Kahan)
  cross-block carry**. Outputs ``<name>.acc`` / ``<name>.c``; the true total
  is ``acc + c`` combined in f64 on host. Exact for integers when every block
  partial is exactly representable (guaranteed by the lane/route choice);
  ~1e-7-relative for floats (in-block MXU rounding only — the carry removes
  cross-block error growth).
- ``lanes`` — wide integers on the f32 matmul path: values split into four
  8-bit lanes, one matmul column per lane (block lane sums < 2^24 => exact
  f32), Kahan carries per lane, host combine ``sum(lane_l << 8l)`` => exact
  int64 totals up to ~2^47.
- ``limbs`` — integers on the scatter path: values split into 16-bit lanes,
  row-chunked i32 ``segment_sum`` (chunk partials bounded < 2^31), partials
  decomposed into four 16-bit limbs accumulated in i32 over a ``lax.scan``,
  renormalized with carry propagation. Host combine => exact int64. Renormed
  limbs are < 2^16, so cross-chip ``psum`` in i32 is exact for <= 2^15 chips.
- ``i32`` / ``f32`` — min/max/anyvalue in the value's own dtype with
  I32_MAX/I32_MIN / +-F32_MAX empty-group sentinels. Never round-trips an
  integer through f32 (the storage dtype for LONG/DATE/codes is i32, so i32
  compares are exact).

Cross-chip merge: routes with ``merged=True`` (limbs, i32/f32 min-max, f64)
merge on-device via psum/pmin/pmax inside shard_map; ``ff``/``lanes`` pairs
would lose low bits in an f32 psum, so they are returned **per chip**
(out_spec along the segment axis) and combined exactly in f64 on host — the
analog of the reference's historical-mode Spark-side final aggregate.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32_MAX = jnp.float32(3.4e38)
I32_MAX = np.int32(2**31 - 1)
I32_MIN = np.int32(-(2**31))
I64_MAX = np.int64(2**63 - 1)
I64_MIN = np.int64(-(2**63))
N_LIMBS = 4
N_LANES = 4
FFL_LANES = 128              # 'ffl' route: per-VPU-lane compensated pairs
_CHUNK_ROWS = 1 << 14        # scatter-path row chunk: 2^16 * 2^14 < 2^31


def _x64() -> bool:
    return bool(jax.config.jax_enable_x64) and jax.default_backend() == "cpu"


@dataclasses.dataclass
class AggInput:
    """One lowered aggregation: kind in {'count','sum','min','max'} — and,
    in the hashed tier's cores only, 'hll' (``values`` are what is
    hashed, ``log2m`` the sketch's precision; ops/sorted_groupby.py);
    ``values`` is the [S, R] input (None for count); ``mask`` an optional
    per-agg filter mask (filtered aggregations, reference
    FilteredAggregationSpec). ``is_int``/``maxabs`` are static metadata
    driving the numeric route (column min/max from segment metadata).
    ``same_in_group`` says every row of a group holds the same value (an
    FD-demoted grouping column, the planner's ``anyvalue``): a core that
    has a group's rows side by side may read one of them instead of
    reducing them all (ops/sorted_groupby.py does)."""

    name: str
    kind: str
    values: Optional[object] = None
    mask: Optional[object] = None
    is_int: bool = False
    maxabs: Optional[float] = None
    same_in_group: bool = False
    log2m: int = 0


@dataclasses.dataclass(frozen=True)
class Route:
    """Static numeric route for one aggregation (see module docstring)."""

    name: str
    kind: str                 # count|sum|min|max|hll (hashed tier: the
    #                           finished estimate, one int32 a slot)
    tag: str                  # f64|i64|ff|lanes|limbs|i32|f32
    n_lanes: int = 1
    merged: bool = True       # device-collective merge vs per-chip host merge

    def outputs(self, n_keys: int):
        """[(output_name, flat_length, dtype_str)] this route emits."""
        if self.tag == "f64":
            return [(self.name, n_keys, "f64")]
        if self.tag == "i64":
            return [(self.name, n_keys, "i64")]
        if self.tag == "ff":
            return [(self.name + ".acc", n_keys, "f32"),
                    (self.name + ".c", n_keys, "f32")]
        if self.tag == "ffl":
            # fused-pallas sums: one compensated (acc, c) pair PER VPU
            # LANE — the 128-lane reduction happens in f64 on host, so
            # per-lane exactness is all the kernel must guarantee
            return [(self.name + ".acc", n_keys * FFL_LANES, "f32"),
                    (self.name + ".c", n_keys * FFL_LANES, "f32")]
        if self.tag == "lanes":
            return [(self.name + ".acc", n_keys * self.n_lanes, "f32"),
                    (self.name + ".c", n_keys * self.n_lanes, "f32")]
        if self.tag == "limbs":
            return [(self.name + ".limbs", n_keys * N_LIMBS, "i32")]
        if self.tag == "s64":
            # sorted-run wide int sums (ops/sorted_groupby.py): an exact
            # 64-bit total as an (hi: i32, lo: u32-bitcast-i32) limb pair
            return [(self.name + ".hi", n_keys, "i32"),
                    (self.name + ".lo", n_keys, "i32")]
        if self.tag == "i32":
            return [(self.name, n_keys, "i32")]
        return [(self.name, n_keys, "f32")]


def choose_path(n_keys: int, matmul_max: int) -> str:
    """'matmul' (one-hot MXU) vs 'scatter' (XLA segment ops)."""
    if _x64():
        # x64 only happens off-TPU; scatter keeps native-i64 sums exact at
        # any magnitude (and CPU BLAS loses to scatter-add anyway)
        return "scatter"
    if jax.default_backend() == "cpu" and n_keys > 64:
        # the one-hot matmul only pays off on the MXU; CPU BLAS loses badly
        # to vectorized scatter-add at moderate K (TPC-H q9 on CPU: 31x)
        return "scatter"
    return "matmul" if n_keys <= matmul_max else "scatter"


def plan_route(name: str, kind: str, is_int: bool, maxabs: Optional[float],
               path: str, blk: int,
               n_rows: Optional[int] = None) -> Route:
    """Decide the numeric route for one aggregation. Static — callable at
    plan time (no traced values)."""
    if kind in ("min", "max"):
        if _x64():
            # native-64-bit compares: i64 exact for wide ints, f64 for
            # doubles; 32-bit backends keep the i32/f32 routes
            return Route(name, kind, "i64" if is_int else "f64")
        return Route(name, kind, "i32" if is_int else "f32")
    if _x64():
        # native-i64 sums are exact at any magnitude; f64 for doubles
        return Route(name, kind, "i64" if (is_int or kind == "count")
                     else "f64")
    if path == "scatter":
        if kind == "count" or is_int:
            if n_rows is not None and maxabs is not None \
                    and maxabs * n_rows < 2**31:
                # the WHOLE table's contribution fits i32: one exact
                # scatter-add pass, no limb splitting/chunk scan (the
                # q18-class hot path — sum(l_quantity) over 1.5M keys)
                return Route(name, kind, "i32")
            return Route(name, kind, "limbs")
        return Route(name, kind, "ff", merged=False)
    # matmul path
    if kind == "count":
        # mask contributes 1.0 per row; block sums <= blk < 2^24 => exact
        return Route(name, kind, "ff", merged=False)
    if is_int:
        if maxabs is not None and maxabs * blk < 2**24:
            return Route(name, kind, "ff", merged=False)
        return Route(name, kind, "lanes", n_lanes=N_LANES, merged=False)
    return Route(name, kind, "ff", merged=False)


def plan_routes(inputs: Sequence[AggInput], n_keys: int,
                matmul_max: int, pallas_max: int = 0,
                n_rows: Optional[int] = None) -> Dict[str, Route]:
    path = choose_path(n_keys, matmul_max)
    blk = _block_size(n_keys, 1 << 30)
    use_pallas = False
    if pallas_max:
        from spark_druid_olap_tpu.ops import pallas_groupby as PG
        use_pallas = PG.eligible(n_keys, inputs, pallas_max,
                                 n_rows=n_rows)
    out = {}
    for a in inputs:
        if use_pallas and a.kind in ("sum", "count"):
            # the fused kernel's sums travel as per-lane Kahan pairs
            out[a.name] = Route(a.name, a.kind, "ffl", merged=False)
        else:
            out[a.name] = plan_route(a.name, a.kind, a.is_int, a.maxabs,
                                     path, blk, n_rows=n_rows)
    return out


def run_weighted_partials(run_values, run_lengths, n_keys: int,
                          run_sums=None) -> Dict[str, np.ndarray]:
    """RLE-aware host partials: aggregate run-at-a-time instead of
    row-at-a-time. A run of length L with key k contributes L to
    count[k] in one add — the count partial IS the run length — and a
    pre-reduced per-run metric sum lands in sum[k] the same way, so a
    group-by over an RLE-encoded dimension touches O(runs) values
    (encode/exec.py:rle_groupby drives this over encoded chunks; keys
    outside [0, n_keys) — filtered sentinels — drop, matching the
    device kernels' overflow-slot semantics). Exact: counts accumulate
    in int64, sums in f64."""
    counts = np.zeros(n_keys, dtype=np.int64)
    out = {"count": counts}
    run_values = np.asarray(run_values)
    run_lengths = np.asarray(run_lengths, dtype=np.int64)
    if len(run_lengths) == 0:
        if run_sums is not None:
            out["sum"] = np.zeros(n_keys, dtype=np.float64)
        return out
    keep = (run_values >= 0) & (run_values < n_keys)
    v = run_values[keep].astype(np.int64)
    np.add.at(counts, v, run_lengths[keep])
    if run_sums is not None:
        sums = np.zeros(n_keys, dtype=np.float64)
        np.add.at(sums, v, np.asarray(run_sums, dtype=np.float64)[keep])
        out["sum"] = sums
    return out


def fuse_keys(code_arrays: Sequence[object], cards: Sequence[int]):
    """Fuse per-dim codes into one dense int32 key in [0, prod(cards))."""
    assert len(code_arrays) == len(cards) and len(cards) > 0
    key = code_arrays[0].astype(jnp.int32)
    for codes, card in zip(code_arrays[1:], cards[1:]):
        key = key * jnp.int32(card) + codes.astype(jnp.int32)
    total = 1
    for c in cards:
        total *= int(c)
    return key, total


def unfuse_key(indices, cards: Sequence[int]):
    """Host-side inverse of fuse_keys: group index -> per-dim codes."""
    out = []
    rem = np.asarray(indices, dtype=np.int64)
    for card in reversed(list(cards)):
        out.append(rem % card)
        rem = rem // card
    return list(reversed(out))


# =============================================================================
# host-side combine of route outputs -> final numpy values
# =============================================================================

def combine_route(route: Route, out: Dict[str, np.ndarray],
                  n_keys: int) -> np.ndarray:
    """Route outputs (possibly with a leading per-chip axis for unmerged
    routes in sharded mode) -> one exact [n_keys] f64/i64-valued array.

    min/max sentinels are preserved (caller maps them to null)."""
    def chips(x, cols=1):
        x = np.asarray(x)
        return x.reshape(-1, n_keys * cols)      # [n_chips, K*cols]

    if route.tag == "f64":
        return np.asarray(out[route.name], np.float64)
    if route.tag == "i64":
        return np.asarray(out[route.name], np.int64)
    if route.tag == "ff":
        acc = chips(out[route.name + ".acc"]).astype(np.float64)
        c = chips(out[route.name + ".c"]).astype(np.float64)
        return (acc + c).sum(axis=0)
    if route.tag == "ffl":
        acc = chips(out[route.name + ".acc"], FFL_LANES).astype(np.float64)
        c = chips(out[route.name + ".c"], FFL_LANES).astype(np.float64)
        return (acc + c).sum(axis=0).reshape(n_keys, FFL_LANES).sum(axis=1)
    if route.tag == "lanes":
        ln = route.n_lanes
        acc = chips(out[route.name + ".acc"], ln).astype(np.float64)
        c = chips(out[route.name + ".c"], ln).astype(np.float64)
        tot = (acc + c).sum(axis=0).reshape(n_keys, ln)
        scale = np.float64(256.0) ** np.arange(ln)
        return tot @ scale
    if route.tag == "s64":
        hi = np.asarray(out[route.name + ".hi"]).astype(np.int64)
        lo = np.asarray(out[route.name + ".lo"]).view(np.uint32) \
            .astype(np.int64)
        return (hi << 32) | lo
    if route.tag == "limbs":
        limbs = np.asarray(out[route.name + ".limbs"]) \
            .reshape(n_keys, N_LIMBS).astype(np.int64)
        val = np.zeros(n_keys, dtype=np.int64)
        carry = np.zeros(n_keys, dtype=np.int64)
        for i in range(N_LIMBS):
            v = limbs[:, i] + carry
            if i < N_LIMBS - 1:
                carry = v >> 16
                val += (v & 0xFFFF) << (16 * i)
            else:
                val += v << (16 * i)
        return val
    return np.asarray(out[route.name])


def int_lanes8(v):
    """Split i32 values into four 8-bit lanes (top lane signed)."""
    v = v.astype(jnp.int32)
    return [(v & 0xFF).astype(jnp.float32),
            ((v >> 8) & 0xFF).astype(jnp.float32),
            ((v >> 16) & 0xFF).astype(jnp.float32),
            (v >> 24).astype(jnp.float32)]


# =============================================================================
# kernels
# =============================================================================

def dense_groupby(key, mask, n_keys: int, inputs: List[AggInput],
                  routes: Dict[str, Route],
                  matmul_max: int = 4096) -> Dict[str, object]:
    """Aggregate ``inputs`` grouped by dense ``key`` under ``mask``.

    key: int32 [S, R] (or any shape); mask: bool same shape (row validity &
    query filter already folded in). Returns dict output_name -> array per
    each route's ``outputs`` contract. Callers must include a '__rows__'
    count input (used to drop empty groups — Druid groupBy only emits
    existing groups).
    """
    key = jnp.where(mask, key, jnp.int32(n_keys))
    path = choose_path(n_keys, matmul_max)

    if any(r.tag == "ffl" for r in routes.values()):
        # plan_routes is the single source of truth for the fused-kernel
        # decision (it assigns 'ffl' to every sum/count iff eligible);
        # re-deriving eligibility here from local shapes could disagree
        # with the planned route set
        from spark_druid_olap_tpu.ops import pallas_groupby as PG
        flat = PG.pallas_dense_groupby(key, n_keys, [
            dataclasses.replace(
                a, values=None if a.values is None
                else a.values.reshape(-1),
                mask=None if a.mask is None else a.mask.reshape(-1))
            for a in inputs])
        return _pallas_to_routes(flat, inputs, routes)
    if path == "scatter":
        return _scatter_groupby(key, mask, n_keys, inputs, routes)
    return _matmul_groupby(key.reshape(-1), mask.reshape(-1), n_keys,
                           inputs, routes)


def _pallas_to_routes(flat: Dict[str, object], inputs: List[AggInput],
                      routes: Dict[str, Route]) -> Dict[str, object]:
    """Adapt the pallas kernel's outputs to the route contract: sums and
    counts arrive as [K, 128] per-lane Kahan (acc, comp) pairs for the
    'ffl' route; min/max arrive as reduced [K] f32 (exact under the
    eligible() gate, so route-dtype conversion is lossless)."""
    out: Dict[str, object] = {}
    for a in inputs:
        r = routes[a.name]
        v = flat[a.name]
        if r.tag == "ffl":
            acc, comp = v                        # [K, 128] each
            out[r.name + ".acc"] = acc.reshape(-1)
            out[r.name + ".c"] = comp.reshape(-1)  # Neumaier: acc + comp
        elif r.tag == "i32":
            big = jnp.abs(v) >= F32_MAX
            iv = jnp.clip(v, -2.0**31 + 1, 2.0**31 - 1).astype(jnp.int32)
            sent = I32_MAX if r.kind == "min" else I32_MIN
            out[r.name] = jnp.where(big, jnp.int32(sent), iv)
        elif r.tag == "f64":
            if r.kind in ("min", "max"):
                # kernel empty-group sentinel (+-3.4e38) -> the f64
                # route's +-inf sentinel, or the group would decode as a
                # huge value instead of NULL
                big = jnp.abs(v) >= F32_MAX
                sent = jnp.inf if r.kind == "min" else -jnp.inf
                out[r.name] = jnp.where(big, sent, v.astype(jnp.float64))
            else:
                out[r.name] = v.astype(jnp.float64)
        elif r.tag == "i64":
            big = jnp.abs(v) >= F32_MAX          # empty-group f32 sentinel
            sent = I64_MAX if r.kind == "min" else I64_MIN
            out[r.name] = jnp.where(
                big, sent, jnp.round(v).astype(jnp.int64))
        else:
            out[r.name] = v
    return out


def _block_size(n_keys: int, n: int) -> int:
    # keep the onehot block around ~16M f32 elements
    target = max(1024, (1 << 24) // max(n_keys, 1))
    target = min(target, 1 << 16)
    return int(min(n, (target // 1024) * 1024 or 1024))


def _matmul_groupby(key, mask, n_keys, inputs, routes):
    n = key.shape[0]
    blk = _block_size(n_keys, n)
    nb = -(-n // blk)
    padded = nb * blk
    x64 = _x64()
    sum_dtype = jnp.float64 if x64 else jnp.float32

    def prep(arr, fill, dtype=None):
        arr = arr.reshape(-1)
        if dtype is not None:
            arr = arr.astype(dtype)
        if padded > n:
            arr = jnp.pad(arr, (0, padded - n), constant_values=fill)
        return arr.reshape(nb, blk)

    keys = prep(key, n_keys)
    masks = prep(mask, False)

    # Sum-matmul columns: each (agg, lane). count contributes its mask as
    # 1.0; 'lanes' aggs contribute 4 byte-lane columns.
    sum_aggs = [a for a in inputs if a.kind in ("sum", "count")]
    minmax = [a for a in inputs if a.kind in ("min", "max")]
    col_of = {}              # agg name -> (start_col, n_lanes)
    sum_cols = []            # list of [nb, blk] f32/f64 value blocks
    sum_masks = []           # matching effective-mask blocks
    col_is_count = []        # static per-column flag
    for a in sum_aggs:
        r = routes[a.name]
        am = masks if a.mask is None else prep(a.mask, False)
        start = len(sum_cols)
        if a.kind == "count":
            col_of[a.name] = (start, 1)
            sum_cols.append(masks)             # placeholder; mask is value
            sum_masks.append(am)
            col_is_count.append(True)
        elif r.tag == "lanes":
            col_of[a.name] = (start, r.n_lanes)
            for lane in int_lanes8(a.values):
                sum_cols.append(prep(lane, 0, sum_dtype))
                sum_masks.append(am)
                col_is_count.append(False)
        else:
            col_of[a.name] = (start, 1)
            sum_cols.append(prep(a.values, 0, sum_dtype))
            sum_masks.append(am)
            col_is_count.append(False)
    m_cols = len(sum_cols)

    mm_route = [routes[a.name] for a in minmax]
    _mm_dt = {"i32": jnp.int32, "f64": jnp.float64}
    mm_vals = [prep(a.values, 0,
                    _mm_dt.get(routes[a.name].tag, jnp.float32))
               for a in minmax]
    mm_masks = [prep(a.mask, False) if a.mask is not None else masks
                for a in minmax]

    iota = jnp.arange(n_keys, dtype=jnp.int32)

    def body(carry, xs):
        k_blk, m_blk, svals, smasks, mvals, mmasks = xs
        onehot = (k_blk[:, None] == iota[None, :])               # [blk, K]
        acc_sums, comp, acc_min, acc_max = carry
        if m_cols:
            cols = []
            for is_cnt, v, am in zip(col_is_count, svals, smasks):
                eff = am & m_blk
                if is_cnt:
                    cols.append(eff.astype(sum_dtype))
                else:
                    cols.append(v * eff.astype(sum_dtype))
            x = jnp.stack(cols, axis=1)                          # [blk, M]
            blk_sums = jax.lax.dot(onehot.astype(sum_dtype).T, x,
                                   preferred_element_type=sum_dtype)
            if x64:
                acc_sums = acc_sums + blk_sums
            else:
                # Kahan: exact carries keep integer totals exact (block
                # sums are exactly representable by route construction)
                y = blk_sums - comp
                t = acc_sums + y
                comp = (t - acc_sums) - y
                acc_sums = t
        new_min, new_max = list(acc_min), list(acc_max)
        for i, (r, v, am) in enumerate(zip(mm_route, mvals, mmasks)):
            eff = am & m_blk
            sel = onehot & eff[:, None]
            if r.tag == "i32":
                lo_s, hi_s = I32_MIN, I32_MAX
            elif r.tag == "f64":
                lo_s, hi_s = -jnp.inf, jnp.inf
            else:
                lo_s, hi_s = -F32_MAX, F32_MAX
            if r.kind == "min":
                cur = jnp.min(jnp.where(sel, v[:, None], hi_s), axis=0)
                new_min[i] = jnp.minimum(acc_min[i], cur)
            else:
                cur = jnp.max(jnp.where(sel, v[:, None], lo_s), axis=0)
                new_max[i] = jnp.maximum(acc_max[i], cur)
        return (acc_sums, comp, new_min, new_max), None

    sval_xs = sum_cols

    def mm_init(r, kind):
        if r.tag == "i32":
            fill = I32_MAX if kind == "min" else I32_MIN
            return jnp.full((n_keys,), fill, dtype=jnp.int32)
        if r.tag == "f64":
            fill = jnp.inf if kind == "min" else -jnp.inf
            return jnp.full((n_keys,), fill, dtype=jnp.float64)
        fill = F32_MAX if kind == "min" else -F32_MAX
        return jnp.full((n_keys,), fill, dtype=jnp.float32)

    init = (jnp.zeros((n_keys, m_cols), dtype=sum_dtype),
            jnp.zeros((n_keys, m_cols), dtype=sum_dtype),
            [mm_init(r, "min") for r in mm_route],
            [mm_init(r, "max") for r in mm_route])
    (sums, comp, mins, maxs), _ = jax.lax.scan(
        body, init, (keys, masks, sval_xs, sum_masks, mm_vals, mm_masks))

    out: Dict[str, object] = {}
    for a in sum_aggs:
        r = routes[a.name]
        start, nl = col_of[a.name]
        if r.tag == "f64":
            out[r.name] = sums[:, start]
        else:
            acc = sums[:, start: start + nl]
            c = -comp[:, start: start + nl]     # true sum = acc - comp
            if nl == 1:
                acc, c = acc[:, 0], c[:, 0]
            else:
                acc, c = acc.reshape(-1), c.reshape(-1)
            out[r.name + ".acc"] = acc
            out[r.name + ".c"] = c
    for i, a in enumerate(minmax):
        out[a.name] = mins[i] if a.kind == "min" else maxs[i]
    return out


def _kahan_axis0(arr):
    """Compensated sum over axis 0 of [S, K] f32 -> (acc, c) with
    true total == acc + c (f64-combined on host)."""
    def step(carry, row):
        acc, comp = carry
        y = row - comp
        t = acc + y
        comp = (t - acc) - y
        return (t, comp), None

    init = (jnp.zeros(arr.shape[1:], arr.dtype),
            jnp.zeros(arr.shape[1:], arr.dtype))
    (acc, comp), _ = jax.lax.scan(step, init, arr)
    return acc, -comp


def _scatter_groupby(key, mask, n_keys, inputs, routes):
    """Large-K path: XLA segment ops per route (see module docstring)."""
    out: Dict[str, object] = {}
    num = n_keys + 1  # overflow slot for masked-out rows
    if key.ndim == 1:
        key = key[None, :]
        mask = mask[None, :]
    x64 = _x64()

    def seg2d(a):
        return a.reshape(key.shape)

    def seg_sum(a, am, dtype):
        """Masked per-segment scatter-add in ``dtype``, summed across
        segments: the one shared body of the i32/i64/f64 sum routes."""
        if a.values is None:                 # count: the mask is the value
            v = am.astype(dtype)
        else:
            v = jnp.where(am, seg2d(a.values).astype(dtype),
                          jnp.zeros((), dtype))
        per = jax.vmap(lambda x, k: jax.ops.segment_sum(x, k, num))(v, key)
        return per.sum(axis=0)[:n_keys]

    for a in inputs:
        r = routes[a.name]
        am = mask if a.mask is None else (mask & seg2d(a.mask))
        if r.tag in ("f64", "i64") and r.kind in ("min", "max"):
            if r.tag == "i64":
                sent = I64_MAX if r.kind == "min" else I64_MIN
                v = jnp.where(am, seg2d(a.values).astype(jnp.int64), sent)
            else:
                sent = jnp.inf if r.kind == "min" else -jnp.inf
                v = jnp.where(am, seg2d(a.values).astype(jnp.float64), sent)
            op = jax.ops.segment_min if r.kind == "min" \
                else jax.ops.segment_max
            per = jax.vmap(lambda x, k: op(x, k, num))(v, key)
            red = per.min(axis=0) if r.kind == "min" else per.max(axis=0)
            out[r.name] = red[:n_keys]
        elif r.tag == "i64":
            # native 64-bit sums: exact at any magnitude (x64 backends only)
            out[r.name] = seg_sum(a, am, jnp.int64)
        elif r.tag == "f64":
            out[r.name] = seg_sum(a, am, jnp.float64)
        elif r.tag == "i32" and r.kind in ("sum", "count"):
            # single-pass exact i32 scatter-add (static bound
            # maxabs * total_rows < 2^31 — no limb splitting needed)
            out[r.name] = seg_sum(a, am, jnp.int32)
        elif r.tag == "limbs":
            ones = jnp.ones(key.shape, jnp.int32)
            v = ones if a.kind == "count" else seg2d(a.values) \
                .astype(jnp.int32)
            v = jnp.where(am, v, 0)
            k_eff = jnp.where(am, key, jnp.int32(n_keys))
            out[r.name + ".limbs"] = _limb_scatter_sum(v, k_eff, n_keys)
        elif r.tag == "ff":
            v = seg2d(a.values).astype(jnp.float32) * am.astype(jnp.float32)
            per_seg = jax.vmap(lambda x, k: jax.ops.segment_sum(x, k, num))(
                v, key)
            acc, c = _kahan_axis0(per_seg[:, :n_keys])
            out[r.name + ".acc"] = acc
            out[r.name + ".c"] = c
        elif r.kind == "min":
            if r.tag == "i32":
                v = jnp.where(am, seg2d(a.values).astype(jnp.int32), I32_MAX)
                dt_min = jax.vmap(
                    lambda x, k: jax.ops.segment_min(x, k, num))(v, key)
                out[r.name] = dt_min.min(axis=0)[:n_keys]
            else:
                v = jnp.where(am, seg2d(a.values).astype(jnp.float32),
                              F32_MAX)
                per = jax.vmap(
                    lambda x, k: jax.ops.segment_min(x, k, num))(v, key)
                out[r.name] = per.min(axis=0)[:n_keys]
        elif r.kind == "max":
            if r.tag == "i32":
                v = jnp.where(am, seg2d(a.values).astype(jnp.int32), I32_MIN)
                per = jax.vmap(
                    lambda x, k: jax.ops.segment_max(x, k, num))(v, key)
                out[r.name] = per.max(axis=0)[:n_keys]
            else:
                v = jnp.where(am, seg2d(a.values).astype(jnp.float32),
                              -F32_MAX)
                per = jax.vmap(
                    lambda x, k: jax.ops.segment_max(x, k, num))(v, key)
                out[r.name] = per.max(axis=0)[:n_keys]
        else:
            raise ValueError(f"route {r.tag}/{r.kind}")
    return out


def renorm_limbs(l0, l1, l2, l3):
    """Propagate carries so limbs 0..2 land in [0, 2^16) (top limb signed,
    two's-complement correct for negative totals). Needed after a psum of
    independently-renormalized per-chip limbs."""
    c0 = l0 >> 16
    l0 = l0 & 0xFFFF
    l1 = l1 + c0
    c1 = l1 >> 16
    l1 = l1 & 0xFFFF
    l2 = l2 + c1
    c2 = l2 >> 16
    l2 = l2 & 0xFFFF
    l3 = l3 + c2
    return l0, l1, l2, l3


def literal_limbs(v: int):
    """The four 16-bit limbs of a python int in the renormalized layout
    (limbs 0..2 unsigned, top limb signed/arithmetic)."""
    v = int(v)
    return ((v & 0xFFFF), (v >> 16) & 0xFFFF, (v >> 32) & 0xFFFF, v >> 48)


def limbs_compare(limbs, lit: int, op: str):
    """Exact device comparison of renormalized limb totals vs an int
    literal: lexicographic from the signed top limb down (lower limbs are
    unsigned, so per-limb i32 compares are exact at any total magnitude).
    ``limbs`` is [n_keys, 4]; returns bool [n_keys]."""
    l = renorm_limbs(limbs[:, 0], limbs[:, 1], limbs[:, 2], limbs[:, 3])
    t = literal_limbs(lit)
    eq = None
    gt = None
    for i in (3, 2, 1, 0):
        li = l[i]
        ti = jnp.int32(t[i])
        gi = li > ti
        ei = li == ti
        if gt is None:
            gt, eq = gi, ei
        else:
            gt = gt | (eq & gi)
            eq = eq & ei
    if op == ">":
        return gt
    if op == ">=":
        return gt | eq
    if op == "<":
        return ~(gt | eq)
    if op == "<=":
        return ~gt
    if op == "=":
        return eq
    return ~eq                                     # '!='


def _limb_scatter_sum(values, key, n_keys: int):
    """Exact 64-bit grouped integer sum without i64/f64: 16-bit value lanes,
    row-chunked i32 segment_sums, 16-bit limb accumulation over a scan.

    values: i32 [S, R] (masked rows already 0); key: i32 [S, R] (masked rows
    at sentinel n_keys). Returns renormalized i32 limbs flat [n_keys*4]
    (limbs 0..2 in [0, 2^16), top limb signed).
    """
    num = n_keys + 1
    total = int(np.prod(values.shape))
    rc = min(_CHUNK_ROWS, total)
    n_chunks = -(-total // rc)
    pad = n_chunks * rc - total
    v = values.reshape(-1)
    k = key.reshape(-1)
    if pad:
        v = jnp.pad(v, (0, pad))
        k = jnp.pad(k, (0, pad), constant_values=n_keys)
    v = v.reshape(n_chunks, rc)
    k = k.reshape(n_chunks, rc)

    renorm = renorm_limbs

    def step(limbs, xs):
        vc, kc = xs
        lo = vc & 0xFFFF                       # [rc] in [0, 2^16)
        hi = vc >> 16                          # signed
        p_lo = jax.ops.segment_sum(lo, kc, num)   # < 2^30
        p_hi = jax.ops.segment_sum(hi, kc, num)   # |.| < 2^29
        l0 = limbs[0] + (p_lo & 0xFFFF)
        l1 = limbs[1] + (p_lo >> 16) + (p_hi & 0xFFFF)
        l2 = limbs[2] + (p_hi >> 16)
        # per-step renorm keeps every limb < 2^16 regardless of chunk
        # count, so no row-count ceiling (carries land in the top limb)
        return list(renorm(l0, l1, l2, limbs[3])), None

    init = [jnp.zeros(num, jnp.int32) for _ in range(N_LIMBS)]
    limbs, _ = jax.lax.scan(step, init, (v, k))
    stacked = jnp.stack(list(renorm(*limbs)), axis=1)   # [num, 4]
    return stacked[:n_keys].reshape(-1)


def route_score(route: Route, out: Dict[str, object], n_keys: int,
                axis_name: Optional[str] = None):
    """Device-side per-key value of one aggregation reconstructed from its
    route outputs — the *selection* score for top-k epilogues.

    Exact for f64/i64/i32/f32 routes; f32-rounded (~1e-7 relative) for the
    split-representation routes (ff pairs, byte lanes, 16-bit limbs). The
    final ordering of the selected candidates is still done with the exact
    host combine, so rounding here only affects which keys make the
    candidate set — callers add slack beyond the requested limit. Inside
    shard_map pass ``axis_name``: per-chip partial routes (merged=False)
    are psum'd to the global value; merged routes are already global.
    """
    t = route.tag
    if t in ("f64", "i64"):
        return out[route.name].astype(
            jnp.float64 if _x64() else jnp.float32)
    if t == "ffl":
        v = (out[route.name + ".acc"] + out[route.name + ".c"]) \
            .reshape(n_keys, FFL_LANES).sum(axis=1)
    elif t == "ff":
        v = out[route.name + ".acc"] + out[route.name + ".c"]
    elif t == "lanes":
        acc = out[route.name + ".acc"].reshape(n_keys, route.n_lanes)
        c = out[route.name + ".c"].reshape(n_keys, route.n_lanes)
        scale = jnp.float32(256.0) ** jnp.arange(
            route.n_lanes, dtype=jnp.float32)
        v = ((acc + c) * scale[None, :]).sum(axis=1)
    elif t == "limbs":
        limbs = out[route.name + ".limbs"].reshape(n_keys, N_LIMBS) \
            .astype(jnp.float32)
        scale = jnp.float32(65536.0) ** jnp.arange(
            N_LIMBS, dtype=jnp.float32)
        v = (limbs * scale[None, :]).sum(axis=1)
    elif t == "s64":
        hi = out[route.name + ".hi"].astype(jnp.float32)
        lo = jax.lax.bitcast_convert_type(
            out[route.name + ".lo"], jnp.uint32).astype(jnp.float32)
        v = hi * jnp.float32(4294967296.0) + lo
    elif t == "i32":
        v = out[route.name].astype(jnp.float32)
    else:
        v = out[route.name]
    if axis_name is not None and not route.merged:
        v = jax.lax.psum(v, axis_name)
    return v


def route_null_mask(route: Route, out: Dict[str, object]):
    """Device bool mask of keys whose min/max metric is NULL (the
    empty-group sentinel survived: every contributing row was masked by
    the per-agg filter). None for sum/count routes (their NULL identity is
    0 — indistinguishable from a true zero sum by design)."""
    if route.kind not in ("min", "max"):
        return None
    v = out[route.name]
    if route.tag == "i32":
        sent = I32_MAX if route.kind == "min" else I32_MIN
    elif route.tag == "i64":
        sent = I64_MAX if route.kind == "min" else I64_MIN
    elif route.tag == "f64":
        sent = jnp.inf if route.kind == "min" else -jnp.inf
    else:
        sent = F32_MAX if route.kind == "min" else -F32_MAX
    return v == sent


def merge_partials(partials: Dict[str, object], routes: Dict[str, Route],
                   axis_name: str) -> Dict[str, object]:
    """Cross-chip merge of per-chip partials via ICI collectives (inside
    shard_map) for the ``merged`` routes. ≈ the broker merge / Spark-side
    final HashAggregate (reference DruidStrategy.scala:349-360). Unmerged
    (ff/lanes) outputs must be returned per-chip by the caller."""
    out = {}
    for name, arr in partials.items():
        base = name.split(".")[0]
        r = routes.get(base)
        if r is None:
            out[name] = jax.lax.psum(arr, axis_name)
        elif not r.merged:
            out[name] = arr                    # caller keeps per-chip
        elif r.kind == "min":
            out[name] = jax.lax.pmin(arr, axis_name)
        elif r.kind == "max":
            out[name] = jax.lax.pmax(arr, axis_name)
        else:                                  # limbs / f64 / i32 sums
            out[name] = jax.lax.psum(arr, axis_name)
    return out


# Sketch register algebras by sketch family — the runtime source of
# truth the lint pass (tools/sdlint/mergeclosure.py) cross-checks each
# AGG_CLOSURE ``merge`` declaration against. Keep this a plain literal.
SKETCH_MERGE_OPS = {"hll": "max", "theta": "min", "kll": "minsum"}


def merge_lane_partials(out, routes: Dict[str, Route],
                        sketch_kinds: Dict[str, str], axis_name: str):
    """Cross-chip merge of ONE lane's complete output dict — the single
    mergeable-partial layout every sharded program (solo executor cores
    and the mesh execution tier, parallel/meshexec.py) folds with:

    - dense routes via :func:`merge_partials` — exactly the register
      algebra ``AGG_CLOSURE.merge`` declares (``psum`` sums/counts,
      ``pmin``/``pmax`` extrema); unmerged ff/lanes pairs stay per-chip
      for the exact f64 host combine,
    - sketch registers via their own register algebra: HLL rho registers
      are maxima (``hll.merge_registers``), theta k-min registers are
      minima (``theta.merge_registers``), KLL survivor registers are a
      lex-min over (tiebreak, value) plus an exact count psum
      (``kll.merge_registers``) — never plain addition.

    ``sketch_kinds`` maps output name -> "hll" | "theta" | "kll" for the
    lane's register-valued aggregations (algebra per SKETCH_MERGE_OPS).
    """
    from spark_druid_olap_tpu.ops import hll as _hll
    from spark_druid_olap_tpu.ops import kll as _kll
    from spark_druid_olap_tpu.ops import theta as _theta
    dense = {k: v for k, v in out.items() if k not in sketch_kinds}
    merged = merge_partials(dense, routes, axis_name)
    folds = {"hll": _hll.merge_registers, "theta": _theta.merge_registers,
             "kll": _kll.merge_registers}
    for name, sk in sketch_kinds.items():
        merged[name] = folds[sk](out[name], axis_name)
    return merged
