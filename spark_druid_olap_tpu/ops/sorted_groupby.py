"""Sorted-run aggregation for the hashed group-by tier.

The hashed tier's slot assignment (``hash_groupby.build_slots``) already
pays ONE ``lax.sort`` over the fused key pairs. The scatter core then
scatters every aggregation's values into its slot — one XLA scatter per
aggregation, 4.7-4.9 ns an update on a v5e even with sorted unique indices
(5.1 ms per 2^20 rows, 28 ms per 6.0M; my chip run, PR 27), and q18-class
programs stack ~6 of those. This module replaces the scatters entirely:

- **Ride the aggregation values as sort payloads.** After the sort, every
  group's rows are one contiguous run.
- **Sums** become prefix-sum + run-boundary difference. Integer sums run
  in (emulated) int64 — two's-complement prefix wrap-around cancels in
  the difference, so any per-group total that fits i64 is EXACT (wider
  than the 4-limb route's practical range, with no chunked carry scan).
  Counts fit i32 by construction.
- **Float sums** use a SEGMENTED compensated scan (TwoSum carry inside an
  ``associative_scan`` that resets at run starts) — per-group error stays
  ~log2(run) ulps of the GROUP total. A plain prefix-sum difference would
  carry the PREFIX magnitude's cancellation error into small groups,
  which is why the naive version is wrong and this one is not.
- **min/max** use a segmented scan with the same reset flag — unless the
  planner says every row of a group holds the same value
  (``AggInput.same_in_group``: an FD-demoted grouping column, ``anyvalue``):
  then the run's last row holds the final as it is and nothing is scanned.
  A segmented scan is ``log2(n)`` unrolled levels of slices, pads and
  concatenations; TPC-H Q18's outer statement carried four of them over
  8.0 M rows, and the chip's compiler did not finish that program inside
  a benchmark run's 20 minutes (``PERF.md`` §6, PR 33).
- **Per-group finals** sit at each run's LAST row, and a row is the last
  of its run iff the next row starts one — known without any search. A
  second ``lax.sort`` keyed on the run-last rows' group id compacts them
  to the front in group order, carrying the key parts and each
  aggregation's scanned column; the ``[T]`` table is the first ``T``
  entries (prefix-sum routes take the adjacent difference after the
  compaction). Work after the first sort is O(n) whatever ``T``; where the
  table is under 1/64 of the rows the row index rides alone and ``T``-wide
  takes read the columns (``_run_last_to_front``).

What it costs on one v5e at q3's shape — n = 2^20 rows (``compact_m``),
T = 2^21 slots, three aggregations (max, max, sum ``ff``), six table
columns (all: my chip runs, PR 27; "trace" = device time per q3 in the
benchmark's traced ``adhoc_seq`` run, "micro" = the op alone, host clock
around ``block_until_ready``):

- first sort, 2 keys + 3 payloads: 4.4 ms (micro, random keys), 2.4-2.5
  ms (trace); a 2-operand sort of 2^20 rows 2.0 ms; each further operand
  0.3 ms per 2^20 rows, 0.6 ns a row at 6.0M rows (the cost model's
  ``sort.payload.seconds.per.row`` 6.7e-10 holds);
- ``cumsum`` 0.8 ms, one segmented max scan 2.9 ms (micro);
- the compaction sort, 1 key + 6 columns: 4.0 ms (micro), 2.5 ms (trace);
  the whole program 71.3 ms a q3 then, 51 of them late materialization's
  six gathers of 2^20 survivors out of 4.0M rows, before this module
  runs; since PR 29 those columns ride late materialization's own sort
  (``ops.scan.compact_scan``: 15 ms a q3, trace) and the program takes
  29 ms;
- what it replaced, a binary search for the run end of EVERY slot
  (``T`` x 21 rounds of ``T``-probe gathers in a ``fori_loop``, then six
  ``[T]``-wide takes): 316 ms + 139 ms of q3's 524 ms (trace; 501 ms as a
  micro) — 7.1 ns a probe, as ``gather.seconds.per.probe`` said (7e-9
  then; 9e-9 since PR 29, fit at sorted positions). It
  was written for ``T << n`` ("log2(N) x T probes versus N scatter
  updates"); late materialization sizes ``T`` at twice the rows that
  survive it, so the search ran over two million slots of which 99.4 %
  were empty. At n = 6.0M, T = 2^14 the same search took 9.8 ms, the index
  way here 10.5 ms, carrying all six columns 33.3 ms (micro).

- **HLL sketches** (kind ``hll``; PR 35) never become registers. The
  core already orders rows by group: the sketch's coupon — ``register <<
  b | rho`` over a few more bits of the hash
  (``ops.hll.packed_registers``) — is a THIRD sort key, so inside a group's
  run the rows of one register lie together with the largest rho last
  and equal values side by side, and the group's ``sum of 2^-rho``, its
  count of live registers and its count of distinct coupons are three
  more prefix sums read at the run ends like any integer sum
  (``ops.hll.run_sums``); the estimate is computed on the ``[T]`` table
  (``ops.hll.estimate_sums``) and one int32 a slot travels. One sketch
  rides the main sort; each further one pays a three-key sort of its own
  — the groups' runs lie at the same rows in every such sort (the same
  keys in the same order, whatever lies inside a run), so its three columns
  are read at the main sort's run ends too. An estimate is final: nothing
  merges two of them, so the executor runs such a program on one chip in
  one wave.

Outputs keep the hashed tier's existing contracts (``groupby.Route``
outputs / ``combine_route`` / host key-wise merge): ``i32`` for counts
and provably-in-range int sums, the new ``s64`` hi/lo pair for wide int
sums, the ``ff`` (acc, c) pair for float sums, ``i32``/``f32``(/x64
``i64``/``f64``) sentinel min-max. Table keys/'__unres__' match
``build_slots`` exactly (sorted occupied prefix, EMPTY padding).

Backend economics: on TPU one sort operand costs about a tenth of one
scatter of the same rows (above), so this path wins whenever >=1
aggregation exists; the CPU fallback's x64 sort is the expensive op
(~0.3s/M rows measured) while its scatters are cheap, so the executor
gates this to TPU backends (config-overridable — tests force it on CPU for
differential coverage).

≈ reference scope: the groupBy v2 per-segment aggregation the reference
delegated to Druid historicals (``DruidQuerySpec.scala:638-683``); the
sort-based formulation is original TPU design.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from spark_druid_olap_tpu.ops import hash_groupby as H
from spark_druid_olap_tpu.ops import hll as HLL
from spark_druid_olap_tpu.ops.groupby import (
    AggInput,
    F32_MAX,
    I32_MAX,
    I32_MIN,
    I64_MAX,
    I64_MIN,
    Route,
    _x64,
)

SUPPORTED_KINDS = ("count", "sum", "min", "max", "hll")


def plan_sorted_routes(inputs: List[AggInput],
                       n_rows: Optional[int] = None) -> Optional[Dict[str, Route]]:
    """Routes for the sorted-run core, or None when some aggregation kind
    is outside its reach (theta, KLL -> caller keeps the scatter path).
    Static — callable at plan time."""
    out: Dict[str, Route] = {}
    for a in inputs:
        if a.kind not in SUPPORTED_KINDS:
            return None
        if a.kind == "hll":
            out[a.name] = Route(a.name, a.kind, "i32")
        elif a.kind in ("min", "max"):
            if _x64():
                out[a.name] = Route(a.name, a.kind,
                                    "i64" if a.is_int else "f64")
            else:
                out[a.name] = Route(a.name, a.kind,
                                    "i32" if a.is_int else "f32")
        elif a.kind == "count":
            out[a.name] = Route(a.name, a.kind,
                                "i64" if _x64() else "i32")
        elif a.is_int:
            if _x64():
                out[a.name] = Route(a.name, a.kind, "i64")
            elif n_rows is not None and a.maxabs is not None \
                    and a.maxabs * n_rows < 2**31:
                out[a.name] = Route(a.name, a.kind, "i32")
            else:
                out[a.name] = Route(a.name, a.kind, "s64")
        else:
            out[a.name] = Route(a.name, a.kind,
                                "f64" if _x64() else "ff", merged=False)
    return out


# Past this many rows a segmented scan runs as a loop of doubling shifts
# (``_seg_scan_doubling``), not as ``associative_scan``'s unrolled tree:
# the chip's compiler takes 1-2 min for a program with one such tree over
# 2^20 rows (q3, q10: their late-materialized prefixes) and did not finish
# one over 8.0 M rows in a quarter of an hour (PR 35; four of them: PR 33).
_SCAN_TREE_MAX_ROWS = 1 << 21


def _seg_scan(flag, vals, combine_vals, read=None):
    """Segmented scan: inclusive scan of ``vals`` that RESETS wherever
    ``flag`` is True (run starts). Classic associative segmented-scan
    lifting: op((f1,v1),(f2,v2)) = (f1|f2, f2 ? v2 : combine(v1,v2)).
    ``read``: the rows whose result anyone reads (None: all of them);
    the doubling form need not finish a run of the others."""
    if flag.shape[0] > _SCAN_TREE_MAX_ROWS:
        return _seg_scan_doubling(flag, vals, combine_vals, read)

    def op(a, b):
        fa, va = a[0], a[1:]
        fb, vb = b[0], b[1:]
        keep_b = fb
        merged = combine_vals(va, vb)
        vals_out = tuple(jnp.where(keep_b, y, m)
                         for y, m in zip(vb, merged))
        return (fa | fb,) + vals_out

    res = jax.lax.associative_scan(op, (flag,) + tuple(vals))
    return res[1:]


def _seg_scan_doubling(flag, vals, combine_vals, read=None):
    """``_seg_scan``'s result by Hillis-Steele doubling: after the round
    with shift ``d`` row ``i`` holds its run's rows ``(i - 2d, i]``
    combined, so ``ceil(log2(longest run))`` rounds of one shifted,
    masked ``combine`` each finish every run — a ``while_loop`` whose
    body the compiler sees once, and whose trip count the data set: 5
    rounds where the longest run has 30 rows, 23 for one run of 8.0 M.
    The longest run is taken over the rows that are ``read``: the
    sorted-run core's invalid and padding rows sort last as ONE run that
    nobody reads (2.0 M of SF1's 8.0 M rows, more under a filter — 21
    rounds by itself), and is left unfinished.
    O(n log(longest run)) work where the tree does O(n); the same
    associative ``combine`` over the same rows in the same order, grouped
    differently (floats: the same compensated sum to its last few ulps)."""
    n = flag.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    start = jax.lax.cummax(jnp.where(flag, pos, 0))
    length = pos - start if read is None else jnp.where(read, pos - start, 0)
    longest = jnp.max(length) + 1

    def body(carry):
        d, vals = carry
        # x[i - d] at i; what wraps around lies before its run's start
        merged = combine_vals(tuple(jnp.roll(v, d) for v in vals), vals)
        inside = pos - d >= start
        return d * 2, tuple(jnp.where(inside, m, v)
                            for m, v in zip(merged, vals))

    return jax.lax.while_loop(lambda c: c[0] < longest, body,
                              (jnp.int32(1), tuple(vals)))[1]


def _two_sum(a, b):
    """Knuth TwoSum: s + e == a + b exactly (f32)."""
    s = a + b
    bb = s - a
    e = (a - (s - bb)) + (b - bb)
    return s, e


def _cumsum64(v32):
    """Inclusive prefix sum of i32 values in TRUE 64-bit on a 32-bit
    backend (jnp.int64 silently canonicalizes to i32 there): the value is
    a (hi: i32, lo: u32) limb pair combined with add-with-carry in an
    associative scan. 64-bit limb addition is associative, so the scan is
    exact; the run-boundary difference then subtracts with borrow."""
    lo = v32.astype(jnp.uint32)
    hi = jnp.where(v32 < 0, jnp.int32(-1), jnp.int32(0))

    def op(a, b):
        ahi, alo = a
        bhi, blo = b
        slo = alo + blo                       # u32 wrap
        carry = (slo < alo).astype(jnp.int32)
        return ahi + bhi + carry, slo

    return jax.lax.associative_scan(op, (hi, lo))


def _sub64(ahi, alo, bhi, blo):
    """(a - b) on (hi i32, lo u32) pairs, with borrow."""
    lo = alo - blo
    borrow = (alo < blo).astype(jnp.int32)
    return ahi - bhi - borrow, lo


def _sentinel(kind: str, tag: str):
    """What an unoccupied slot of a min/max route holds."""
    if tag == "i32":
        return I32_MAX if kind == "min" else I32_MIN
    if tag == "i64":
        return I64_MAX if kind == "min" else I64_MIN
    if tag == "f64":
        return jnp.float64(np.inf if kind == "min" else -np.inf)
    return F32_MAX if kind == "min" else -F32_MAX


# Below this share of the rows the table is small enough that carrying the
# row index alone through the compaction sort and reading the columns with
# [T]-wide takes beats carrying every column (v5e, PR 27: a column costs
# the sort 0.3-0.6 ns a row, a take ~18 ns a probe; at n = 6.0M, T = 2^14
# the index way took 10.5 ms, the carry-all way 33.3 ms).
_TAKE_BELOW = 64


def _run_last_to_front(keep, gid, cols, T: int):
    """The ``[T]`` table columns: ``cols`` read at the run-last rows
    (``keep``), in gid order. ONE ``lax.sort`` keyed on the kept rows' gid
    (unique among them, so stability is moot; every other row keys
    INT32_MAX and sorts behind) brings them to the front — carrying the
    columns themselves, or, where the table is a small share of the rows,
    only the row index for ``T`` takes. The table is the first ``T``
    entries, zero-padded where ``T > n`` (the caller masks everything past
    the occupied prefix). No search: work is O(n), whatever ``T``."""
    n = gid.shape[0]
    key = jnp.where(keep, gid, I32_MAX)
    if T * _TAKE_BELOW <= n:
        _, pos = jax.lax.sort((key, jnp.arange(n, dtype=jnp.int32)),
                              num_keys=1, is_stable=False)
        pos = jax.lax.slice_in_dim(pos, 0, T)
        return [jnp.take(c, pos) for c in cols]
    moved = jax.lax.sort((key,) + tuple(cols), num_keys=1,
                         is_stable=False)[1:]
    if T <= n:
        return [jax.lax.slice_in_dim(c, 0, T) for c in moved]
    return [jnp.concatenate([c, jnp.zeros((T - n,), c.dtype)])
            for c in moved]


def _shift1(c):
    """``c[g-1]`` at ``g`` (0 at ``g == 0``): the previous run's end."""
    return jnp.concatenate([jnp.zeros((1,), c.dtype), c[:-1]])


def sorted_hash_groupby(khi, klo, valid, T: int, inputs: List[AggInput],
                        routes: Dict[str, Route]) -> Dict[str, object]:
    """Sorted-run hashed group-by: returns the same output dict the
    ``build_slots`` + ``dense_groupby`` pair produces — route outputs per
    ``Route.outputs(T)`` plus '__tkhi__', '__tklo__', '__unres__'."""
    x64 = _x64()
    base = valid.reshape(-1)
    khi_f = jnp.where(base, khi.reshape(-1).astype(jnp.int32), H.EMPTY)
    klo_f = jnp.where(base, klo.reshape(-1).astype(jnp.int32), H.EMPTY)

    # payloads: pre-masked per-agg value vectors (masking BEFORE the sort
    # keeps the per-agg filter masks off the sort operand list)
    payloads = []
    packed = []
    for a in inputs:
        r = routes[a.name]
        am = base if a.mask is None else (base & a.mask.reshape(-1))
        if a.kind == "hll":
            packed.append(HLL.packed_registers(a.values, am, a.log2m))
            continue
        if a.kind == "count":
            payloads.append(am.astype(jnp.int32))
            continue
        v = a.values.reshape(-1)
        if a.kind in ("min", "max"):
            dt = {"i32": jnp.int32, "i64": jnp.int64,
                  "f64": jnp.float64}.get(r.tag, jnp.float32)
            v = jnp.where(am, v.astype(dt), _sentinel(a.kind, r.tag))
        elif r.tag in ("i32", "s64", "i64"):
            v = jnp.where(am, v.astype(
                jnp.int64 if (x64 and r.tag == "i64") else jnp.int32), 0)
        else:
            v = jnp.where(am, v.astype(
                jnp.float64 if r.tag == "f64" else jnp.float32), 0.0)
        payloads.append(v)

    # the first sketch's packed registers are the main sort's third key;
    # every further sketch sorts (group, its registers) by itself. No
    # aggregation here reads the order of equal keys, and the chip's
    # compiler takes under half as long for a sort that need not keep it
    # (three keys, four operands, 8.0 M rows: 59 s against 127; PR 35):
    # a program with a sketch asks for none (the others' sorts stay as
    # the accepted cells compiled them)
    riding = packed[:1]
    ops = jax.lax.sort((khi_f, klo_f) + tuple(riding) + tuple(payloads),
                       num_keys=2 + len(riding), is_stable=not riding)
    skh, skl = ops[0], ops[1]
    n = skh.shape[0]
    sorted_packed = iter(list(ops[2: 2 + len(riding)]) + [
        jax.lax.sort((khi_f, klo_f, s), num_keys=3, is_stable=False)[2]
        for s in packed[1:]])
    values = iter(ops[2 + len(riding):])

    new = (skh != jnp.roll(skh, 1)) | (skl != jnp.roll(skl, 1))
    new = new.at[0].set(True)
    gid = jnp.cumsum(new.astype(jnp.int32)) - 1
    occupied_row = skh != H.EMPTY
    unresolved = jnp.sum((occupied_row & (gid >= T)).astype(jnp.int32))
    # a row is the LAST of its run iff the next row starts one: no search.
    # Invalid rows sort last as one trailing pseudo-group and are not kept.
    run_end = jnp.roll(new, -1).at[n - 1].set(True)
    keep = run_end & occupied_row

    # per-row scanned columns; a group's final sits at its run-last row.
    # ``plan`` remembers which table columns each aggregation reads.
    cols = [skh, skl]
    plan = []
    for a in inputs:
        r = routes[a.name]
        at = len(cols)
        if a.kind == "hll":
            cols += HLL.run_sums(next(sorted_packed), run_end, a.log2m)
            plan.append((a, r, "hll", at))
            continue
        v = next(values)
        if a.kind in ("min", "max"):
            if a.same_in_group:
                # the run's rows agree: its last row holds the final
                cols.append(v)
            else:
                pick = jnp.minimum if a.kind == "min" else jnp.maximum
                cols += _seg_scan(
                    new, (v,), lambda x, y, pick=pick: (pick(x[0], y[0]),),
                    occupied_row)
            how = "final"
        elif r.tag == "i32":
            # wrap-exact mod 2^32: per-group totals fit i32 by the route
            # gate, so the two's-complement prefix difference is exact
            cols.append(jnp.cumsum(v.astype(jnp.int32)))
            how = "diff"
        elif r.tag == "i64":
            # x64 CPU: native 64-bit prefix sums, exact at any magnitude
            cols.append(jnp.cumsum(v.astype(jnp.int64)))
            how = "diff"
        elif r.tag == "s64":
            cols += _cumsum64(v.astype(jnp.int32))
            how = "diff64"
        elif r.tag == "f64":
            cols += _seg_scan(new, (v,), lambda x, y: (x[0] + y[0],),
                              occupied_row)
            how = "final"
        else:
            # float sums: segmented COMPENSATED scan — (sum, err) pairs
            # combined with TwoSum so the error term never carries the
            # prefix magnitude into a small group's total
            def comb(xa, xb):
                s, e = _two_sum(xa[0], xb[0])
                return (s, e + xa[1] + xb[1])
            cols += _seg_scan(new, (v, jnp.zeros_like(v)), comb,
                              occupied_row)
            how = "ff"
        plan.append((a, r, how, at))

    tab = _run_last_to_front(keep, gid, cols, T)
    # occupied groups are exactly the first sum(keep) gids
    g_occ = jnp.arange(T, dtype=jnp.int32) < jnp.sum(keep.astype(jnp.int32))

    out: Dict[str, object] = {}
    for a, r, how, at in plan:
        if how == "final":
            fill = _sentinel(a.kind, r.tag) if a.kind in ("min", "max") \
                else 0.0
            out[r.name] = jnp.where(g_occ, tab[at], fill)
        elif how == "diff":
            # cumulative value at this run's end minus the previous run's
            out[r.name] = jnp.where(g_occ, tab[at] - _shift1(tab[at]), 0)
        elif how == "hll":
            out[r.name] = jnp.where(g_occ, HLL.estimate_sums(
                *(tab[at + i] - _shift1(tab[at + i]) for i in range(3)),
                a.log2m), 0)
        elif how == "diff64":
            thi, tlo = _sub64(tab[at], tab[at + 1],
                              _shift1(tab[at]), _shift1(tab[at + 1]))
            out[r.name + ".hi"] = jnp.where(g_occ, thi, 0)
            out[r.name + ".lo"] = jax.lax.bitcast_convert_type(
                jnp.where(g_occ, tlo, jnp.uint32(0)), jnp.int32)
        else:
            out[r.name + ".acc"] = jnp.where(g_occ, tab[at], 0.0)
            out[r.name + ".c"] = jnp.where(g_occ, tab[at + 1], 0.0)

    out["__tkhi__"] = jnp.where(g_occ, tab[0], H.EMPTY)
    out["__tklo__"] = jnp.where(g_occ, tab[1], H.EMPTY)
    out["__unres__"] = unresolved.reshape(1)
    return out
