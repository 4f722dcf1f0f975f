"""HyperLogLog approximate count-distinct, grouped, on device.

Druid-parity capability: the reference pushes ``count(distinct x)`` down as a
``cardinality``/``hyperUnique`` aggregation (``AggregationSpec``
``DruidQuerySpec.scala:340-360``, planner side
``AggregateTransform.ApproximateCountAggregate:454-479``); the sketch itself
ran inside Druid. This module is that sketch engine:

- hash: murmur3 finalizer over int32 dictionary codes / values (VPU ops);
- register index = low ``p`` bits, rho = leading-zero count of the remaining
  bits (``lax.clz``) + 1;
- grouped register maxima, in one of three forms that hold the same
  maxima (``register_form`` chooses from the static shapes and the
  backend's unit costs). Two are DENSE — a ``[K, m]`` int32 block over
  the fused ``group_key * m + register`` slot space, which travels to the
  host (or between chips, or from a historical to its broker) and is
  estimated or merged there:

  - ``sort``: ``slot * 2^b + rho`` is ONE int32 key; sorted ascending (one
    operand, no stability), the last element of each slot's run is that
    slot's maximum, and the run ends are found by a binary search on the
    slot boundaries — a sort of N rows and ``slots * log2 N`` probes. What
    a TPU takes wherever the scanned rows far outnumber the slots: its
    scatter is serial in its updates (6.8 ns a row on a v5e: 54.6 ms for
    8.0M rows into 16k slots, against 8.2 ms for the sort and the search
    together; ``scripts/micro_hll.py``);
  - ``scatter``: one ``segment_max`` of every row — what the CPU fallback
    takes (its sort costs ~75 scatter updates a row), and what every
    backend takes for a small input, or a slot space so large that the
    search outweighs the scan or the packed key does not fit 31 bits;

  and one is SPARSE — no slot of the block exists that no row touched:

  - ``sparse``: rows sorted by ``(group, register, rho)`` (two int32
    operands: the group, and the value's COUPON — ``register * 2^b +
    rho`` over the bits of the hash an int32 has left,
    ``packed_registers``); the last row of each ``(group, register)``
    run holds that register's maximum, so a group's ``sum of 2^-rho``
    over its live registers, the count of them and the count of its
    distinct coupons are run-boundary differences of three integer
    prefix sums — no scatter, no ``[K, m]`` block — and the estimate is
    computed where the sums are, on the device (``estimate_sums``). One
    integer a group travels. The hashed tier's form, where a statement
    goes whose group count makes the block the cost: 10,000 groups at
    2^14 registers are 655 MB of registers a sketch column, 150,000 are
    9.8 GB. It needs the whole of every group in one place, so a program
    that merges partial registers (the dense tier's: waves, chips, a
    cluster's historicals) keeps a dense form;
- the estimate: harmonic mean with the standard small-range (linear
  counting) and large-range corrections — on the host in float64 over a
  dense block (``estimate``), on the device in float32 over the sparse
  form's sums (``estimate_sums``). The sparse form, which has every row
  of a group sorted by its hash, answers a SMALL group — up to
  ``coupon_limit`` = 3 m / 16 distinct coupons, where HyperLogLog++
  leaves its sparse representation — with the count of its coupons, at
  their ~2^27.6 precision and not the registers' ``m``: a bound held on
  each of 10,000 groups of ~80 values is left by one data set in seven
  at 2^14 registers by the registers' linear counting (four pairs of a
  group's 79 values in one register each: 75), by none of 20 with the
  coupons (``PERF.md`` section 4). The dense forms ship registers, which
  merge, and answer as registers can. The default is 2^11 registers,
  Druid's ``hyperUnique``; ``sdot.engine.hll.log2m`` sets it.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def _murmur_fmix32(x):
    """murmur3 finalizer — avalanches int32 values (uint32 wraparound)."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


class RegisterCosts(NamedTuple):
    """The backend's unit costs ``register_form`` prices the forms with
    (``parallel.cost.unit_cost`` of ``sort.seconds.per.row``,
    ``gather.seconds.per.probe``, ``scatter.seconds.per.update``). Part of
    the signature of every program that takes HLL registers: a ``SET``
    that flips the form re-keys it."""

    sort_row_s: float
    probe_s: float
    scatter_s: float


def _rho_bits(log2m: int) -> int:
    """Bits of the packed key's rho field: rho <= 32 - log2m + 1."""
    return (32 - log2m + 1).bit_length()


# A dense [K, m] int32 block past this many bytes is no form a program
# that may take the sparse one considers: the block is fetched whole and
# estimated by numpy on every send (49 ns a register on the builder's
# host: 0.2 s for the 2^22 this allows; 3,000 groups at 2^14 registers
# took 9.4 s a send on the CPU), and 150,000 groups at 2^14 are 9.8 GB,
# over half a v5e's memory.
DENSE_BLOCK_MAX_BYTES = 1 << 24

# What the sparse form costs a row, in one-operand sorts of that row
# (``sort.seconds.per.row``): measured 2.01-2.11 at 6.0 M and 8.0 M rows
# on a v5e, whatever the groups and log2m (16.0 ms against the packed
# key's 7.8 at 8.0 M rows and 7 groups, 17.7 at 10,000 groups of 2^16
# registers; ``scripts/micro_hll.py``, PR 35) — its two-key, two-operand
# sort is 15.1 of them; the prefix sums, flags and the search fuse into
# the rest.
_SPARSE_ROW_SORTS = 2.1


def register_form(n_rows: int, n_keys: int, log2m: int,
                  costs: RegisterCosts, sparse_ok: bool = False) -> str:
    """``"sort"``, ``"scatter"`` or — only where the caller says the
    statement can take it (``sparse_ok``: every row of a group would
    reach ONE table of the hashed tier, and nobody merges its registers;
    the executor asks for statements of many groups only) —
    ``"sparse"``: the cheapest way to the per-slot maxima of ``n_rows``
    rows over ``(n_keys + 1) * 2^log2m`` slots (the last group is the
    masked rows' sentinel).
    Static shapes and unit costs only.

    The dense two: the sort form costs a one-operand sort of every row
    and ``ceil(log2 n_rows)`` probes a live slot; the scatter an update a
    row. On a v5e's constants the sort wins where ``n_rows`` is over
    ~30-35x the live slots (``acd`` at SF1, 8.0M rows over 16,384 slots:
    priced 5.6 + 3.0 ms against 54, measured 8.2 against 54.6; 2^20
    rows: 2.9 against 7.3) and loses for a [compact_m]-wide input (2^16
    rows: 2.0 against 0.6); on the CPU fallback's never. A slot space
    whose packed key does not fit int32 (past 32,767 groups at log2m 11)
    scatters: there the search alone outweighs any scan an int32 row
    index can hold.

    The sparse form costs ``_SPARSE_ROW_SORTS`` one-operand sorts a row
    and a run-end search over the GROUPS, whatever ``log2m`` is. It is
    the only form where the block passes ``DENSE_BLOCK_MAX_BYTES`` or its
    slot index int32; below that it is priced against the cheaper dense
    form. On a v5e's constants ``acd``'s 7 groups over 8.0 M rows keep
    ``sort`` at 2^11 registers (priced 8.6 ms against 11.8, measured 8.2
    against 16.0) and would price sparse at 2^14 (29.3 against 11.8,
    measured 25.4 against 16.0: the sort form searches every one of
    114,688 slots) — but 7 groups are a dense-tier statement, which is
    never asked with ``sparse_ok`` and keeps ``sort`` there too; a
    thousand groups are sparse at any precision
    (measured 16.2 ms against the scatter's 54.6 and the sort form's
    358). On the CPU fallback's it never wins on price (its sort is ~75
    scatter updates a row)."""
    m = 1 << log2m
    slots = (n_keys + 1) * m
    rounds = max(1, int(n_rows - 1).bit_length())
    scatter_s = n_rows * costs.scatter_s
    sort_s = float("inf") if slots << _rho_bits(log2m) > 1 << 31 else \
        n_rows * costs.sort_row_s + n_keys * m * rounds * costs.probe_s
    dense = "sort" if sort_s < scatter_s else "scatter"
    if not sparse_ok:
        return dense
    if slots * 4 > DENSE_BLOCK_MAX_BYTES:
        return "sparse"
    sparse_s = n_rows * _SPARSE_ROW_SORTS * costs.sort_row_s \
        + (n_keys + 1) * rounds * costs.probe_s
    return "sparse" if sparse_s < min(sort_s, scatter_s) else dense


def _registers_scatter(rho, fused, n_keys: int, m: int):
    regs = jax.ops.segment_max(
        rho, fused, num_segments=(n_keys + 1) * m, indices_are_sorted=False)
    regs = jnp.maximum(regs, 0)           # segment_max fills empty with dtype-min
    return regs[: n_keys * m]


def _registers_sort(rho, fused, n_keys: int, m: int, bits: int):
    packed = jax.lax.sort((fused << bits) | rho, is_stable=False)
    slots = jnp.arange(n_keys * m, dtype=jnp.int32)
    # the element before the first of the next slot is this slot's last,
    # so its largest rho — where this slot has an element at all
    ends = jnp.searchsorted(packed, (slots + 1) << bits, side="left")
    last = packed[jnp.maximum(ends - 1, 0)]
    return jnp.where((ends > 0) & (last >> bits == slots),
                     last & jnp.int32((1 << bits) - 1), jnp.int32(0))


def _hash_parts(values, log2m: int):
    """Per row: (rho, register, the hash's bits above the register's) of
    the value's hash — int32, int32, uint32."""
    m = 1 << log2m
    h = _murmur_fmix32(values.reshape(-1))
    reg = (h & jnp.uint32(m - 1)).astype(jnp.int32)
    w = h >> jnp.uint32(log2m)            # (32 - p) significant bits
    # rho = position of first 1-bit in w within (32-p) bits, 1-based;
    # w == 0 -> (32 - p) + 1
    clz = jax.lax.clz(w.astype(jnp.int32))  # counts over 32 bits
    rho = jnp.where(w == 0, jnp.int32(32 - log2m + 1),
                    clz - jnp.int32(log2m) + 1).astype(jnp.int32)
    return rho, reg, w


def _rho_and_slot(key, mask, values, n_keys: int, log2m: int):
    """Per row: (rho, fused slot ``group * m + register``); a masked-out
    row goes to the sentinel group ``n_keys``."""
    rho, reg, _ = _hash_parts(values, log2m)
    fused = jnp.where(mask.reshape(-1), key.reshape(-1),
                      jnp.int32(n_keys)) * jnp.int32(1 << log2m) + reg
    return rho, fused


@jax.named_scope("sdot_hll_registers")
def hll_registers(key, mask, values, n_keys: int, log2m: int,
                  costs: RegisterCosts):
    """Per-group HLL register maxima.

    key: [N] int32 dense group key (sentinel n_keys for masked-out rows);
    values: [N] int32 (dictionary codes or integer-viewed values);
    costs: the backend's unit costs — with the static shapes they choose
    between the two dense forms (``register_form``), which return the
    same integers.
    Returns int32 [n_keys, m] register array (rho values, 0 = empty).
    """
    m = 1 << log2m
    rho, fused = _rho_and_slot(key, mask, values, n_keys, log2m)
    if register_form(rho.shape[0], n_keys, log2m, costs) == "sort":
        regs = _registers_sort(rho, fused, n_keys, m, _rho_bits(log2m))
    else:
        regs = _registers_scatter(rho, fused, n_keys, m)
    return regs.reshape(n_keys, m)


def _coupon_bits(log2m: int) -> int:
    """Bits of the hash a coupon keeps below its rho field: what an
    int32 >= 0 has left after the register and rho (12 at 2^14
    registers, 15 at 2^11)."""
    return max(0, 31 - log2m - _rho_bits(log2m))


def coupon_limit(log2m: int) -> int:
    """The most distinct coupons a group is COUNTED by (``estimate_sums``):
    HyperLogLog++'s rule for leaving its sparse representation (Heule,
    Nunkesser, Hall 2013, section 5.3) — the coupons, 32 bits each, would
    outgrow the ``m`` six-bit registers: ``3 m / 16`` (3,072 at 2^14
    registers, 384 at 2^11)."""
    return 3 * (1 << log2m) // 16


def packed_registers(values, mask, log2m: int):
    """Per row the sparse form's second sort key, the value's COUPON:
    ``(register << b | rho) << t | low t bits of the hash's rho part``
    (int32, >= 0; b = ``_rho_bits``, t = ``_coupon_bits``), or -1 where
    ``mask`` (None: every row) is off — a run of its own at the head of
    its group that counts for nothing. Sorted, a register's rows lie
    together with the largest rho last, as without the low bits; with
    them two distinct values share a coupon once in ~2^27.6 pairs (the
    register, rho's ~1.6 bits, t bits) where they share a register once
    in ``m``."""
    rho, reg, w = _hash_parts(values, log2m)
    t = _coupon_bits(log2m)
    low = (w & jnp.uint32((1 << t) - 1)).astype(jnp.int32)
    packed = (((reg << _rho_bits(log2m)) | rho) << t) | low
    if mask is None:
        return packed
    return jnp.where(mask.reshape(-1), packed, jnp.int32(-1))


def run_sums(packed, group_end, log2m: int):
    """The sparse form's three per-row prefix sums over rows SORTED by
    (group, ``packed_registers``): ``group_end`` says the next row starts
    another group (the last row says True). A row is the last of its
    (group, register) run iff the next row has another register or
    another group, and then holds that register's maximum; such a row
    adds ``2^(R - rho)`` (R = 32 - log2m + 1, the largest rho, so the
    term is a whole number >= 1) to the first sum and 1 to the second;
    the last row of a run of one coupon adds 1 to the third, the group's
    distinct coupons. All are int32 prefix sums that wrap: a group's own
    totals are the differences at its ends, exact modulo 2^32, and a
    group's first sum is at most ``m * 2^(R-1)`` = 2^32, reached only
    with every register live at rho 1 (``estimate_sums`` reads a 0
    beside ``m`` live registers as that)."""
    bits, t = _rho_bits(log2m), _coupon_bits(log2m)
    nxt = jnp.roll(packed, -1)
    real = packed >= 0
    live = (group_end | ((nxt >> (bits + t)) != (packed >> (bits + t)))) \
        & real
    last = (group_end | (nxt != packed)) & real
    # (a masked row's -1 reads as the largest rho field: no shift for it)
    shift = jnp.maximum(jnp.int32(32 - log2m + 1)
                        - ((packed >> t) & jnp.int32((1 << bits) - 1)), 0)
    term = jnp.where(live, jnp.left_shift(jnp.int32(1), shift), 0)
    return (jnp.cumsum(term), jnp.cumsum(live.astype(jnp.int32)),
            jnp.cumsum(last.astype(jnp.int32)))


def estimate_sums(s, live, coupons, log2m: int):
    """A group's estimate on the device, int32, rounded, from its
    ``sum of 2^(R - rho)`` over its live registers (``s``: the int32
    whose bits are that sum modulo 2^32), their count ``live`` and its
    count of distinct ``coupons``. Up to ``coupon_limit`` coupons the
    estimate IS their count, as HyperLogLog++ counts a sketch still in
    its sparse representation at that one's precision: the registers
    alone cannot tell 79 values of which four pairs share a register
    from 75, and a bound held on each of 10,000 groups of 80 values
    meets such a group on one data set in seven at 2^14 registers
    (``PERF.md`` section 4). Past it, ``estimate``'s three branches.
    float32 where ``estimate`` has float64: ``s``
    rounds to 24 bits (6e-8), the quotient and the logarithm add an ulp
    or two each, so an estimate leaves the host's by about 3e-7 of
    itself — under one unit below 3 million, and a rounding to the other
    neighbour where the host's value lies that close to a half."""
    m = 1 << log2m
    alpha = _alpha(m)
    live_f = live.astype(jnp.float32)
    sf = jax.lax.bitcast_convert_type(s, jnp.uint32).astype(jnp.float32)
    sf = jnp.where((live == m) & (s == 0), jnp.float32(2.0 ** 32), sf)
    zeros = jnp.float32(m) - live_f
    z = sf * jnp.float32(2.0 ** -(32 - log2m + 1)) + zeros
    e = jnp.float32(alpha * m * m) / z
    # m * log(m / zeros), as -m * log1p(-live / m): live / m is exact
    lin = jnp.float32(-m) * jnp.log1p(-live_f / jnp.float32(m))
    e = jnp.where((e <= 2.5 * m) & (live < m), lin, e)
    two32 = jnp.float32(2.0 ** 32)
    big = e > two32 / 30.0
    e = jnp.where(big, -two32 * jnp.log1p(
        -jnp.minimum(e / two32, jnp.float32(1.0 - 2.0 ** -24))), e)
    e = jnp.round(jnp.minimum(e, jnp.float32(2.0 ** 31 - 128))) \
        .astype(jnp.int32)
    return jnp.where(coupons <= coupon_limit(log2m), coupons, e)


@jax.named_scope("sdot_hll_sparse")
def hll_sums(key, mask, values, n_keys: int, log2m: int):
    """The sparse form over a dense group key — the hashed tier's
    scatter core gives its table slots: per group the three totals
    ``estimate_sums`` reads, each int32 [n_keys]. One two-operand sort,
    three prefix sums and a search for the ``n_keys + 1`` group boundaries
    (the sorted-run core, ``ops.sorted_groupby``, has the run ends
    without a search)."""
    packed = packed_registers(values, mask, log2m)
    group = jnp.where(mask.reshape(-1), key.reshape(-1).astype(jnp.int32),
                      jnp.int32(n_keys))
    group, packed = jax.lax.sort((group, packed), num_keys=2,
                                 is_stable=False)
    group_end = (jnp.roll(group, -1) != group).at[-1].set(True)
    sums = run_sums(packed, group_end, log2m)
    # first row of group g, for g = 0 .. n_keys (the sentinel's start)
    starts = jnp.searchsorted(
        group, jnp.arange(n_keys + 1, dtype=jnp.int32), side="left")
    at = jnp.maximum(starts - 1, 0)
    out = []
    for c in sums:
        before = jnp.where(starts > 0, c[at], 0)
        out.append(before[1:] - before[:-1])
    return tuple(out)


def hll_estimates(key, mask, values, n_keys: int, log2m: int):
    """Per-group estimates, int32 [n_keys], in the sparse form: what
    the hashed tier's scatter core ships a table slot instead of
    ``hll_registers``' block."""
    return estimate_sums(*hll_sums(key, mask, values, n_keys, log2m),
                         log2m)


def merge_registers(regs, axis_name: str):
    """Cross-chip merge = elementwise max (inside shard_map)."""
    return jax.lax.pmax(regs, axis_name)


def _alpha(m: int) -> float:
    if m >= 128:
        return 0.7213 / (1 + 1.079 / m)
    return {64: 0.709, 32: 0.697}.get(m, 0.673)


def estimate(regs: np.ndarray) -> np.ndarray:
    """Host-side HLL estimate per group from [K, m] registers."""
    regs = np.asarray(regs)
    k, m = regs.shape
    alpha = _alpha(m)
    z = np.sum(np.power(2.0, -regs.astype(np.float64)), axis=1)
    e = alpha * m * m / z
    zeros = np.sum(regs == 0, axis=1)
    small = (e <= 2.5 * m) & (zeros > 0)
    with np.errstate(divide="ignore"):
        lin = m * np.log(m / np.maximum(zeros, 1).astype(np.float64))
    e = np.where(small, lin, e)
    big = e > (1 << 32) / 30.0
    e = np.where(big, -(1 << 32) * np.log1p(-e / (1 << 32)), e)
    return e
