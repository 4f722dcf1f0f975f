"""HyperLogLog approximate count-distinct, grouped, on device.

Druid-parity capability: the reference pushes ``count(distinct x)`` down as a
``cardinality``/``hyperUnique`` aggregation (``AggregationSpec``
``DruidQuerySpec.scala:340-360``, planner side
``AggregateTransform.ApproximateCountAggregate:454-479``); the sketch itself
ran inside Druid. This module is that sketch engine:

- hash: murmur3 finalizer over int32 dictionary codes / values (VPU ops);
- register index = low ``p`` bits, rho = leading-zero count of the remaining
  bits (``lax.clz``) + 1;
- grouped register maxima over the fused ``group_key * m + register`` slot
  space, [K, m] registers, in one of two forms that return the same
  integers (``register_form`` chooses from the static shapes and the
  backend's unit costs):

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
- host-side harmonic-mean estimation with the standard small/large-range
  corrections (matches Druid's default 2^11 registers).
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
    """The backend's unit costs ``register_form`` prices the two forms
    with (``parallel.cost.unit_cost`` of ``sort.seconds.per.row``,
    ``gather.seconds.per.probe``, ``scatter.seconds.per.update``). Part of
    the signature of every program that takes HLL registers: a ``SET``
    that flips the form re-keys it."""

    sort_row_s: float
    probe_s: float
    scatter_s: float


def _rho_bits(log2m: int) -> int:
    """Bits of the packed key's rho field: rho <= 32 - log2m + 1."""
    return (32 - log2m + 1).bit_length()


def register_form(n_rows: int, n_keys: int, log2m: int,
                  costs: RegisterCosts) -> str:
    """``"sort"`` or ``"scatter"``: the cheaper way to the per-slot maxima
    of ``n_rows`` rows over ``(n_keys + 1) * 2^log2m`` slots (the last
    group is the masked rows' sentinel). Static shapes and unit costs
    only. The sort form costs a one-operand sort of every row and
    ``ceil(log2 n_rows)`` probes a live slot; the scatter an update a
    row. On a v5e's constants the sort wins where ``n_rows`` is over
    ~30-35x the live slots (``acd`` at SF1, 8.0M rows over 16,384 slots:
    priced 5.6 + 3.0 ms against 54, measured 8.2 against 54.6; 2^20
    rows: 2.9 against 7.3) and loses for a [compact_m]-wide input (2^16
    rows: 2.0 against 0.6); on the CPU fallback's never. A slot space
    whose packed key does not fit int32 (past 32,767 groups at log2m 11)
    scatters: there the search alone outweighs any scan an int32 row
    index can hold."""
    m = 1 << log2m
    if ((n_keys + 1) * m) << _rho_bits(log2m) > 1 << 31:
        return "scatter"
    rounds = max(1, int(n_rows - 1).bit_length())
    sort_s = n_rows * costs.sort_row_s + n_keys * m * rounds * costs.probe_s
    return "sort" if sort_s < n_rows * costs.scatter_s else "scatter"


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


def _rho_and_slot(key, mask, values, n_keys: int, log2m: int):
    """Per row: (rho, fused slot ``group * m + register``); a masked-out
    row goes to the sentinel group ``n_keys``."""
    m = 1 << log2m
    h = _murmur_fmix32(values.reshape(-1))
    reg = (h & jnp.uint32(m - 1)).astype(jnp.int32)
    w = h >> jnp.uint32(log2m)            # (32 - p) significant bits
    # rho = position of first 1-bit in w within (32-p) bits, 1-based;
    # w == 0 -> (32 - p) + 1
    clz = jax.lax.clz(w.astype(jnp.int32))  # counts over 32 bits
    rho = jnp.where(w == 0, jnp.int32(32 - log2m + 1),
                    clz - jnp.int32(log2m) + 1).astype(jnp.int32)
    fused = jnp.where(mask.reshape(-1), key.reshape(-1),
                      jnp.int32(n_keys)) * jnp.int32(m) + reg
    return rho, fused


@jax.named_scope("sdot_hll_registers")
def hll_registers(key, mask, values, n_keys: int, log2m: int,
                  costs: RegisterCosts):
    """Per-group HLL register maxima.

    key: [N] int32 dense group key (sentinel n_keys for masked-out rows);
    values: [N] int32 (dictionary codes or integer-viewed values);
    costs: the backend's unit costs — with the static shapes they choose
    the form (``register_form``); both forms return the same integers.
    Returns int32 [n_keys, m] register array (rho values, 0 = empty).
    """
    m = 1 << log2m
    rho, fused = _rho_and_slot(key, mask, values, n_keys, log2m)
    if register_form(rho.shape[0], n_keys, log2m, costs) == "sort":
        regs = _registers_sort(rho, fused, n_keys, m, _rho_bits(log2m))
    else:
        regs = _registers_scatter(rho, fused, n_keys, m)
    return regs.reshape(n_keys, m)


def merge_registers(regs, axis_name: str):
    """Cross-chip merge = elementwise max (inside shard_map)."""
    return jax.lax.pmax(regs, axis_name)


def estimate(regs: np.ndarray) -> np.ndarray:
    """Host-side HLL estimate per group from [K, m] registers."""
    regs = np.asarray(regs)
    k, m = regs.shape
    if m >= 128:
        alpha = 0.7213 / (1 + 1.079 / m)
    elif m == 64:
        alpha = 0.709
    elif m == 32:
        alpha = 0.697
    else:
        alpha = 0.673
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
