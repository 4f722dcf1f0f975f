"""HyperLogLog approximate count-distinct, grouped, on device.

Druid-parity capability: the reference pushes ``count(distinct x)`` down as a
``cardinality``/``hyperUnique`` aggregation (``AggregationSpec``
``DruidQuerySpec.scala:340-360``, planner side
``AggregateTransform.ApproximateCountAggregate:454-479``); the sketch itself
ran inside Druid. This module is that sketch engine:

- hash: murmur3 finalizer over int32 dictionary codes / values (VPU ops);
- register index = low ``p`` bits, rho = leading-zero count of the remaining
  bits (``lax.clz``) + 1;
- grouped register maxima via one ``segment_max`` over the fused
  ``group_key * m + register`` space — [K, m] registers in one scatter pass;
- host-side harmonic-mean estimation with the standard small/large-range
  corrections (matches Druid's default 2^11 registers).
"""

from __future__ import annotations

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


@jax.named_scope("sdot_hll_registers")
def hll_registers(key, mask, values, n_keys: int, log2m: int = 11):
    """Per-group HLL register maxima.

    key: [N] int32 dense group key (sentinel n_keys for masked-out rows);
    values: [N] int32 (dictionary codes or integer-viewed values).
    Returns int32 [n_keys, m] register array (rho values, 0 = empty).
    """
    m = 1 << log2m
    h = _murmur_fmix32(values.reshape(-1))
    reg = (h & jnp.uint32(m - 1)).astype(jnp.int32)
    w = h >> jnp.uint32(log2m)            # (32 - p) significant bits
    # rho = position of first 1-bit in w within (32-p) bits, 1-based;
    # w == 0 -> (32 - p) + 1
    clz = jax.lax.clz(w.astype(jnp.int32))  # counts over 32 bits
    rho = jnp.where(w == 0, jnp.int32(32 - log2m + 1),
                    clz - jnp.int32(log2m) + 1).astype(jnp.int32)
    key = key.reshape(-1)
    mask = mask.reshape(-1)
    fused = jnp.where(mask, key, jnp.int32(n_keys)) * jnp.int32(m) + reg
    regs = jax.ops.segment_max(
        rho, fused, num_segments=(n_keys + 1) * m, indices_are_sorted=False)
    regs = jnp.maximum(regs, 0)           # segment_max fills empty with dtype-min
    return regs[: n_keys * m].reshape(n_keys, m)


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
