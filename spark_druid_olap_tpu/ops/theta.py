"""Theta-sketch-class approximate distinct counting: a k-mins sketch.

≈ the reference mapping Druid ``thetaSketch`` metric columns to approximate
distinct counts (``DruidDataSource.scala:24-40``; Druid's theta sketch is a
KMV — k minimum hash values — structure). The TPU-shaped equivalent keeps,
per group, the MINIMUM of k independent uniform hashes of the value: a
"k-mins" sketch. Identical update/merge algebra to KMV (set union = element
-wise min), identical estimator family, and it maps onto the engine's
existing exact-min machinery:

- update   = per-lane ``segment_min`` into a dense ``[n_keys, k]`` f32 table
- merge    = elementwise min — across chips via ``lax.pmin`` on ICI, across
  waves/hash partials via ``np.minimum`` on host
- estimate = MLE for n given k independent Beta(1, n) minima:
  ``n_hat = k / sum(min_j) - 1`` (empty group: every lane at the 1.0 clip
  gives n_hat = 0 exactly)

Relative error ~ 1/sqrt(k) (k=64 -> ~12.5%), the same class as Druid's
default-size theta sketches; lanes are compile-time constants so the whole
sketch fuses into the scan program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

K_LANES = 64
_SENTINEL = np.float32(2.0)     # > any hash; empty-group marker pre-clip


def _hash01(v, seed: int):
    """Value -> uniform (0, 1] float32, per-lane independent."""
    h = v.astype(jnp.uint32) * jnp.uint32(0x9E3779B1) \
        ^ jnp.uint32((0x85EBCA6B * (2 * seed + 1)) & 0xFFFFFFFF)
    h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    # 24 significant bits: exact in i32 and in f32. Through i32 because
    # Mosaic has no unsigned -> float cast (the wave kernel traces this)
    return ((h >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
            * jnp.float32(1.0 / (1 << 24))) + jnp.float32(1e-7)


@jax.named_scope("sdot_theta_registers")
def theta_registers(key, mask, values, n_keys: int,
                    k: int = K_LANES):
    """Per-group k-mins registers: ``[n_keys, k]`` f32 lane minima."""
    if key.ndim == 1:
        key = key[None, :]
        mask = mask[None, :]
    v = values.reshape(key.shape)
    num = n_keys + 1
    k_eff = jnp.where(mask, key, jnp.int32(n_keys))
    lanes = []
    for j in range(k):
        hv = jnp.where(mask, _hash01(v, j), _SENTINEL)
        per = jax.vmap(
            lambda x, kk: jax.ops.segment_min(x, kk, num))(hv, k_eff)
        lanes.append(per.min(axis=0)[:n_keys])
    return jnp.stack(lanes, axis=1)


def merge_registers(regs, axis_name: str):
    """Cross-chip union: elementwise min over the mesh axis."""
    return jax.lax.pmin(regs, axis_name)


def estimate(regs: np.ndarray) -> np.ndarray:
    """[n_keys, k] lane minima -> per-group distinct estimates."""
    r = np.minimum(np.asarray(regs, np.float64), 1.0)
    k = r.shape[1]
    s = r.sum(axis=1)
    return np.maximum(k / np.maximum(s, 1e-12) - 1.0, 0.0)
