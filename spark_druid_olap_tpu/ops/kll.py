"""KLL-class mergeable quantile sketch, grouped, on device.

Completes the sketch tier (``ops/hll.py``, ``ops/theta.py``) with
``percentile_approx``: a fixed-width register sketch whose merge is a
pure elementwise algebra — associative, commutative, and therefore
byte-identical whether registers are folded across waves on host,
across chips with mesh collectives, or across historicals at the
broker. Like Druid's KLL quantiles sketch it keeps a small number of
weighted levels of sampled values; unlike the textbook streaming
compactor (whose output depends on arrival order) the sampling here is
*content-seeded*, so any merge order replays to the same registers.

Layout (int32, width ``W = 2*L*K + L`` with L levels and K lanes):

- ``[0 : L*K]``        tiebreak hashes ``t`` (``EMPTY`` = unoccupied lane)
- ``[L*K : 2*L*K]``    sampled-value payload (float32 bits viewed int32)
- ``[2*L*K : W]``      per-level exact row counts

Update: each row hashes its CONTENT (value bits + timestamp bits — never
a row or segment index, which would differ between shard scan orders) to
one lane (one-permutation hashing), a capped-geometric level, and a
tiebreak ``t``; the lane keeps the lexicographically smallest ``(t, v)``
pair seen, and the level counts every routed row exactly. On device this
is two fused ``segment_min`` passes plus one ``segment_sum`` — the same
scatter shapes as HLL.

Merge: elementwise lex-min on ``(t, v)`` plus integer sum of counts —
``pmin``/``pmin``/``psum`` across a mesh axis, ``np.minimum``/``where``/
``+`` on host. Declared as ``"minsum"`` in ``AGG_CLOSURE`` and
machine-checked by sdlint's mergeclosure/mesh passes.

Estimate (host, finalized ONCE): within level ``l`` each occupied lane
represents ``count_l / occupied_l`` rows; the weighted sample set's
empirical quantile is returned (an actually-sampled value, float64).
Rank error ~ c/sqrt(K) — K=256 lanes x 4 levels holds p50/p95/p99 well
inside the default 0.05 rank-error bound (``sdot.quantile.rank_bound``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

N_LEVELS = 4                    # fixed; lane count K is the size knob
K_LANES = 256                   # default lanes per level (sdot.quantile.lanes)
EMPTY = np.int32(2 ** 31 - 1)   # unoccupied-lane sentinel (= int32 max)


def width(lanes: int = K_LANES) -> int:
    """Register row width for a lane count: t block + v block + counts."""
    return 2 * N_LEVELS * lanes + N_LEVELS


def lanes_of(w: int) -> int:
    """Invert :func:`width` (levels are a module constant)."""
    return (w - N_LEVELS) // (2 * N_LEVELS)


def _mix(h):
    h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


@jax.named_scope("sdot_kll_registers")
def kll_registers(key, mask, values, times, n_keys: int,
                  lanes: int = K_LANES):
    """Per-group KLL registers: ``[n_keys, width(lanes)]`` int32.

    key: [N] int32 dense group key; values: [N] numeric (quantile domain,
    canonicalized to float32 so every tier sees identical bits); times:
    [N] integer timestamps or None — hashed with the value bits as the
    content salt (content-only so shard scan order can't change the
    sampled set). NaN values are nulls and don't contribute.
    """
    key = key.reshape(-1)
    mask = mask.reshape(-1)
    v32 = values.reshape(-1).astype(jnp.float32)
    mask = mask & ~jnp.isnan(v32)
    v_bits = jax.lax.bitcast_convert_type(v32, jnp.int32)
    if times is None:
        t_bits = jnp.zeros_like(v_bits)
    else:
        t_bits = times.reshape(-1).astype(jnp.int32)
    h = v_bits.astype(jnp.uint32) * jnp.uint32(0x9E3779B1) \
        ^ t_bits.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B)
    h = _mix(h)
    lane = (h % jnp.uint32(lanes)).astype(jnp.int32)
    # capped-geometric level: P(>=l) = 2^-l, top level absorbs the tail
    u = _mix(h ^ jnp.uint32(0xC2B2AE35))
    level = jnp.zeros_like(lane)
    for i in range(1, N_LEVELS):
        level = level + (u < jnp.uint32(1 << (32 - i))).astype(jnp.int32)
    tie = (_mix(h ^ jnp.uint32(0x27D4EB2F)) >> jnp.uint32(1)).astype(jnp.int32)
    tie = jnp.minimum(tie, jnp.int32(EMPTY - 1))

    k_eff = jnp.where(mask, key, jnp.int32(n_keys))
    sid = (k_eff * jnp.int32(N_LEVELS) + level) * jnp.int32(lanes) + lane
    nseg = (n_keys + 1) * N_LEVELS * lanes
    t_regs = jax.ops.segment_min(
        jnp.where(mask, tie, jnp.int32(EMPTY)), sid, num_segments=nseg)
    # second pass: the value whose tiebreak won the lane (ties on t break
    # by min value bits -> a deterministic total order)
    cand = jnp.where(mask & (tie == t_regs[sid]), v_bits, jnp.int32(EMPTY))
    v_regs = jax.ops.segment_min(cand, sid, num_segments=nseg)
    csid = k_eff * jnp.int32(N_LEVELS) + level
    c_regs = jax.ops.segment_sum(
        mask.astype(jnp.int32), csid, num_segments=(n_keys + 1) * N_LEVELS)
    lk = N_LEVELS * lanes
    return jnp.concatenate([
        t_regs[: n_keys * lk].reshape(n_keys, lk),
        v_regs[: n_keys * lk].reshape(n_keys, lk),
        c_regs[: n_keys * N_LEVELS].reshape(n_keys, N_LEVELS)], axis=1)


def merge_registers(regs, axis_name: str):
    """Cross-chip merge: lex-min on (t, v) lanes + psum of level counts."""
    w = regs.shape[-1]
    lk = (w - N_LEVELS) // 2
    t, v, c = regs[..., :lk], regs[..., lk:2 * lk], regs[..., 2 * lk:]
    t_min = jax.lax.pmin(t, axis_name)
    cand = jnp.where(t == t_min, v, jnp.int32(EMPTY))
    v_min = jax.lax.pmin(cand, axis_name)
    c_sum = jax.lax.psum(c, axis_name)
    return jnp.concatenate([t_min, v_min, c_sum], axis=-1)


def merge(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Host-side register fold — same algebra as :func:`merge_registers`."""
    a = np.asarray(a, dtype=np.int32)
    b = np.asarray(b, dtype=np.int32)
    w = a.shape[-1]
    lk = (w - N_LEVELS) // 2
    ta, va, ca = a[..., :lk], a[..., lk:2 * lk], a[..., 2 * lk:]
    tb, vb, cb = b[..., :lk], b[..., lk:2 * lk], b[..., 2 * lk:]
    t = np.minimum(ta, tb)
    v = np.where(ta < tb, va, np.where(tb < ta, vb, np.minimum(va, vb)))
    return np.concatenate([t, v, ca + cb], axis=-1)


def identity_registers(w: int) -> np.ndarray:
    """The merge identity: every lane empty, every count zero."""
    lk = (w - N_LEVELS) // 2
    out = np.full(w, EMPTY, dtype=np.int32)
    out[2 * lk:] = 0
    return out


def estimate(regs: np.ndarray, fraction: float) -> np.ndarray:
    """[n_keys, W] registers -> per-group quantile estimates (float64).

    Finalized ONCE (at the broker for distributed queries), so the
    clustered estimate is byte-identical to the single-engine estimate.
    Empty groups (zero rows) estimate NaN (SQL NULL).
    """
    regs = np.asarray(regs, dtype=np.int32)
    if regs.ndim == 1:
        regs = regs[None, :]
    g, w = regs.shape
    lk = (w - N_LEVELS) // 2
    lanes = lk // N_LEVELS
    t = regs[:, :lk].reshape(g, N_LEVELS, lanes)
    v_bits = regs[:, lk:2 * lk].reshape(g, N_LEVELS, lanes)
    counts = regs[:, 2 * lk:].astype(np.float64)           # [g, L]
    occ = (t != EMPTY)
    n_occ = np.maximum(occ.sum(axis=2), 1).astype(np.float64)   # [g, L]
    weights = np.where(occ, (counts / n_occ)[:, :, None], 0.0)
    vals = v_bits.view(np.float32).astype(np.float64)
    vals = np.where(occ, vals, np.inf).reshape(g, lk)
    weights = weights.reshape(g, lk)
    order = np.argsort(vals, axis=1, kind="stable")
    vals_s = np.take_along_axis(vals, order, axis=1)
    w_s = np.take_along_axis(weights, order, axis=1)
    cum = np.cumsum(w_s, axis=1)
    total = counts.sum(axis=1)                             # [g]
    target = np.asarray(fraction, dtype=np.float64) * total
    # first sampled value whose cumulative weight reaches the target rank
    idx = np.minimum((cum < target[:, None] - 1e-9).sum(axis=1),
                     max(lk - 1, 0))
    out = np.take_along_axis(vals_s, idx[:, None], axis=1)[:, 0]
    return np.where(total > 0, out, np.nan)


def to_bytes(regs: np.ndarray) -> bytes:
    """Serialize registers (little-endian int32) for the SDW1 wire."""
    return np.ascontiguousarray(
        np.asarray(regs, dtype="<i4")).tobytes()


def from_bytes(buf: bytes, w: int) -> np.ndarray:
    """Inverse of :func:`to_bytes`; reshapes to ``[-1, w]``."""
    return np.frombuffer(buf, dtype="<i4").reshape(-1, w).astype(np.int32)


def rank_bound(config) -> float:
    """The configured acceptable rank error (``sdot.quantile.rank_bound``)
    — the gate bench.py's percentile legs and the loadtest's quantile
    storm hold KLL estimates to: an estimate for fraction q must sit
    between the exact q-eps and q+eps quantiles of the data."""
    from spark_druid_olap_tpu.utils.config import QUANTILE_RANK_BOUND
    return float(config.get(QUANTILE_RANK_BOUND))
