"""Pallas TPU kernel for the small-K scan-filter-aggregate hot loop.

This is the fused, single-HBM-pass version of :func:`groupby.dense_groupby`
for small group cardinalities — the shape of the reference's headline
benchmark queries (TPC-H Q1 groups by returnflag x linestatus = 6 keys;
the basic-agg / shipdate-range queries are global or single-dim; reference
``docs/benchmark/BenchMarkDetails.org:140-163``). The XLA one-hot-matmul
path materializes the one-hot and several intermediates in HBM and
serializes a ``lax.scan``; this kernel streams each row block through VMEM
exactly once.

Design:

- Grid over row blocks ``[B, 128]`` (TPU grids run sequentially, so the
  output block is a legal cross-step accumulator).
- Per group key ``k`` (static unroll — small K only): lane-wise partial
  reductions ``[B, 128] -> [128]`` on the VPU (sublane reduce only, no
  scalar-unit traffic). Masked-out rows carry the sentinel key ``n_keys``
  and match no ``k``, so filtering costs nothing.
- Output is ``[K * M, 128]`` per-lane partials accumulated in VMEM; the
  final 128-lane reduction is a tiny XLA epilogue outside the kernel (same
  jit), giving exact ``[K]`` results.
- Sums/counts accumulate in f32 (matches the XLA TPU path); min/max use the
  same +/-F32_MAX empty-group sentinel the decoder expects.

The kernel is selected by :func:`groupby.dense_groupby` when the backend is
TPU and ``n_keys <= sdot.engine.groupby.pallas.max.keys``; tests exercise it
on CPU via ``interpret=True``.
"""

from __future__ import annotations

import os
from typing import List

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32_MAX = jnp.float32(3.4e38)

LANES = 128
MIN_BLOCK_ROWS = 128              # floor: 16K rows/step
MAX_BLOCK_ROWS = 2048             # ceiling: 256K rows/step
VMEM_BUDGET = 8 << 20             # ~half of a v5e core's ~16MB VMEM


def choose_block_rows(inputs) -> int:
    """Largest power-of-two sublane block (grid-step depth) that (a) fits
    every operand double-buffered in the VMEM budget and (b) keeps every
    integer sum's per-lane block partial exactly representable in f32
    (``maxabs * block_rows < 2^24``). Deterministic from the agg metadata
    alone so :func:`eligible` (route planning) and
    :func:`pallas_dense_groupby` (dispatch) always agree. Fewer, deeper
    grid steps amortize Mosaic's per-step overhead — the fixed 256-row
    block this replaces put a 6M-row scan at 184 steps."""
    # Count ONLY what is knowable from plan-time metadata (kind): value
    # blocks. Mask blocks (i8, filtered aggregations) are deliberately
    # NOT counted — plan-time metas carry mask=None while dispatch-time
    # inputs carry the real arrays, and the block choice MUST be
    # identical on both sides (the exactness gate is proved at the
    # planned block size). The budget's 8MB-of-16MB slack absorbs the
    # uncounted i8 blocks (<= 0.5MB per mask at the 2048-row ceiling).
    n_bytes_per_row = 4                          # the key block, i32
    for a in inputs:
        if a.kind != "count":
            n_bytes_per_row += 4                 # f32 value block
    b = MAX_BLOCK_ROWS
    while b > MIN_BLOCK_ROWS \
            and b * LANES * n_bytes_per_row * 2 > VMEM_BUDGET:
        b //= 2
    for a in inputs:
        if a.kind == "sum" and a.is_int and a.maxabs:
            while b > MIN_BLOCK_ROWS and a.maxabs * b >= 2**24:
                b //= 2
    return b


def eligible(n_keys: int, inputs, pallas_max: int,
             n_rows=None) -> bool:
    """Whether the fused kernel applies: small dense K, plain agg kinds,
    TPU backend (or interpret mode forced via SDOT_PALLAS=interpret — CPU
    differential tests otherwise keep the f64 XLA path), and per-agg
    exactness at the block size :func:`choose_block_rows` picks:

    - integer sums: each VPU lane accumulates ``block_rows`` values per
      grid step, so the per-lane block partial is exact f32 iff
      ``maxabs * block_rows < 2^24``; cross-step Kahan carries and the
      host's f64 lane reduction keep the total exact at any row count
      (the same invariant as the XLA 'ff' route's block sums).
    - float sums: in-block f32 rounding only, like 'ff'.
    - integer min/max: values must be exact in f32 (compares happen in
      the f32 domain).

    Static metadata only — callable at route-planning time, and the
    executor's plan and the kernel dispatch must make the SAME call.
    """
    env = os.environ.get("SDOT_PALLAS", "")
    if env == "0":
        return False
    if env != "interpret" and not _tpu_backend():
        return False
    if pallas_max <= 0 or n_keys > pallas_max:
        return False
    block_rows = choose_block_rows(inputs)
    for a in inputs:
        if a.kind not in ("count", "sum", "min", "max"):
            return False
        if a.kind == "sum" and a.is_int:
            if a.maxabs is None or a.maxabs * block_rows >= 2**24:
                return False
            # Neumaier comp accumulates integer roundoffs exactly only
            # while it stays < 2^24: comp <= steps * ulp(acc)/2 with
            # acc <= maxabs*n_rows/128 and steps = n_rows/(block*128)
            # gives the conservative growth bound maxabs * n_rows^2 <
            # 2^70 (TPC-H SF100 counts/qty sums sit near 2^64)
            if n_rows is not None \
                    and a.maxabs * float(n_rows) * float(n_rows) >= 2**70:
                return False
        if a.kind in ("min", "max") and a.is_int:
            if a.maxabs is None or a.maxabs >= 2**24:
                return False
    return True


def _tpu_backend() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:  # sdlint: disable=purity (trace-time mode
    # flag: freezing the env read into the compiled program is the point
    # — interpret-vs-Mosaic must be decided once per compilation)
    """Interpreter mode is a TEST setting: only ``SDOT_PALLAS=interpret``
    turns it on. Anywhere else the kernel lowers through Mosaic, and a
    backend that cannot compile it says so."""
    return os.environ.get("SDOT_PALLAS", "") == "interpret"


_INIT = {"count": 0.0, "sum": 0.0, "min": 3.4e38, "max": -3.4e38}


def _row_offsets(specs):
    """Per-agg row offset inside each key's output stripe. Sums/counts
    take TWO rows (Kahan acc + comp); min/max one."""
    offs, rpk = [], 0
    for kind, _, _ in specs:
        offs.append(rpk)
        rpk += 2 if kind in ("count", "sum") else 1
    return offs, rpk


def init_rows(out_ref, row: int, kind: str) -> None:
    """Fill one agg's accumulator row(s) with its identity (shared by this
    kernel and the shared-scan wave mega-kernel, ops/pallas_wave.py)."""
    out_ref[row, :] = jnp.full((LANES,), jnp.float32(_INIT[kind]),
                               dtype=jnp.float32)
    if kind in ("count", "sum"):
        out_ref[row + 1, :] = jnp.zeros((LANES,), dtype=jnp.float32)


def accumulate_rows(out_ref, row: int, kind: str, part) -> None:
    """Fold one [LANES] block partial into the accumulator rows at
    ``row``. Sums/counts use per-lane NEUMAIER accumulation across grid
    steps: 2Sum's branch captures the EXACT roundoff of ``cur + part``
    regardless of relative magnitudes (plain Kahan's 'part - comp' can
    itself round once the accumulator is large); integer roundoffs are
    integers, so comp accumulates exactly within the eligible() growth
    bound. True total = acc + comp. min/max fold exactly."""
    cur = out_ref[row, :]
    if kind in ("count", "sum"):
        comp = out_ref[row + 1, :]
        t = cur + part
        big = jnp.abs(cur) >= jnp.abs(part)
        err = jnp.where(big, (cur - t) + part, (part - t) + cur)
        out_ref[row + 1, :] = comp + err
        out_ref[row, :] = t
    elif kind == "min":
        out_ref[row, :] = jnp.minimum(cur, part)
    else:
        out_ref[row, :] = jnp.maximum(cur, part)


def block_partial(kind: str, eff, values):
    """One [B, LANES] tile -> [LANES] per-VPU-lane block partial for one
    (agg, key) pair; ``eff`` is the effective row mask (key match & agg
    filter), ``values`` the f32 value tile (None for count)."""
    fmax = 3.4e38     # python literal: kernels may not close over jnp consts
    if kind == "count":
        return jnp.sum(eff.astype(jnp.float32), axis=0)
    if kind == "sum":
        return jnp.sum(jnp.where(eff, values, 0.0), axis=0)
    if kind == "min":
        return jnp.min(jnp.where(eff, values, fmax), axis=0)
    return jnp.max(jnp.where(eff, values, -fmax), axis=0)


def _make_kernel(n_keys: int, specs, n_in: int):
    """specs: list of (kind, value_ref_idx or None, mask_ref_idx or None)."""
    offs, rpk = _row_offsets(specs)

    def kernel(key_ref, *refs):
        out_ref = refs[n_in]
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _():
            for m, (kind, _, _) in enumerate(specs):
                for k in range(n_keys):
                    init_rows(out_ref, k * rpk + offs[m], kind)

        kb = key_ref[:]                                   # [B, 128] int32
        for k in range(n_keys):
            mk = kb == k
            for m, (kind, vi, mi) in enumerate(specs):
                eff = mk if mi is None else (mk & (refs[mi][:] != 0))
                part = block_partial(
                    kind, eff, None if vi is None else refs[vi][:])
                accumulate_rows(out_ref, k * rpk + offs[m], kind, part)

    return kernel


def pallas_dense_groupby(key, n_keys: int, inputs: List,
                         block_rows: int = 0):
    """Fused scan-aggregate for dense small-K group-by.

    key: int32 [N] with filtered-out rows already set to the sentinel
    ``n_keys``; inputs: list of ``groupby.AggInput`` with flat [N] values /
    masks. Returns dict name -> value per agg: sums/counts yield an
    ``([K, 128] acc, [K, 128] comp)`` per-lane Kahan pair (the 'ffl'
    route — host reduces lanes in f64); min/max yield a reduced
    ``[n_keys]`` f32 array.
    """
    if not block_rows:
        block_rows = choose_block_rows(inputs)
    key = key.reshape(-1).astype(jnp.int32)
    n = key.shape[0]
    tile = block_rows * LANES
    n_pad = -(-max(n, 1) // tile) * tile

    def pad2d(arr, fill, dtype):
        arr = arr.reshape(-1).astype(dtype)
        if n_pad > n:
            arr = jnp.pad(arr, (0, n_pad - n), constant_values=fill)
        return arr.reshape(n_pad // LANES, LANES)

    key2 = pad2d(key, n_keys, jnp.int32)

    specs = []       # (kind, value_idx, mask_idx) into `operands`
    operands = []
    for a in inputs:
        vi = mi = None
        if a.kind != "count":
            vi = len(operands)
            operands.append(pad2d(a.values, 0, jnp.float32))
        if a.mask is not None:
            mi = len(operands)
            operands.append(pad2d(a.mask, 0, jnp.int8))
        specs.append((a.kind, vi, mi))

    n_in = len(operands)
    offs, rpk = _row_offsets(specs)
    grid = (n_pad // tile,)
    blk = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    out_blk = pl.BlockSpec((n_keys * rpk, LANES), lambda i: (0, 0))

    out = pl.pallas_call(
        _make_kernel(n_keys, specs, n_in),
        name="sdot_dense_groupby",      # the HLO instruction's name
        grid=grid,
        in_specs=[blk] * (1 + n_in),
        out_specs=out_blk,
        out_shape=jax.ShapeDtypeStruct((n_keys * rpk, LANES),
                                       jnp.float32),
        interpret=_interpret(),
    )(key2, *operands)

    # sums/counts leave as per-lane (acc, comp) pairs (host combines in
    # f64); min/max reduce their 128 lanes here (order-free, exact)
    out3 = out.reshape(n_keys, rpk, LANES)
    result = {}
    for a, (kind, _, _), off in zip(inputs, specs, offs):
        if kind in ("count", "sum"):
            result[a.name] = (out3[:, off, :], out3[:, off + 1, :])
        elif kind == "min":
            result[a.name] = jnp.min(out3[:, off, :], axis=-1)
        else:
            result[a.name] = jnp.max(out3[:, off, :], axis=-1)
    return result
