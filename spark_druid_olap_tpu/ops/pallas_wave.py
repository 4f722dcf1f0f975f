"""Pallas mega-kernel: one hand-scheduled kernel per fused shared-scan wave.

The shared-scan tier (parallel/sharedscan.py) already runs a dashboard
storm as ONE bind + ONE XLA dispatch per segment wave, but the fused
jaxpr's VMEM schedule is implicit: XLA materializes per-lane masks and
one-hot intermediates in HBM, and every lane's aggregation re-streams the
union columns. This module lowers the group's FusionPlan (the CSE'd
predicate DAG + per-lane residuals + agg sets, planner/fusion.py) to ONE
hand-written ``pl.pallas_call``: union columns tile through VMEM exactly
once per wave, shared predicate sub-expressions evaluate once per tile
(the trace-time ``CSECache`` runs INSIDE the kernel body), and every
lane's filtered aggregates accumulate in a resident scratch block — the
whole-pipeline native-compilation move of Flare (arxiv 1703.08219) and
the device-side operator design of GPU-Presto (arxiv 2606.24647).

How lane semantics stay exact: ``ScanContext`` (ops/scan.py) is shape-
agnostic — every method is elementwise over ``arrays`` plus host
metadata — so the kernel body constructs a REAL ``ScanContext`` over the
``[block_rows, 128]`` tiles read from its refs and reuses the engine's
own lowering verbatim: ``ops.filters.lower_filter`` through the fusion
planner's ``CSECache`` (with ``prelower``, so cross-lane shared masks
compute once per tile), the planned dimension builders, ``fuse_keys``,
and each ``AggPlan``'s value/mask builders. The kernel never re-implements
query semantics; it re-schedules them.

Scratch accumulator layout (one f32 ``[out_rows, 128]`` block, resident
across grid steps — TPU grids are sequential, so the output block is a
legal cross-step accumulator, same contract as ops/pallas_groupby.py):

- per lane, per key ``k``: a stripe of ``rpk`` rows — two rows (Neumaier
  acc + comp) per sum/count, one row (±F32_MAX sentinel) per min/max —
  shared row-offset/init/accumulate helpers with pallas_groupby.
- per in-kernel theta sketch: ``n_keys * K_LANES`` rows of per-VPU-lane
  hash minima (exact min algebra: bit-identical to
  ``ops.theta.theta_registers``; the 128-lane reduction is an XLA
  epilogue in the same jit).

Fallback matrix (every PLANNED decline lowers through the unchanged
jaxpr-fused program — routing tiers never change; a compiler's refusal
of an accepted group is an error, not a row here; see docs/KERNELS.md):

- ``sdot.pallas.wave.enabled`` off, non-TPU backend without
  ``SDOT_PALLAS=interpret``, or group wider than
  ``sdot.pallas.wave.max.lanes``  -> jaxpr path (static precheck).
- any lane whose planned sum/count routes are not 'ffl' (i.e.
  ``pallas_groupby.eligible`` declined: numeric bounds, key cap) -> jaxpr.
- lane lowering that traces non-elementwise primitives (LUT gathers from
  pattern/extraction dims, tz-shifted granularities, ...) -> jaxpr,
  caught by a chip-independent 8x128 trace probe against a Mosaic-safe
  primitive whitelist, NOT by a device compile error.
- HLL registers (per-slot maxima over n_keys * 2^log2m slots —
  infeasible in a VMEM-tiled scratch block at the default m=2048; a
  packed-key sort or a scatter, ``ops.hll.register_form``) and theta
  sketches
  over the in-kernel row cap: computed by the existing XLA register ops
  in the SAME jit after the kernel — still one kernel launch per wave,
  at the cost of one extra XLA stream of the sketch lanes' columns.

Interpreter mode (``SDOT_PALLAS=interpret``, a test setting) runs the
identical kernel through ``pl.pallas_call(..., interpret=True)`` — the
chip-independent CI differential against the jaxpr path. What Mosaic
refuses and the interpreter passes shows in tests/test_chip_compile.py.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from spark_druid_olap_tpu.ops import filters as F
from spark_druid_olap_tpu.ops import groupby as G
from spark_druid_olap_tpu.ops import hll as HLL
from spark_druid_olap_tpu.ops import kll as KLL
from spark_druid_olap_tpu.ops import pallas_groupby as PG
from spark_druid_olap_tpu.ops import theta as TH
from spark_druid_olap_tpu.ops.scan import ScanContext, array_dtype
from spark_druid_olap_tpu.planner import fusion as FU

LANES = PG.LANES

# in-kernel theta cap: a sketch's scratch stripe is n_keys * K_LANES rows;
# past this the registers compute in the XLA epilogue instead (the j*k
# unrolled min loop also grows the kernel trace linearly with this)
THETA_KERNEL_MAX_ROWS = 256

# total scratch rows the wave accumulator block may occupy (2MiB f32 at
# 128 lanes); wider storms fall back to the jaxpr program
MAX_OUT_ROWS = 4096


class WaveFallback(Exception):
    """Raised at build time when the group declines the wave kernel BY
    PLAN (whitelist probe, scratch cap); the caller builds the
    jaxpr-fused program instead."""


class WaveCompileError(RuntimeError):
    """The backend's compiler refused a wave program the planner had
    accepted. Never a fallback: it fails the group's statements with
    the compiler's text (docs/KERNELS.md "Dispatch-path selection")."""


# =============================================================================
# eligibility
# =============================================================================

def wave_eligible(lanes, max_lanes: int) -> bool:
    """Static precheck from plan metadata only — callable on EVERY fused
    execution (warm program-cache runs included) so the compile signature
    and the dispatch path always agree. The numeric gates ride on the
    planned routes: ``plan_routes`` assigns 'ffl' to a lane's sums/counts
    iff ``pallas_groupby.eligible`` accepted the lane (backend, key cap,
    f32-exactness bounds), so requiring every sum/count route to be 'ffl'
    inherits the proven per-lane gates without re-deriving them."""
    env = os.environ.get("SDOT_PALLAS", "")
    if env == "0":
        return False
    if env != "interpret" and not PG._tpu_backend():
        return False
    if max_lanes <= 0 or len(lanes) > max_lanes:
        return False
    for lp in lanes:
        for r in lp.routes.values():
            if r.kind in ("sum", "count") and r.tag != "ffl":
                return False
        for p in lp.agg_plans:
            if p.kind not in ("count", "sum", "min", "max", "hll",
                              "theta", "kll"):
                return False
    return True


# Mosaic-safe primitives a lane's mask/key/value builders may trace.
# Anything outside (gather/take LUTs, sorts, scans, dots) rejects the
# lane at build time — deterministically, on any backend.
_SAFE_PRIMS = frozenset({
    "add", "sub", "mul", "div", "rem", "pow", "integer_pow", "max", "min",
    "and", "or", "xor", "not", "eq", "ne", "lt", "le", "gt", "ge",
    "select_n", "convert_element_type", "bitcast_convert_type",
    "broadcast_in_dim", "reshape", "squeeze", "iota", "clamp",
    "shift_left", "shift_right_logical", "shift_right_arithmetic",
    "neg", "abs", "sign", "floor", "ceil", "round", "is_finite",
    "exp", "log", "sqrt", "rsqrt", "stop_gradient", "copy",
    "nextafter", "sub_f", "add_any",
})
_CALL_PRIMS = frozenset({"jit", "closed_call", "custom_jvp_call",
                         "custom_vjp_call", "remat2", "checkpoint"})


def _check_jaxpr(jaxpr) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _CALL_PRIMS:
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    _check_jaxpr(inner)
            continue
        if name not in _SAFE_PRIMS:
            raise WaveFallback(f"lane lowering traces non-elementwise "
                               f"primitive {name!r}")


def _lane_parts(lp, ctx: ScanContext, cse: Optional[FU.CSECache]):
    """One lane's traced parts over ``ctx`` — the engine's own builders,
    shared verbatim between the trace probe, the kernel body, and the
    sketch epilogue (the jaxpr path composes the same calls, which is
    what makes the differential bit-exact by construction)."""
    base = ctx.row_valid()
    fm = cse.lower(lp.q.filter) if cse is not None \
        else F.lower_filter(lp.q.filter, ctx)
    if fm is not None:
        base = base & fm
    im = cse.interval(lp.q.intervals) if cse is not None \
        else F.interval_mask(lp.q.intervals, ctx)
    if im is not None:
        base = base & im
    if lp.dim_plans:
        codes = [p.build(ctx) for p in lp.dim_plans]
        key, _ = G.fuse_keys(codes, [p.card for p in lp.dim_plans])
    else:
        key = jnp.zeros(base.shape, dtype=jnp.int32)
    dense = []
    sketch = []
    for p in lp.agg_plans:
        vals = p.build_values(ctx)
        am = p.build_mask(ctx, cse=cse)
        if p.kind in ("hll", "theta", "kll"):
            sketch.append((p, vals, am))
        else:
            dense.append((p.kind, p.spec.name, vals, am))
    dense.append(("count", "__rows__", None, None))
    return base, key, dense, sketch


def _key_masks(lp, base, key):
    """Per-key row masks of one lane inside the kernel body.

    Kernel contract (docs/KERNELS.md): every ``i1`` mask the kernel
    combines is computed from a tile. Mosaic gives a constant the
    replicated layout and cannot relayout a tile's mask into it, so a
    select whose BRANCHES are all constants (``where(base, 0, n_keys)``
    over the zero key of an ungrouped lane) is refused on the chip
    though interpret mode passes it. An ungrouped lane's one key mask IS
    its base mask — the same rows ``kb == 0`` selects."""
    if not lp.dim_plans:
        return [base]
    kb = jnp.where(base, key.astype(jnp.int32), jnp.int32(lp.n_keys))
    return [kb == k for k in range(lp.n_keys)]


# =============================================================================
# layout
# =============================================================================

class _LaneLayout:
    """Scratch rows one lane owns inside the wave accumulator block."""

    __slots__ = ("base", "offs", "rpk", "dense_meta", "theta_base",
                 "theta_epilogue", "hll", "kll", "next_row")

    def __init__(self, lp, base_row: int):
        dense_kinds = [p.kind for p in lp.agg_plans
                       if p.kind not in ("hll", "theta", "kll")] + ["count"]
        self.offs, self.rpk = PG._row_offsets(
            [(k, None, None) for k in dense_kinds])
        self.base = base_row
        row = base_row + self.rpk * lp.n_keys
        # metas drive the route adaptation (G._pallas_to_routes)
        self.dense_meta = [
            G.AggInput(p.spec.name, p.kind, is_int=p.is_int,
                       maxabs=p.maxabs)
            for p in lp.agg_plans
            if p.kind not in ("hll", "theta", "kll")]
        self.dense_meta.append(
            G.AggInput("__rows__", "count", is_int=True, maxabs=1.0))
        self.theta_base: Dict[str, int] = {}
        self.theta_epilogue: List[str] = []
        self.hll: List[str] = []
        self.kll: List[str] = []
        for p in lp.agg_plans:
            if p.kind == "theta":
                stripe = lp.n_keys * TH.K_LANES
                if stripe <= THETA_KERNEL_MAX_ROWS:
                    self.theta_base[p.spec.name] = row
                    row += stripe
                else:
                    self.theta_epilogue.append(p.spec.name)
            elif p.kind == "hll":
                self.hll.append(p.spec.name)
            elif p.kind == "kll":
                # survivor registers need a segment_min scatter over
                # (key, level, lane) — XLA epilogue, same as HLL
                self.kll.append(p.spec.name)
        self.next_row = row


def _prep_dtype(dt) -> object:
    """Kernel-side dtype of one union array after input prep: validity
    masks ship as i8 (converted back to bool tiles in the kernel body),
    narrow integer codes widen to i32 (uniform Mosaic tiling), everything
    else keeps its (device-canonicalized) dtype.

    Encoded segments (encode/) do NOT change this contract: chunks
    decode to their logical dtype at fault time (tier/store.py), so the
    kernel always sees the same widened tiles whether the cold bytes
    were bit-packed, RLE, or raw — compression buys host I/O and hot-set
    residency, never a divergent Mosaic tiling. Feeding packed codes
    straight into the kernel would need a per-codec unpack prologue and
    a different (data-dependent) tile plan; see docs/KERNELS.md."""
    dt = jnp.zeros((), dtype=dt).dtype      # apply x64 canonicalization
    if dt == jnp.bool_:
        return jnp.int8
    if dt.kind == "i" and dt.itemsize < 4:
        return jnp.int32
    return dt


# =============================================================================
# program build
# =============================================================================

def build_wave_fn(ds, lanes, min_day: int, max_day: int, fplan, *,
                  union_names, tz: str, log2m: int, tile_bytes: int,
                  kll_lanes: int = KLL.K_LANES, hll_costs=None):
    """Lower a fused group to the wave mega-kernel.

    Returns ``(wave_fn, info)`` where ``wave_fn(arrays)`` maps the wave's
    device bind to a per-lane list of route-conformant output dicts
    (exactly what ``_build_fused_program``'s per-lane ``dense_groupby`` +
    sketch stages produce, so the engine's packers/decoders downstream
    are untouched), and ``info`` carries the static launch accounting
    (block_rows, tiles per dispatch, scratch rows, VMEM estimate).
    Raises :class:`WaveFallback` when any lane cannot lower.
    """
    names = list(union_names)
    probe_tiles = {}
    bool_names = set()
    for k in names:
        dt = np.dtype(array_dtype(ds, k))
        if dt == np.bool_:
            bool_names.add(k)
            probe_tiles[k] = jnp.zeros((8, LANES), dtype=jnp.bool_)
        else:
            pdt = _prep_dtype(dt)
            probe_tiles[k] = jnp.zeros((8, LANES), dtype=pdt)

    # ---- chip-independent trace probe: every lane's builders must stay
    # inside the Mosaic-safe elementwise set on a fake [8, 128] tile
    def probe(tiles):
        ctx = ScanContext(ds, tiles, min_day, max_day, tz=tz)
        cse = FU.CSECache(ctx)
        if fplan is not None:
            cse.prelower(fplan)
        outs = []
        for lp in lanes:
            base, key, dense, sketch = _lane_parts(lp, ctx, cse)
            outs += [base, key]
            outs += [v for _, _, v, _ in dense if v is not None]
            outs += [m for _, _, _, m in dense if m is not None]
            # sketch VALUES/masks trace in-kernel only for in-kernel
            # theta; HLL + epilogue theta run in XLA where anything goes
        return outs

    _check_jaxpr(jax.make_jaxpr(probe)(probe_tiles).jaxpr)

    # ---- scratch layout
    layouts: List[_LaneLayout] = []
    row = 0
    for lp in lanes:
        lay = _LaneLayout(lp, row)
        row = lay.next_row
        layouts.append(lay)
    out_rows = -(-row // 8) * 8                  # f32 sublane tile align
    if out_rows > MAX_OUT_ROWS:
        raise WaveFallback(f"scratch block {out_rows} rows exceeds "
                           f"{MAX_OUT_ROWS}")

    # in-kernel theta values must ALSO pass the probe (they trace inside
    # the kernel); check them against the same whitelist
    def probe_theta(tiles):
        ctx = ScanContext(ds, tiles, min_day, max_day, tz=tz)
        cse = FU.CSECache(ctx)
        outs = []
        for lp, lay in zip(lanes, layouts):
            if not lay.theta_base:
                continue
            for p in lp.agg_plans:
                if p.spec.name in lay.theta_base:
                    outs.append(p.build_values(ctx))
                    m = p.build_mask(ctx, cse=cse)
                    if m is not None:
                        outs.append(m)
        return outs

    if any(lay.theta_base for lay in layouts):
        _check_jaxpr(jax.make_jaxpr(probe_theta)(probe_tiles).jaxpr)

    # ---- tile shape against the VMEM budget (planner/fusion.py)
    itemsizes = [np.dtype(_prep_dtype(np.dtype(array_dtype(ds, k))))
                 .itemsize for k in names]
    int_maxabs = [p.maxabs for lp in lanes for p in lp.agg_plans
                  if p.kind == "sum" and p.is_int and p.maxabs]
    block_rows = FU.plan_wave_tiles(itemsizes, int_maxabs, out_rows,
                                    int(tile_bytes))
    n_in = len(names)

    # per-row identity column, broadcast once at step 0 (one [out_rows, 1]
    # f32 operand instead of an unrolled store per accumulator row —
    # pallas kernels cannot close over array constants); comp rows and
    # alignment pads stay 0
    init_col = np.zeros((out_rows, 1), dtype=np.float32)
    for lp, lay in zip(lanes, layouts):
        for m, meta in enumerate(lay.dense_meta):
            for k in range(lp.n_keys):
                r = lay.base + k * lay.rpk + lay.offs[m]
                init_col[r, 0] = PG._INIT[meta.kind]
        for tbase in lay.theta_base.values():
            init_col[tbase: tbase + lp.n_keys * TH.K_LANES, 0] = 2.0

    # ---- the kernel
    def kernel(*refs):
        init_ref = refs[n_in]
        out_ref = refs[n_in + 1]
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _():
            out_ref[:, :] = jnp.broadcast_to(init_ref[:],
                                             (out_rows, LANES))

        tiles = {}
        for i, name in enumerate(names):
            x = refs[i][:]
            tiles[name] = (x != 0) if name in bool_names else x
        ctx = ScanContext(ds, tiles, min_day, max_day, tz=tz)
        cse = FU.CSECache(ctx)
        if fplan is not None:
            cse.prelower(fplan)                  # shared masks: once/tile
        for lp, lay in zip(lanes, layouts):
            base, key, dense, sketch = _lane_parts(lp, ctx, cse)
            key_masks = _key_masks(lp, base, key)
            for k, mk in enumerate(key_masks):
                for m, (kind, _, vals, am) in enumerate(dense):
                    eff = mk if am is None else (mk & am)
                    v32 = None if vals is None \
                        else vals.astype(jnp.float32)
                    part = PG.block_partial(kind, eff, v32)
                    PG.accumulate_rows(
                        out_ref, lay.base + k * lay.rpk + lay.offs[m],
                        kind, part)
            for p, vals, am in sketch:
                tbase = lay.theta_base.get(p.spec.name)
                if tbase is None:
                    continue                     # epilogue sketch
                eff = base if am is None else (base & am)
                for j in range(TH.K_LANES):
                    hv = jnp.where(eff, TH._hash01(vals, j), 2.0)
                    for k, mk in enumerate(key_masks):
                        r = tbase + k * TH.K_LANES + j
                        part = jnp.min(jnp.where(mk, hv, 2.0), axis=0)
                        out_ref[r, :] = jnp.minimum(out_ref[r, :], part)

    interpret = PG._interpret()
    tile = block_rows * LANES
    blk = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    out_blk = pl.BlockSpec((out_rows, LANES), lambda i: (0, 0))
    need_epilogue = any(lay.hll or lay.theta_epilogue or lay.kll
                        for lay in layouts)

    def wave_fn(arrays):
        n = 1
        for d in arrays[names[0]].shape:
            n *= int(d)
        n_pad = -(-max(n, 1) // tile) * tile
        ops = []
        with jax.named_scope("sdot_wave_prep"):
            for name in names:
                a = arrays[name].reshape(-1)
                if name in bool_names:
                    a = a.astype(jnp.int8)
                elif a.dtype.kind == "i" and a.dtype.itemsize < 4:
                    a = a.astype(jnp.int32)
                if n_pad > n:
                    a = jnp.pad(a, (0, n_pad - n))   # pads row_valid=0 rows
                ops.append(a.reshape(n_pad // LANES, LANES))
            ops.append(jnp.asarray(init_col))    # step-0 identity column
        # the name is the HLO instruction's (``%sdot_wave.N``), which is
        # what a TPU trace's ``XLA Ops`` line calls the kernel
        out = pl.pallas_call(
            kernel,
            name="sdot_wave",
            grid=(n_pad // tile,),
            in_specs=[blk] * n_in
            + [pl.BlockSpec((out_rows, 1), lambda i: (0, 0))],
            out_specs=out_blk,
            out_shape=jax.ShapeDtypeStruct((out_rows, LANES), jnp.float32),
            interpret=interpret,
        )(*ops)

        epi = None
        if need_epilogue:
            # sketches the scratch block cannot hold (HLL scatter-max,
            # wide theta) reuse the engine's XLA register ops in the SAME
            # jit — still one kernel launch; the sketch lanes' columns
            # stream once more through XLA
            ctx = ScanContext(ds, arrays, min_day, max_day, tz=tz)
            epi = FU.CSECache(ctx)
            if fplan is not None:
                epi.prelower(fplan)
            epi = (ctx, epi)

        results = []
        for lp, lay in zip(lanes, layouts):
            block = out[lay.base: lay.base + lp.n_keys * lay.rpk, :] \
                .reshape(lp.n_keys, lay.rpk, LANES)
            flat = {}
            for m, meta in enumerate(lay.dense_meta):
                off = lay.offs[m]
                if meta.kind in ("count", "sum"):
                    flat[meta.name] = (block[:, off, :],
                                       block[:, off + 1, :])
                elif meta.kind == "min":
                    flat[meta.name] = jnp.min(block[:, off, :], axis=-1)
                else:
                    flat[meta.name] = jnp.max(block[:, off, :], axis=-1)
            routed = G._pallas_to_routes(flat, lay.dense_meta, lp.routes)
            for name, tbase in lay.theta_base.items():
                tb = out[tbase: tbase + lp.n_keys * TH.K_LANES, :] \
                    .reshape(lp.n_keys, TH.K_LANES, LANES)
                routed[name] = jnp.min(tb, axis=-1)      # exact min union
            if lay.hll or lay.theta_epilogue or lay.kll:
                ctx, cse = epi
                base, key, _, sketch = _lane_parts(lp, ctx, cse)
                for p, vals, am in sketch:
                    nm = p.spec.name
                    if nm in lay.theta_base:
                        continue
                    m = base if am is None else (base & am)
                    if p.kind == "hll":
                        routed[nm] = HLL.hll_registers(
                            key, m, vals, lp.n_keys, log2m, hll_costs)
                    elif p.kind == "kll":
                        tcol = ctx.col(ds.time.name) \
                            if ds.time is not None else None
                        routed[nm] = KLL.kll_registers(
                            key, m, vals, tcol, lp.n_keys, kll_lanes)
                    else:
                        routed[nm] = TH.theta_registers(
                            key, m, vals, lp.n_keys)
            results.append(routed)
        return results

    info = {
        "block_rows": int(block_rows),
        "out_rows": int(out_rows),
        "lanes": len(lanes),
        "interpret": bool(interpret),
        "theta_inkernel": sum(len(lay.theta_base) for lay in layouts),
        "sketch_epilogue": sum(len(lay.hll) + len(lay.theta_epilogue)
                               + len(lay.kll) for lay in layouts),
        # double-buffered input tiles + the resident scratch block
        "vmem_bytes": int(block_rows * LANES * sum(itemsizes) * 2
                          + out_rows * LANES * 4),
    }
    return wave_fn, info
