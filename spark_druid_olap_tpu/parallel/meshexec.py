"""Multi-chip device-mesh execution tier for the fused shared-scan path.

The reference system scales a scan by fanning segment groups out across
historical servers and merging per-server partial aggregates at the
broker (``DruidRDD.getPartitions:244-277``). On a TPU host the same
shape exists one level down: several chips hang off one interconnect,
and a fused shared-scan wave — K dashboard queries riding one column
bind — is exactly a scan that wants to fan out. This module is the
local analog of that broker contract, built data-movement-first
(Theseus, arxiv 2508.05029): per-device partial aggregates never leave
HBM; only the merged registers cross the interconnect.

Execution shape (used by ``parallel/sharedscan.py``):

- ``decide`` is the static eligibility precheck. Every disqualifying
  condition falls back to single-device execution with a named reason
  (the fallback matrix in docs/MESH.md); nothing is decided inside a
  traced program.
- ``build_sharded_program`` wraps a per-lane program — the jaxpr-fused
  core or the Pallas wave mega-kernel from ops/pallas_wave.py, both of
  which already produce route-conformant per-lane output dicts — in
  ``shard_map`` over the 1-D segment axis. Inside the body each lane's
  partials merge with exactly the register algebra ``AGG_CLOSURE.merge``
  declares and the sdlint mesh pass statically enforces:

  * ``psum``  — sums / counts (limb routes; Neumaier-compensated
    ff/ffl pairs stay per-chip, sharded out, and are summed as
    f64-exact pairs by the host ``combine_route`` decode),
  * ``pmax``  — max aggregates and HLL registers,
  * ``pmin``  — min aggregates and theta hash minima.

  The merged buffer replicates (out_spec ``P()``); the per-chip pair
  buffer stays sharded (``P(SEGMENT_AXIS)``) so the unchanged unpack
  path sees chips exactly as the solo sharded executor does.
- ``merged_payload_bytes`` / ``collective_bytes`` statically account
  the interconnect traffic a dispatch will generate (the mesh lint
  pass forbids host-state writes inside shard bodies, so accounting is
  computed host-side from route metadata, never measured in-trace).
- ``PartialLedger`` tracks device-resident packed partial buffers
  across the double-buffered wave loop (``acquire_partials`` /
  ``release_partials`` — a registered sdlint leaks pair).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Callable, Sequence, Tuple

import jax
from jax.sharding import PartitionSpec as P

from spark_druid_olap_tpu.ops import groupby as G
from spark_druid_olap_tpu.ops import kll as KLL
from spark_druid_olap_tpu.ops import theta as TH
from spark_druid_olap_tpu.parallel import cost as C
from spark_druid_olap_tpu.parallel import mesh as M
from spark_druid_olap_tpu.parallel import multihost as MH
from spark_druid_olap_tpu.parallel.mesh import (SEGMENT_AXIS, named_jit,
                                                 shard_map)
from spark_druid_olap_tpu.utils.config import (
    COST_MODEL_ENABLED,
    HLL_LOG2M,
    MESH_ENABLED,
    MESH_MIN_SEGMENTS,
    QUANTILE_LANES,
)


@dataclass(frozen=True)
class MeshDecision:
    """Outcome of the static precheck. ``reason`` is one of the
    fallback-matrix rows in docs/MESH.md (or ``"sharded"`` /
    ``"cost-sharded"`` when the wave shards)."""
    sharded: bool
    n_dev: int
    reason: str

    def sig_fields(self) -> Tuple:
        """The fields that shape the traced program (folded into the
        fused compile signature — sdlint K1: a config flip or device-
        count change must re-key the executable)."""
        return (self.sharded, self.n_dev)


SINGLE = MeshDecision(False, 1, "no-mesh")


def decide(eng, ds, lanes, n_segments: int) -> MeshDecision:
    """Static mesh-eligibility precheck for one fused group.

    Single-device on ANY disqualifying condition — the fused tier never
    errors because of the mesh; it just declines it. Reasons:

    - ``no-mesh``       engine has no mesh / one device
    - ``disabled``      sdot.mesh.enabled is False (kill switch)
    - ``multihost``     jax.process_count() > 1 — the fused tier binds
                        process-local arrays; the cross-process plane
                        stays the solo executor's multihost path
    - ``partial-store`` datasource rows live across the pod
    - ``few-segments``  fewer selected segments than
                        sdot.mesh.min.segments (a 1-segment-per-device
                        split pays collective latency for nothing)
    - ``cost-single``   the cost model priced the merge above the scan
                        win (parallel/cost.py mesh_estimate)
    """
    n = M.mesh_size(eng.mesh)
    if n <= 1:
        return SINGLE
    if not bool(eng.config.get(MESH_ENABLED)):
        return MeshDecision(False, 1, "disabled")
    if MH.is_multihost():
        return MeshDecision(False, 1, "multihost")
    if getattr(ds, "is_partial", False):
        return MeshDecision(False, 1, "partial-store")
    if n_segments < max(2, int(eng.config.get(MESH_MIN_SEGMENTS))):
        return MeshDecision(False, 1, "few-segments")
    if not bool(eng.config.get(COST_MODEL_ENABLED)):
        return MeshDecision(True, n, "sharded")
    try:
        est = C.mesh_estimate(
            eng.config, n_dev=n, rows=int(ds.num_rows),
            groups=max(lp.n_keys for lp in lanes),
            n_aggs=sum(len(lp.agg_plans) for lp in lanes),
            merge_bytes=collective_bytes(eng, lanes, n))
    except Exception:   # noqa: BLE001 — cost must never fail a query
        return MeshDecision(True, n, "sharded")
    if not est.recommend_sharded:
        return MeshDecision(False, 1, "cost-single")
    return MeshDecision(True, n, "cost-sharded")


# -- static interconnect accounting -------------------------------------------

def merged_payload_bytes(eng, lanes) -> int:
    """Size of the replicated (collective-merged) output buffers for one
    dispatch, computed from route metadata exactly the way
    ``_agg_meta_packers`` lays the merged buffer out: merged routes +
    rows route + HLL register blocks + theta lane blocks + KLL survivor
    blocks, at the packed buffer itemsize (i64 on x64 backends, i32
    otherwise)."""
    m = 1 << int(eng.config.get(HLL_LOG2M))
    kll_w = KLL.width(int(eng.config.get(QUANTILE_LANES)))
    widths = {"hll": m, "theta": TH.K_LANES, "kll": kll_w}
    itemsize = 8 if G._x64() else 4
    elems = 0
    for lp in lanes:
        sketch = {p.spec.name: p.kind for p in lp.agg_plans
                  if p.kind in ("hll", "theta", "kll")}
        for name, r in lp.routes.items():
            if name in sketch or not r.merged:
                continue
            elems += sum(size for _, size, _ in r.outputs(lp.n_keys))
        for name, kind in sketch.items():
            elems += lp.n_keys * widths[kind]
    return elems * itemsize


def collective_bytes(eng, lanes, n_dev: int) -> int:
    """Interconnect bytes one sharded dispatch moves: every device
    contributes its merged-payload partial to an all-reduce, so the
    reduction ships ``payload x (n_dev - 1)`` across the links (the
    ring-all-reduce convention; documented in docs/MESH.md and priced
    by parallel/cost.py)."""
    return merged_payload_bytes(eng, lanes) * max(0, int(n_dev) - 1)


# -- the sharded program wrapper ----------------------------------------------

def build_sharded_program(eng, lane_outs_fn: Callable, lanes,
                          packers: Sequence[Tuple]):
    """Wrap ``lane_outs_fn`` (arrays -> per-lane route-conformant output
    dicts; either the jaxpr-fused core or the Pallas wave mega-kernel)
    in ``shard_map`` over the engine mesh.

    Inside the body each device runs the UNCHANGED inner loop over its
    ``S / n_dev`` segment slice, then every lane's partials fold with
    ``ops.groupby.merge_lane_partials`` — psum / pmin / pmax per the
    route's declared algebra, sketch registers per ``AGG_CLOSURE.merge``
    — before packing. Merged buffers replicate; per-chip Neumaier /
    theta-lane pair buffers stay sharded for the host's exact f64
    combine. Returns a jitted callable with the same signature and
    output pytree as the single-device program, so dispatch, unpack and
    decode are byte-for-byte shared."""
    mesh = eng.mesh
    sketch_kinds = [
        {p.spec.name: p.kind for p in lp.agg_plans
         if p.kind in ("hll", "theta", "kll")}
        for lp in lanes]

    def sharded_lanes(arrays):
        outs = lane_outs_fn(arrays)
        packed = []
        for lp, out, (pack, _), sk in zip(lanes, outs, packers,
                                          sketch_kinds):
            merged = G.merge_lane_partials(out, lp.routes, sk,
                                           SEGMENT_AXIS)
            packed.append(pack(merged))
        return tuple(packed)

    smfn = shard_map(
        sharded_lanes, mesh=mesh,
        in_specs=(P(SEGMENT_AXIS, None),),
        out_specs=tuple((P(), P(SEGMENT_AXIS)) for _ in lanes),
        check_vma=False)
    return named_jit("sdot_mesh_program", smfn)


# -- device-resident partial-buffer ledger ------------------------------------

class _PartialToken:
    __slots__ = ("nbytes", "released")

    def __init__(self, nbytes: int):
        self.nbytes = int(nbytes)
        self.released = False


class PartialLedger:
    """Accounting for packed per-device partial buffers while a
    double-buffered wave loop holds them on device (between dispatch
    and host unpack). ``acquire_partials``/``release_partials`` are a
    registered sdlint leaks pair — every acquire must release on all
    paths, so a crashed wave loop can never strand phantom device
    bytes in the gauge."""

    def __init__(self):
        self._lock = threading.Lock()
        self.outstanding_bytes = 0
        self.peak_bytes = 0
        self.acquires = 0

    def acquire_partials(self, nbytes: int) -> _PartialToken:
        tok = _PartialToken(nbytes)
        with self._lock:
            self.acquires += 1
            self.outstanding_bytes += tok.nbytes
            self.peak_bytes = max(self.peak_bytes, self.outstanding_bytes)
        return tok

    def release_partials(self, tok: _PartialToken) -> None:
        with self._lock:
            if not tok.released:
                tok.released = True
                self.outstanding_bytes -= tok.nbytes

    def stats(self) -> dict:
        with self._lock:
            return {"outstanding_bytes": self.outstanding_bytes,
                    "peak_bytes": self.peak_bytes,
                    "acquires": self.acquires}


#: process-wide gauge (stats surface: wlm.stats()["sharedscan"]["mesh"])
LEDGER = PartialLedger()
