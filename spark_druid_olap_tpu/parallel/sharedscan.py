"""Shared-scan multi-query execution: coalesce concurrent eligible queries
over one datasource into ONE fused device program.

The BI-dashboard storm the reference system was built for is K small
concurrent star-schema queries over the *same* columns; executed solo,
they pay K× scan bandwidth and K× dispatch overhead (each device
round trip is a launch plus a host sync). Classic shared-scan / fused-
operator results (Flare, arxiv 1703.08219; Theseus, arxiv 2508.05029)
say the win is multiplicative with concurrency, so this tier converts
concurrency into a throughput multiplier instead of a queue:

- The first eligible query on a datasource becomes the *leader* of an
  open group and holds for ``sdot.wlm.batch.window.ms`` (group-commit
  semantics; held time counts against the query's own timeout).
- Companions arriving inside the window join as *followers* and park.
- At close, the leader plans every constituent, binds the COLUMN UNION
  of the group once per segment wave (through the engine's shared
  device-array cache), runs one fused program evaluating every
  constituent's filter mask + aggregation lanes against the shared
  in-HBM bind, and demultiplexes per-query results.
- Every constituent that cannot ride the fused program (hashed-tier
  cardinality, sketch-over-unsupported, empty pruning, host residual)
  falls back to its own solo execution on its own thread — coalescing
  is an optimization, never a semantics change.

Cache interaction: the coalescer runs *under* the result-cache layer
(QueryEngine._execute_admitted), so each constituent still populates /
serves the semantic cache under its own canonical key.

Fused-program shape: one ``ScanContext`` over the union bind; per-lane
``base = row_valid & filter & interval`` masks feed per-lane
``dense_groupby`` calls; outputs pack through the engine's existing
two-buffer packers per lane. ``row_valid`` travels IN the bound arrays
(ops/scan.py), so the compiled program is segment-selection independent
and keys the compile cache on the sorted tuple of constituent plan
signatures — a warm dashboard mix reuses one executable.
"""

from __future__ import annotations

import threading
import time as _time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from spark_druid_olap_tpu.ir import spec as S
from spark_druid_olap_tpu.ops import filters as F
from spark_druid_olap_tpu.ops import groupby as G
from spark_druid_olap_tpu.ops import hll as HLL
from spark_druid_olap_tpu.ops import kll as KLL
from spark_druid_olap_tpu.ops import theta as TH
from spark_druid_olap_tpu.ops import pallas_wave as PW
from spark_druid_olap_tpu.ops import time_ops as T
from spark_druid_olap_tpu.ops.scan import ScanContext, array_dtype, array_names
from spark_druid_olap_tpu.parallel import cost as C
from spark_druid_olap_tpu.parallel import mesh as M
from spark_druid_olap_tpu.parallel import meshexec as MX
from spark_druid_olap_tpu.planner import fusion as FU
from spark_druid_olap_tpu.result import QueryResult
from spark_druid_olap_tpu.utils import phases as PH
from spark_druid_olap_tpu.utils.config import (
    GROUPBY_DENSE_MAX_KEYS,
    GROUPBY_MATMUL_MAX_KEYS,
    HLL_LOG2M,
    PALLAS_WAVE_ENABLED,
    PALLAS_WAVE_MAX_LANES,
    PALLAS_WAVE_TILE_BYTES,
    QUANTILE_LANES,
    SHAREDSCAN_ENABLED,
    SHAREDSCAN_FUSION_ENABLED,
    SHAREDSCAN_FUSION_MAX_NODES,
    SHAREDSCAN_MAX_QUERIES,
    TZ_ID,
    WLM_BATCH_WINDOW_MS,
)

# a member's outcome slot: None = pending, _FALLBACK = run solo on the
# member's own thread, an exception instance = raise it there, anything
# else = the demultiplexed QueryResult
_FALLBACK = object()

# how often a parked follower re-checks its own cancel flag / deadline
# while waiting for the leader to deliver
_WAIT_POLL_S = 0.02


class _Member:
    __slots__ = ("q", "t0", "leader", "event", "outcome", "stats", "tok")

    def __init__(self, q, t0, leader: bool, tok=None):
        self.q = q
        self.t0 = t0
        self.leader = leader
        self.event = threading.Event()
        self.outcome = None
        self.stats = None
        self.tok = tok


class _Group:
    __slots__ = ("gid", "ds_name", "members", "state", "close_ev",
                 "closed_ns")

    def __init__(self, gid: int, ds_name: str):
        self.gid = gid
        self.ds_name = ds_name
        self.members: List[_Member] = []
        self.state = "open"          # open -> closing -> closed
        self.close_ev = threading.Event()
        # perf_counter_ns at which the leader left its hold: where every
        # member's coalesce.hold span ends and a follower's ride begins
        self.closed_ns = None


class _LanePlan:
    """One fused-program lane: the planned form of one distinct
    constituent spec (members sharing a plan signature share a lane)."""

    __slots__ = ("q", "sig", "dims", "aggs", "post", "having", "limit",
                 "gran", "seg", "dim_plans", "agg_plans", "n_keys",
                 "routes", "needed", "time_in_play", "names")

    def __init__(self, q, sig, dims, aggs, post, having, limit, gran, seg):
        self.q = q
        self.sig = sig
        self.dims = dims
        self.aggs = aggs
        self.post = post
        self.having = having
        self.limit = limit
        self.gran = gran
        self.seg = seg


class SharedScanCoalescer:
    """One per QueryEngine. ``run`` replaces ``_execute_inner`` for
    eligible queries; everything ineligible (or racing a closed group)
    degrades to the solo path."""

    def __init__(self, engine):
        self.engine = engine
        self._lock = threading.Lock()
        self._groups: Dict[str, _Group] = {}
        self._next_gid = 0
        # monotone global counters (GET /metadata/wlm, loadtest)
        self.groups_coalesced = 0     # groups that ran >= 2 fused lanes
        self.solo_groups = 0          # window expired with one live member
        self.queries_coalesced = 0    # constituents served by fused runs
        self.fallbacks = 0            # members bounced to solo execution
        self.last_error = None        # newest fused-path crash behind them
        self.binds_saved_bytes = 0
        self.dispatches_saved = 0
        self.wlm_handoffs = 0         # queued waiters bypassed into groups
        # fusion planner (planner/fusion.py) — deterministic plan-time
        # counters, ticked on EVERY fused run (warm program cache too)
        self.fusion_groups = 0          # fused runs that planned CSE
        self.fusion_fallbacks = 0       # planning errors -> unfused lowering
        self.fusion_shared_predicates = 0
        self.fusion_predicate_evals_saved = 0
        self.fusion_predicate_evals_total = 0
        self.fusion_column_streams_saved = 0
        # solo-path CSE (parallel/executor.py threads the same cache
        # through the dense/hashed cores; one query's tree can repeat
        # sub-predicates, e.g. OR-of-bounds over one column)
        self.fusion_solo_evals_saved = 0
        self.fusion_solo_evals_total = 0
        # pallas wave mega-kernel (ops/pallas_wave.py): one hand-
        # scheduled kernel launch per dispatch wave when the group is
        # wave-eligible; fallbacks count build-time lowerings back to
        # the jaxpr program (routing tiers unchanged)
        self.pallas_launches = 0
        self.pallas_tiles = 0
        self.pallas_fallbacks = 0
        self.pallas_vmem_peak = 0
        # multi-chip mesh tier (parallel/meshexec.py): fused groups whose
        # segment waves sharded across the local device mesh, with
        # per-device partials merged on the interconnect. Fallback
        # reasons mirror the docs/MESH.md matrix; collective_bytes is
        # the STATIC route-metadata accounting (the mesh lint pass
        # forbids measuring inside shard bodies)
        self.mesh_groups = 0            # fused groups dispatched sharded
        self.mesh_dispatches = 0        # sharded wave dispatches
        self.mesh_collective_bytes = 0  # est. interconnect merge bytes
        self.mesh_fallbacks: Dict[str, int] = {}   # reason -> groups

    # -- eligibility -----------------------------------------------------------
    def enabled(self) -> bool:
        return bool(self.engine.config.get(SHAREDSCAN_ENABLED))

    def should_try(self, q) -> bool:
        """Cheap pre-gate: spec shapes the fused tier can demultiplex.
        Select (pagination state) and Search never coalesce; neither does
        anything when the backend is lost (the host tier is serving)."""
        if not self.enabled():
            return False
        if self.engine._backend_lost_at is not None:
            return False
        return isinstance(q, (S.GroupByQuerySpec, S.TimeseriesQuerySpec,
                              S.TopNQuerySpec))

    def open_group_hint(self, datasource) -> bool:
        """True when an open group on ``datasource`` still has room — the
        WLM poll loop uses this to hand a queued compatible query to the
        coalescer instead of draining it serially."""
        if not self.enabled() or datasource is None:
            return False
        maxq = int(self.engine.config.get(SHAREDSCAN_MAX_QUERIES))
        with self._lock:
            g = self._groups.get(datasource)
            return g is not None and g.state == "open" \
                and len(g.members) < maxq

    # -- group membership ------------------------------------------------------
    def run(self, q, t0: float) -> QueryResult:
        """Join (or lead) the open group for q's datasource; return the
        demultiplexed result, or fall back to solo execution."""
        eng = self.engine
        window_s = max(0.0,
                       float(eng.config.get(WLM_BATCH_WINDOW_MS)) / 1000.0)
        maxq = max(1, int(eng.config.get(SHAREDSCAN_MAX_QUERIES)))
        tok = getattr(eng._tls, "inflight_tok", None)
        joined_ns = _time.perf_counter_ns()
        with self._lock:
            g = self._groups.get(q.datasource)
            if g is not None and g.state == "open" and len(g.members) < maxq:
                m = _Member(q, t0, leader=False, tok=tok)
                g.members.append(m)
                if len(g.members) >= maxq:
                    g.state = "closing"
                    g.close_ev.set()
            else:
                self._next_gid += 1
                g = _Group(self._next_gid, q.datasource)
                m = _Member(q, t0, leader=True, tok=tok)
                g.members.append(m)
                self._groups[q.datasource] = g

        if m.leader:
            with PH.phase("coalesce.hold"):
                self._hold_window(g, m, window_s)
                g.closed_ns = _time.perf_counter_ns()
            with self._lock:
                g.state = "closed"
                if self._groups.get(q.datasource) is g:
                    del self._groups[q.datasource]
                members = list(g.members)
            self._close_group(g, members)
        else:
            while not m.event.wait(_WAIT_POLL_S):
                # honors the follower's OWN cancel/timeout while parked;
                # a late delivery into an abandoned slot is harmless
                eng._stage_check(q, t0)
            # parked through both: the spans are cut at the group's close
            # after the fact (add() writes no profiler annotation)
            woke_ns = _time.perf_counter_ns()
            closed_ns = min(max(g.closed_ns or woke_ns, joined_ns), woke_ns)
            PH.add("coalesce.hold", (closed_ns - joined_ns) / 1e9, closed_ns)
            PH.add("coalesce.ride", (woke_ns - closed_ns) / 1e9, woke_ns)

        out = m.outcome
        if out is _FALLBACK:
            return eng._execute_inner(q, t0)
        if isinstance(out, BaseException):
            raise out
        if m.stats:
            eng.last_stats.update(m.stats)
        eng.last_stats["total_ms"] = (_time.perf_counter() - t0) * 1000
        return out

    def _hold_window(self, g: _Group, m: _Member, window_s: float) -> None:
        """Leader parks for the micro-batch window (early close when the
        group fills, or when the leader's own cancel/deadline fires —
        held time counts against timeout_millis)."""
        deadline = _time.perf_counter() + window_s
        while not g.close_ev.is_set():
            rem = deadline - _time.perf_counter()
            if rem <= 0:
                break
            g.close_ev.wait(min(rem, 0.005))
            try:
                self.engine._stage_check(m.q, m.t0)
            except BaseException:
                break   # close now; _close_group re-checks and drops us

    def _close_group(self, g: _Group, members: List[_Member]) -> None:
        """Runs on the leader's thread. Every member gets an outcome and
        (followers) a set event, no matter what — never a hang. A
        compiler refusal of the wave kernel is the live members' outcome
        (their statements fail with the compiler's text); any other
        fused-path crash degrades the group to solo execution and is
        kept in ``stats()["last_error"]`` beside the fallback count."""
        eng = self.engine
        live = []
        for m in members:
            try:
                eng._stage_check(m.q, m.t0)
                live.append(m)
            except BaseException as e:  # noqa: BLE001 — delivered as outcome
                m.outcome = e           # cancelled/timed out while held:
                #                         drops out before execution
        fused_tried = len(live) >= 2
        try:
            if fused_tried:
                self._run_fused(g, live)
            else:
                with self._lock:
                    self.solo_groups += 1
        except PW.WaveCompileError as e:
            for m in live:
                if m.outcome is None:
                    m.outcome = e
        except Exception as e:  # noqa: BLE001 — degrade, don't strand
            with self._lock:
                self.last_error = f"{type(e).__name__}: {e}"
        finally:
            n_fallback = 0
            for m in members:
                if m.outcome is None:
                    m.outcome = _FALLBACK
                    if fused_tried:
                        n_fallback += 1
                if not m.leader:
                    m.event.set()
            if n_fallback:
                with self._lock:
                    self.fallbacks += n_fallback

    # -- fused planning + execution -------------------------------------------
    def _run_fused(self, g: _Group, live: List[_Member]) -> None:
        """Plan every live member against the union segment selection,
        build/fetch ONE fused program keyed on the sorted tuple of lane
        signatures, bind the column union once per wave, dispatch, and
        demultiplex. Members that cannot ride stay at _FALLBACK."""
        from spark_druid_olap_tpu.parallel import executor as X
        eng = self.engine
        with PH.phase("coalesce.plan"):
            ds_name = live[0].q.datasource
            try:
                ds = eng.store.get(ds_name)
            except Exception:  # noqa: BLE001 — solo path reports it
                return
            if getattr(ds, "is_partial", False) or ds.num_rows == 0:
                return

            shaped = []
            for m in live:
                lp = self._shape_member(eng, ds, m.q)
                if lp is not None:
                    shaped.append((m, lp))
            if len(shaped) < 2:
                return

            seg_u = np.unique(np.concatenate([lp.seg for _, lp in shaped]))
            mins, maxs = ds.segment_time_bounds()
            min_day = int(mins[seg_u].min() // T.MILLIS_PER_DAY)
            max_day = int(maxs[seg_u].max() // T.MILLIS_PER_DAY)

            planned = []
            for m, lp in shaped:
                if self._plan_lane(eng, ds, lp, min_day, max_day):
                    planned.append((m, lp))
            if len(planned) < 2:
                return

            # dedup identical specs into shared lanes, sorted by signature so
            # the compile-cache key is order-independent across arrivals
            by_sig: Dict[str, _LanePlan] = {}
            for _, lp in planned:
                by_sig.setdefault(lp.sig, lp)
            sigs = tuple(sorted(by_sig))
            lanes = [by_sig[s] for s in sigs]
            lane_idx = {s: i for i, s in enumerate(sigs)}

            union_cols = sorted(set().union(*[lp.needed for lp in lanes]))
            union_time = any(lp.time_in_play for lp in lanes)
            union_names = array_names(ds, union_cols, union_time)
            seg_bytes = C.bytes_per_segment(ds, union_names)
            # mesh tier (parallel/meshexec.py): static precheck; any
            # disqualifying condition falls back to single-device with a
            # named reason. The decision shapes the traced program AND the
            # wave plan (per-device budgets multiply by n_dev)
            dec = MX.decide(eng, ds, lanes, len(seg_u))
            n_dev = dec.n_dev
            spw, n_waves = C.plan_waves(
                len(seg_u), n_dev, seg_bytes, C.wave_budget_bytes(eng.config),
                eng.config, max(lp.n_keys for lp in lanes),
                sum(len(lp.agg_plans) for lp in lanes),
                io_budget=C.tier_io_budget(ds, eng.config))
            s_pad = spw if n_waves > 1 else X._pad_segments(len(seg_u), n_dev)

            # fusion planning is advisory: any error lowers the unfused way
            # (routing tiers never change). Runs on EVERY fused execution —
            # warm program-cache runs included — so the counters below are
            # deterministic and CI-guardable without a chip.
            fplan = None
            if bool(eng.config.get(SHAREDSCAN_FUSION_ENABLED)):
                try:
                    fplan = FU.plan_lanes(
                        [(lp.q.filter, lp.q.intervals,
                          tuple(a.filter for a in lp.aggs)) for lp in lanes],
                        per_lane_cols=[len(lp.needed) for lp in lanes],
                        union_cols=len(union_cols),
                        max_nodes=int(
                            eng.config.get(SHAREDSCAN_FUSION_MAX_NODES)))
                except Exception:  # noqa: BLE001 — fall back to unfused
                    fplan = None
                    with self._lock:
                        self.fusion_fallbacks += 1

            wave_ok = bool(eng.config.get(PALLAS_WAVE_ENABLED)) \
                and PW.wave_eligible(
                    lanes, int(eng.config.get(PALLAS_WAVE_MAX_LANES)))

            hll_costs = eng._hll_costs(
                [p for lp in lanes for p in lp.agg_plans])
            sig = ("aggmulti", eng._sig_base(ds), s_pad, min_day, max_day,
                   tuple(union_names), sigs,
                   # the fusion plan shapes the traced program: the token is
                   # a pure function of the sorted lane set (arrival-order
                   # independent), None when planning declined or failed
                   int(eng.config.get(SHAREDSCAN_FUSION_MAX_NODES)),
                   fplan.token() if fplan is not None else None,
                   # wave mega-kernel routing: eligibility is re-derived on
                   # EVERY fused execution from plan metadata + env + config,
                   # so a config flip or backend change re-keys the program
                   wave_ok,
                   bool(eng.config.get(PALLAS_WAVE_ENABLED)),
                   int(eng.config.get(PALLAS_WAVE_TILE_BYTES)),
                   int(eng.config.get(PALLAS_WAVE_MAX_LANES)),
                   # mesh decision re-derived on EVERY fused execution (a
                   # sdot.mesh.* flip, device-count change, or cost-model
                   # swing re-keys the program — sdlint K1)
                   dec.sig_fields(),
                   # what the lanes' HLL registers choose their form under
                   hll_costs)

        def _build():
            """Wave first (one pallas launch per wave), jaxpr-fused on
            a planned decline (``WaveFallback``) — the group stays FUSED
            either way, so the wave path can never change routing
            tiers."""
            if wave_ok:
                try:
                    return self._build_wave_program(
                        ds, lanes, min_day, max_day, fplan,
                        union_names=union_names, s_pad=s_pad,
                        mesh_dec=dec, hll_costs=hll_costs)
                except PW.WaveFallback:
                    # a planned decline only: trace, lowering and
                    # compiler errors propagate (docs/KERNELS.md)
                    with self._lock:
                        self.pallas_fallbacks += 1
            fn, unp = self._build_fused_program(ds, lanes, min_day,
                                                max_day, fplan,
                                                mesh_dec=dec,
                                                hll_costs=hll_costs)
            return fn, unp, None

        prog_fn, unpacks, wave_info = eng._cached_program(sig, _build)

        per_lane_finals = self._dispatch(ds, union_names, seg_u, s_pad,
                                         n_waves, prog_fn, unpacks,
                                         lanes, live[0],
                                         wave_info=wave_info,
                                         mesh_dec=dec)
        results = [self._decode_lane(eng, ds, lp, fin)
                   for lp, fin in zip(lanes, per_lane_finals)]

        solo_bytes = sum(
            C.bytes_per_segment(ds, lp.names) * len(lp.seg)
            for _, lp in planned)
        saved_bytes = max(0, solo_bytes - int(seg_bytes) * len(seg_u))
        saved_disp = (len(planned) - 1) * n_waves
        wave_tiles = 0
        if wave_info is not None:
            wave_tiles = -(-(s_pad * ds.padded_rows)
                           // (wave_info["block_rows"] * PW.LANES))
        # per-device kernel launches: each mesh shard runs its own wave
        # kernel over its segment slice
        launches = n_waves * (n_dev if dec.sharded else 1)
        cbytes = MX.collective_bytes(eng, lanes, n_dev) * n_waves \
            if dec.sharded else 0
        with self._lock:
            self.groups_coalesced += 1
            if dec.sharded:
                self.mesh_groups += 1
                self.mesh_dispatches += n_waves
                self.mesh_collective_bytes += cbytes
            else:
                self.mesh_fallbacks[dec.reason] = \
                    self.mesh_fallbacks.get(dec.reason, 0) + 1
            if wave_info is not None:
                self.pallas_launches += launches
                # total tiles are launch-count invariant: the mesh splits
                # the SAME [s_pad x rows] scan across devices
                self.pallas_tiles += n_waves * wave_tiles
                self.pallas_vmem_peak = max(self.pallas_vmem_peak,
                                            wave_info["vmem_bytes"])
            self.queries_coalesced += len(planned)
            self.binds_saved_bytes += saved_bytes
            self.dispatches_saved += saved_disp
            if fplan is not None:
                self.fusion_groups += 1
                self.fusion_shared_predicates += fplan.shared_predicates
                self.fusion_predicate_evals_saved += \
                    fplan.predicate_evals_saved
                self.fusion_predicate_evals_total += fplan.n_nodes
                self.fusion_column_streams_saved += \
                    fplan.column_streams_saved

        for m, lp in planned:
            li = lane_idx[lp.sig]
            fin = per_lane_finals[li]
            m.stats = {
                "datasource": ds.name, "segments": int(len(lp.seg)),
                "sharded": bool(dec.sharded),
                "rows_scanned": int(ds.num_rows),
                "groups": int(np.count_nonzero(fin["__rows__"] > 0)),
                "waves": int(n_waves), "segments_per_wave": int(spw),
                "bytes_scanned": int(seg_bytes) * int(len(seg_u)),
                "mesh": {"devices": int(n_dev),
                         "decision": dec.reason,
                         "collective_bytes": int(cbytes)},
                "sharedscan": {
                    "group": g.gid, "queries": len(planned),
                    "lanes": len(lanes),
                    "role": "leader" if m.leader else "follower",
                    "binds_saved_bytes": saved_bytes,
                    "dispatches_saved": saved_disp,
                    "fusion": (fplan.counters()
                               if fplan is not None else None),
                    "pallas": ({"launches": int(launches),
                                "tiles": int(n_waves * wave_tiles),
                                "block_rows": wave_info["block_rows"],
                                "vmem_bytes": wave_info["vmem_bytes"]}
                               if wave_info is not None else None)}}
            m.outcome = results[li]
            eng.inflight.annotate(m.tok, sharedscan_group=g.gid)

    @staticmethod
    def _shape_member(eng, ds, q) -> Optional[_LanePlan]:
        """Map the spec to the engine's (dims, aggs, post, having, limit,
        gran) shape (``executor._agg_shape``) + prune segments. None =
        this member runs solo (e.g. empty pruning takes the engine's own
        empty/identity-row path, which never touches the device)."""
        from spark_druid_olap_tpu.parallel.executor import (_agg_shape,
                                                            _cache_repr)
        try:
            shape = _agg_shape(q)
            if shape is None:
                return None
            dims, having, limit = shape
            seg = ds.prune_segments(q.intervals, q.filter)
            if len(seg) == 0:
                return None
            return _LanePlan(q, _cache_repr(q), dims, q.aggregations,
                             q.post_aggregations, having, limit,
                             q.granularity, seg)
        except Exception:  # noqa: BLE001 — solo path reports the real error
            return None

    @staticmethod
    def _plan_lane(eng, ds, lp: _LanePlan, min_day: int,
                   max_day: int) -> bool:
        """Detailed planning against the GROUP's min/max day (every lane
        must share one ScanContext day basis). False = member falls back
        (hashed-tier cardinality, unsupported aggregation, wide ints on a
        32-bit backend — everything the solo path handles specially)."""
        from spark_druid_olap_tpu.parallel import executor as X
        from spark_druid_olap_tpu.utils import config as CF
        try:
            gran_kind = lp.gran.kind if lp.gran else "all"
            tz = eng.config.get(TZ_ID)
            dim_plans = [X.plan_dimension(d, ds, min_day, max_day, tz)
                         for d in lp.dims]
            if gran_kind != "all":
                dim_plans = [X.plan_granularity_dim(
                    lp.gran, ds, min_day, max_day, tz)] + dim_plans
            agg_plans = [X.plan_aggregation(a, ds) for a in lp.aggs]
            n_keys = 1
            for p in dim_plans:
                n_keys *= p.card
            if n_keys > eng.config.get(GROUPBY_DENSE_MAX_KEYS):
                return False    # hashed tier: solo handles it
            min_k = int(eng.config.get(CF.GROUPBY_SORTED_MIN_KEYS))
            if min_k > 0 and n_keys >= min_k \
                    and not any(p.kind in ("hll", "theta", "kll")
                                for p in agg_plans) \
                    and eng._sorted_run_wanted():
                return False    # medium-K reroute territory: keep parity
            needed = set()
            for p in dim_plans:
                needed |= set(p.source_cols)
            for p in agg_plans:
                needed |= set(p.source_cols)
            needed |= F.columns_of_filter(lp.q.filter)
            time_in_play = ds.time is not None and (
                lp.q.intervals is not None or gran_kind != "all"
                or ds.time.name in needed)
            if time_in_play:
                needed.add(ds.time.name)
            names = array_names(ds, sorted(needed), time_in_play)
            if not G._x64():
                for k in names:
                    if array_dtype(ds, k) == np.int64:
                        return False   # wide ints on a 32-bit backend
            lp.dim_plans = dim_plans
            lp.agg_plans = agg_plans
            lp.n_keys = n_keys
            lp.routes = eng._plan_routes(agg_plans, n_keys, ds)
            lp.needed = needed
            lp.time_in_play = time_in_play
            lp.names = names
            return True
        except Exception:  # noqa: BLE001 — solo path reports the real error
            return False

    def _build_fused_program(self, ds, lanes: List[_LanePlan],
                             min_day: int, max_day: int, fplan=None,
                             mesh_dec=None, hll_costs=None):
        """(jit_fn, [per-lane unpack]). One ScanContext over the union
        bind; each lane is the engine's dense core (mask -> fused keys ->
        dense_groupby -> sketch registers) packed through its own
        two-buffer packers, so per-lane decode reuses the solo path
        byte-for-byte. With a sharded mesh decision the same per-lane
        core wraps in ``shard_map`` (parallel/meshexec.py): each device
        scans its segment slice and partials merge on the interconnect
        before packing — unpack/decode stay byte-for-byte shared.

        With a fusion plan, the program is single-pass with predicate
        CSE: cross-lane shared masks lower FIRST (each union column
        streams through VMEM once while they compute), then every lane's
        ``base = row_valid & shared & residual`` combine reuses them via
        the trace-time CSE cache — bit-identical to the unfused trace
        because masks only combine with exact bool ops."""
        eng = self.engine
        matmul_max = eng.config.get(GROUPBY_MATMUL_MAX_KEYS)
        log2m = eng.config.get(HLL_LOG2M)
        kll_lanes = eng.config.get(QUANTILE_LANES)
        tz = eng.config.get(TZ_ID)
        packers = [eng._agg_meta_packers(lp.agg_plans, lp.routes,
                                         lp.n_keys, with_idx=False)
                   for lp in lanes]

        def lane_outs(arrays):
            """Per-lane route-conformant output dicts — the shared inner
            loop both the single-device pack and the mesh shard body
            close over (each mesh shard runs it over its own slice)."""
            ctx = ScanContext(ds, arrays, min_day, max_day, tz=tz)
            rv = ctx.row_valid()
            cse = None
            if fplan is not None:
                cse = FU.CSECache(ctx)
                cse.prelower(fplan)
            outs = []
            for lp in lanes:
                base = rv
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
                    key, _ = G.fuse_keys(codes,
                                         [p.card for p in lp.dim_plans])
                else:
                    key = jnp.zeros_like(base, dtype=jnp.int32)
                inputs = []
                for p in lp.agg_plans:
                    if p.kind in ("hll", "theta", "kll"):
                        continue
                    inputs.append(G.AggInput(p.spec.name, p.kind,
                                             p.build_values(ctx),
                                             p.build_mask(ctx, cse=cse),
                                             is_int=p.is_int,
                                             maxabs=p.maxabs))
                inputs.append(G.AggInput("__rows__", "count", is_int=True,
                                         maxabs=1.0))
                out = G.dense_groupby(key, base, lp.n_keys, inputs,
                                      lp.routes, matmul_max)
                for p in lp.agg_plans:
                    if p.kind not in ("hll", "theta", "kll"):
                        continue
                    vals = p.build_values(ctx)
                    am = p.build_mask(ctx, cse=cse)
                    m = base if am is None else (base & am)
                    if p.kind == "hll":
                        out[p.spec.name] = HLL.hll_registers(
                            key, m, vals, lp.n_keys, log2m, hll_costs)
                    elif p.kind == "kll":
                        tcol = ctx.col(ds.time.name) \
                            if ds.time is not None else None
                        out[p.spec.name] = KLL.kll_registers(
                            key, m, vals, tcol, lp.n_keys, kll_lanes)
                    else:
                        out[p.spec.name] = TH.theta_registers(
                            key, m, vals, lp.n_keys)
                outs.append(out)
            return outs

        if mesh_dec is not None and mesh_dec.sharded:
            fn = MX.build_sharded_program(eng, lane_outs, lanes, packers)
        else:
            def fused(arrays):
                return tuple(pack(o) for (pack, _), o
                             in zip(packers, lane_outs(arrays)))
            fn = M.named_jit("sdot_fused_program", fused)
        return fn, [u for _, u in packers]

    def _wave_program_fn(self, ds, lanes: List[_LanePlan],
                         min_day: int, max_day: int, fplan=None, *,
                         union_names, s_pad, mesh_dec=None,
                         hll_costs=None):
        """(jit_fn, [per-lane unpack], wave_info, arg shapes) — the
        traced but not yet compiled wave program. The group's whole wave
        lowers through ONE hand-scheduled Pallas mega-kernel
        (ops/pallas_wave.py); outputs are route-conformant per lane, so
        the same packers/unpackers/decode as the jaxpr program apply.
        Raises :class:`PW.WaveFallback` when the group declines by plan
        — the caller then builds the jaxpr-fused program, keeping the
        group fused."""
        eng = self.engine
        log2m = eng.config.get(HLL_LOG2M)
        tz = eng.config.get(TZ_ID)
        wave_fn, info = PW.build_wave_fn(
            ds, lanes, min_day, max_day, fplan,
            union_names=union_names, tz=tz, log2m=log2m,
            hll_costs=hll_costs,
            tile_bytes=int(eng.config.get(PALLAS_WAVE_TILE_BYTES)),
            kll_lanes=eng.config.get(QUANTILE_LANES))
        packers = [eng._agg_meta_packers(lp.agg_plans, lp.routes,
                                         lp.n_keys, with_idx=False)
                   for lp in lanes]

        sharding = None
        if mesh_dec is not None and mesh_dec.sharded:
            # the wave mega-kernel is shape-generic over the segment dim:
            # inside shard_map each device launches it over its own
            # [s_pad / n_dev, R] slice, partials merge on the
            # interconnect, and the SAME packers/unpacks apply
            fn = MX.build_sharded_program(eng, wave_fn, lanes, packers)
            sharding = M.segment_sharding(eng.mesh)
        else:
            def fused(arrays):
                outs = wave_fn(arrays)
                return tuple(pack(o)
                             for (pack, _), o in zip(packers, outs))
            fn = M.named_jit("sdot_wave_program", fused)
        shapes = {k: jax.ShapeDtypeStruct(
            (s_pad, ds.padded_rows),
            jnp.zeros((), dtype=array_dtype(ds, k)).dtype,
            sharding=sharding)
            for k in union_names}
        return fn, [u for _, u in packers], info, shapes

    def _build_wave_program(self, ds, lanes: List[_LanePlan],
                            min_day: int, max_day: int, fplan=None, *,
                            union_names, s_pad, mesh_dec=None,
                            hll_costs=None):
        """(compiled program, [per-lane unpack], wave_info). Compiles at
        BUILD time and keeps the executable as the program (nothing
        compiles twice), so what the backend's compiler refuses — a
        Mosaic relayout, a VMEM limit, a per-shard lowering through
        shard_map — raises here as :class:`PW.WaveCompileError` naming
        the lane set, never at the group's first dispatch."""
        fn, unpacks, info, shapes = self._wave_program_fn(
            ds, lanes, min_day, max_day, fplan, union_names=union_names,
            s_pad=s_pad, mesh_dec=mesh_dec, hll_costs=hll_costs)
        try:
            prog = fn.lower(shapes).compile()
        except Exception as e:  # noqa: BLE001 — re-raised with the lane set
            raise PW.WaveCompileError(
                f"wave kernel for lanes {[lp.sig for lp in lanes]} was "
                f"refused by the {jax.default_backend()} compiler: "
                f"{type(e).__name__}: {e}") from e
        return prog, unpacks, info

    def _dispatch(self, ds, union_names, seg_u, s_pad, n_waves,
                  prog_fn, unpacks, lanes: List[_LanePlan], leader,
                  wave_info=None, mesh_dec=None):
        """One shared bind + ONE program dispatch per wave (the engine's
        wave pipeline, ``_waves``); per-lane unpack -> finals -> cross-
        wave merge. All device ticks land on the leader's thread —
        including the wave-kernel launch tick (dispatch_counts[2]) when
        the wave program is live. With a sharded mesh decision binds
        carry the segment-axis sharding, launch ticks count per device,
        and the packed per-device partial buffers the wave loop holds on
        device are accounted through the meshexec partial ledger
        (acquire/release pair — sdlint leaks)."""
        from spark_druid_olap_tpu.parallel import executor as X
        eng = self.engine
        sharded = mesh_dec is not None and mesh_dec.sharded
        n_dev = mesh_dec.n_dev if sharded else 1
        if wave_info is not None:
            # pallas kernel launches: one per device per wave
            eng._tick(2, n_waves * n_dev)
        sketch = [[p for p in lp.agg_plans
                   if p.kind in ("hll", "theta", "kll")]
                  for lp in lanes]
        payload = MX.merged_payload_bytes(eng, lanes) * n_dev

        def lane_finals(bufs):
            return [X._finals_from_out(unpacks[i](bufs[i]), lp.routes,
                                       lp.n_keys, sketch[i])
                    for i, lp in enumerate(lanes)]

        # several waves: each ordered so the mesh's per-device blocks
        # carry balanced row loads; one wave keeps the selection's order,
        # which is the bind-cache entry the solo path shares
        seg_rows = None
        if sharded and n_waves > 1:
            try:
                seg_rows = {int(s): int(ds.segments[int(s)].num_rows)
                            for s in seg_u}
            except Exception:  # noqa: BLE001 — handles without segment objects
                seg_rows = None
        wave_segs = FU.plan_device_waves(seg_u, s_pad, n_dev, seg_rows)
        finals: List[Optional[dict]] = [None] * len(lanes)
        # mesh-parallel cold-tier faults: open a devices-aware pin scope
        # so eviction sees the whole n_dev-wide wave as one pinned unit
        tier = getattr(ds, "tier", None)
        ptok = tier.acquire_pins(devices=n_dev) \
            if (sharded and tier is not None and n_waves > 1) else None
        try:
            tok = MX.LEDGER.acquire_partials(payload)
            try:
                # leader-thread attribution: every wave's launch, the
                # overlapped prefetch and bind, wait and fetch are spans
                # of the leader's record
                for wave in eng._waves(
                        leader.q, leader.t0, ds, union_names, wave_segs,
                        s_pad, sharded, None, prog_fn, lane_finals):
                    for li, lp in enumerate(lanes):
                        finals[li] = wave[li] if finals[li] is None \
                            else X._merge_wave_finals(
                                finals[li], wave[li], lp.routes,
                                sketch[li])
            finally:
                MX.LEDGER.release_partials(tok)
        finally:
            if ptok is not None:
                tier.release_pins(ptok)
        return finals

    @staticmethod
    def _decode_lane(eng, ds, lp: _LanePlan, finals) -> QueryResult:
        """Host demultiplex of one lane: the engine's dense decode, which
        the fused tier never hands a device top-k. Charged to the
        ``demux`` phase of whichever statement's thread runs it."""
        with PH.phase("demux"):
            return eng._decode_dense(
                ds, lp.dim_plans, lp.agg_plans, lp.routes, finals,
                lp.gran.kind if lp.gran else "all", lp.post, lp.having,
                lp.limit)[0]

    def note_handoff(self) -> None:
        """Called by the WLM poll loop when a queued waiter bypasses its
        lane to ride an open group's dispatch."""
        with self._lock:
            self.wlm_handoffs += 1

    def note_solo_cse(self, saved: int, total: int) -> None:
        """Called by the solo executor path's plan-time CSE accounting
        (one query's own tree repeating sub-predicates)."""
        with self._lock:
            self.fusion_solo_evals_saved += int(saved)
            self.fusion_solo_evals_total += int(total)

    # -- observability ---------------------------------------------------------
    def stats(self) -> dict:
        with self._lock:
            total = self.fusion_predicate_evals_total \
                + self.fusion_solo_evals_total
            saved = self.fusion_predicate_evals_saved \
                + self.fusion_solo_evals_saved
            return {"enabled": self.enabled(),
                    "groups_coalesced": self.groups_coalesced,
                    "solo_groups": self.solo_groups,
                    "queries_coalesced": self.queries_coalesced,
                    "fallbacks": self.fallbacks,
                    "last_error": self.last_error,
                    "binds_saved_bytes": self.binds_saved_bytes,
                    "dispatches_saved": self.dispatches_saved,
                    "wlm_handoffs": self.wlm_handoffs,
                    "pallas": {
                        "launches": self.pallas_launches,
                        "tiles": self.pallas_tiles,
                        "fallbacks": self.pallas_fallbacks,
                        "vmem_bytes_peak": self.pallas_vmem_peak},
                    "mesh": {
                        "devices": M.mesh_size(self.engine.mesh),
                        "groups": self.mesh_groups,
                        "dispatches": self.mesh_dispatches,
                        "collective_bytes": self.mesh_collective_bytes,
                        "fallbacks": dict(self.mesh_fallbacks),
                        "partials": MX.LEDGER.stats()},
                    "fusion": {
                        "groups": self.fusion_groups,
                        "plan_fallbacks": self.fusion_fallbacks,
                        "shared_predicates":
                            self.fusion_shared_predicates,
                        "predicate_evals_saved":
                            self.fusion_predicate_evals_saved,
                        "predicate_evals_total":
                            self.fusion_predicate_evals_total,
                        "column_streams_saved":
                            self.fusion_column_streams_saved,
                        "solo_evals_saved": self.fusion_solo_evals_saved,
                        "solo_evals_total": self.fusion_solo_evals_total,
                        "cse_hit_rate": round(saved / total, 4)
                        if total else 0.0}}
