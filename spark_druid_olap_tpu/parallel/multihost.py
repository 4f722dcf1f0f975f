"""Multi-host execution: one JAX process per host, one global mesh.

≈ the reference's genuinely distributed plane: segments are assigned to
historical servers by priority and least-load
(``DruidMetadataCache.assignHistoricalServers``,
``metadata/DruidMetadataCache.scala:105-148``) and a scan fans out one
Spark partition per (server × segment group)
(``DruidRDD.getPartitions:244-277``). The TPU translation:

- ``jax.distributed.initialize`` joins every host's process into one
  runtime; ``jax.devices()`` then lists EVERY chip in the pod and the
  1-D segment mesh (``mesh.make_mesh``) spans them. ICI/DCN collectives
  (psum / all_gather inside ``shard_map``) replace the broker merge.
- **Host-level segment ownership** (``assign_segments_to_hosts``):
  contiguous time-blocks balanced by rows — contiguity keeps interval
  pruning host-aligned, the balance term is the least-load analog. Each
  process materializes ONLY its own segments' column data
  (``Datasource.local_seg_ids``); global metadata (segment bounds,
  dictionaries from the streamer's pass A) is replicated everywhere, so
  planning stays deterministic across processes.
- **Transfers provide only local shards**: a globally-sharded array is
  assembled with ``jax.make_array_from_callback`` — the callback is
  invoked per locally-addressable device and reads the local store
  block (``layout_segments`` fixes the segment→device order so every
  host's devices carry exactly that host's segments; no cross-host
  traffic at bind time).
- Sharded programs whose outputs stayed per-chip in single-process mode
  (the hashed tier's slot tables) gain an in-mesh ``all_gather`` so the
  result is replicated and every process can fetch it (the executor's
  ``_shard_wrap``).

Every *planning* decision (pruning, slot sizing, wave split, compaction
budgets) runs on metadata that is identical on every process — a
divergent decision would deadlock the mesh, so zone-map pruning (which
reads per-host column data) is disabled for partial datasources
(``store.Datasource._filter_keep_mask``).
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import numpy as np

import jax


def initialize(coordinator_address: str, num_processes: int,
               process_id: int,
               local_device_count: Optional[int] = None) -> None:
    """Join this process into the multi-host JAX runtime. Call before any
    other JAX use (backend initialization pins the topology).

    ``local_device_count`` forces N virtual CPU devices per process — the
    test rig for multi-host sharding without N real chips (the same trick
    as the single-process virtual mesh, conftest.py)."""
    if local_device_count is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        want = f"--xla_force_host_platform_device_count={local_device_count}"
        if want not in flags:
            os.environ["XLA_FLAGS"] = f"{flags} {want}".strip()
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def is_multihost() -> bool:
    try:
        return jax.process_count() > 1
    except Exception:   # noqa: BLE001 — uninitialized backend
        return False


def assign_segments_to_hosts(row_counts: np.ndarray,
                             n_hosts: int) -> np.ndarray:
    """[S] -> host id. Contiguous time-blocks balanced by rows.

    Segments are time-ordered, so contiguous blocks keep a host's data one
    time range (interval pruning then prunes whole hosts, the way Druid's
    time-chunk assignment does); the row-balance objective is the
    least-load term of ``assignHistoricalServers``. Greedy split at the
    ideal cumulative boundaries — deterministic, metadata-only (every
    process computes the identical assignment)."""
    rows = np.asarray(row_counts, dtype=np.int64)
    s = len(rows)
    if n_hosts <= 1 or s == 0:
        return np.zeros(s, dtype=np.int32)
    cum = np.cumsum(rows)
    total = int(cum[-1])
    out = np.zeros(s, dtype=np.int32)
    # boundary h sits where cumulative rows pass h/n of the total
    targets = total * np.arange(1, n_hosts) / n_hosts
    cuts = np.searchsorted(cum - rows / 2.0, targets)
    prev = 0
    for h, c in enumerate(np.clip(cuts, 0, s)):
        out[prev:c] = h
        prev = max(prev, int(c))
    out[prev:] = n_hosts - 1
    return out


def host_blocks(mesh) -> Tuple[int, int]:
    """(n_hosts, devices_per_host) of the 1-D segment mesh. Requires the
    homogeneous-pod shape (same chip count per host) — the only topology
    ``jax.distributed`` + a dense Mesh supports cleanly."""
    n_proc = jax.process_count()
    n_dev = int(np.prod(list(mesh.shape.values())))
    if n_dev % n_proc:
        raise ValueError(
            f"mesh of {n_dev} devices over {n_proc} processes is not "
            f"host-homogeneous")
    return n_proc, n_dev // n_proc


def layout_segments(assignment: np.ndarray, seg_idx: np.ndarray,
                    n_hosts: int, devs_per_host: int):
    """Fix the segment→device order for a (pruned) selection so each
    host's devices scan exactly that host's segments.

    Returns ``(ordered, s_pad)``: ``ordered`` is a [n_hosts * per_host]
    int64 array of global segment ids with ``-1`` padding slots (empty,
    row-validity False), ``per_host`` padded to a common multiple of
    ``devs_per_host`` so the global segment axis divides evenly. Every
    process computes this identically from global metadata — it is the
    multi-host replacement for the executor's contiguous ``_pad_segments``
    split."""
    seg_idx = np.asarray(seg_idx, dtype=np.int64)
    per_host_lists = [seg_idx[assignment[seg_idx] == h]
                      for h in range(n_hosts)]
    longest = max((len(x) for x in per_host_lists), default=0)
    longest = max(longest, 1)
    per_host = -(-longest // devs_per_host) * devs_per_host
    ordered = np.full(n_hosts * per_host, -1, dtype=np.int64)
    for h, lst in enumerate(per_host_lists):
        ordered[h * per_host: h * per_host + len(lst)] = lst
    return ordered, per_host


def layout_segments_waves(assignment: np.ndarray, seg_idx: np.ndarray,
                          n_hosts: int, devs_per_host: int, n_waves: int,
                          seg_bytes: int = 0, wave_budget: int = 0):
    """Wave-mode variant of ``layout_segments`` (VERDICT r4 item 2: waves
    must compose with multi-host — SF100's overflow valve).

    Each WAVE is itself a host-blocked layout: wave ``w`` holds the
    ``w``-th chunk of every host's pruned segment list, padded to a common
    per-host-per-wave count that divides ``devs_per_host``. Returns
    ``(ordered, spw)``: ``ordered`` is [n_waves_eff * spw] with ``-1``
    padding; contiguous ``spw``-slices of it are exactly the per-wave
    layouts the executor's wave pipeline already slices, so ``_waves``
    needs no multi-host awareness beyond the shard-aware bind. Every
    process computes this identically from global metadata."""
    seg_idx = np.asarray(seg_idx, dtype=np.int64)
    per_host_lists = [seg_idx[assignment[seg_idx] == h]
                      for h in range(n_hosts)]
    longest = max((len(x) for x in per_host_lists), default=0)
    longest = max(longest, 1)
    n_waves = max(1, min(int(n_waves), longest))
    phw = -(-longest // n_waves)                   # per host per wave
    phw = -(-phw // devs_per_host) * devs_per_host
    if seg_bytes and wave_budget:
        # cap per-host-per-wave from the byte budget DIRECTLY: the
        # caller's n_waves assumed a balanced assignment, so a host
        # owning more than its share would bind phw/devs_per_host
        # segments past the per-device budget (the HBM-overflow valve)
        phw_budget = max(1, int(wave_budget) // int(seg_bytes)) \
            * devs_per_host
        phw = min(phw, max(phw_budget, devs_per_host))
    n_waves_eff = -(-longest // phw)
    spw = n_hosts * phw
    ordered = np.full(n_waves_eff * spw, -1, dtype=np.int64)
    for h, lst in enumerate(per_host_lists):
        for w in range(n_waves_eff):
            blk = lst[w * phw: (w + 1) * phw]
            base = w * spw + h * phw
            ordered[base: base + len(blk)] = blk
    return ordered, spw


def exchange_block(local: np.ndarray):
    """All-gather a VARIABLE-LENGTH per-process numpy array; returns one
    array per process (ascending process id). The cross-process host-data
    exchange under select paging, search counts, and the host-tier
    gather on partial stores (≈ the reference's Spark-side fallback scan
    pulling rows off historicals, ``DruidRDD.getPartitions:244-277``).

    Works on numeric/bool arrays only (dimensions travel as dictionary
    CODES and decode against the replicated global dictionary). int64
    payloads travel as (2x int32) words so the exchange survives non-x64
    backends, where jnp silently canonicalizes int64 to int32."""
    from jax.experimental import multihost_utils as mhu
    local = np.ascontiguousarray(local)
    n_proc = jax.process_count()
    if n_proc <= 1:
        return [local]
    orig_dtype = local.dtype
    orig_trailing = local.shape[1:]
    if orig_dtype == np.bool_:
        local = local.astype(np.uint8)
    elif orig_dtype in (np.dtype(np.int64), np.dtype(np.uint64),
                        np.dtype(np.float64)) \
            and not jax.config.jax_enable_x64:
        local = local.view(np.int32).reshape(local.shape + (2,))
    sizes = np.asarray(mhu.process_allgather(
        np.asarray([local.shape[0]], np.int32))).reshape(-1)
    m = int(sizes.max()) if sizes.size else 0
    if m == 0:
        return [np.empty((0,) + orig_trailing, orig_dtype)
                for _ in range(n_proc)]
    if local.shape[0] < m:
        pad = np.zeros((m - local.shape[0],) + local.shape[1:],
                       local.dtype)
        local = np.concatenate([local, pad], axis=0)
    out = np.asarray(mhu.process_allgather(local))   # [P, m, ...]
    blocks = []
    for p in range(out.shape[0]):
        blk = out[p, : int(sizes[p])]
        if orig_dtype == np.bool_:
            blk = blk.astype(np.bool_)
        elif blk.dtype != orig_dtype and blk.shape[-1:] == (2,):
            blk = np.ascontiguousarray(blk).view(orig_dtype) \
                .reshape(blk.shape[:-1])
        blocks.append(blk)
    return blocks


def put_sharded_blocks(build_block, ordered: np.ndarray, row_dim: int,
                       dtype, sharding) -> jax.Array:
    """Assemble the global [len(ordered), row_dim] device array, providing
    only locally-addressable shards. ``build_block(segment_ids)`` returns
    the host rows for a block of the ``ordered`` layout (padding ids (-1)
    and non-local ids must yield zero rows — callers use
    ``ops.scan.build_array_blocks`` which enforces that)."""
    gshape = (len(ordered), row_dim)

    def cb(index):
        sl = index[0] if index else slice(None)
        return build_block(ordered[sl])

    return jax.make_array_from_callback(gshape, sharding, cb)
