"""Device mesh helpers.

The reference's cluster topology plane (ZooKeeper discovery via
``CuratorConnection.scala``, historical-server assignment in
``DruidMetadataCache.historicalServers:105-148``) collapses, on TPU, into the
JAX device runtime: ``jax.devices()`` *is* the discovery service, and a 1-D
``Mesh`` over the chips is the scan-parallel axis (segments shard across it
the way segments spread across historicals). Multi-host pods extend the same
mesh over ICI/DCN via ``jax.distributed`` — no new code path.
"""

from __future__ import annotations

import os
import re
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SEGMENT_AXIS = "shards"


def make_mesh(n_devices: Optional[int] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh over (the first n) local devices; the single axis is the
    segment-scan axis."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (SEGMENT_AXIS,))


def named_jit(name: str, fn):
    """``jax.jit`` of ``fn`` under a name that says the tier: a
    profiler trace's ``XLA Modules`` line and the compile cache's file
    names read ``jit_<name>``, not ``jit__lambda_``."""
    def program(*args):
        return fn(*args)
    program.__name__ = program.__qualname__ = name
    return jax.jit(program)


def segment_sharding(mesh: Mesh) -> NamedSharding:
    """[S, R] arrays shard along the segment axis."""
    return NamedSharding(mesh, P(SEGMENT_AXIS, None))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def mesh_size(mesh: Optional[Mesh]) -> int:
    return 1 if mesh is None else int(np.prod(list(mesh.shape.values())))


def mesh_subset(mesh: Mesh, n_devices: int) -> Mesh:
    """1-D sub-mesh over the first ``n_devices`` of an existing mesh —
    the bench/loadtest A-B legs scale the SAME device population down
    (1, 2, 4, ...) instead of constructing meshes from scratch, so every
    leg shards over a prefix of one device order."""
    devs = list(np.asarray(mesh.devices).reshape(-1))
    n = max(1, min(int(n_devices), len(devs)))
    return Mesh(np.array(devs[:n]), (SEGMENT_AXIS,))


_EMULATED_RE = re.compile(
    r"--xla_force_host_platform_device_count=(\d+)")


def emulated_host_devices() -> Optional[int]:
    """Device count of the CPU-emulated mesh when this process was
    launched with ``--xla_force_host_platform_device_count=N`` (the
    chipless-CI recipe, tests/conftest.py / docs/MESH.md), else None.
    Purely an observability hint — the mesh itself always comes from
    ``jax.devices()``."""
    m = _EMULATED_RE.search(os.environ.get("XLA_FLAGS", ""))
    return int(m.group(1)) if m else None


# every engine shard_map site imports it from here (the sdlint mesh pass
# roots on any call whose last segment is ``shard_map``)
shard_map = jax.shard_map
