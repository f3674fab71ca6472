"""Device mesh + multi-host initialization (SURVEY.md §2 #9, §5).

The workload is pure data parallelism over edge shards (SURVEY.md §2
parallelism table), so the mesh is one axis, ``shards``. Within a slice the
collectives ride ICI; across hosts (jax.distributed) the same program runs
with the global device set and the collectives ride DCN — the comm surface
(merge reduction + counter psum) is identical, mirroring how the
reference's MPI ranks scatter shards and reduce partial trees (§3.1).
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import numpy as np
from jax import shard_map  # noqa: F401 — the sharded pipeline's import point
from jax.sharding import Mesh

SHARD_AXIS = "shards"


def device_count() -> int:
    return jax.device_count()


def shards_mesh(n_devices: Optional[int] = None) -> Mesh:
    """A 1-D mesh over the first n_devices (default: all)."""
    devs = jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(f"requested {n_devices} devices, have {len(devs)}")
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (SHARD_AXIS,))


def init_distributed(coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """Multi-host bring-up (the reference's mpirun equivalent).

    With no arguments, reads the standard JAX env vars / cluster
    autodetection. Safe to call once per process before any jax op.
    """
    kwargs = {}
    if coordinator is not None:
        kwargs["coordinator_address"] = coordinator
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)


def host_shard_info():
    """(shard, num_shards) for EdgeStream sharding at the host level."""
    return jax.process_index(), jax.process_count()


def force_cpu_devices(n: int) -> None:
    """Best-effort: fake an n-device CPU platform (test/dryrun helper).

    Must run before the backend initializes; jax is pre-imported in this
    environment, so we use config.update rather than env vars alone.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
