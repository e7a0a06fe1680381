"""Mesh + sharding helpers.

The dense half trains synchronously data-parallel over the ``data`` mesh axis
(ref capability: `persia/distributed.py:74-202` DDP / Bagua allreduce).
Gradient averaging is implicit: with batch inputs sharded over ``data`` and
parameters replicated, XLA lowers the grad reduction to a psum over ICI.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def shard_map_compat(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with ``check_vma`` off unless the caller asks for
    it. Every shard_map call site in the repo goes through here."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def data_parallel_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D ``data`` mesh over the first ``n_devices`` devices (default all)."""
    devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(f"requested {n_devices} devices, have {len(devices)}")
        devices = devices[:n_devices]
    return Mesh(np.array(devices), axis_names=("data",))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Shard leading (batch) axis over ``data``."""
    return NamedSharding(mesh, P("data"))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
