"""Device mesh construction + state sharding for the actor world.

The reference scales by adding scheduler threads over cores
(src/libponyrt/sched/scheduler.c:1273-1309, one scheduler_t per core);
this framework scales by sharding the actor-row axis of every runtime
array over a 1-D `jax.sharding.Mesh` axis named 'actors'. Messages whose
target lives on another shard ride one `lax.all_to_all` per tick
(route._route) — ICI between chips of a slice, DCN between hosts, with
XLA choosing the transport (the reference's lock-free queues have no
cross-process analog; this is the distributed communication backend built
in its place).
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def make_mesh(n_shards: Optional[int] = None, devices=None) -> Mesh:
    """A 1-D mesh over the actor axis. n_shards defaults to all devices."""
    devices = list(devices if devices is not None else jax.devices())
    n = n_shards or len(devices)
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for {n} actor shards, have {len(devices)}")
    return Mesh(np.array(devices[:n]), ("actors",))


def shard_state(state, mesh: Mesh):
    """Place every runtime array with its LAST axis over 'actors' (the
    actor-lane axis — see runtime/state.py's layout note)."""
    def put(x):
        spec = PartitionSpec(*([None] * (x.ndim - 1) + ["actors"]))
        return jax.device_put(x, NamedSharding(mesh, spec))
    return jax.tree.map(put, state)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, PartitionSpec())
