"""Multi-host execution — the DCN tier of the communication backend.

The reference is strictly single-process (SURVEY.md §2.4: no NCCL/MPI
anywhere); its only cross-machine story is application-level TCP
(lang/socket.c). This framework's scale-out axis extends across hosts
the JAX-native way: every host in the job calls `initialize()`, the
actor mesh is built over *global* devices, and the engine's
`all_to_all`/`psum` collectives ride ICI within a slice and DCN between
slices — XLA picks the transport per edge, no hand-written NCCL/MPI
(the "pick a mesh, annotate, let XLA insert collectives" recipe).

Typical multi-host launch (one command per host):

    import ponyc_tpu.parallel.distributed as dist
    dist.initialize(coordinator="host0:9876", num_processes=4,
                    process_id=<rank>)
    opts = RuntimeOptions(mesh_shards=dist.device_count())
    ...                       # identical program on every host

Host-resident subsystems (bridge/net/process) stay per-host: OS events
enter through *this host's* inject lane and reach any shard through
routing — the same pattern the reference uses to funnel ASIO events
through one thread (asio.c), generalised across hosts.
"""

from __future__ import annotations

import os
from typing import Optional

import jax


def initialize(coordinator: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Join (or start) a multi-host JAX job. No-ops on single-host.

    Arguments may come from the environment instead
    (JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID),
    matching how cluster launchers inject rank info.
    """
    coordinator = coordinator or os.environ.get("JAX_COORDINATOR_ADDRESS")
    if coordinator is None:
        return                      # single-host: nothing to do
    num_processes = int(num_processes
                        or os.environ.get("JAX_NUM_PROCESSES", 1))
    process_id = int(process_id
                     if process_id is not None
                     else os.environ.get("JAX_PROCESS_ID", 0))
    # The CPU backend refuses cross-process computations ("Multiprocess
    # computations aren't implemented on the CPU backend") unless a
    # collectives implementation is selected; gloo ships with jaxlib.
    # Must land BEFORE the backend initialises — harmless for
    # accelerator backends, which ignore the knob.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)


def device_count() -> int:
    """Global device count across every host in the job."""
    return jax.device_count()


def process_index() -> int:
    return jax.process_index()


def is_leader() -> bool:
    """True on exactly one host — put driver-only side effects (bench
    prints, checkpoint writes) behind this, as each host runs the same
    program."""
    return jax.process_index() == 0
