"""Measure the quiet-mesh tick: an 8-shard world with NO traffic vs the
same world on 1 shard. The idle-collective tax is the gap between
them; the world-bits gating (engine.py) is the fix.

Runs on the CPU backend with a virtual 8-device mesh (same harness as
tests/conftest.py). In-executable timing: a fused window of K ticks per
dispatch, wall / K.
"""

import os
import sys
import time

# FORCE cpu: this is a CPU-mesh measurement by construction (8 virtual
# devices); it must never claim a chip.
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax                                            # noqa: E402
import jax.numpy as jnp                               # noqa: E402

from ponyc_tpu import RuntimeOptions                  # noqa: E402
from ponyc_tpu.models import ubench                   # noqa: E402


def measure(shards: int, actors: int, busy: bool, ticks: int = 64):
    opts = RuntimeOptions(mailbox_cap=4, batch=4, max_sends=1,
                          msg_words=1, spill_cap=256, inject_slots=8,
                          mesh_shards=shards)
    rt, ids = ubench.build(actors, opts, pings=4)
    if busy:
        ubench.seed_all(rt, ids, hops=1 << 30, pings=4)
        rt.run(max_steps=2)
    K = 64
    limit = jnp.int32(K)
    inj = rt._empty_inject
    state = rt.state
    # A quiet world quiesces instantly; force full windows by measuring
    # the step fn directly tick by tick inside the fused window via
    # occupancy: for the quiet case the while cond exits after 1 tick,
    # so time single steps in a loop instead.
    if busy:
        state, aux, _ = rt._multi(state, *inj, limit)
        jax.block_until_ready(aux)
        t0 = time.perf_counter()
        for _ in range(max(1, ticks // K)):
            state, aux, _ = rt._multi(state, *inj, limit)
        jax.block_until_ready(aux)
        dt = (time.perf_counter() - t0) / (max(1, ticks // K) * K)
    else:
        state, aux = rt._step(state, *inj)
        jax.block_until_ready(aux)
        t0 = time.perf_counter()
        for _ in range(ticks):
            state, aux = rt._step(state, *inj)
        jax.block_until_ready(aux)
        dt = (time.perf_counter() - t0) / ticks
    rt.state = state
    return 1e3 * dt


if __name__ == "__main__":
    actors = int(sys.argv[1]) if len(sys.argv) > 1 else 1 << 14
    for shards in (1, 8):
        q = measure(shards, actors, busy=False)
        print(f"shards={shards} actors={actors} quiet_tick_ms={q:.3f}",
              flush=True)
