"""Blob-pool overhead A/B on the ubench tick (CPU-relative evidence for
the structural claim: a program that never touches the pool pays nothing
— the threading is gated per cohort (engine.use_blob), and a merely
ENABLED pool only adds the per-tick free-slot compaction when some
cohort allocates. Run:
    JAX_PLATFORMS=cpu python profiling/_blob_overhead.py
"""
import sys
import time

sys.path.insert(0, "/root/repo")

import jax                                     # noqa: E402
import jax.numpy as jnp                        # noqa: E402

from ponyc_tpu import RuntimeOptions           # noqa: E402
from ponyc_tpu.models import ubench            # noqa: E402
from ponyc_tpu.runtime import engine           # noqa: E402

N = 4096
KT = 64


def tick_ms(**optkw):
    opts = RuntimeOptions(mailbox_cap=4, batch=1, max_sends=1, msg_words=1,
                          spill_cap=1024, inject_slots=8, **optkw)
    rt, ids = ubench.build(N, opts)
    ubench.seed_all(rt, ids, hops=1 << 30)
    multi = engine.jit_multi_step(rt.program, opts)
    inj = rt._empty_inject
    limit = jnp.int32(KT)
    state, aux, _k = multi(rt.state, *inj, limit)
    jax.block_until_ready(aux)
    best = 1e9
    for _ in range(5):
        t1 = time.time()
        state, aux, _k = multi(state, *inj, limit)
        jax.block_until_ready(aux)
        best = min(best, time.time() - t1)
    return best / KT * 1e3


base = tick_ms()
pool = tick_ms(blob_slots=4096, blob_words=16)
print(f"ubench tick_ms: pool-disabled {base:.3f}  "
      f"pool-enabled-unused {pool:.3f}  "
      f"(delta {100 * (pool - base) / base:+.1f}%)")
