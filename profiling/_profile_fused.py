"""Follow-up to _profile_all.py: the A/B rows it doesn't cover —
pallas_fused (the north-star fused dispatch kernel, ops/fused_dispatch.py)
and dispatch_gating — plus a cap sweep on the winner axis. Appends to the
same /tmp/p9_results.txt. Run after _profile_all.py has exited (one
process per chip):
    nohup python -u _profile_fused.py > /tmp/p9_fused.log 2>&1 &
"""
import sys
import time

sys.path.insert(0, "/root/repo")

RES = "/tmp/p9_results.txt"


def note(line):
    with open(RES, "a") as f:
        f.write(line + "\n")
    print(line, flush=True)


t0 = time.time()
print("waiting for TPU claim...", flush=True)
import jax                                    # noqa: E402
import jax.numpy as jnp                       # noqa: E402

dev = jax.devices()[0]
note(f"# fused-campaign claimed {dev} after {time.time() - t0:.0f}s")

from ponyc_tpu import RuntimeOptions          # noqa: E402
from ponyc_tpu.models import ubench           # noqa: E402
from ponyc_tpu.runtime import engine          # noqa: E402

N = 1 << 20


def run_variant(variant, pings=1, cap=4, **optkw):
    opts = RuntimeOptions(mailbox_cap=cap, batch=pings, max_sends=1,
                          msg_words=1, spill_cap=1024, inject_slots=8,
                          **optkw)
    rt, ids = ubench.build(N, opts, pings=pings)
    ubench.seed_all(rt, ids, hops=1 << 30, pings=pings)
    KT = 64
    limit = jnp.int32(KT)
    inj = rt._empty_inject
    multi = engine.jit_multi_step(rt.program, opts)
    state = rt.state
    t1 = time.time()
    state, aux, _k = multi(state, *inj, limit)
    jax.block_until_ready(aux)
    compile_s = time.time() - t1
    best = 1e9
    for _ in range(4):
        t1 = time.time()
        state, aux, _k = multi(state, *inj, limit)
        jax.block_until_ready(aux)
        best = min(best, time.time() - t1)
    tick_ms = best / KT * 1e3
    note(f"{variant} tick_ms = {tick_ms:.3f} (compile {compile_s:.0f}s, "
         f"msgs/s = {N * pings / tick_ms * 1e3:.3e})")
    return tick_ms


for name, kw in [
    ("fused", dict(pallas_fused=True)),
    ("fused-pings4", dict(pallas_fused=True)),
    ("gating", dict(dispatch_gating=True)),
    ("cosort-fused", dict(pallas_fused=True, delivery="cosort")),
    ("cap8", dict()),
    ("cap2", dict()),
]:
    pings = 4 if "pings4" in name else 1
    cap = {"cap8": 8, "cap2": 2}.get(name, 4)
    try:
        run_variant(name, pings=pings, cap=cap, **kw)
    except Exception as e:                    # noqa: BLE001
        note(f"{name} FAILED: {type(e).__name__}: {str(e)[:300]}")

# Blob-pipeline throughput (models/records at scale): the rich-payload
# path's on-chip cost — alloc/write/migrate-free dispatch + pool churn.
# First full run warms the jit cache (same world shapes); the timed run
# is a FRESH world so only warm execution is measured, like the
# best-of-N rows above.
try:
    from ponyc_tpu.models import records

    n_src, n_per = 4096, 8
    records.run_records(n_sources=n_src, n_records=n_per)   # warm/compile
    t1 = time.time()
    rt, st = records.run_records(n_sources=n_src, n_records=n_per)
    el = time.time() - t1
    n_rec = n_src * n_per
    note(f"records[{n_src}x{n_per}] warm {el:.2f}s = "
         f"{n_rec / el:.3e} records/s (steps {rt.steps_run})")
except Exception as e:                        # noqa: BLE001
    note(f"records FAILED: {type(e).__name__}: {str(e)[:300]}")
note("FUSED_DONE")
