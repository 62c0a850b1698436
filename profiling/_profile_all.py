"""One long-lived TPU profiling session: every measurement in ONE
process (the chip belongs to one process at a time), appending results
to /tmp/p9_results.txt incrementally.
"""
import os
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
from jax import lax                           # noqa: E402

dev = jax.devices()[0]
note(f"# claimed {dev} after {time.time() - t0:.0f}s")

# ---------------- primitive op facts ----------------
N = 1 << 20
K = 32
key = jax.random.PRNGKey(0)
perm = jax.random.permutation(key, N).astype(jnp.int32)
x = jnp.arange(N, dtype=jnp.int32)


def timeit_loop(name, body, init, reps=3):
    @jax.jit
    def run(c):
        return lax.fori_loop(0, K, lambda i, c: body(c), c)
    out = run(init)
    jax.block_until_ready(out)
    best = 1e9
    for _ in range(reps):
        t1 = time.time()
        out = run(init)
        jax.block_until_ready(out)
        best = min(best, time.time() - t1)
    note(f"op {name:42s} {best / K * 1e3:8.3f} ms/iter")
    return out


timeit_loop("vector add [1M] i32", lambda v: v + 1, x)
timeit_loop("gather x[perm] [1M]", lambda v: v[perm] + 1, x)
timeit_loop("gather 2row [2,1M][:,perm]",
            lambda v: v[:, perm] + 1, jnp.stack([x, x]))
timeit_loop("sort [1M] keys", lambda v: lax.sort(v) + 1, x)
timeit_loop("sort [1M] argsort2op",
            lambda v: lax.sort((v, x), num_keys=1)[0] + 1, x)
timeit_loop("sort [1M] co-sort4op",
            lambda v: lax.sort((v, x, x, x), num_keys=1)[0] + 1, x)
timeit_loop("searchsorted 1M into 1M",
            lambda v: jnp.searchsorted(x, v, side="left").astype(jnp.int32),
            x)
timeit_loop("select chain x8 [1M]",
            lambda v: sum(jnp.where(v % 8 == c, v + c, 0)
                          for c in range(8)), x)
timeit_loop("scatter at[perm].set [1M]",
            lambda v: jnp.zeros((N,), jnp.int32).at[perm].set(v) + 1, x)
timeit_loop("cumsum [1M]", lambda v: jnp.cumsum(v) + 1, x)
timeit_loop("roll [1M]", lambda v: jnp.roll(v, 1) + 1, x)
note("OPS_DONE")

# ---------------- step variants ----------------
from ponyc_tpu import RuntimeOptions          # noqa: E402
from ponyc_tpu.models import ubench           # noqa: E402
from ponyc_tpu.runtime import engine, delivery  # noqa: E402


def run_variant(variant, pings=1, cap=4, pallas=False, patch=None,
                delivery="plan"):
    if patch:
        patch()
    opts = RuntimeOptions(mailbox_cap=cap, batch=pings, max_sends=1,
                          msg_words=1, spill_cap=1024, inject_slots=8,
                          pallas=pallas, delivery=delivery)
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


real_deliver = delivery.deliver


def patch_nodeliver():
    def deliver_nd(buf, head, tail, alive, entries, **kw):
        res = real_deliver(buf, head, tail, alive, entries, **kw)
        return res._replace(buf=buf, tail=tail)
    engine.deliver = deliver_nd


def patch_restore():
    engine.deliver = real_deliver


def patch_nodisp():
    real_cd = engine._cohort_dispatch

    def patched_cd(cohort, opts, noyield, program):
        inner = real_cd(cohort, opts, noyield, program)

        def run_cohort(ts, buf_rows, head_rows, occ_rows, runnable_rows,
                       ids, resv, blob=None):
            return inner(ts, buf_rows, head_rows, occ_rows,
                         jnp.zeros_like(runnable_rows), ids, resv,
                         blob=blob)
        return run_cohort
    engine._cohort_dispatch = patched_cd
    return real_cd


run_variant("full")
run_variant("cosort", delivery="cosort")
run_variant("pings4", pings=4)
run_variant("pings4-cosort", pings=4, delivery="cosort")
run_variant("pallas", pallas=True)
patch_nodeliver()
run_variant("nodeliver")
patch_restore()
real_cd = patch_nodisp()
run_variant("nodisp")
engine._cohort_dispatch = real_cd
note("VARIANTS_DONE")

# ---------------- xprof trace of the full step ----------------
try:
    import glob
    opts = RuntimeOptions(mailbox_cap=4, batch=1, max_sends=1,
                          msg_words=1, spill_cap=1024, inject_slots=8)
    rt, ids = ubench.build(N, opts)
    ubench.seed_all(rt, ids, hops=1 << 30)
    multi = engine.jit_multi_step(rt.program, opts)
    inj = rt._empty_inject
    limit = jnp.int32(16)
    state, aux, _k = multi(rt.state, *inj, limit)
    jax.block_until_ready(aux)
    logdir = "/tmp/xprof_ubench"
    os.system(f"rm -rf {logdir}")
    jax.profiler.start_trace(logdir)
    state, aux, _k = multi(state, *inj, limit)
    jax.block_until_ready(aux)
    jax.profiler.stop_trace()
    planes = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    note(f"xprof planes: {planes}")
except Exception as e:                        # noqa: BLE001
    note(f"xprof failed: {e}")
note("ALL_DONE")
