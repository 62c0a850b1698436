"""Primitive-op facts on the real chip: what do gather / sort / select
chains / searchsorted actually cost at [1M] on TPU? One small jit per
op, each chained K times in-executable so launch latency divides
out. These numbers decide the delivery design (gather-based vs
sort-based vs reshape fast path)."""
import sys
import time

sys.path.insert(0, "/root/repo")
from ponyc_tpu.platforms import force_cpu
if "tpu" not in sys.argv:
    force_cpu()

import jax
import jax.numpy as jnp
from jax import lax

N = 1 << 20
K = 32
print("platform:", jax.devices()[0].platform, flush=True)

key = jax.random.PRNGKey(0)
perm = jax.random.permutation(key, N).astype(jnp.int32)
x = jnp.arange(N, dtype=jnp.int32)
xf = x.astype(jnp.float32)


def timeit_loop(name, body, init, reps=3):
    @jax.jit
    def run(c):
        return lax.fori_loop(0, K, lambda i, c: body(c), c)
    out = run(init)
    jax.block_until_ready(out)
    best = 1e9
    for _ in range(reps):
        t0 = time.time()
        out = run(init)
        jax.block_until_ready(out)
        best = min(best, time.time() - t0)
    print(f"{name:46s} {best / K * 1e3:8.3f} ms/iter", flush=True)
    return out


timeit_loop("vector add [1M] i32 (baseline)", lambda v: v + 1, x)
timeit_loop("gather x[perm] [1M] i32", lambda v: v[perm] + 1, x)
timeit_loop("gather x[perm] [1M] f32", lambda v: v[perm] + 1, xf)
timeit_loop("gather 2-row [2,1M][:,perm]",
            lambda v: v[:, perm] + 1, jnp.stack([x, x]))
timeit_loop("sort [1M] i32 (keys only)",
            lambda v: lax.sort(v) + 1, x)
timeit_loop("sort [1M] 2-operand (argsort)",
            lambda v: lax.sort((v, x), num_keys=1)[0] + 1, x)
timeit_loop("sort [1M] 4-operand (co-sort payload)",
            lambda v: lax.sort((v, x, x, x), num_keys=1)[0] + 1, x)
timeit_loop("searchsorted [1M] into [1M]",
            lambda v: jnp.searchsorted(
                x, v, side="left").astype(jnp.int32), x)
timeit_loop("select chain x8 [1M]",
            lambda v: sum(jnp.where(v % 8 == c, v + c, 0)
                          for c in range(8)), x)
timeit_loop("scatter .at[perm].set [1M]",
            lambda v: jnp.zeros((N,), jnp.int32).at[perm].set(v) + 1, x)
timeit_loop("cumsum [1M] i32", lambda v: jnp.cumsum(v) + 1, x)
# the reshape/strided fast-path candidate: [4, N] planes read by static idx
b4 = jnp.stack([x, x + 1, x + 2, x + 3])
timeit_loop("4-plane where-select rebuild",
            lambda v: jnp.stack([jnp.where((x + c) % 4 == 0, v[c], v[c] + 1)
                                 for c in range(4)]), b4)
