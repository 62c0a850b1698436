"""Round-3 planar profile, in-executable: each component chained K times
inside ONE jitted fori_loop so the per-call launch latency divides
out. Prints ms per iteration of each component."""
import sys
import time

sys.path.insert(0, "/root/repo")
from ponyc_tpu.platforms import force_cpu
if "tpu" not in sys.argv:
    force_cpu()

import jax
import jax.numpy as jnp
from jax import lax

from ponyc_tpu import RuntimeOptions
from ponyc_tpu.models import ubench
from ponyc_tpu.runtime import engine, delivery
from ponyc_tpu.ops.segment import stable_sort_by

N = 1 << 20
CAP = 4
K = 20


def timeit_loop(name, body, init, reps=3):
    """body: carry -> carry, chained K times in one executable."""
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
    print(f"{name:52s} {best / K * 1e3:8.3f} ms/iter")
    return out


SEL = set(a for a in sys.argv[1:] if a != "tpu") or None


def want(tag):
    return SEL is None or tag in SEL


opts = RuntimeOptions(mailbox_cap=CAP, batch=1, max_sends=1, msg_words=1,
                      spill_cap=1024, inject_slots=8)
rt, ids = ubench.build(N, opts)
ubench.seed_all(rt, ids, hops=1 << 30)
print("platform:", jax.devices()[0].platform)

inj = rt._empty_inject
st, aux = rt._step(rt.state, *inj)
jax.block_until_ready(aux)
rt.state = st

# 0. full step chained (ground truth per-tick device cost)
if want("step"):
    timeit_loop("FULL STEP (chained in-executable)",
                lambda s: engine.build_step(rt.program, opts)(s, *inj)[0],
                st)

# 1. dispatch only
ch = rt.program.device_cohorts[0]
LAYOUT = tuple((c.atype.__name__, c.local_start, c.local_stop,
                1 + c.msg_words) for c in rt.program.cohorts)
disp = engine._cohort_dispatch(ch, opts, opts.noyield, rt.program)
idsj = jnp.arange(N, dtype=jnp.int32)


def disp_body(s):
    occ = s.tail - s.head
    runnable = s.alive & ~s.muted
    out = disp(s.type_state[ch.atype.__name__], s.buf[ch.atype.__name__],
               s.head, occ, runnable, idsj, {})
    # chain: fold outbox into head so the loop carries a dependency
    return s._replace(head=out[2])


if want("disp"):
    timeit_loop("dispatch only", disp_body, st)

# one real outbox for delivery inputs
occ = st.tail - st.head
runnable = st.alive & ~st.muted
out = jax.jit(lambda s: disp(s.type_state[ch.atype.__name__],
                             s.buf[ch.atype.__name__],
                             s.head, occ, runnable, idsj, {}))(st)
ent = out[1]
tgt, sender, words = (jnp.asarray(ent.tgt), jnp.asarray(ent.sender),
                      jnp.asarray(ent.words))
E = tgt.shape[0]
inj_t = jnp.full((opts.inject_slots,), -1, jnp.int32)
inj_w = jnp.zeros((words.shape[0], opts.inject_slots), jnp.int32)
tgt_f = jnp.concatenate([st.dspill_tgt, inj_t, st.rspill_tgt, tgt])
snd_f = jnp.concatenate([st.dspill_sender, inj_t, st.rspill_sender, sender])
wrd_f = jnp.concatenate([st.dspill_words, inj_w, st.rspill_words, words],
                        axis=1)


def deliver_body(plan):
    def go(s, use_plan):
        e = delivery.Entries(tgt=tgt_f, sender=snd_f, words=wrd_f)
        res = delivery.deliver(
            s.buf, s.head, s.tail, s.alive, e,
            n_local=N, mailbox_cap=CAP, spill_cap=1024,
            overload_occ=opts.overload_occ, shard_base=jnp.int32(0),
            cohort_layout=LAYOUT, mute_slots=opts.mute_slots,
            plan=(s.plan_key, s.plan_perm, s.plan_bounds) if use_plan
            else None)
        return s._replace(buf=res.buf, plan_key=res.plan_key,
                          plan_perm=res.plan_perm,
                          plan_bounds=res.plan_bounds)
    return go


if want("delc"):
    timeit_loop("delivery (plan cached)",
                lambda s: deliver_body(True)(s, True), st)
if want("deln"):
    timeit_loop("delivery (no plan cache)",
                lambda s: deliver_body(False)(s, False), st)

# sub-pieces, chained
key = jnp.where(tgt_f >= 0, tgt_f, N).astype(jnp.int32)
if want("sub"):
    timeit_loop("stable_sort [E]",
                lambda k: stable_sort_by(k) + k * 0, key)
perm = stable_sort_by(key)
if want("sub"):
    timeit_loop("payload gather words[:, perm] (planar)",
                lambda w: w[:, perm] + w * 0, wrd_f)
ks = key[perm]
bounds = jnp.searchsorted(ks, jnp.arange(N + 1, dtype=jnp.int32),
                          side="left").astype(jnp.int32)
seg = bounds[:-1]
wds = wrd_f[:, perm]
EF = tgt_f.shape[0]


def plane_rebuild(buf, head, tail):
    space = jnp.maximum(CAP - (tail - head), 0)
    cnt = bounds[1:] - seg
    acc = jnp.minimum(cnt, space)
    planes = []
    for ci in range(CAP):
        rel = (ci - tail) % CAP
        wmask = rel < acc
        src = jnp.minimum(seg + rel, EF - 1)
        planes.append(jnp.where(wmask[None, :],
                                jnp.take(wds, src, axis=1),
                                buf[ci]))
    return jnp.stack(planes)


if want("sub"):
    timeit_loop("plane rebuild (CAP planes)",
                lambda b: plane_rebuild(b, st.head, st.tail),
                st.buf[ch.atype.__name__])
    timeit_loop("_ring_take (cap select chain)",
                lambda b: b.at[0].set(engine._ring_take(b, st.head % CAP)),
                st.buf[ch.atype.__name__])
    timeit_loop("1-D lane gather wds[0][src]",
                lambda s: wds[0][jnp.minimum(seg + s[0] * 0, EF - 1)] + s,
                jnp.zeros((N,), jnp.int32))
    timeit_loop("plan key compare", lambda a: a + jnp.all(a == key), key)
    timeit_loop("searchsorted bounds",
                lambda b: jnp.searchsorted(
                    ks, jnp.arange(N + 1, dtype=jnp.int32) + b[0] * 0,
                    side="left").astype(jnp.int32) + b * 0, bounds)
