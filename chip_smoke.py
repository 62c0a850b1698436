#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the actor world still starts
on the chip.

Drives the system's main path once, in ONE process, through the entry
points a user calls (Runtime / declare / start / spawn_many / bulk_send /
run, models.*, serve.build + Server.listen + loadgen.run_load), at the
size the bench calls real, and checks what comes out by the repo's own
means. It is not a benchmark: the seconds it prints are that run's
set-up evidence (first call = compile + run, rest = warm), filed under
no metric's name.

    python3 chip_smoke.py        # on a machine with a TPU; exit 0 = ok

Exit code is non-zero — and no result line is printed — when JAX
resolves anything but a TPU, when the package is not importable next to
this file, or when any single check fails: no phase is wrapped in a
`try` that lets the run continue. The last stdout line of a passing run
is one JSON object: {"ok": true, "device": {"platform", "kind",
"count"}}.

Phases (sizes are main()'s; tests/test_chip_smoke.py imports the phase
functions and runs them tiny on the CPU backend):
  (a) ubench      1,048,576 Pingers x 4 pings, bench geometry, default
                  delivery="plan", Runtime.run(max_steps=256)
  (b) ring        models.ring, 1024 nodes, one token to quiescence
  (c) serve       serve.build(256) behind a real socket, loadgen traffic
  (d) formulations  the same seeded ubench world under plan / cosort /
                  pallas / pallas_fused, every state leaf bit-for-bit
                  against plan (interpret=False on a TPU)
  (e) mesh        (a) and (b) at mesh_shards=4 when >= 4 devices are
                  visible, then a small fan-in under skew whose
                  producers mostly live on another shard than their
                  aggregator (200 ticks mid-pressure, then to
                  quiescence: conserved, nobody left muted); with fewer
                  devices it prints "mesh: not run (N device)" — the
                  only permitted non-run

The compile cache is jax's persistent one, at JAX_COMPILATION_CACHE_DIR
where that is set and else at the checkout's .cache/ponyc_tpu/xla
(ponyc_tpu.tuning.enable_compile_cache): a second run against the same
directory shows first-call seconds collapse.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

# The smoke's ubench geometry: 4 pings in flight per pinger, the drain
# batch and mailbox sized to match, one payload word.
PINGS = 4
UBENCH_GEOMETRY = dict(mailbox_cap=4, batch=PINGS, max_sends=1,
                       msg_words=1, spill_cap=1024, inject_slots=8)
MESH_SHARDS = 4

# The plan formulation's private cache (state.py) and its count of the
# ticks delivered over the list's prefix: only delivery="plan" writes
# them, so they are what "cosort" may legitimately differ in.
PLAN_CACHE_LEAVES = ("plan_key", "plan_perm", "plan_bounds", "n_prefix")


class SmokeFailure(AssertionError):
    """A named check did not hold; main() lets it end the process."""


def check(name: str, ok, detail: str = "") -> None:
    print(f"  check {name}: {'ok' if ok else 'FAILED'}"
          + (f" ({detail})" if detail else ""), flush=True)
    if not ok:
        raise SmokeFailure(f"{name} ({detail})")


def timed(fn):
    """(result, wall seconds) — every fn here ends in a host fetch, so
    the device work is inside the timed region."""
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def formulation(rt) -> str:
    o = rt.opts
    return (f"delivery={o.delivery} pallas={o.pallas} "
            f"pallas_fused={o.pallas_fused} mesh_shards={o.mesh_shards}")


# ---------------------------------------------------------------------------
# start-up


def resolve_device() -> dict:
    """Touch JAX (here, in the one process that ever does) and return
    the device as JAX reports it."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def rebuild_native() -> float:
    """Rebuild the host library from ponyc_tpu/native/src. build/ is
    git-ignored, native/__init__.py trusts file mtimes, and a stale .so
    on disk would be copied to the chip machine — so always from clean.
    A missing toolchain is an error, not a skip."""
    native = os.path.join(ROOT, "ponyc_tpu", "native")
    t0 = time.perf_counter()
    subprocess.run(["make", "-C", native, "clean", "all"], check=True,
                   stdout=subprocess.DEVNULL)
    so = os.path.join(native, "build", "libponyx_host.so")
    check("native library rebuilt", os.path.exists(so), so)
    return time.perf_counter() - t0


# ---------------------------------------------------------------------------
# (a) ubench — the main path


def spread_check(rt, shards: int) -> None:
    """The proof that state is really spread: every sharded leaf's
    addressable shards sit on `shards` distinct devices (nothing lands
    only on device 0)."""
    import jax
    sharded = replicated = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(rt.state)[0]:
        if leaf.size == 0:
            continue
        if leaf.is_fully_replicated:
            replicated += 1
            continue
        devices = {s.device for s in leaf.addressable_shards}
        if len(devices) != shards:
            check("state leaf spread", False,
                  f"{jax.tree_util.keystr(path)} on {len(devices)} "
                  f"device(s)")
        sharded += 1
    check("state leaves on distinct devices", sharded > 0,
          f"{sharded} sharded leaves on {shards} devices each, "
          f"{replicated} replicated")


def phase_ubench(n: int, steps: int, mesh_shards: int = 1) -> dict:
    """First call run(max_steps=steps) pays the compile; a second, short
    run(max_steps=steps // 8) is the warm sample."""
    from ponyc_tpu import RuntimeOptions
    from ponyc_tpu.models import ubench

    def build():
        rt, ids = ubench.build(
            n, RuntimeOptions(**UBENCH_GEOMETRY, mesh_shards=mesh_shards),
            pings=PINGS)
        ubench.seed_all(rt, ids, hops=1 << 30, pings=PINGS)
        return rt

    rt, setup_s = timed(build)
    print(f"  formulation: {formulation(rt)}", flush=True)

    def verify(want: int) -> None:
        check("steps_run", rt.steps_run == want, f"{rt.steps_run}")
        processed = rt.counter("n_processed") & 0xFFFFFFFF
        check("n_processed == steps*N*pings (mod 2^32)",
              processed == (want * n * PINGS) % (1 << 32), f"{processed}")
        pings = rt.cohort_state(ubench.Pinger)["pings"]
        # The check that catches a lane that never ran.
        check("every actor's pings == steps*pings",
              pings.shape == (n,) and bool((pings == want * PINGS).all()),
              f"min {int(pings.min())} max {int(pings.max())} "
              f"want {want * PINGS}")

    warm_steps = max(1, steps // 8)
    code, first_s = timed(lambda: rt.run(max_steps=steps))
    check("run() return code", code == 0, f"{code}")
    verify(steps)
    code, rest_s = timed(lambda: rt.run(max_steps=warm_steps))
    check("run() return code (warm)", code == 0, f"{code}")
    verify(steps + warm_steps)
    if mesh_shards > 1:
        spread_check(rt, mesh_shards)
        # The cycle's arrivals fit one shard's outbox on every tick: each
        # shard delivers over the received buckets joined front to front.
        unpacked = rt.counter("n_unpacked")
        check("every shard of every tick took the short delivery list",
              unpacked == mesh_shards * rt.steps_run, f"{unpacked}")
        # Every mailbox of this geometry sits over its overload line (4
        # of 4 queued, the line at 3), so from the second tick on every
        # shard looks its routed entries' targets up in the mesh-wide
        # hot word — and mutes nobody: a sender that is itself over its
        # line is exempt. (A mesh on which nobody is overloaded or
        # declares pressure looks nothing up: tests/test_fanin_mesh.py.)
        looked_up = rt.counter("n_route_pressure")
        check("every tick but the first looked the hot word up",
              looked_up == mesh_shards * (rt.steps_run - 1), f"{looked_up}")
        muted = rt.counter("n_mutes")
        check("and nobody was muted", muted == 0, f"{muted}")
    rt.stop()
    return {"setup_s": setup_s, "first_call_s": first_s, "rest_s": rest_s,
            "rest_steps": warm_steps}


# ---------------------------------------------------------------------------
# (b) ring


def phase_ring(n_nodes: int, hops: int, mesh_shards: int = 1) -> dict:
    from ponyc_tpu import RuntimeOptions
    from ponyc_tpu.models import ring

    rt, ids = ring.build(n_nodes, RuntimeOptions(
        mailbox_cap=8, batch=1, max_sends=1, msg_words=1,
        mesh_shards=mesh_shards))
    print(f"  formulation: {formulation(rt)}", flush=True)
    if mesh_shards > 1:
        import numpy as np
        shard_of = ids // rt.program.n_local
        check("every hop crosses a shard",
              bool((shard_of != np.roll(shard_of, -1)).all()))

    def passes() -> int:
        return int(rt.cohort_state(ring.RingNode)["passes"].sum())

    # First call on the still-empty world: compiles the window, runs
    # one tick, and must quiesce at once. Then the token's whole
    # journey is warm.
    code, first_s = timed(rt.run)
    check("empty world quiesces by itself", code == 0 and passes() == 0,
          f"code {code} steps_run {rt.steps_run}")
    rt.send(int(ids[0]), ring.RingNode.token, hops)
    code, rest_s = timed(rt.run)        # to quiescence: no max_steps
    check("run() returns 0 by itself", code == 0, f"{code}")
    check("passes.sum() == hops", passes() == hops, f"{passes()}")
    check("one hop per tick", hops <= rt.steps_run <= hops + 8,
          f"steps_run {rt.steps_run}")
    if mesh_shards > 1:
        spread_check(rt, mesh_shards)
    rt.stop()
    return {"first_call_s": first_s, "rest_s": rest_s}


# ---------------------------------------------------------------------------
# (e) backpressure across shards


def phase_fanin_mesh(producers: int, aggregators: int, items: int,
                     mesh_shards: int) -> dict:
    """A fan-in under skew on a mesh (models.fanin: slow aggregators,
    BATCH 1): a receiver's overload has to mute senders on other shards
    and release them when it has recovered. 200 ticks, then on to
    quiescence."""
    import numpy as np

    from ponyc_tpu import Runtime, RuntimeOptions
    from ponyc_tpu.models import fanin

    # two items a producer outside a mailbox at the most (route._route_spill)
    spill = 1 << (2 * producers - 1).bit_length()
    rt = Runtime(RuntimeOptions(
        mailbox_cap=8, batch=2, msg_words=1, spill_cap=spill,
        mesh_shards=mesh_shards))
    rt.declare(fanin.Producer, producers).declare(fanin.Aggregator,
                                                  aggregators)
    rt.start()
    print(f"  formulation: {formulation(rt)}", flush=True)
    aggs = rt.spawn_many(fanin.Aggregator, aggregators)
    # log-uniform ranks: rank 0 draws producers / log2(aggregators)
    rank = (aggregators ** np.random.default_rng(0).random(producers)
            ).astype(np.int64) - 1
    wired = np.bincount(rank, minlength=aggregators)
    prods = rt.spawn_many(fanin.Producer, producers, out=aggs[rank])
    crossing = prods // rt.program.n_local != aggs[rank] // rt.program.n_local
    check("most edges cross a shard", crossing.mean() > 0.5,
          f"{crossing.mean():.2f}")
    rt.bulk_send(prods, fanin.Producer.produce, [items] * producers)

    def accounted():
        """Per aggregator: counted + queued + parked in a spill, and
        what its producers have sent."""
        st = rt.state
        total = rt.cohort_state(fanin.Aggregator)["total"].astype(np.int64)
        queued = (np.asarray(st.tail)[aggs].astype(np.int64)
                  - np.asarray(st.head)[aggs])
        tgt = np.asarray(st.dspill_tgt).astype(np.int64)
        gid = tgt + (np.arange(len(tgt)) // (len(tgt) // mesh_shards)
                     ) * rt.program.n_local
        index_of = np.zeros(mesh_shards * rt.program.n_local, np.int64)
        index_of[aggs] = np.arange(aggregators)
        parked = np.bincount(index_of[gid[tgt >= 0]], minlength=aggregators)
        sent = np.bincount(
            rank, weights=rt.cohort_state(fanin.Producer)["sent"],
            minlength=aggregators).astype(np.int64)
        return total + queued + parked, sent

    code, first_s = timed(lambda: rt.run(max_steps=200))
    check("run() return code", code == 0, f"{code}")
    have, sent = accounted()
    check("mid-pressure: counted + queued + spilled == sent",
          bool((have == sent).all()), f"off by {int(abs(have - sent).sum())}")
    remote = rt.counter("n_remote_mutes")
    check("senders on other shards were muted", remote > 0, f"{remote}")
    code, rest_s = timed(rt.run)                 # to quiescence
    check("run() returns 0 by itself", code == 0, f"{code}")
    total = rt.cohort_state(fanin.Aggregator)["total"]
    check("every item counted once", bool((total == items * wired).all()),
          f"{int(total.sum())} of {items * producers}")
    muted = int(np.asarray(rt.state.muted).sum())
    check("nobody left muted", muted == 0, f"{muted}")
    check("no dead letter, no bad message",
          rt.counter("n_deadletter") == rt.counter("n_badmsg") == 0)
    spread_check(rt, mesh_shards)
    rt.stop()
    return {"first_call_s": first_s, "rest_s": rest_s}


# ---------------------------------------------------------------------------
# (c) the served path


def phase_serve(workers: int, requests: int) -> dict:
    from ponyc_tpu import loadgen, metrics, serve

    rt, server = serve.build(workers)
    print(f"  formulation: {formulation(rt)}", flush=True)
    port = server.listen("127.0.0.1", 0)
    loads, client_error = [], []

    def client():
        # Two closed-loop loads from one thread: the first pays the
        # compiles (hence the long reply timeout), the second is warm.
        try:
            for _ in range(2):
                loads.append(timed(lambda: loadgen.run_load(
                    "127.0.0.1", port, conns=2, depth=2,
                    requests=requests // 2, timeout_s=600.0)))
        except BaseException as e:       # re-raised on the main thread
            client_error.append(e)
        finally:
            server.begin_drain()

    t = threading.Thread(target=client, daemon=True)
    t.start()
    code = rt.run()
    t.join(timeout=60.0)
    check("client thread finished", not t.is_alive())
    if client_error:
        raise client_error[0]
    for which, (load, _secs) in zip(("first", "warm"), loads):
        check(f"ok == sent ({which} load)",
              load["ok"] == load["sent"] == requests,
              f"ok {load['ok']} sent {load['sent']}")
        check(f"busy == unanswered == bad_value == 0 ({which} load)",
              load["busy"] == load["unanswered"] == load["bad_value"] == 0,
              f"busy {load['busy']} unanswered {load['unanswered']} "
              f"bad_value {load['bad_value']}")
    check("run() returns 0 after the drain", code == 0, f"{code}")
    check("server drained", bool(server.stats()["drained"]))
    errors = metrics.snapshot(rt)["errors"]
    check("no sticky ring-failure counters", not errors, f"{errors}")
    rt.stop()
    return {"first_call_s": loads[0][1], "rest_s": loads[1][1]}


# ---------------------------------------------------------------------------
# (d) formulations


def phase_formulations(n: int, window: int, windows: int) -> dict:
    """The same seeded ubench world advanced `windows` fixed windows of
    `window` ticks under each formulation, every state leaf compared
    bit-for-bit with plan's."""
    import jax
    import numpy as np
    from ponyc_tpu import RuntimeOptions
    from ponyc_tpu.models import ubench

    def advance(overrides):
        rt, ids = ubench.build(
            n, RuntimeOptions(**UBENCH_GEOMETRY, quiesce_interval=window,
                              **overrides), pings=PINGS)
        ubench.seed_all(rt, ids, hops=1 << 30, pings=PINGS)
        print(f"  formulation: {formulation(rt)}", flush=True)
        _, first_s = timed(lambda: rt.run(max_steps=window))
        _, rest_s = timed(
            lambda: rt.run(max_steps=window * (windows - 1)))
        check("steps_run", rt.steps_run == window * windows,
              f"{rt.steps_run}")
        leaves = {jax.tree_util.keystr(path): np.asarray(leaf) for
                  path, leaf in
                  jax.tree_util.tree_flatten_with_path(rt.state)[0]}
        rt.stop()
        return leaves, first_s, rest_s

    out = {}
    plan = None
    for name, overrides in (("plan", {}),
                            ("cosort", {"delivery": "cosort"}),
                            ("pallas", {"pallas": True}),
                            ("pallas_fused", {"pallas_fused": True})):
        leaves, first_s, rest_s = advance(overrides)
        out[name] = {"first_call_s": first_s, "rest_s": rest_s}
        if plan is None:
            plan = leaves
            continue
        skip = PLAN_CACHE_LEAVES if name == "cosort" else ()
        compared = [k for k in plan if not any(s in k for s in skip)]
        bad = [k for k in compared
               if not np.array_equal(plan[k], leaves[k])]
        check(f"{name} == plan bit-for-bit", not bad and
              set(leaves) == set(plan),
              f"{len(compared)} leaves compared, mismatched {bad[:4]}")
    return out


# ---------------------------------------------------------------------------


def _rounded(v):
    if isinstance(v, dict):
        return {k: _rounded(x) for k, x in v.items()}
    return round(v, 2) if isinstance(v, float) else v


def run_phase(title: str, fn, *args, **kw):
    print(f"phase {title}", flush=True)
    out, total_s = timed(lambda: fn(*args, **kw))
    print(f"  seconds: {json.dumps(_rounded(out))} total {total_s:.1f}",
          flush=True)
    return out


def main() -> int:
    t_start = time.perf_counter()
    dev = resolve_device()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: JAX resolved {dev}, not a TPU — no chip, no "
              "result", file=sys.stderr)
        return 2
    print(f"device: platform={dev['platform']} "
          f"device_kind={dev['kind']!r} count={dev['count']}", flush=True)

    from ponyc_tpu import tuning
    from ponyc_tpu.ops import mailbox_kernel
    check("interpret_mode() is False",
          mailbox_kernel.interpret_mode() is False)
    print(f"compile cache: {tuning.enable_compile_cache()}", flush=True)
    print(f"native: rebuilt in {rebuild_native():.1f}s", flush=True)

    run_phase("(a) ubench 1,048,576 actors x 4 pings, run(max_steps=256)",
              phase_ubench, 1 << 20, 256)
    run_phase("(b) ring 1024 nodes, 2000 hops", phase_ring, 1024, 2000)
    run_phase("(c) serve.build(256), 32 loadgen requests per load",
              phase_serve, 256, 32)
    run_phase("(d) formulations, 65,536 actors, 4 windows of 16 ticks",
              phase_formulations, 1 << 16, 16, 4)
    if dev["count"] >= MESH_SHARDS:
        run_phase(f"(e) mesh: ubench 1,048,576 actors at mesh_shards="
                  f"{MESH_SHARDS}", phase_ubench, 1 << 20, 256,
                  mesh_shards=MESH_SHARDS)
        run_phase(f"(e) mesh: ring 1024 nodes at mesh_shards="
                  f"{MESH_SHARDS}, every hop crossing a shard",
                  phase_ring, 1024, 2000, mesh_shards=MESH_SHARDS)
        run_phase(f"(e) mesh: fan-in under skew, 8,192 producers on 256 "
                  f"aggregators at mesh_shards={MESH_SHARDS}",
                  phase_fanin_mesh, 8192, 256, 2, MESH_SHARDS)
    else:
        print(f"mesh: not run ({dev['count']} device)", flush=True)

    print(f"total: {time.perf_counter() - t_start:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
