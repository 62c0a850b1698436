"""Randomized differential testing: the device engine vs a sequential
Python oracle of actor semantics.

≙ the role the aggregated stdlib test binary plays for the reference
(packages/stdlib/_test.pony) — broad behavioural coverage — plus the
layer the reference lacks (SURVEY.md §4): direct scheduler/delivery
semantics checks. Message outcomes here are commutative (per-actor sums
and counts), so the terminal state is schedule-independent: ANY correct
scheduler — the reference's work-stealing M:N, our lockstep ticks, the
oracle's sequential walk — must produce identical columns. Tiny mailbox
caps force the spill → mute → unmute machinery; the mesh variants force
routing and cross-shard spill; both delivery formulations must agree.
"""

import numpy as np
import pytest

from ponyc_tpu import (Blob, I32, Ref, Runtime, RuntimeOptions, actor,
                       behaviour)


@actor
class Walker:
    """Token walk over a random functional graph: receive v, accumulate,
    forward v-1 to this actor's fixed successor while v > 0."""
    acc: I32
    hits: I32
    nxt: Ref["Walker"]

    MAX_SENDS = 1

    @behaviour
    def step(self, st, v: I32):
        self.send(st["nxt"], Walker.step, v - 1, when=v > 0)
        return {**st, "acc": st["acc"] + v, "hits": st["hits"] + 1}


@actor
class HostLog:
    """Host-resident termination counter: Walkers report each chain's
    end (v == 0 arrivals), so the randomized harness also exercises the
    device→host drain path."""
    HOST = True
    ends: I32
    total: I32

    @behaviour
    def done(self, st, tail: I32):
        return {**st, "ends": st["ends"] + 1, "total": st["total"] + tail}


@actor
class WalkerH:
    """Walker variant that reports chain termination to a host actor."""
    acc: I32
    nxt: Ref["WalkerH"]
    log: Ref["HostLog"]

    MAX_SENDS = 2

    @behaviour
    def step(self, st, v: I32):
        self.send(st["nxt"], WalkerH.step, v - 1, when=v > 0)
        self.send(st["log"], HostLog.done, st["acc"] + v, when=v == 0)
        return {**st, "acc": st["acc"] + v}


@actor
class Splitter:
    """Receive v: accumulate, and while v > 0 send v-1 to BOTH a Walker
    and another Splitter (bounded binary fan-out — message count grows
    then dies; exercises bursts far above mailbox capacity)."""
    acc: I32
    w_ref: Ref["Walker"]
    s_ref: Ref["Splitter"]

    MAX_SENDS = 2

    @behaviour
    def burst(self, st, v: I32):
        self.send(st["w_ref"], Walker.step, v - 1, when=v > 0)
        self.send(st["s_ref"], Splitter.burst, v - 2, when=v > 1)
        return {**st, "acc": st["acc"] + v}


def oracle(n_w, n_s, w_nxt, s_w, s_s, seeds):
    """Sequential simulator with unbounded FIFO queues (the reference's
    semantics modulo scheduling, which the commutative outcome erases)."""
    from collections import deque
    w_acc = np.zeros(n_w, np.int64)
    w_hits = np.zeros(n_w, np.int64)
    s_acc = np.zeros(n_s, np.int64)
    q = deque(seeds)                       # ('w'|'s', idx, v)
    while q:
        kind, i, v = q.popleft()
        if kind == "w":
            w_acc[i] += v
            w_hits[i] += 1
            if v > 0:
                q.append(("w", w_nxt[i], v - 1))
        else:
            s_acc[i] += v
            if v > 0:
                q.append(("w", s_w[i], v - 1))
            if v > 1:
                q.append(("s", s_s[i], v - 2))
    return w_acc, w_hits, s_acc


def run_device(n_w, n_s, w_nxt, s_w, s_s, seeds, opts):
    rt = Runtime(opts)
    rt.declare(Walker, n_w).declare(Splitter, n_s)
    rt.start()
    wids = rt.spawn_many(Walker, n_w)
    sids = rt.spawn_many(Splitter, n_s)
    rt.set_fields(Walker, wids, nxt=wids[np.asarray(w_nxt)])
    rt.set_fields(Splitter, sids, w_ref=wids[np.asarray(s_w)],
                  s_ref=sids[np.asarray(s_s)])
    for kind, i, v in seeds:
        if kind == "w":
            rt.send(int(wids[i]), Walker.step, v)
        else:
            rt.send(int(sids[i]), Splitter.burst, v)
    assert rt.run(max_steps=300_000) == 0, "must quiesce"
    # Slot order == spawn order; a mesh rounds capacity up to a shard
    # multiple, so slice to the actually-spawned rows.
    wst = rt.cohort_state(Walker)
    sst = rt.cohort_state(Splitter)
    assert not np.asarray(rt.state.muted).any(), "terminal world unmuted"
    return (wst["acc"][:n_w].astype(np.int64),
            wst["hits"][:n_w].astype(np.int64),
            sst["acc"][:n_s].astype(np.int64))


def _case(seed, n_w=24, n_s=8, n_seeds=10, vmax=14):
    rng = np.random.default_rng(seed)
    w_nxt = rng.integers(0, n_w, n_w)
    s_w = rng.integers(0, n_w, n_s)
    s_s = rng.integers(0, n_s, n_s)
    seeds = []
    for _ in range(n_seeds):
        if rng.random() < 0.6:
            seeds.append(("w", int(rng.integers(0, n_w)),
                          int(rng.integers(1, vmax))))
        else:
            seeds.append(("s", int(rng.integers(0, n_s)),
                          int(rng.integers(2, vmax))))
    return w_nxt, s_w, s_s, seeds


def _fanin_case(seed, n_w=24, n_s=8, vmax=14, hubs=2, tokens=1):
    """Every Walker forwards to one of `hubs` hubs and every Walker
    starts with `tokens` tokens, so a hub takes a dozen or more messages
    in one tick: at `mailbox_cap=32` the rebuild runs more than one rank
    block."""
    rng = np.random.default_rng(seed)
    w_nxt = rng.integers(0, hubs, n_w)
    s_w = rng.integers(0, hubs, n_s)
    s_s = rng.integers(0, n_s, n_s)
    seeds = ([("w", i, int(rng.integers(3, vmax))) for i in range(n_w)
              for _ in range(tokens)]
             + [("s", i, int(rng.integers(2, vmax))) for i in range(n_s)])
    return w_nxt, s_w, s_s, seeds


CONFIGS = [
    ("tiny-cap-forces-spill", dict(mailbox_cap=2, batch=1, msg_words=1,
                                   max_sends=2, spill_cap=512,
                                   inject_slots=16)),
    ("cosort", dict(mailbox_cap=4, batch=2, msg_words=1, max_sends=2,
                    spill_cap=512, inject_slots=16, delivery="cosort")),
    ("mesh4", dict(mailbox_cap=4, batch=2, msg_words=1, max_sends=2,
                   spill_cap=1024, inject_slots=32, mesh_shards=4,
                   quiesce_interval=2)),
    ("mesh4-tiny-bucket", dict(mailbox_cap=2, batch=1, msg_words=1,
                               max_sends=2, spill_cap=2048,
                               inject_slots=32, mesh_shards=4,
                               route_bucket=8, quiesce_interval=1)),
    ("fused-kernel", dict(mailbox_cap=4, batch=2, msg_words=1,
                          max_sends=2, spill_cap=512, inject_slots=16,
                          pallas_fused=True)),
    # The Pallas drain kernel alone (ops/mailbox_kernel.py, interpret
    # mode on CPU) under XLA dispatch.
    ("pallas-drain", dict(mailbox_cap=2, batch=1, msg_words=1,
                          max_sends=2, spill_cap=512, inject_slots=16,
                          pallas=True)),
    # PR 25: rings deeper than one rebuild block (delivery.REBUILD_BLOCK)
    # under a fan-in (_fanin_case) that fills more than one block a tick.
    ("deep-cap", dict(mailbox_cap=32, batch=2, msg_words=1, max_sends=2,
                      spill_cap=512, inject_slots=32)),
    ("deep-cap-cosort", dict(mailbox_cap=32, batch=2, msg_words=1,
                             max_sends=2, spill_cap=512, inject_slots=32,
                             delivery="cosort")),
    ("deep-cap-mesh4", dict(mailbox_cap=32, batch=2, msg_words=1,
                            max_sends=2, spill_cap=1024, inject_slots=32,
                            mesh_shards=4, quiesce_interval=2)),
    # PR 39: a block is as wide as the rows that have a message in it.
    # Four hubs, two tokens a Walker, all injected in one tick: the 20
    # other Walkers then send 40 at once, about 10 a hub. A compacted
    # block holds M = 3 of the 24 Walkers, and the hubs past the first
    # block are 4 at seed 7 (two full blocks, then a compacted one) and
    # 3 or fewer at seed 23 (one full, two compacted); every other tick
    # of either seed has few receivers and compacts its first block.
    ("deep-cap-straddle", dict(mailbox_cap=32, batch=2, msg_words=1,
                               max_sends=2, spill_cap=512, inject_slots=64,
                               fanin=dict(hubs=4, tokens=2))),
]


def test_host_reporting_matches_oracle():
    """Chains terminate into a HOST actor; end-count and tail sums must
    match a sequential oracle exactly (device→host drain under random
    traffic, tiny caps)."""
    seed, n_w = 31, 20
    rng = np.random.default_rng(seed)
    w_nxt = rng.integers(0, n_w, n_w)
    starts = [(int(rng.integers(0, n_w)), int(rng.integers(1, 12)))
              for _ in range(8)]
    # oracle: walk each chain; on v==0 arrival, record acc_after + 0
    acc = np.zeros(n_w, np.int64)
    ends = 0
    tails = 0
    from collections import deque
    q = deque([("w", i, v) for i, v in starts])
    while q:
        _, i, v = q.popleft()
        acc[i] += v
        if v > 0:
            q.append(("w", int(w_nxt[i]), v - 1))
        else:
            ends += 1
            tails += int(acc[i])
    # NOTE: tails depends on acc-at-arrival order, which IS schedule
    # dependent — compare only the schedule-independent outputs.
    rt = Runtime(RuntimeOptions(mailbox_cap=2, batch=1, msg_words=2,
                                max_sends=2, spill_cap=512,
                                inject_slots=16))
    rt.declare(WalkerH, n_w).declare(HostLog, 1).start()
    wids = rt.spawn_many(WalkerH, n_w)
    log = rt.spawn(HostLog)
    rt.set_fields(WalkerH, wids, nxt=wids[np.asarray(w_nxt)],
                  log=np.full(n_w, log))
    for i, v in starts:
        rt.send(int(wids[i]), WalkerH.step, v)
    assert rt.run(max_steps=100_000) == 0
    wst = rt.cohort_state(WalkerH)
    assert (wst["acc"].astype(np.int64) == acc).all()
    assert rt.state_of(log)["ends"] == ends == len(starts)


def test_uneven_cohorts_on_mesh_match_oracle():
    """Cohort sizes NOT divisible by the shard count (capacity rounds up;
    the padded rows must stay inert and slot-order reads must slice
    clean)."""
    n_w, n_s = 37, 11                  # neither divides 4
    w_nxt, s_w, s_s, seeds = _case(51, n_w, n_s)
    want = oracle(n_w, n_s, w_nxt, s_w, s_s, seeds)
    got = run_device(n_w, n_s, w_nxt, s_w, s_s, seeds, RuntimeOptions(
        mailbox_cap=2, batch=1, msg_words=1, max_sends=2, spill_cap=2048,
        inject_slots=32, mesh_shards=4, quiesce_interval=2))
    for g, w in zip(got, want):
        assert (g == w).all()


@pytest.mark.parametrize("name,okw", CONFIGS, ids=[c[0] for c in CONFIGS])
@pytest.mark.parametrize("seed", [7, 23])
def test_device_matches_oracle(name, okw, seed):
    n_w, n_s = 24, 8
    okw = dict(okw)
    fanin = okw.pop("fanin", {})
    case = _fanin_case if name.startswith("deep-cap") else _case
    w_nxt, s_w, s_s, seeds = case(seed, n_w, n_s, **fanin)
    want = oracle(n_w, n_s, w_nxt, s_w, s_s, seeds)
    got = run_device(n_w, n_s, w_nxt, s_w, s_s, seeds,
                     RuntimeOptions(**okw))
    for g, w, what in zip(got, want, ("w_acc", "w_hits", "s_acc")):
        assert (g == w).all(), (
            name, seed, what, np.nonzero(g != w)[0][:5], g.sum(), w.sum())


def test_multi_behaviour_dispatch_matches_oracle():
    """Three behaviours of different arities on one type under random
    traffic: per-lane behaviour-id selection across batch slots (the
    lax.switch-equivalent path the single-behaviour configs never
    exercise). Commutative outputs compared exactly; acc only for
    actors untouched by the non-commutative behaviour."""
    from collections import deque

    @actor
    class Tri:
        acc: I32
        count: I32
        nxt: Ref["Tri"]

        MAX_SENDS = 2

        @behaviour
        def add(self, st, v: I32):
            self.send(st["nxt"], Tri.add, v - 2, when=v > 2)
            return {**st, "acc": st["acc"] + v,
                    "count": st["count"] + 1}

        @behaviour
        def mul2_then_ping(self, st, v: I32, flag: I32):
            self.send(st["nxt"], Tri.ping, when=flag > 0)
            return {**st, "acc": st["acc"] * 2 + v,
                    "count": st["count"] + 1}

        @behaviour
        def ping(self, st):
            return {**st, "count": st["count"] + 1}

    def oracle(n, nxt, seeds):
        acc = np.zeros(n, np.int64)
        cnt = np.zeros(n, np.int64)
        q = deque(seeds)
        while q:
            op, i, args = q.popleft()
            if op == "add":
                v, = args
                acc[i] += v
                cnt[i] += 1
                if v > 2:
                    q.append(("add", int(nxt[i]), (v - 2,)))
            elif op == "mul":
                v, flag = args
                acc[i] = acc[i] * 2 + v
                cnt[i] += 1
                if flag > 0:
                    q.append(("ping", int(nxt[i]), ()))
            else:
                cnt[i] += 1
        return acc, cnt

    for seed, mode in ((501, "plan"), (506, "cosort")):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(10, 40))
        nxt = rng.integers(0, n, n)
        seeds = []
        for _ in range(10):
            r = rng.random()
            i = int(rng.integers(0, n))
            if r < 0.5:
                seeds.append(("add", i, (int(rng.integers(1, 12)),)))
            elif r < 0.85:
                seeds.append(("mul", i, (int(rng.integers(0, 5)),
                                         int(rng.integers(0, 2)))))
            else:
                seeds.append(("ping", i, ()))
        want_acc, want_cnt = oracle(n, nxt, seeds)
        mul_targets = {i for op, i, _ in seeds if op == "mul"}
        rt = Runtime(RuntimeOptions(mailbox_cap=2, batch=1, msg_words=2,
                                    max_sends=2, spill_cap=1024,
                                    inject_slots=16, delivery=mode))
        rt.declare(Tri, n).start()
        ids = rt.spawn_many(Tri, n)
        rt.set_fields(Tri, ids, nxt=ids[np.asarray(nxt)])
        for op, i, args in seeds:
            b = {"add": Tri.add, "mul": Tri.mul2_then_ping,
                 "ping": Tri.ping}[op]
            rt.send(int(ids[i]), b, *args)
        assert rt.run(max_steps=100_000) == 0
        st = rt.cohort_state(Tri)
        assert (st["count"][:n].astype(np.int64) == want_cnt).all()
        for i in range(n):
            if i not in mul_targets:
                assert int(st["acc"][i]) == int(want_acc[i])


@actor
class BlobWalker:
    """Walker whose token carries a one-word device BLOB: each hop reads
    the word, frees the incoming blob, and (while v > 0) allocates a
    FRESH blob carrying word+1 for the successor — ownership cannot be
    conditionally forwarded-or-freed (both are trace-time moves), so
    conditional routing re-allocates; this is also the harder test:
    alloc/free churn and slot recycling on every hop."""
    acc: I32
    nxt: Ref["BlobWalker"]

    MAX_SENDS = 1
    MAX_BLOBS = 1
    BLOB_DISPATCHES = 1
    BATCH = 1

    @behaviour
    def step(self, st, v: I32, h: Blob):
        w0 = self.blob_get(h, 0)
        self.blob_free(h)
        go = v > 0
        h2 = self.blob_alloc(length=1, when=go)
        self.blob_set(h2, 0, w0 + 1, when=go)
        self.send(st["nxt"], BlobWalker.step, v - 1, h2, when=go)
        return {**st, "acc": st["acc"] + w0}


def run_blob_chain(seed, opts_kw, n=None, n_starts=6, vmax=10,
                   expect_moves=False):
    """One randomized blob-chain world vs the sequential oracle
    (shared by the pytest cases below and tests/hunt.py --blob): random
    functional graph, random seeds; every hop reads + frees + re-allocs
    the token blob, chains cross shards freely (migration)."""
    rng = np.random.default_rng(seed)
    n = n or int(rng.integers(8, 40))
    nxt = rng.integers(0, n, n)

    def oracle_blob(seeds):
        from collections import deque
        acc = np.zeros(n, np.int64)
        q = deque(seeds)                   # (idx, v, word)
        while q:
            i, v, w = q.popleft()
            acc[i] += w
            if v > 0:
                q.append((int(nxt[i]), v - 1, w + 1))
        return acc

    seeds = [(int(rng.integers(0, n)), int(rng.integers(1, vmax)),
              int(rng.integers(0, 50))) for _ in range(n_starts)]
    want = oracle_blob(seeds)
    opts = RuntimeOptions(msg_words=3, blob_slots=256, blob_words=2,
                          **opts_kw)
    rt = Runtime(opts)
    rt.declare(BlobWalker, n).start()
    ids = rt.spawn_many(BlobWalker, n, acc=0)
    rt.set_fields(BlobWalker, ids, nxt=ids[np.asarray(nxt)])
    for i, v, w in seeds:
        # Host injections don't route, so allocate on the seed's shard;
        # after that, chains cross shards freely — blobs MIGRATE with
        # the routed messages (route._route).
        h = rt.blob_store([w], near=int(ids[i]))
        rt.send(int(ids[i]), BlobWalker.step, v, h)
    assert rt.run(max_steps=100_000) == 0
    st = rt.cohort_state(BlobWalker)
    assert (st["acc"][:n].astype(np.int64) == want).all(), (
        st["acc"][:n], want)
    assert rt.blobs_in_use == 0            # every chain end freed its blob
    assert rt.counter("n_blob_remote") == 0    # nothing arrived dead
    if expect_moves:
        assert rt.counter("n_blob_moved") > 0  # chains DID cross shards
    return rt


@pytest.mark.parametrize("mode,shards,bucket", [
    ("plan", 1, 0), ("cosort", 1, 0), ("plan", 2, 0),
    # Tiny route bucket: blob-carrying messages PARK in the route spill
    # and migrate only when the retry actually ships — the
    # spilled-blobs-stay-local invariant under congestion.
    ("plan", 2, 2)])
def test_blob_chain_matches_oracle(mode, shards, bucket):
    run_blob_chain(77, dict(mailbox_cap=2, batch=1, max_sends=1,
                            spill_cap=1024, inject_slots=16,
                            delivery=mode, mesh_shards=shards,
                            route_bucket=bucket),
                   n=16, expect_moves=shards > 1)
