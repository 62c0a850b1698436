"""Order-SENSITIVE differential testing: per-edge FIFO (causal order).

The Pony guarantee under test: messages from sender A to receiver B are
dispatched in the order A sent them (messageq FIFO,
reference src/libponyrt/actor/messageq.c:102-160). The commutative
differential suite (test_differential.py) cannot see an ordering
violation by design; this file can see a SINGLE one.

Method: every producer stamps each message with a per-edge sequence
number; every consumer checks ON DEVICE that each in-edge's stamps
arrive exactly contiguous (seq == last_seen + 1) and counts violations.
The per-edge oracle sequence is 0,1,2,… by construction, so
`violations == 0` + `last_seen == n-1` IS the exact oracle comparison —
any inversion, duplication, or loss anywhere in delivery (plan/cosort),
the device spill retry, the route-spill retry, or the aged-unmute
release window trips it.

Configs deliberately aim at the reordering windows SURVEY §7 hard part
(c) names: tiny caps (device-spill retry), 4-shard mesh with a tiny
route bucket (route-spill retry), aggressive mute aging (aged-unmute
release), both delivery formulations, and the fused Pallas kernel.
"""

import numpy as np
import pytest

from ponyc_tpu import I32, Ref, Runtime, RuntimeOptions, actor, behaviour

IN_SLOTS = 4          # in-edges tracked per consumer (fixed-width state)


@actor
class Cons:
    """Consumer with IN_SLOTS tracked in-edges: asserts per-edge stamps
    arrive contiguous; `bad` counts every FIFO violation."""
    last0: I32
    last1: I32
    last2: I32
    last3: I32
    bad: I32
    got: I32

    BATCH = 1          # slow consumer → overload → mute machinery engages

    @behaviour
    def consume(self, st, slot: I32, seq: I32):
        upd = {"bad": st["bad"], "got": st["got"] + 1}
        for s in range(IN_SLOTS):
            is_s = slot == s
            last = st[f"last{s}"]
            viol = is_s & (seq != last + 1)
            upd["bad"] = upd["bad"] + np.int32(1) * viol
            upd[f"last{s}"] = last + (seq - last) * is_s
        return {**st, **upd}


@actor
class Prod:
    """Producer streaming to two fixed (consumer, slot) edges, one stamp
    per tick via a self-send chain (so its own mailbox also carries a
    FIFO-critical stream: the self-edge n,n-1,… chain)."""
    c1: Ref["Cons"]
    c2: Ref["Cons"]
    slot1: I32
    slot2: I32
    seq: I32

    MAX_SENDS = 3

    @behaviour
    def produce(self, st, n: I32):
        self.send(st["c1"], Cons.consume, st["slot1"], st["seq"], when=n > 0)
        self.send(st["c2"], Cons.consume, st["slot2"], st["seq"], when=n > 0)
        self.send(self.actor_id, Prod.produce, n - 1, when=n > 0)
        return {**st, "seq": st["seq"] + (n > 0) * np.int32(1)}


def _wire(seed, n_cons):
    """Random bipartite wiring: every consumer gets exactly IN_SLOTS
    in-edges, every producer exactly two out-edges (a producer may draw
    two slots of the SAME consumer — two edges into one mailbox)."""
    rng = np.random.default_rng(seed)
    pairs = [(c, s) for c in range(n_cons) for s in range(IN_SLOTS)]
    rng.shuffle(pairs)
    n_prod = len(pairs) // 2
    return n_prod, pairs[:n_prod], pairs[n_prod:]


def run_fifo(seed, okw, n_cons=6, items=60, chains=1, hot=None):
    """`chains` self-send chains a producer: with `batch >= chains` it
    stamps that many messages an edge a tick, so a consumer's four
    in-edges bring it 4 * chains arrivals in one tick. With `hot`, only
    the first `hot` producers run `chains` chains and the others one: a
    consumer then takes 4 to 4 * chains a tick by its in-edges."""
    n_prod, e1, e2 = _wire(seed, n_cons)
    per = np.full(n_prod, chains)
    if hot is not None:
        per[hot:] = 1
    opts = RuntimeOptions(msg_words=2, **okw)
    rt = Runtime(opts)
    rt.declare(Prod, n_prod).declare(Cons, n_cons)
    rt.start()
    cids = rt.spawn_many(Cons, n_cons,
                         last0=np.full(n_cons, -1, np.int32),
                         last1=np.full(n_cons, -1, np.int32),
                         last2=np.full(n_cons, -1, np.int32),
                         last3=np.full(n_cons, -1, np.int32))
    pids = rt.spawn_many(Prod, n_prod,
                         c1=cids[np.asarray([c for c, _ in e1])],
                         c2=cids[np.asarray([c for c, _ in e2])],
                         slot1=np.asarray([s for _, s in e1], np.int32),
                         slot2=np.asarray([s for _, s in e2], np.int32))
    for k in range(chains):
        rt.bulk_send(pids[per > k], Prod.produce,
                     np.full(int((per > k).sum()), items, np.int32))
    assert rt.run(max_steps=500_000) == 0, "must quiesce"
    st = rt.cohort_state(Cons)
    bad = st["bad"][:n_cons]
    assert not bad.any(), f"FIFO violations: {np.asarray(bad)}"
    # Completeness: every edge delivered its full stream (the per-slot
    # last stamp is exactly its producer's items-1, matching the oracle
    # sequence).
    want = np.zeros((IN_SLOTS, n_cons), int)
    for edges in (e1, e2):
        for (c, s), stamps in zip(edges, items * per):
            want[s, c] = stamps
    for s in range(IN_SLOTS):
        last = np.asarray(st[f"last{s}"][:n_cons])
        assert (last == want[s] - 1).all(), (s, last)
    got = np.asarray(st["got"][:n_cons])
    assert (got == want.sum(0)).all(), got
    # Producer self-chains all ran to exhaustion.
    pst = rt.cohort_state(Prod)
    assert (np.asarray(pst["seq"][:n_prod]) == items * per).all()
    return rt


CONFIGS = [
    ("tiny-cap-dspill", dict(mailbox_cap=2, batch=1, max_sends=3,
                             spill_cap=2048, inject_slots=16)),
    ("cosort", dict(mailbox_cap=4, batch=2, max_sends=3, spill_cap=2048,
                    inject_slots=16, delivery="cosort")),
    ("aged-unmute", dict(mailbox_cap=2, batch=1, max_sends=3,
                         spill_cap=2048, inject_slots=16,
                         mute_age_limit=2)),
    ("mesh4-route-spill", dict(mailbox_cap=2, batch=1, max_sends=3,
                               spill_cap=4096, inject_slots=32,
                               mesh_shards=4, route_bucket=8,
                               quiesce_interval=2)),
    # PR 42: the program's own bucket. Every shard of every tick
    # delivers over the SHORT list (the received buckets joined front
    # to front, route._route_unpack), with the receiver spill's retry
    # and the mutes at work on it.
    ("mesh4-default-bucket", dict(mailbox_cap=2, batch=1, max_sends=3,
                                  spill_cap=4096, inject_slots=32,
                                  mesh_shards=4, quiesce_interval=2)),
    ("fused-kernel", dict(mailbox_cap=4, batch=2, max_sends=3,
                          spill_cap=2048, inject_slots=16,
                          pallas_fused=True)),
    # The Pallas drain kernel alone (ops/mailbox_kernel.py): the ring
    # slots it reads must come out in arrival order.
    ("pallas-drain", dict(mailbox_cap=2, batch=1, max_sends=3,
                          spill_cap=2048, inject_slots=16,
                          pallas=True)),
    # PR 25: rings deeper than one rebuild block (delivery.REBUILD_BLOCK),
    # four chains a producer at batch 4: a consumer takes 16 stamped
    # messages in a tick, four an edge, so two rank blocks run and one
    # edge's messages span both.
    ("deep-cap", dict(mailbox_cap=32, batch=4, max_sends=3, spill_cap=2048,
                      inject_slots=32, chains=4)),
    # PR 39: a block is as wide as the rows that have a message in it.
    # 16 consumers (a compacted block holds M = 2 of them), 10 of the 32
    # producers on four chains and the rest on one: a consumer takes 4
    # to 16 a tick by its in-edges and by who is muted, so how many go
    # past the first block straddles M from tick to tick — every form
    # runs (first block compacted or full, second none, compacted or
    # full), and an edge's messages span blocks of both forms.
    ("deep-cap-straddle", dict(mailbox_cap=32, batch=4, max_sends=3,
                               spill_cap=2048, inject_slots=32, chains=4,
                               hot=10, n_cons=16)),
]


@pytest.mark.parametrize("name,okw", CONFIGS, ids=[c[0] for c in CONFIGS])
def test_per_edge_fifo(name, okw):
    okw = dict(okw)
    rt = run_fifo(seed=101, chains=okw.pop("chains", 1),
                  hot=okw.pop("hot", None), n_cons=okw.pop("n_cons", 6),
                  okw=okw)
    if name == "mesh4-default-bucket":
        ticks = np.asarray(rt.state.step_no)
        np.testing.assert_array_equal(
            np.asarray(rt.state.route_counts["n_unpacked"]), ticks)
        assert rt.counter("n_rejected") > 0 and rt.counter("n_mutes") > 0


def test_per_edge_fifo_more_seeds_tiny():
    for seed in (202, 303):
        run_fifo(seed, CONFIGS[0][1], n_cons=4, items=40)


def test_detector_catches_single_inversion():
    """Sensitivity proof: an artificially inverted pair of stamps on one
    edge MUST trip the violation counter — the detector is not
    vacuously green."""
    opts = RuntimeOptions(mailbox_cap=8, batch=1, msg_words=2,
                          max_sends=3, spill_cap=64, inject_slots=8)
    rt = Runtime(opts)
    rt.declare(Prod, 1).declare(Cons, 1)
    rt.start()
    c = rt.spawn(Cons, last0=-1, last1=-1, last2=-1, last3=-1)
    rt.spawn(Prod)
    rt.send(c, Cons.consume, 0, 1)     # seq 1 first — inverted
    rt.send(c, Cons.consume, 0, 0)     # then seq 0
    rt.run(max_steps=1000)
    assert rt.state_of(c)["bad"] > 0, \
        "inverted stamps did not trip the FIFO detector"


def test_host_consumer_fifo():
    """The SAME per-edge streams terminating in a HOST actor: the
    device→host out-ring drain must preserve per-edge order too (the
    ASIO-side half of the FIFO claim). The host log records real arrival
    order; each edge's subsequence must equal 0,1,2,… exactly."""
    logs = {}

    @actor
    class HCons:
        HOST = True
        got: I32

        @behaviour
        def consume(self, st, edge: I32, seq: I32):
            logs.setdefault(int(edge), []).append(int(seq))
            return {**st, "got": st["got"] + 1}

    n_prod, items = 6, 40

    @actor
    class HProd:
        sink: Ref["HCons"]
        edge: I32
        seq: I32

        MAX_SENDS = 2

        @behaviour
        def produce(self, st, n: I32):
            self.send(st["sink"], HCons.consume, st["edge"], st["seq"],
                      when=n > 0)
            self.send(self.actor_id, HProd.produce, n - 1, when=n > 0)
            return {**st, "seq": st["seq"] + (n > 0) * np.int32(1)}

    opts = RuntimeOptions(mailbox_cap=2, batch=1, msg_words=2, max_sends=2,
                          spill_cap=2048, inject_slots=16,
                          host_out_slots=8)   # tiny out-ring → drain churn
    rt = Runtime(opts)
    rt.declare(HProd, n_prod).declare(HCons, 1)
    rt.start()
    sink = rt.spawn(HCons)
    pids = rt.spawn_many(HProd, n_prod, sink=np.full(n_prod, sink),
                         edge=np.arange(n_prod, dtype=np.int32))
    rt.bulk_send(pids, HProd.produce, np.full(n_prod, items, np.int32))
    assert rt.run(max_steps=200_000) == 0
    assert rt.state_of(sink)["got"] == n_prod * items
    for e in range(n_prod):
        assert logs.get(e) == list(range(items)), (e, (logs.get(e)
                                                       or [])[:10])


# --- blob payload↔message binding under order stress -------------------
# The commutative blob differential cannot see a PAYLOAD SWAP between
# two in-flight messages (the multiset of values survives); here every
# message carries its sequence stamp BOTH in a payload word and inside
# its blob, and the consumer checks on device that (a) per-edge stamps
# stay contiguous (FIFO) and (b) blob stamp == word stamp (binding) —
# through tiny-cap spills and, on a mesh, through migration.

@actor
class BlobProd:
    c1: "Ref[BlobCons]"
    slot1: I32
    seq: I32

    MAX_SENDS = 2
    MAX_BLOBS = 1
    BLOB_DISPATCHES = 1
    BATCH = 1

    @behaviour
    def produce(self, st, n: I32):
        from ponyc_tpu import Blob  # noqa: F401
        go = n > 0
        h = self.blob_alloc(length=2, when=go)
        self.blob_set(h, 0, st["seq"], when=go)
        self.blob_set(h, 1, self.actor_id, when=go)
        self.send(st["c1"], BlobCons.consume, st["slot1"], st["seq"], h,
                  when=go)
        self.send(self.actor_id, BlobProd.produce, n - 1, when=n > 1)
        return {**st, "seq": st["seq"] + (n > 0) * np.int32(1)}


@actor
class BlobCons:
    last0: I32
    last1: I32
    got: I32
    bad: I32          # FIFO violations (stamp not contiguous per edge)
    badbind: I32      # payload/message binding violations

    @behaviour
    def consume(self, st, slot: I32, seq: I32, h: "Blob"):
        bseq = self.blob_get(h, 0)
        self.blob_free(h)
        upd = dict(st)
        upd["badbind"] = st["badbind"] + (bseq != seq)
        viol = np.int32(0)
        for s in range(2):
            is_s = slot == s
            last = st[f"last{s}"]
            upd[f"last{s}"] = last + (seq - last) * is_s
        viol = sum((slot == s) & (seq != st[f"last{s}"] + 1)
                   for s in range(2))
        upd["bad"] = st["bad"] + viol
        upd["got"] = st["got"] + 1
        return upd


def run_blob_fifo(seed, okw, n_cons=4, items=30):
    rng = np.random.default_rng(seed)
    n_prod = 2 * n_cons                  # exactly two edges per consumer
    perm = rng.permutation(n_prod)
    cons_of = np.repeat(np.arange(n_cons), 2)[perm]
    slot_of = np.tile(np.arange(2), n_cons)[perm]
    opts = RuntimeOptions(msg_words=3,
                          blob_slots=max(256, n_prod * items),
                          blob_words=2, **okw)
    rt = Runtime(opts)
    rt.declare(BlobProd, n_prod).declare(BlobCons, n_cons)
    rt.start()
    cids = rt.spawn_many(BlobCons, n_cons,
                         last0=np.full(n_cons, -1, np.int32),
                         last1=np.full(n_cons, -1, np.int32))
    pids = rt.spawn_many(BlobProd, n_prod,
                         c1=cids[cons_of], slot1=slot_of.astype(np.int32))
    rt.bulk_send(pids, BlobProd.produce, np.full(n_prod, items, np.int32))
    assert rt.run(max_steps=500_000) == 0, "must quiesce"
    st = rt.cohort_state(BlobCons)
    assert not np.asarray(st["badbind"][:n_cons]).any(), (
        "payload/message binding violated", np.asarray(st["badbind"]))
    assert not np.asarray(st["bad"][:n_cons]).any(), (
        "FIFO violated", np.asarray(st["bad"]))
    for s in range(2):
        assert (np.asarray(st[f"last{s}"][:n_cons]) == items - 1).all()
    assert (np.asarray(st["got"][:n_cons]) == 2 * items).all()
    assert rt.blobs_in_use == 0
    return rt


@pytest.mark.parametrize("name,okw", [
    ("tiny", dict(mailbox_cap=2, batch=1, max_sends=2, spill_cap=2048,
                  inject_slots=16)),
    ("cosort", dict(mailbox_cap=4, batch=2, max_sends=2, spill_cap=2048,
                    inject_slots=16, delivery="cosort")),
    ("mesh4-bucket", dict(mailbox_cap=2, batch=1, max_sends=2,
                          spill_cap=4096, inject_slots=32, mesh_shards=4,
                          route_bucket=4, quiesce_interval=2)),
])
def test_blob_payload_binding_fifo(name, okw):
    run_blob_fifo(11, okw)
