"""Device blob pool: rich message payloads without host round-trips.

≙ the reference's actor-heap message payloads — pony_alloc_msg packs a
per-behaviour pony_msg_t subtype (src/libponyc/codegen/genfun.c) whose
pointer fields reference objects on the sending actor's heap
(src/libponyrt/mem/heap.c); ORCA moves ownership with the message. Here
the heap is the device-resident pool (RuntimeOptions.blob_slots ×
blob_words, runtime/state.py), the pointer is a global i32 handle with
mode iso (ops.pack.Blob), and the move discipline is the trace-time
capability checker. v1 scoped semantics under test here:

  - alloc/write/read/free via ctx.blob_* (api.BlobPoolView);
  - sending a handle as a Blob parameter MOVES it (use-after-move and
    free-then-use reject at build);
  - pool exhaustion raises BlobCapacityError host-side (sticky flag);
  - per-dispatch alloc budget = MAX_BLOBS (exceeding rejects at build);
  - on a mesh a blob MIGRATES with its routed message (fresh local
    slot + generation at the receiver, route._route; n_blob_moved);
    host injections bypass routing — allocate near the receiver;
  - the host side allocates/reads via Runtime.blob_store/blob_fetch.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from ponyc_tpu import (Actor, Blob, BlobCapacityError, I32, Ref, Runtime,
                       RuntimeOptions, actor, behaviour)

OPTS = dict(mailbox_cap=4, batch=2, max_sends=1, msg_words=2,
            inject_slots=8, blob_slots=16, blob_words=8)


@actor
class Producer(Actor):
    out: Ref["Consumer"]
    MAX_BLOBS = 1
    MAX_SENDS = 1

    @behaviour
    def go(self, st, n: I32):
        h = self.blob_alloc(length=4)
        for i in range(4):
            self.blob_set(h, i, n * 10 + i)
        self.send(st["out"], Consumer.take, h)
        return st


@actor
class Consumer(Actor):
    total: I32
    seen: I32

    @behaviour
    def take(self, st, h: Blob):
        s = jnp.int32(0)
        for i in range(4):
            s = s + self.blob_get(h, i)
        st["total"] = st["total"] + s
        st["seen"] = st["seen"] + self.blob_length(h)
        self.blob_free(h)
        return st


def _world(**kw):
    rt = Runtime(RuntimeOptions(**{**OPTS, **kw}))
    rt.declare(Producer, 4).declare(Consumer, 4).start()
    c = rt.spawn(Consumer, total=0, seen=0)
    p = rt.spawn(Producer, out=c)
    return rt, p, c


def test_alloc_write_move_read_free_roundtrip():
    rt, p, c = _world()
    rt.send(p, Producer.go, 7)
    rt.run(max_steps=10)
    st = rt.state_of(c)
    assert st["total"] == 70 + 71 + 72 + 73
    assert st["seen"] == 4                      # blob_length(h)
    assert rt.counter("n_blob_alloc") == 1
    assert rt.counter("n_blob_free") == 1
    assert rt.blobs_in_use == 0
    assert rt.counter("n_blob_remote") == 0


def test_slots_recycle_through_free():
    rt, p, c = _world()
    # 8 sequential messages through a 16-slot pool with free() each time:
    # never exhausts, every alloc gets a slot.
    for k in range(8):
        rt.send(p, Producer.go, k)
        rt.run(max_steps=6)
    assert rt.counter("n_blob_alloc") == 8
    assert rt.counter("n_blob_free") == 8
    assert rt.blobs_in_use == 0
    assert rt.state_of(c)["total"] == sum(
        sum(k * 10 + i for i in range(4)) for k in range(8))


def test_pool_exhaustion_raises():
    @actor
    class Leaker(Actor):
        n: I32
        MAX_BLOBS = 1

        @behaviour
        def leak(self, st):
            self.blob_alloc()                   # never freed
            return st

    rt = Runtime(RuntimeOptions(**{**OPTS, "blob_slots": 2}))
    rt.declare(Leaker, 4).start()
    a = rt.spawn(Leaker, n=0)
    for _ in range(3):
        rt.send(a, Leaker.leak)
    with pytest.raises(BlobCapacityError):
        rt.run(max_steps=10)


def test_pool_exhaustion_message_names_blob_slots():
    # The POOL-exhaustion error must point at blob_slots, never at
    # BLOB_DISPATCHES (they were conflated under one sticky flag once).
    @actor
    class Leaker(Actor):
        n: I32
        MAX_BLOBS = 1

        @behaviour
        def leak(self, st):
            self.blob_alloc()
            return st

    rt = Runtime(RuntimeOptions(**{**OPTS, "blob_slots": 2}))
    rt.declare(Leaker, 4).start()
    a = rt.spawn(Leaker, n=0)
    for _ in range(3):
        rt.send(a, Leaker.leak)
    with pytest.raises(BlobCapacityError, match="blob_slots"):
        rt.run(max_steps=10)


def test_budget_exhaustion_names_blob_dispatches():
    # BLOB_DISPATCHES exhaustion with a half-empty pool must blame the
    # BUDGET knob: 2 allocating dispatches in one tick against
    # BLOB_DISPATCHES=1, 16 free slots.
    @actor
    class Hungry(Actor):
        n: I32
        MAX_BLOBS = 1
        BLOB_DISPATCHES = 1

        @behaviour
        def grab(self, st):
            self.blob_alloc(length=1)
            return st

    rt = Runtime(RuntimeOptions(**OPTS))       # batch=2: both msgs in
    rt.declare(Hungry, 4).start()              # one tick's drain
    a = rt.spawn(Hungry, n=0)
    rt.send(a, Hungry.grab)
    rt.send(a, Hungry.grab)
    with pytest.raises(BlobCapacityError, match="BLOB_DISPATCHES"):
        rt.run(max_steps=10)


def test_host_iso_blob_double_send_raises():
    # ADVICE round 5: the host moving an iso blob it does not own must
    # be LOUD (matching HostHeap.send_iso and the device trace), not a
    # silent null-read downstream.
    from ponyc_tpu.hostmem import CapabilityError
    rt = Runtime(RuntimeOptions(**OPTS))
    rt.declare(Consumer, 2).start()
    c = rt.spawn(Consumer, total=0, seen=0)
    h = rt.blob_store([5])
    rt.send(c, Consumer.take, h)               # legal move
    with pytest.raises(CapabilityError, match="aliased move"):
        rt.send(c, Consumer.take, h)           # double-send of an iso
    with pytest.raises(CapabilityError, match="aliased move"):
        rt.send(c, Consumer.take, 12345)       # never-owned forged int
    rt.run(max_steps=6)
    assert rt.state_of(c)["seen"] == 1


def test_max_blobs_budget_rejects_at_build():
    @actor
    class Greedy(Actor):
        n: I32
        MAX_BLOBS = 1

        @behaviour
        def two(self, st):
            self.blob_alloc()
            self.blob_alloc()
            return st

    rt = Runtime(RuntimeOptions(**OPTS))
    rt.declare(Greedy, 4).start()
    with pytest.raises(RuntimeError, match="MAX_BLOBS"):
        rt.run(max_steps=1)            # behaviours trace at first run


def test_send_is_a_move_use_after_rejects():
    @actor
    class BadSender(Actor):
        out: Ref["Consumer"]
        MAX_BLOBS = 1
        MAX_SENDS = 1

        @behaviour
        def go(self, st):
            h = self.blob_alloc()
            self.send(st["out"], Consumer.take, h)
            self.blob_set(h, 0, 1)              # use-after-move
            return st

    rt = Runtime(RuntimeOptions(**OPTS))
    rt.declare(BadSender, 4).declare(Consumer, 4).start()
    with pytest.raises(TypeError, match="use-after-move"):
        rt.run(max_steps=1)


def test_free_then_use_rejects():
    @actor
    class FreeUse(Actor):
        n: I32
        MAX_BLOBS = 1

        @behaviour
        def go(self, st):
            h = self.blob_alloc()
            self.blob_free(h)
            st["n"] = st["n"] + self.blob_get(h, 0)
            return st

    rt = Runtime(RuntimeOptions(**OPTS))
    rt.declare(FreeUse, 4).start()
    with pytest.raises(TypeError, match="use-after-move"):
        rt.run(max_steps=1)


def test_blob_requires_pool_enabled():
    rt = Runtime(RuntimeOptions(mailbox_cap=4, batch=2, max_sends=1,
                                msg_words=2))
    rt.declare(Producer, 4).declare(Consumer, 4)
    with pytest.raises(TypeError, match="blob"):
        rt.start()


def test_host_actor_cannot_hold_blobs():
    @actor
    class HostEater(Actor):
        HOST = True
        n: I32

        @behaviour
        def eat(self, st, h: Blob):
            return st

    rt = Runtime(RuntimeOptions(**OPTS))
    rt.declare(HostEater, 2)
    with pytest.raises(TypeError, match="host"):
        rt.start()


def test_host_store_device_reads_and_frees():
    @actor
    class Summer(Actor):
        total: I32

        @behaviour
        def add(self, st, h: Blob):
            s = jnp.int32(0)
            for i in range(3):
                s = s + self.blob_get(h, i)
            st["total"] = st["total"] + s
            self.blob_free(h)
            return st

    rt = Runtime(RuntimeOptions(**OPTS))
    rt.declare(Summer, 4).start()
    a = rt.spawn(Summer, total=0)
    h = rt.blob_store([5, 6, 7])
    assert rt.blobs_in_use == 1
    np.testing.assert_array_equal(rt.blob_fetch(h), [5, 6, 7])
    rt.send(a, Summer.add, h)                   # host moves it to the actor
    rt.run(max_steps=10)
    assert rt.state_of(a)["total"] == 18
    assert rt.blobs_in_use == 0
    with pytest.raises(KeyError):
        rt.blob_fetch(h)                        # freed device-side


def test_blob_send_coexists_with_host_heap():
    # Blob shares the iso MODE with HostHeap handles but lives in the
    # device pool: a host send of a Blob arg must NOT run the HostHeap
    # send_iso discipline (a pool slot id is not a heap handle).
    @actor
    class Summer(Actor):
        total: I32

        @behaviour
        def add(self, st, h: Blob):
            st["total"] = st["total"] + self.blob_get(h, 0)
            self.blob_free(h)
            return st

    rt = Runtime(RuntimeOptions(**OPTS))
    rt.declare(Summer, 4).start()
    a = rt.spawn(Summer, total=0)
    rt.heap.box([1, 2, 3])           # materialise the HostHeap
    h = rt.blob_store([41])          # pool slot 0 — NOT a heap handle
    rt.send(a, Summer.add, h)        # must not touch heap.send_iso
    rt.run(max_steps=8)
    assert rt.state_of(a)["total"] == 41
    assert rt.blobs_in_use == 0


def test_generic_actor_keeps_max_blobs():
    from ponyc_tpu import TypeParam
    T = TypeParam("T")

    @actor
    class Box_(Actor):
        n: I32
        MAX_BLOBS = 1

        @behaviour
        def put(self, st, v: T):
            h = self.blob_alloc(length=1)
            self.blob_set(h, 0, 1)
            self.blob_free(h)
            return st

    BoxI = Box_[I32]
    assert getattr(BoxI, "MAX_BLOBS", 0) == 1   # survives reification
    rt = Runtime(RuntimeOptions(**OPTS))
    rt.declare(BoxI, 2).start()
    a = rt.spawn(BoxI, n=0)
    rt.send(a, BoxI.put, 5)
    rt.run(max_steps=6)
    assert rt.counter("n_blob_alloc") == 1
    assert rt.counter("n_blob_free") == 1


def test_host_free_rejects_double_free_and_bad_length():
    rt = Runtime(RuntimeOptions(**OPTS))
    rt.declare(Consumer, 2).start()
    h = rt.blob_store([1, 2])
    rt.blob_free_host(h)
    with pytest.raises(KeyError):
        rt.blob_free_host(h)                    # double free
    with pytest.raises(ValueError):
        rt.blob_store([1], length=100)          # length > blob_words


def test_stale_handle_reads_zero_not_leftovers():
    # A freed slot keeps its words until the next alloc zeroes them; a
    # stale/forged in-range handle must read 0, not the previous blob's
    # payload (cross-actor data leak).
    @actor
    class Reader(Actor):
        got: I32

        @behaviour
        def probe(self, st, k: Blob):
            # k is a STALE handle: freed host-side after the send, so by
            # dispatch time the slot is unallocated (words still there).
            return {**st, "got": st["got"] + self.blob_get(k, 0)}

    rt = Runtime(RuntimeOptions(**OPTS))
    rt.declare(Reader, 2).start()
    a = rt.spawn(Reader, got=0)
    h = rt.blob_store([777])
    rt.send(a, Reader.probe, h)         # legal move (host owns h here)
    rt.blob_free_host(h)                # freed before dispatch: by the
    #   time probe runs the slot is unallocated (words still there)
    rt.run(max_steps=6)
    assert rt.state_of(a)["got"] == 0   # used-gate: no leftover leak


def test_recycled_slot_stale_handle_reads_zero():
    # ABA guard: free a blob, let the SLOT be re-allocated to a new
    # owner, then read through the old handle — generation mismatch
    # must yield 0, never the new owner's words. (The used-gate alone
    # cannot catch this: the slot IS allocated, just not to you.)
    @actor
    class Reader(Actor):
        got: I32

        @behaviour
        def probe(self, st, h: Blob):
            return {**st, "got": st["got"] + self.blob_get(h, 0)}

    rt = Runtime(RuntimeOptions(**{**OPTS, "blob_slots": 1}))
    rt.declare(Reader, 2).start()
    a = rt.spawn(Reader, got=0)
    h_old = rt.blob_store([111])
    rt.send(a, Reader.probe, h_old)     # legal move (host owns h_old)
    rt.blob_free_host(h_old)            # ...then freed before dispatch
    h_new = rt.blob_store([222])        # 1-slot pool: SAME slot, new gen
    from ponyc_tpu.ops import pack
    assert pack.blob_slot(h_old) == pack.blob_slot(h_new)
    assert h_old != h_new               # generations differ: the
    #   in-flight message now carries a stale handle
    rt.run(max_steps=6)
    assert rt.state_of(a)["got"] == 0   # gen mismatch → null read
    with pytest.raises(KeyError, match="STALE"):
        rt.blob_fetch(h_old)            # host side rejects it too
    np.testing.assert_array_equal(rt.blob_fetch(h_new), [222])


def test_blob_store_near_targets_receiver_shard():
    opts = RuntimeOptions(**{**OPTS, "mesh_shards": 2})
    rt = Runtime(opts)
    rt.declare(Consumer, 4).start()
    c_sh0 = rt.spawn(Consumer, total=0, seen=0)   # slot 0 → shard 0
    c_sh1 = rt.spawn(Consumer, total=0, seen=0)   # slot 1 → shard 1
    from ponyc_tpu.ops import pack
    h0 = rt.blob_store([7, 7, 7, 7], near=int(c_sh0))
    h1 = rt.blob_store([9, 9, 9, 9], near=int(c_sh1))
    assert pack.blob_slot(h0) // opts.blob_slots == 0
    assert pack.blob_slot(h1) // opts.blob_slots == 1   # receiver's shard
    rt.send(int(c_sh0), Consumer.take, h0)
    rt.send(int(c_sh1), Consumer.take, h1)
    rt.run(max_steps=10)
    assert rt.state_of(c_sh0)["total"] == 28
    assert rt.state_of(c_sh1)["total"] == 36
    assert rt.counter("n_blob_remote") == 0       # both landed local


def test_snapshot_preserves_host_blob_roots():
    from ponyc_tpu import serialise
    rt = Runtime(RuntimeOptions(**OPTS))
    rt.declare(Consumer, 2).start()
    h = rt.blob_store([5])
    path = "/tmp/test_blob_snapshot.npz"
    serialise.save(rt, path)
    rt2 = Runtime(RuntimeOptions(**OPTS))
    rt2.declare(Consumer, 2).start()
    serialise.restore(rt2, path)
    rt2.gc()                            # must NOT sweep the host's blob
    assert rt2.blobs_in_use == 1
    np.testing.assert_array_equal(rt2.blob_fetch(h), [5])


def test_records_model_oracle():
    # The records pipeline (models/records.py): variable-length blob
    # payloads through source → worker → fan-in sink, word-for-word
    # against the NumPy oracle, every blob freed by its consumer.
    from ponyc_tpu.models import records
    rt, st = records.run_records(n_sources=8, n_records=6)
    assert st["n"] == 48
    assert rt.counter("n_blob_alloc") == 48
    assert rt.counter("n_blob_free") == 48


def test_mesh_blob_migrates_with_routed_message():
    # 2-shard world: Producer on shard 0 allocates and sends to a
    # Consumer row on shard 1 — the blob MIGRATES with the routed
    # message (payload rides the all_to_all; fresh local slot +
    # generation at the receiver), so the consumer reads it like any
    # local blob and frees it normally.
    opts = RuntimeOptions(**{**OPTS, "mesh_shards": 2})
    rt = Runtime(opts)
    rt.declare(Producer, 4).declare(Consumer, 4).start()
    # slot_to_gid: even slots shard 0, odd slots shard 1.
    c1 = rt.spawn(Consumer, total=0, seen=0)    # slot 0 → shard 0
    c2 = rt.spawn(Consumer, total=0, seen=0)    # slot 1 → shard 1
    p1 = rt.spawn(Producer, out=c2)             # slot 0 → shard 0: routes!
    rt.send(p1, Producer.go, 3)
    rt.run(max_steps=10)
    assert rt.state_of(c2)["total"] == 30 + 31 + 32 + 33
    assert rt.state_of(c2)["seen"] == 4         # full logical length
    assert rt.counter("n_blob_moved") == 1      # one cross-shard hop
    assert rt.counter("n_blob_remote") == 0     # nothing arrived dead
    assert rt.blobs_in_use == 0                 # freed at the receiver
    # Same-shard delivery migrates nothing (off-shard blocks only).
    p2 = rt.spawn(Producer, out=c2)             # slot 1 → shard 1: local
    rt.send(p2, Producer.go, 5)
    rt.run(max_steps=10)
    assert rt.state_of(c2)["total"] == 126 + 50 + 51 + 52 + 53
    assert rt.counter("n_blob_moved") == 1      # unchanged
    assert rt.blobs_in_use == 0
def test_gc_sweeps_dead_actor_field_blobs():
    # An actor holding a blob in a Blob FIELD dies unreachable → the
    # next collection frees both the actor and its blob (≙ the actor's
    # heap dying with it). A live holder keeps its blob alive.
    @actor
    class Holder(Actor):
        stash: Blob
        MAX_BLOBS = 1

        @behaviour
        def keep(self, st):
            h = self.blob_alloc(length=2)
            self.blob_set(h, 0, 9)
            return {**st, "stash": h}

    rt = Runtime(RuntimeOptions(**OPTS))
    rt.declare(Holder, 4).start()
    a = rt.spawn(Holder)
    b = rt.spawn(Holder)
    rt.send(a, Holder.keep)
    rt.send(b, Holder.keep)
    rt.run(max_steps=6)
    assert rt.blobs_in_use == 2
    assert rt.gc() == 0                 # both pinned (host refs) → live
    assert rt.blobs_in_use == 2         # field-held blobs marked live
    rt.release(b)                       # unpin: b becomes garbage
    assert rt.gc() == 1
    assert rt.blobs_in_use == 1         # b's blob swept with it
    assert rt.counter("n_blob_free") == 1


def test_blob_dispatches_bounds_reservation_footprint():
    # Without the bound each runnable actor reserves batch×MAX_BLOBS
    # windows, so 4 allocators × batch=2 would outsize a 4-slot pool
    # even though only 4 slots get used; BLOB_DISPATCHES=1 shrinks the
    # static window to 1 per actor and the same program fits exactly.
    @actor
    class Lean(Actor):
        stash: Blob
        MAX_BLOBS = 1
        BLOB_DISPATCHES = 1

        @behaviour
        def fill(self, st, v: I32):
            h = self.blob_alloc(length=1)
            self.blob_set(h, 0, v)
            return {**st, "stash": h}

    rt = Runtime(RuntimeOptions(**{**OPTS, "blob_slots": 4}))
    rt.declare(Lean, 4).start()
    ids = [rt.spawn(Lean) for _ in range(4)]
    for i, a in enumerate(ids):
        rt.send(a, Lean.fill, i)
    rt.run(max_steps=8)                 # must NOT raise BlobCapacityError
    assert rt.blobs_in_use == 4
    assert sorted(int(rt.blob_fetch(int(rt.state_of(a)["stash"]))[0])
                  for a in ids) == [0, 1, 2, 3]


def test_gc_keeps_host_held_and_inflight_blobs():
    rt = Runtime(RuntimeOptions(**OPTS))
    rt.declare(Consumer, 2).start()
    c = rt.spawn(Consumer, total=0, seen=0)
    h_held = rt.blob_store([1])         # host-owned root
    h_sent = rt.blob_store([2, 3, 4, 5])
    rt.send(c, Consumer.take, h_sent)   # in-flight (inject queue)
    rt.gc()                             # must sweep NEITHER
    assert rt.blobs_in_use == 2
    rt.run(max_steps=8)                 # take() frees h_sent
    assert rt.blobs_in_use == 1
    rt.blob_free_host(h_held)
    assert rt.blobs_in_use == 0


def test_freeze_shares_one_payload_with_many_readers():
    # ≙ Pony's `String val` broadcast: freeze once, send the SAME
    # handle to two readers in one dispatch (an iso handle would reject
    # the second send as an aliased move); nobody frees — the GC mark
    # pass reclaims the slot once the readers have consumed it.
    from ponyc_tpu import BlobVal

    @actor
    class Caster(Actor):
        a: Ref["ValReader"]
        b: Ref["ValReader"]
        MAX_BLOBS = 1
        MAX_SENDS = 2

        @behaviour
        def cast(self, st, x: I32):
            h = self.blob_alloc(length=2)
            self.blob_set(h, 0, x)
            self.blob_set(h, 1, x * 2)
            v = self.blob_freeze(h)
            self.send(st["a"], ValReader.read, v)
            self.send(st["b"], ValReader.read, v)   # alias: legal for val
            return st

    @actor
    class ValReader(Actor):
        got: I32

        @behaviour
        def read(self, st, v: BlobVal):
            return {**st, "got": st["got"] + self.blob_get(v, 0)
                    + self.blob_get(v, 1)}

    rt = Runtime(RuntimeOptions(**OPTS))
    rt.declare(Caster, 2).declare(ValReader, 4).start()
    r1 = rt.spawn(ValReader, got=0)
    r2 = rt.spawn(ValReader, got=0)
    c = rt.spawn(Caster, a=r1, b=r2)
    rt.send(c, Caster.cast, 7)
    rt.run(max_steps=10)
    assert rt.state_of(r1)["got"] == 7 + 14
    assert rt.state_of(r2)["got"] == 7 + 14
    assert rt.blobs_in_use == 1          # nobody freed (val has no owner)
    rt.gc()                              # ...but nothing references it now
    assert rt.blobs_in_use == 0


def test_frozen_blob_rejects_write_and_free():
    @actor
    class BadFreezer(Actor):
        n: I32
        MAX_BLOBS = 1

        @behaviour
        def w(self, st):
            h = self.blob_freeze(self.blob_alloc(length=1))
            self.blob_set(h, 0, 1)               # write-after-freeze
            return st

    rt = Runtime(RuntimeOptions(**OPTS))
    rt.declare(BadFreezer, 2).start()
    with pytest.raises(TypeError, match="frozen"):
        rt.run(max_steps=1)

    @actor
    class BadFreer(Actor):
        n: I32
        MAX_BLOBS = 1

        @behaviour
        def f(self, st):
            h = self.blob_freeze(self.blob_alloc(length=1))
            self.blob_free(h)                    # free-after-freeze
            return st

    rt2 = Runtime(RuntimeOptions(**OPTS))
    rt2.declare(BadFreer, 2).start()
    with pytest.raises(TypeError, match="val"):
        rt2.run(max_steps=1)


def test_frozen_handle_rejects_iso_parameter():
    @actor
    class Smuggler(Actor):
        out: Ref["Consumer"]
        MAX_BLOBS = 1
        MAX_SENDS = 1

        @behaviour
        def go(self, st):
            v = self.blob_freeze(self.blob_alloc(length=1))
            self.send(st["out"], Consumer.take, v)   # Consumer.take: Blob
            return st

    rt = Runtime(RuntimeOptions(**OPTS))
    rt.declare(Smuggler, 2).declare(Consumer, 2).start()
    with pytest.raises(TypeError, match="val"):
        rt.run(max_steps=1)


def test_mesh_val_blob_copies_not_moves():
    # A frozen blob broadcast to readers on BOTH shards: the off-shard
    # reader gets a COPY (migration does not free the source), the
    # local reader reads the original; gc reclaims both replicas.
    from ponyc_tpu import BlobVal

    @actor
    class Caster(Actor):
        a: Ref["VReader"]
        b: Ref["VReader"]
        MAX_BLOBS = 1
        MAX_SENDS = 2

        @behaviour
        def cast(self, st, x: I32):
            h = self.blob_alloc(length=1)
            self.blob_set(h, 0, x)
            v = self.blob_freeze(h)
            self.send(st["a"], VReader.read, v)
            self.send(st["b"], VReader.read, v)
            return st

    @actor
    class VReader(Actor):
        got: I32

        @behaviour
        def read(self, st, v: BlobVal):
            return {**st, "got": st["got"] + self.blob_get(v, 0)}

    opts = RuntimeOptions(**{**OPTS, "mesh_shards": 2})
    rt = Runtime(opts)
    rt.declare(Caster, 2).declare(VReader, 4).start()
    r_local = rt.spawn(VReader, got=0)   # slot 0 → shard 0
    r_remote = rt.spawn(VReader, got=0)  # slot 1 → shard 1
    c = rt.spawn(Caster, a=r_local, b=r_remote)   # slot 0 → shard 0
    rt.send(c, Caster.cast, 41)
    rt.run(max_steps=10)
    assert rt.state_of(r_local)["got"] == 41      # original
    assert rt.state_of(r_remote)["got"] == 41     # replica
    assert rt.counter("n_blob_moved") == 1        # the copy that crossed
    assert rt.blobs_in_use == 2                   # original + replica
    rt.gc()
    assert rt.blobs_in_use == 0                   # both reclaimed


def test_string_payload_roundtrip():
    # The `String val` payload path: host stores UTF-8 text as a blob,
    # a device actor forwards the handle, the host reads it back.
    @actor
    class Fwd(Actor):
        sink: Ref["Keeper"]
        MAX_SENDS = 1

        @behaviour
        def fwd(self, st, h: Blob):
            self.send(st["sink"], Keeper.keep, h)
            return st

    @actor
    class Keeper(Actor):
        held: Blob

        @behaviour
        def keep(self, st, h: Blob):
            return {**st, "held": h}

    rt = Runtime(RuntimeOptions(**OPTS))
    rt.declare(Fwd, 2).declare(Keeper, 2).start()
    k = rt.spawn(Keeper, held=-1)
    f = rt.spawn(Fwd, sink=k)
    h = rt.blob_store_str("héllo, pony→tpu")
    rt.send(f, Fwd.fwd, h)
    rt.run(max_steps=8)
    h2 = int(rt.state_of(k)["held"])
    assert h2 == h                        # same-chip: handle unchanged
    assert rt.blob_fetch_str(h2) == "héllo, pony→tpu"


def test_verify_marks_blob_allocs():
    from ponyc_tpu.verify import behaviour_effects

    @actor
    class A(Actor):
        n: I32
        MAX_BLOBS = 2

        @behaviour
        def go(self, st):
            self.blob_alloc()
            self.blob_alloc(length=1)
            return st

    eff = behaviour_effects(A.go)
    assert eff.blob_allocs == 2
    assert "allocs blobs×2" in eff.marks()


def test_snapshot_resumes_midflight_blob_pipeline():
    # Checkpoint while blob messages are QUEUED (allocated, unread),
    # restore into a fresh runtime, run to completion: totals exact,
    # pool leak-free — the blob arrays ride the generic state pytree.
    from ponyc_tpu import serialise
    from ponyc_tpu.models import records

    opts = RuntimeOptions(mailbox_cap=8, batch=2, max_sends=2,
                          msg_words=2, inject_slots=8,
                          blob_slots=128, blob_words=records.W)
    rt, sink, sources = records.build(8, 6, opts)
    for s in sources:
        rt.send(int(s), records.RecSource.emit, 0)
    rt.run(max_steps=3)                     # mid-flight: blobs queued
    assert rt.blobs_in_use > 0
    path = "/tmp/test_blob_midflight.npz"
    serialise.save(rt, path)

    rt2, sink2, _ = records.build(8, 6, opts)
    serialise.restore(rt2, path)
    rt2.run()
    want_n, want_total = records.oracle(8, 6)
    st = rt2.state_of(int(sink2))
    assert st["n"] == want_n
    assert np.int32(st["total"]) == np.int32(want_total)
    assert rt2.blobs_in_use == 0


@pytest.mark.parametrize("target", ["same-layout", "relayout"])
def test_snapshot_of_the_pool_as_a_table_restores_into_the_flat_pool(
        tmp_path, target):
    """A snapshot written when `st.blob_data` was a [words, slots] table
    (before the pool was flat) restores mid-flight, same layout and
    re-laid-out, and finishes exact; so does a blob-free one's empty
    [0, 0] table."""
    from ponyc_tpu import serialise
    from ponyc_tpu.models import records, ring

    def opts(**kw):
        return RuntimeOptions(**{**dict(
            mailbox_cap=8, batch=2, max_sends=2, msg_words=2,
            inject_slots=8, blob_slots=128, blob_words=records.W), **kw})
    rt, _, sources = records.build(8, 6, opts())
    for s in sources:
        rt.send(int(s), records.RecSource.emit, 0)
    rt.run(max_steps=3)
    assert rt.blobs_in_use > 0
    header, arrays = serialise.capture(rt)
    arrays["st.blob_data"] = np.ascontiguousarray(
        arrays["st.blob_data"].reshape(records.W, 128))
    path = str(tmp_path / "table-pool.npz")
    serialise.write_snapshot(header, arrays, path)

    okw = {"same-layout": {}, "relayout": dict(blob_slots=64)}[target]
    rt2, sink2, _ = records.build(8, 6, opts(**okw))
    serialise.restore(rt2, path)
    rt2.run()
    want_n, want_total = records.oracle(8, 6)
    st = rt2.state_of(int(sink2))
    assert st["n"] == want_n
    assert np.int32(st["total"]) == np.int32(want_total)
    assert rt2.blobs_in_use == 0

    if target == "same-layout":
        # two shards' table: each shard's block word-major, shard-major
        from ponyc_tpu.runtime.state import pool_index
        table = np.arange(3 * 8).reshape(3, 8)         # [words, 2 x 4 slots]
        flat = serialise._flat_pool(table, 2)
        for shard, word, slot in ((0, 0, 0), (0, 2, 3), (1, 0, 0), (1, 1, 2)):
            assert flat[shard * 12 + pool_index(4, word, slot)] \
                == table[word, shard * 4 + slot]
        free = RuntimeOptions(mailbox_cap=8, batch=2, max_sends=1,
                              msg_words=1, inject_slots=8)
        rt3, ids = ring.build(8, free)
        rt3.send(int(ids[0]), ring.RingNode.token, 20)
        rt3.run(max_steps=4)
        header, arrays = serialise.capture(rt3)
        arrays["st.blob_data"] = np.zeros((0, 0), np.int32)
        serialise.write_snapshot(header, arrays, path)
        rt4, _ = ring.build(8, free)
        serialise.restore(rt4, path)
        assert rt4.run() == 0
        assert rt4.cohort_state(ring.RingNode)["passes"].sum() == 20
