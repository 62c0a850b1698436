"""Cross-shard pressure paths on the 8-virtual-device mesh.

≙ the reference's backpressure invariants under contention
(mute/unmute walks, scheduler.c:1478-1635; bounded queues are the
divergence — overflow spills are finite and their exhaustion is fatal).
These tests force the paths a quiet mesh never takes: all_to_all bucket
overflow → route spill → sender mute → retry → unmute; receiver-side
overflow spill across shards; and the spill-overflow abort.
"""

import dataclasses

import numpy as np
import pytest

from ponyc_tpu import I32, Ref, Runtime, RuntimeOptions, actor, behaviour
from ponyc_tpu.runtime.runtime import SpillOverflowError


@actor
class Burst:
    """Sends one message per tick to a fixed target, `left` times."""
    out: Ref
    left: I32
    MAX_SENDS = 2

    @behaviour
    def go(self, st, _: I32):
        alive = st["left"] > 0
        self.send(st["out"], Sink.recv, 1, when=alive)
        self.send(self.actor_id, Burst.go, 0, when=st["left"] > 1)
        return {**st, "left": st["left"] - 1}


@actor
class Sink:
    got: I32

    @behaviour
    def recv(self, st, v: I32):
        return {**st, "got": st["got"] + v}


def _run_pressure(opts, n_src=48, items=4):
    """n_src senders spread over all shards flood ONE sink on shard 0."""
    rt = Runtime(opts)
    rt.declare(Burst, n_src).declare(Sink, 4)
    rt.start()
    sink = rt.spawn(Sink)
    srcs = rt.spawn_many(Burst, n_src, out=int(sink), left=items)
    for s in srcs:
        rt.send(int(s), Burst.go, 0)
    return rt, sink, srcs


def test_route_bucket_overflow_spills_mutes_and_recovers():
    # Worst-case fan-in across the mesh: every shard's senders target one
    # shard; per-tick emissions exceed the all_to_all bucket, so messages
    # park in route-spill and their senders mute (route._route pressure
    # branch). Everything must still arrive exactly once.
    opts = RuntimeOptions(mailbox_cap=4, batch=1, max_sends=2, msg_words=2,
                          mesh_shards=4, spill_cap=256, inject_slots=64,
                          quiesce_interval=1, route_bucket=8)
    rt, sink, srcs = _run_pressure(opts, n_src=48, items=4)
    saw_rspill = False
    saw_muted = False
    for _ in range(400):
        rt.run(max_steps=1)
        saw_rspill = saw_rspill or rt.counter("rspill_count") > 0
        saw_muted = saw_muted or bool(np.asarray(rt.state.muted).any())
        if rt.state_of(int(sink))["got"] == 48 * 4:
            break
    assert rt.state_of(int(sink))["got"] == 48 * 4
    assert saw_rspill, "bucket overflow never engaged the route spill"
    assert saw_muted, "pressure never muted a sender"
    assert rt.counter("n_mutes") > 0
    # Quiescent end state: every sender released again (unmute pass).
    rt.run(max_steps=50)
    assert not np.asarray(rt.state.muted).any()
    assert rt.counter("rspill_count") == 0


def test_receiver_spill_crosses_shards_and_drains():
    # Bucket large enough (big spill_cap ⇒ big bucket) that routing
    # passes everything through; the RECEIVER mailbox (cap 4) overflows
    # instead, exercising the delivery spill + mute on a mesh.
    opts = RuntimeOptions(mailbox_cap=4, batch=2, max_sends=2, msg_words=2,
                          mesh_shards=4, spill_cap=2048, inject_slots=64)
    rt, sink, srcs = _run_pressure(opts, n_src=32, items=4)
    saw_dspill = False
    for _ in range(400):
        rt.run(max_steps=1)
        saw_dspill = saw_dspill or rt.counter("dspill_count") > 0
        if rt.state_of(int(sink))["got"] == 32 * 4:
            break
    assert rt.state_of(int(sink))["got"] == 32 * 4
    assert saw_dspill, "receiver overflow never engaged the delivery spill"
    rt.run(max_steps=50)
    assert not np.asarray(rt.state.muted).any()
    assert rt.counter("dspill_count") == 0


def test_spill_overflow_aborts_on_mesh():
    # spill_cap far below the one-tick reject volume: the bounded spill
    # exhausts and the runtime must fail loudly (SpillOverflowError),
    # not drop messages.
    opts = RuntimeOptions(mailbox_cap=4, batch=1, max_sends=2, msg_words=2,
                          mesh_shards=4, spill_cap=4, inject_slots=256,
                          overload_threshold=10.0)  # mute never triggers
    rt = Runtime(opts)
    rt.declare(Burst, 64).declare(Sink, 4)
    rt.start()
    sink = rt.spawn(Sink)
    srcs = rt.spawn_many(Burst, 64, out=int(sink), left=8)
    for s in srcs:
        rt.send(int(s), Burst.go, 0)
    with pytest.raises(SpillOverflowError):
        rt.run(max_steps=200)


def test_mesh_serialise_roundtrip_under_pressure(tmp_path):
    # Snapshot mid-pressure (spills populated, senders muted), restore
    # into a fresh runtime, and finish: nothing lost, nothing doubled.
    from ponyc_tpu import serialise

    opts = RuntimeOptions(mailbox_cap=4, batch=1, max_sends=2, msg_words=2,
                          mesh_shards=4, spill_cap=256, inject_slots=64)
    rt, sink, srcs = _run_pressure(opts, n_src=48, items=4)
    for _ in range(6):
        rt.run(max_steps=1)
    got_mid = rt.state_of(int(sink))["got"]
    assert got_mid < 48 * 4
    path = str(tmp_path / "mesh_pressure.npz")
    serialise.save(rt, path)

    rt2 = Runtime(opts)
    rt2.declare(Burst, 48).declare(Sink, 4)
    rt2.start()
    serialise.restore(rt2, path)
    assert rt2.state_of(int(sink))["got"] == got_mid
    rt2.run(max_steps=400)
    assert rt2.state_of(int(sink))["got"] == 48 * 4
    assert not np.asarray(rt2.state.muted).any()


def test_programmatic_backpressure_on_mesh():
    """apply_backpressure on a sharded world: senders on EVERY shard mute
    when their sends target the pressured (remote) receiver, and release
    after the host clears it (the pressured column shards with the actor
    axis). mailbox_cap is large enough that occupancy muting
    (overload_occ) can never fire — any mute is the programmatic path."""
    opts = RuntimeOptions(mailbox_cap=64, batch=4, max_sends=2,
                          msg_words=2, mesh_shards=4, spill_cap=512,
                          inject_slots=64, quiesce_interval=1)
    rt, sink, srcs = _run_pressure(opts, n_src=16, items=40)
    inj = rt._drain_inject()
    st, aux = rt._step(rt.state, *inj)
    inj = rt._empty_inject
    st, aux = rt._step(st, *inj)
    rt.state = st
    assert not np.asarray(st.muted).any(), "no pressure yet"

    rt.apply_backpressure([int(sink)])
    st = rt.state
    for _ in range(3):
        st, aux = rt._step(st, *inj)
    rt.state = st
    muted = np.asarray(st.muted)
    occ = int(np.asarray(st.tail - st.head)[int(sink)])
    assert muted.any(), "pressured receiver must mute senders"
    assert occ <= rt.opts.overload_occ, \
        "mute was pressure-driven, not occupancy-driven"
    # The pressure signal must cross the mesh: some muted sender lives on
    # a different shard than the sink (ids are shard-major: shard = id //
    # n_local).
    n_local = rt.program.n_local
    sink_shard = int(sink) // n_local
    muted_shards = set(int(i) // n_local for i in np.nonzero(muted)[0])
    assert muted_shards - {sink_shard}, \
        f"only shard {sink_shard} muted: {muted_shards}"

    rt.release_backpressure([int(sink)])
    assert rt.run(max_steps=4000) == 0
    assert rt.state_of(int(sink))["got"] == 16 * 40
    assert not np.asarray(rt.state.muted).any()
