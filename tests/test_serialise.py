"""Checkpoint/resume tests (≙ the serialise subsystem, gc/serialise.c,
promoted to whole-world snapshots; reference parity check = the
round-trip guarantees packages/serialise tests assert)."""

import numpy as np
import pytest

from ponyc_tpu import (I32, Ref, Runtime, RuntimeOptions, actor, behaviour,
                       serialise)
from ponyc_tpu.models import ring


def _opts(**kw):
    base = dict(mailbox_cap=8, batch=1, max_sends=1, msg_words=1,
                spill_cap=64, inject_slots=8)
    base.update(kw)
    return RuntimeOptions(**base)


def _build_ring(n, opts):
    rt = Runtime(opts).declare(ring.RingNode, n).start()
    ids = rt.spawn_many(ring.RingNode, n)
    rt.set_fields(ring.RingNode, ids, next_ref=np.roll(ids, -1))
    return rt, ids


def test_snapshot_mid_flight_resume_matches(tmp_path):
    # Run A: 300 hops straight through.
    rt_a, ids_a = _build_ring(8, _opts())
    rt_a.send(int(ids_a[0]), ring.RingNode.token, 300)
    rt_a.run()
    want = rt_a.cohort_state(ring.RingNode)["passes"]

    # Run B: same program, checkpointed mid-flight, resumed elsewhere.
    rt_b, ids_b = _build_ring(8, _opts())
    rt_b.send(int(ids_b[0]), ring.RingNode.token, 300)
    rt_b.run(max_steps=57)                       # part-way: token in flight
    serialise.save(rt_b, str(tmp_path / "w.npz"))

    rt_c, _ = _build_ring(8, _opts())
    serialise.restore(rt_c, str(tmp_path / "w.npz"))
    assert rt_c.steps_run == rt_b.steps_run
    rt_c.run()
    got = rt_c.cohort_state(ring.RingNode)["passes"]
    np.testing.assert_array_equal(got, want)


def test_snapshot_preserves_queued_host_sends(tmp_path):
    rt, ids = _build_ring(4, _opts())
    rt.send(int(ids[0]), ring.RingNode.token, 7)   # still in _inject_q
    serialise.save(rt, str(tmp_path / "w.npz"))

    rt2, _ = _build_ring(4, _opts())
    serialise.restore(rt2, str(tmp_path / "w.npz"))
    assert len(rt2._inject_q) == 1
    rt2.run()
    assert rt2.cohort_state(ring.RingNode)["passes"].sum() == 7


def test_fingerprint_rejects_different_program(tmp_path):
    rt, _ = _build_ring(4, _opts())
    serialise.save(rt, str(tmp_path / "w.npz"))

    @actor
    class Other:
        x: I32

        @behaviour
        def go(self, st, v: I32):
            return st

    rt2 = Runtime(_opts()).declare(Other, 4).start()
    with pytest.raises(serialise.FingerprintMismatch):
        serialise.restore(rt2, str(tmp_path / "w.npz"))


def test_geometry_change_relayouts_since_v3(tmp_path):
    """Since format v3 a geometry difference is NOT a mismatch: the
    restore re-lays-out the SoA arrays (ISSUE 8 tentpole; the deep
    differential coverage lives in tests/test_durability.py). Mid-
    flight token crosses a mailbox_cap change and still completes to
    the synchronous oracle."""
    rt_a, ids_a = _build_ring(8, _opts())
    rt_a.send(int(ids_a[0]), ring.RingNode.token, 300)
    rt_a.run()
    want = rt_a.cohort_state(ring.RingNode)["passes"]

    rt, ids = _build_ring(8, _opts())
    rt.send(int(ids[0]), ring.RingNode.token, 300)
    rt.run(max_steps=57)                       # token in flight
    serialise.save(rt, str(tmp_path / "w.npz"))
    rt2, _ = _build_ring(8, _opts(mailbox_cap=16, spill_cap=128))
    serialise.restore(rt2, str(tmp_path / "w.npz"))
    assert rt2.steps_run == rt.steps_run
    rt2.run()
    np.testing.assert_array_equal(
        rt2.cohort_state(ring.RingNode)["passes"], want)


@pytest.mark.parametrize("target", ["same-layout", "relayout",
                                    "relayout-mesh2"])
def test_older_phase_lanes_restore_with_new_lane_at_zero(tmp_path, target):
    """A snapshot written at analysis>=1 when state.PHASE_NAMES had only
    its first four lanes still restores — same layout and re-laid-out —
    with the counted lanes kept and the `rebuild` lane at zero."""
    from ponyc_tpu.runtime.state import PHASE_NAMES
    rt, ids = _build_ring(8, _opts(analysis=1))
    rt.send(int(ids[0]), ring.RingNode.token, 300)
    rt.run(max_steps=40)
    assert rt.profile()["phases"]["rebuild"] > 0
    header, arrays = serialise.capture(rt)
    old = arrays["st.phase_cost"].reshape(1, len(PHASE_NAMES))[:, :4]
    assert PHASE_NAMES[4:] == ("rebuild",)
    arrays["st.phase_cost"] = np.ascontiguousarray(old.ravel())
    path = str(tmp_path / "four-lanes.npz")
    serialise.write_snapshot(header, arrays, path)

    okw = {"same-layout": {}, "relayout": dict(mailbox_cap=16),
           "relayout-mesh2": dict(mesh_shards=2)}[target]
    rt2, _ = _build_ring(8, _opts(analysis=1, **okw))
    serialise.restore(rt2, path)
    want = dict(rt.profile()["phases"], rebuild=0)
    assert rt2.profile()["phases"] == want
    rt2.run()
    assert rt2.profile()["phases"]["delivery"] == 300
    assert rt2.profile()["phases"]["rebuild"] > 0


def test_host_actor_state_round_trips(tmp_path):
    @actor
    class Keeper:
        HOST = True
        total: I32

        @behaviour
        def add(self, st, v: I32):
            st["total"] = st["total"] + v
            return st

    def build():
        return Runtime(_opts(msg_words=2, batch=4)).declare(
            Keeper, 1).start()

    rt = build()
    kid = rt.spawn(Keeper)
    rt.send(kid, Keeper.add, 5)
    rt.run(max_steps=50)
    assert rt.state_of(kid)["total"] == 5
    serialise.save(rt, str(tmp_path / "w.npz"))

    rt2 = build()
    rt2.spawn(Keeper)
    serialise.restore(rt2, str(tmp_path / "w.npz"))
    assert rt2.state_of(kid)["total"] == 5
    rt2.send(kid, Keeper.add, 3)
    rt2.run(max_steps=50)
    assert rt2.state_of(kid)["total"] == 8


def test_snapshot_under_mute_pressure_resumes_to_oracle(tmp_path):
    """Checkpoint taken MID-DEADLOCK-PRESSURE (muted senders, live spill,
    aged mute counters) and restored into a fresh runtime must finish to
    the exact oracle state — proving every backpressure column
    (muted/mute_refs/mute_age/mute_ovf/pressured/spills/plan cache)
    round-trips (≙ the serialise subsystem being the checkpoint/resume
    building block, gc/serialise.c; SURVEY.md §5)."""
    import sys as _sys
    _sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent))
    import numpy as np
    import test_differential as td

    from ponyc_tpu import Runtime, RuntimeOptions
    from ponyc_tpu import serialise

    n_w, n_s = 24, 8
    w_nxt, s_w, s_s, seeds = td._case(23, n_w, n_s)   # the deadlock seed
    want = td.oracle(n_w, n_s, w_nxt, s_w, s_s, seeds)

    def build():
        rt = Runtime(RuntimeOptions(mailbox_cap=2, batch=1, msg_words=1,
                                    max_sends=2, spill_cap=512,
                                    inject_slots=16))
        rt.declare(td.Walker, n_w).declare(td.Splitter, n_s)
        rt.start()
        return rt

    rt = build()
    wids = rt.spawn_many(td.Walker, n_w)
    sids = rt.spawn_many(td.Splitter, n_s)
    rt.set_fields(td.Walker, wids, nxt=wids[np.asarray(w_nxt)])
    rt.set_fields(td.Splitter, sids, w_ref=wids[np.asarray(s_w)],
                  s_ref=sids[np.asarray(s_s)])
    for kind, i, v in seeds:
        rt.send(int(wids[i] if kind == "w" else sids[i]),
                td.Walker.step if kind == "w" else td.Splitter.burst, v)
    # run into the thick of it: mutes + spill live at snapshot time
    inj = rt._drain_inject()
    st, aux = rt._step(rt.state, *inj)
    inj = rt._empty_inject
    for _ in range(7):
        st, aux = rt._step(st, *inj)
    rt.state = st
    assert np.asarray(st.muted).any(), "snapshot must land mid-pressure"
    path = str(tmp_path / "mid_pressure.npz")
    serialise.save(rt, path)

    rt2 = build()                     # fresh runtime, same program
    serialise.restore(rt2, path)
    assert np.asarray(rt2.state.muted).any()
    assert rt2.run(max_steps=50_000) == 0
    wst = rt2.cohort_state(td.Walker)
    sst = rt2.cohort_state(td.Splitter)
    assert (wst["acc"].astype(np.int64) == want[0]).all()
    assert (wst["hits"].astype(np.int64) == want[1]).all()
    assert (sst["acc"].astype(np.int64) == want[2]).all()
    assert not np.asarray(rt2.state.muted).any()
