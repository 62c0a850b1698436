"""Durable worlds (ISSUE 8): crash-safe checkpointing, geometry-changing
restore, supervised auto-recovery under fault injection.

Four layers under test, matching the tentpole:
1. the checkpoint ring — cadence-driven crash-consistent snapshots with
   per-array + header checksums, fsync + atomic rename, bounded
   retention, and corruption that is DETECTED (coded errors), never
   silently restored;
2. geometry-changing restore — the differential/FIFO corpus crossing a
   snapshot boundary into grown/shrunk capacity, changed mailbox/spill
   rings and a different mesh shard count, with per-edge FIFO, counters
   and quiescence equal to the synchronous oracle;
3. the supervisor (supervise.py) — coded fatals and SIGKILL answered by
   restore-newest-intact + resume, bounded retries, and the
   deterministic-poison refusal;
4. zero-cost-when-off: checkpoint options never touch the step jaxpr.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from ponyc_tpu import Runtime, RuntimeOptions, serialise, supervise, testing
from ponyc_tpu.errors import ERROR_CODES, PonyError
from ponyc_tpu.models import ring

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _opts(**kw):
    base = dict(mailbox_cap=8, batch=1, max_sends=1, msg_words=1,
                spill_cap=64, inject_slots=8)
    base.update(kw)
    return RuntimeOptions(**base)


# ======================================================= checkpoint ring

def test_periodic_checkpoint_ring_bounded_and_restorable(tmp_path):
    """The run loop writes cadence checkpoints without changing the
    run's observable outcome; the ring stays bounded by
    checkpoint_keep; the newest restores into a fresh runtime with the
    exact final world."""
    hops = 4_000
    rt_off, ids_off = ring.build(16, _opts())   # checkpointing off
    rt_off.send(int(ids_off[0]), ring.RingNode.token, hops)
    assert rt_off.run() == 0
    want = np.asarray(rt_off.cohort_state(ring.RingNode)["passes"])

    prefix = str(tmp_path / "ring")
    opts = _opts(checkpoint_every_s=0.01, checkpoint_path=prefix,
                 checkpoint_keep=3)
    rt, ids = ring.build(16, opts)
    rt.send(int(ids[0]), ring.RingNode.token, hops)
    assert rt.run() == 0
    stats = rt.checkpoint_stats()
    assert stats["checkpoints"] >= 2          # cadence fired mid-run
    assert stats["failures"] == 0
    # capture only READS the world: outcome equals the unarmed run
    np.testing.assert_array_equal(
        np.asarray(rt.cohort_state(ring.RingNode)["passes"]), want)
    rt.stop()                                  # + final fast-start ckpt
    files = serialise.list_checkpoints(prefix)
    assert files and len(files) <= 3           # ring rotated
    seqs = [s for s, _ in files]
    assert seqs == sorted(seqs)
    newest = serialise.newest_intact(prefix)
    assert newest == files[-1][1]

    rt2, _ = ring.build(16, opts)
    serialise.restore(rt2, newest)
    np.testing.assert_array_equal(
        np.asarray(rt2.cohort_state(ring.RingNode)["passes"]), want)
    assert rt2.steps_run == rt.steps_run
    rt2.stop()


def test_checkpoint_options_keep_jaxpr_identity():
    """ACCEPTANCE (PR-4 style): the whole durability layer is host-side
    — with checkpointing configured the step jaxpr is BIT-IDENTICAL to
    the default build."""
    import jax
    import jax.numpy as jnp

    from ponyc_tpu.program import Program
    from ponyc_tpu.runtime import engine
    from ponyc_tpu.runtime.state import init_state

    def build(**kw):
        opts = _opts(analysis=0, **kw)
        prog = Program(opts)
        prog.declare(ring.RingNode, 8)
        prog.finalize()
        st = init_state(prog, opts)
        step = engine.build_step(prog, opts)
        k = opts.inject_slots
        inj_t = jnp.full((k,), -1, jnp.int32)
        inj_w = jnp.zeros((1 + opts.msg_words, k), jnp.int32)
        return str(jax.make_jaxpr(step)(st, inj_t, inj_w))

    baseline = build()
    assert build(checkpoint_every_s=0.5, checkpoint_path="/tmp/x",
                 checkpoint_keep=7) == baseline


def test_checkpoint_option_validation():
    with pytest.raises(ValueError, match="checkpoint_every_s"):
        RuntimeOptions(checkpoint_every_s=0.0)
    with pytest.raises(ValueError, match="checkpoint_keep"):
        RuntimeOptions(checkpoint_keep=0)


# =============================================== corruption detection

def test_corruption_detected_and_fallen_back_past(tmp_path):
    """Truncation and bit flips surface as the coded
    SnapshotCorruptError (code 8), never a raw numpy/zlib traceback;
    newest_intact() walks the ring past them (one shared source/target
    runtime pair — a rejected restore touches no state)."""
    path = str(tmp_path / "w.npz")
    rt, ids = ring.build(8, _opts())
    rt.send(int(ids[0]), ring.RingNode.token, 50)
    rt.run()
    serialise.save(rt, path)
    serialise.verify_snapshot(path)            # intact baseline

    rt2, _ = ring.build(8, _opts())
    for mode in ("truncate", "bitflip"):
        dmg = str(tmp_path / f"{mode}.npz")
        serialise.save(rt, dmg)
        testing.corrupt_snapshot(dmg, mode)
        with pytest.raises(serialise.SnapshotCorruptError):
            serialise.restore(rt2, dmg)
        assert serialise.SnapshotCorruptError.code \
            == ERROR_CODES["SnapshotCorruptError"] == 8

    # ring fallback: corrupt files are skipped newest-first
    prefix = str(tmp_path / "r")
    for seq in range(3):
        serialise.save(rt, serialise.checkpoint_file(prefix, seq))
    files = serialise.list_checkpoints(prefix)
    assert [s for s, _ in files] == [0, 1, 2]
    testing.corrupt_snapshot(files[-1][1], "truncate")
    assert serialise.newest_intact(prefix) == files[1][1]
    testing.corrupt_snapshot(files[1][1], "bitflip")
    assert serialise.newest_intact(prefix) == files[0][1]
    testing.corrupt_snapshot(files[0][1], "truncate")
    assert serialise.newest_intact(prefix) is None
    # the intact one still restores on the shared target
    serialise.restore(rt2, path)


# ================================================== format version gate

def test_unknown_future_format_is_loud(tmp_path):
    path = str(tmp_path / "future.npz")
    serialise.write_snapshot({"format": 99}, {}, path)
    # restore() and verify_snapshot() share the gate (_load_raw), so
    # the verify-side assertion covers both without building a runtime
    with pytest.raises(serialise.SnapshotFormatError):
        serialise.verify_snapshot(path)
    assert serialise.SnapshotFormatError.code == 9
    # the format error is still a FingerprintMismatch for old callers
    assert issubclass(serialise.SnapshotFormatError,
                      serialise.FingerprintMismatch)


def _save_legacy_v2(rt, path):
    """The exact PR-6-era v2 writer (index-named leaves, geometry-full
    fingerprint, no checksums) — the compatibility corpus."""
    import io
    import jax
    arrays = {}
    flat, _ = jax.tree_util.tree_flatten(rt.state)
    for i, leaf in enumerate(flat):
        arrays[f"state_{i}"] = np.asarray(jax.device_get(leaf))
    inject = list(rt._inject_q)
    arrays["inject_tgt"] = np.asarray([t for t, _ in inject], np.int32)
    arrays["inject_words"] = (np.stack([w for _, w in inject]) if inject
                              else np.zeros((0, 1 + rt.opts.msg_words),
                                            np.int32))
    fast = list(rt._host_fast_q)
    arrays["fastq_tgt"] = np.asarray([e[0] for e in fast], np.int32)
    arrays["fastq_words"] = (np.stack([e[1] for e in fast]) if fast
                             else np.zeros((0, 1 + rt.opts.msg_words),
                                           np.int32))
    header = {
        "format": 2,
        "fingerprint": serialise.fingerprint(rt.program, geometry=True),
        "opts": {}, "n_state_leaves": len(flat),
        "free": rt._free,
        "host_state": {str(k): v for k, v in rt._host_state.items()},
        "totals": dict(rt.totals), "last_counters": rt._last_counters,
        "steps_run": rt.steps_run, "exit_code": rt._exit_code,
        "noisy": rt._noisy, "host_blobs": sorted(rt._host_blobs),
    }
    buf = io.BytesIO()
    np.savez_compressed(buf, header=np.frombuffer(
        json.dumps(header).encode(), np.uint8), **arrays)
    open(path, "wb").write(buf.getvalue())


def test_v2_snapshot_still_restores_same_geometry(tmp_path):
    """The FORMAT_VERSION gate keeps accepting v2 (legacy index path,
    exact geometry only); a geometry change on a v2 snapshot stays a
    loud mismatch (legacy snapshots cannot re-layout)."""
    path = str(tmp_path / "v2.npz")
    rt, ids = ring.build(8, _opts())
    rt.send(int(ids[0]), ring.RingNode.token, 120)
    rt.run(max_steps=37)
    _save_legacy_v2(rt, path)
    rt.run()
    want = np.asarray(rt.cohort_state(ring.RingNode)["passes"])

    rt2, _ = ring.build(8, _opts())
    serialise.restore(rt2, path)
    rt2.run()
    np.testing.assert_array_equal(
        np.asarray(rt2.cohort_state(ring.RingNode)["passes"]), want)

    rt3, _ = ring.build(8, _opts(mailbox_cap=16))
    with pytest.raises(serialise.FingerprintMismatch):
        serialise.restore(rt3, path)


def test_v3_restore_keeps_telemetry(tmp_path):
    """Snapshot format v3 carries the PR 4/7 state (profiler lanes,
    error counters) — a restored world keeps its telemetry."""
    path = str(tmp_path / "t.npz")
    rt, ids = ring.build(8, _opts(analysis=1,
                                  analysis_path=str(tmp_path / "a.csv")))
    rt.send(int(ids[0]), ring.RingNode.token, 300)
    rt.run()
    rt._error_counts[("PonyError", 1)] += 2
    prof = rt.profile()
    serialise.save(rt, path)
    rt.stop()

    rt2, _ = ring.build(8, _opts(analysis=1,
                                 analysis_path=str(tmp_path / "b.csv")))
    serialise.restore(rt2, path)
    prof2 = rt2.profile()
    assert prof2["behaviours"] == prof["behaviours"]
    assert prof2["totals"] == prof["totals"]
    assert rt2._error_counts[("PonyError", 1)] == 2
    rt2.stop()


def test_pack_roundtrip_boundary_values_np():
    """The packed snapshot's codec (serialise.pack_words_np) is
    lossless on every int32, the int16 edges and the sentinel's own
    value among them."""
    boundary = np.array(
        [0, 1, -1, 32767, -32767, -32768, 32768, -32769, 65535, -65536,
         2**31 - 1, -(2**31), 12345, -12345], np.int32)
    lo16, esc32 = serialise.pack_words_np(boundary)
    assert lo16.dtype == np.int16 and esc32.dtype == np.int32
    np.testing.assert_array_equal(
        serialise.unpack_words_np(lo16, esc32), boundary)
    # -32768 collides with the sentinel: it MUST ride the escape plane
    # even though it fits int16 (the one value the naive range check
    # gets wrong).
    i = int(np.where(boundary == -32768)[0][0])
    assert lo16[i] == serialise.ESC and esc32[i] == -32768
    # In-range values leave the escape plane zero (what compresses).
    j = int(np.where(boundary == 12345)[0][0])
    assert lo16[j] == 12345 and esc32[j] == 0


def test_packed_snapshot_cross_dtype_restore(tmp_path):
    """save(packed=True)
    stores the word tables as int16 lanes + an int32 escape plane; a
    mid-flight world whose payloads do NOT fit int16 must restore
    bit-identically to the plain-int32 snapshot of the same instant,
    and a packed snapshot missing its escape plane must be a coded
    corruption, never a silent zero-fill."""
    from ponyc_tpu.models import ubench
    okw = dict(mailbox_cap=4, batch=2, max_sends=1, spill_cap=64,
               inject_slots=8)
    rt, ids = ubench.build(8, _opts(**okw), pings=2)
    # Payloads past the int16 edge: every in-flight hops counter rides
    # the escape plane across the save/restore boundary.
    ubench.seed_all(rt, ids, hops=70_000, pings=2)
    rt.run(max_steps=6)
    p_packed = str(tmp_path / "packed.npz")
    p_plain = str(tmp_path / "plain.npz")
    serialise.save(rt, p_packed, packed=True)
    serialise.save(rt, p_plain)

    with np.load(p_packed, allow_pickle=False) as z:
        lo = [n for n in z.files if n.endswith(".lo16")]
        assert lo, "packed snapshot stored no narrow planes"
        assert all(z[n].dtype == np.int16 for n in lo)
        esc = [n[:-len(".lo16")] + ".esc32" for n in lo]
        assert all(n in z.files and z[n].dtype == np.int32 for n in esc)
        # the escape plane genuinely carries the wide payloads
        assert any(np.any(np.asarray(z[n]) != 0) for n in esc)

    restored = {}
    for path in (p_packed, p_plain):
        rt2, _ = ubench.build(8, _opts(**okw), pings=2)
        serialise.restore(rt2, path)
        restored[path] = {
            k: np.asarray(v) for k, v in
            serialise._named_state_arrays(rt2.state).items()}
    for k, v in restored[p_plain].items():
        np.testing.assert_array_equal(restored[p_packed][k], v, err_msg=k)

    # A torn packed snapshot (escape plane gone) is DETECTED:
    header, arrays = serialise.capture(rt)
    packed = serialise.pack_snapshot_arrays(arrays)
    victim = next(n for n in packed if n.endswith(".esc32"))
    del packed[victim]
    p_torn = str(tmp_path / "torn.npz")
    serialise.write_snapshot(header, packed, p_torn)
    rt3, _ = ubench.build(8, _opts(**okw), pings=2)
    with pytest.raises(serialise.SnapshotCorruptError):
        serialise.restore(rt3, p_torn)


# ============================================= geometry-changing restore

def test_grown_capacity_restore_spawns_into_new_room(tmp_path):
    """Restore into a BIGGER cohort: old actors keep their slots, the
    grown slots are immediately spawnable."""
    path = str(tmp_path / "w.npz")
    rt, ids = ring.build(8, _opts())
    rt.send(int(ids[0]), ring.RingNode.token, 100)
    rt.run(max_steps=17)
    serialise.save(rt, path)

    rt2 = Runtime(_opts()).declare(ring.RingNode, 16).start()
    serialise.restore(rt2, path)
    fresh = rt2.spawn_many(ring.RingNode, 8)     # the grown room
    assert len(fresh) == 8
    rt2.run()
    passes = np.asarray(rt2.cohort_state(ring.RingNode)["passes"])
    assert passes[:8].sum() == 100 and passes[8:].sum() == 0


def test_shrunk_capacity_live_rejects_dead_tail_accepts(tmp_path):
    """Shrinking below a LIVE occupant is a loud SnapshotGeometryError;
    shrinking away a never-spawned tail restores fine (one shared
    4-slot target runtime serves both verdicts — a rejected restore
    touches no state)."""
    live8 = str(tmp_path / "live8.npz")
    rt, _ids = ring.build(8, _opts())             # 8 live actors
    serialise.save(rt, live8)
    dead_tail = str(tmp_path / "dead_tail.npz")
    rt_b = Runtime(_opts()).declare(ring.RingNode, 16).start()
    ids = rt_b.spawn_many(ring.RingNode, 4)       # slots 4..15 never live
    rt_b.set_fields(ring.RingNode, ids, next_ref=np.roll(ids, -1))
    rt_b.send(int(ids[0]), ring.RingNode.token, 60)
    rt_b.run(max_steps=11)
    serialise.save(rt_b, dead_tail)

    rt2 = Runtime(_opts()).declare(ring.RingNode, 4).start()
    with pytest.raises(serialise.SnapshotGeometryError):
        serialise.restore(rt2, live8)
    assert serialise.SnapshotGeometryError.code == 10
    serialise.restore(rt2, dead_tail)
    rt2.run()
    assert np.asarray(
        rt2.cohort_state(ring.RingNode)["passes"]).sum() == 60


def test_mailbox_too_deep_for_new_ring_rejected(tmp_path):
    path = str(tmp_path / "w.npz")
    rt, ids = ring.build(8, _opts())
    for _ in range(4):                 # occupancy 4 on one mailbox
        rt.bulk_send(ids[:1], ring.RingNode.token, np.asarray([0]))
    serialise.save(rt, path)
    rt2, _ = ring.build(8, _opts(mailbox_cap=2))
    with pytest.raises(serialise.SnapshotGeometryError,
                       match="mailbox"):
        serialise.restore(rt2, path)
    rt3, _ = ring.build(8, _opts(mailbox_cap=4))   # exactly fits
    # restore(opts=...) spells the intended target geometry at the
    # restore site: it must match what the runtime was started with
    with pytest.raises(ValueError, match="different geometry"):
        serialise.restore(rt3, path, opts=_opts())
    serialise.restore(rt3, path, opts=_opts(mailbox_cap=4))


def test_blob_pool_relayout(tmp_path):
    """Host-owned blobs cross a blob_slots change: handles re-encode,
    contents and ownership survive; live blobs into a pool-less target
    reject."""
    path = str(tmp_path / "w.npz")
    opts = _opts(blob_slots=8, blob_words=4)
    rt, _ids = ring.build(8, opts)
    h1 = rt.blob_store([1, 2, 3])
    h2 = rt.blob_store_str("hi")
    serialise.save(rt, path)

    rt2, _ = ring.build(8, _opts(blob_slots=16, blob_words=8))
    serialise.restore(rt2, path)
    assert len(rt2._host_blobs) == 2
    fetched = {tuple(rt2.blob_fetch(h).tolist())
               for h in rt2._host_blobs}
    assert (1, 2, 3) in fetched
    hs = [h for h in rt2._host_blobs
          if tuple(rt2.blob_fetch(h).tolist()) != (1, 2, 3)]
    assert rt2.blob_fetch_str(hs[0]) == "hi"
    assert rt2.blobs_in_use == 2

    rt3, _ = ring.build(8, _opts())                # blob_slots=0
    with pytest.raises(serialise.SnapshotGeometryError, match="blob"):
        serialise.restore(rt3, path)
    del h1, h2


def _mid_pressure_snapshot(tmp_path):
    """Walker/Splitter deadlock seed run into live backpressure mutes,
    snapshotted — the differential source world, shared by the tier-1
    grown-geometry crossing and the slow mesh crossing. Returns
    (path, oracle, n_w, n_s)."""
    import test_differential as td

    n_w, n_s = 24, 8
    w_nxt, s_w, s_s, seeds = td._case(23, n_w, n_s)  # the deadlock seed
    want = td.oracle(n_w, n_s, w_nxt, s_w, s_s, seeds)
    rt = Runtime(RuntimeOptions(msg_words=1, mailbox_cap=2, batch=1,
                                max_sends=2, spill_cap=512,
                                inject_slots=16))
    rt.declare(td.Walker, n_w).declare(td.Splitter, n_s)
    rt.start()
    wids = rt.spawn_many(td.Walker, n_w)
    sids = rt.spawn_many(td.Splitter, n_s)
    rt.set_fields(td.Walker, wids, nxt=wids[np.asarray(w_nxt)])
    rt.set_fields(td.Splitter, sids, w_ref=wids[np.asarray(s_w)],
                  s_ref=sids[np.asarray(s_s)])
    for kind, i, v in seeds:
        rt.send(int(wids[i] if kind == "w" else sids[i]),
                td.Walker.step if kind == "w" else td.Splitter.burst, v)
    # into the thick of it: backpressure mutes live at snapshot time
    inj = rt._drain_inject()
    st, _aux = rt._step(rt.state, *inj)
    for _ in range(7):
        st, _aux = rt._step(st, *rt._empty_inject)
    rt.state = st
    assert np.asarray(st.muted).any(), "snapshot must land mid-pressure"
    path = str(tmp_path / "midp.npz")
    serialise.save(rt, path)
    return path, want, n_w, n_s


def _assert_crossing(path, want, n_w, n_s, okw, cap_w, cap_s):
    import test_differential as td
    rt2 = Runtime(RuntimeOptions(msg_words=1, **okw))
    rt2.declare(td.Walker, cap_w).declare(td.Splitter, cap_s)
    rt2.start()
    serialise.restore(rt2, path)
    assert rt2.run(max_steps=50_000) == 0
    wst = rt2.cohort_state(td.Walker)
    sst = rt2.cohort_state(td.Splitter)
    assert (wst["acc"][:n_w].astype(np.int64) == want[0]).all()
    assert (wst["hits"][:n_w].astype(np.int64) == want[1]).all()
    assert (sst["acc"][:n_s].astype(np.int64) == want[2]).all()
    assert not np.asarray(rt2.state.muted).any()


def test_differential_corpus_crosses_grown_restore(tmp_path):
    """ROADMAP item 5's named gap: the differential corpus crossing a
    snapshot/restore boundary mid-workload into a GROWN geometry,
    asserting counters and quiescence equal the sequential oracle.
    (The SAME-geometry crossing is pinned by test_serialise.
    test_snapshot_under_mute_pressure_resumes_to_oracle.)"""
    path, want, n_w, n_s = _mid_pressure_snapshot(tmp_path)
    _assert_crossing(path, want, n_w, n_s,
                     dict(mailbox_cap=4, batch=1, max_sends=2,
                          spill_cap=256, inject_slots=16),
                     n_w + 16, n_s + 8)


@pytest.mark.slow
def test_differential_corpus_crosses_mesh_restore(tmp_path):
    """The same mid-pressure world restored ONTO A 2-SHARD MESH (and
    the routing/collective machinery under it) — the elastic-resize
    direction of ROADMAP items 1/5."""
    path, want, n_w, n_s = _mid_pressure_snapshot(tmp_path)
    _assert_crossing(path, want, n_w, n_s,
                     dict(mailbox_cap=4, batch=1, max_sends=2,
                          spill_cap=1024, inject_slots=32,
                          mesh_shards=2, quiesce_interval=2),
                     n_w, n_s)


def test_per_edge_fifo_crosses_restore_boundary(tmp_path):
    """Order-SENSITIVE crossing: the on-device per-edge FIFO detector
    (test_fifo harness) runs a tiny-cap world into mid-stream spill
    pressure, snapshots, restores into a grown geometry and finishes —
    zero violations and full completeness prove the parked-spill →
    inject-lane conversion preserves causal order exactly."""
    import test_fifo as tf

    n_cons, items = 4, 40
    n_prod, e1, e2 = tf._wire(101, n_cons)
    src = RuntimeOptions(msg_words=2, mailbox_cap=2, batch=1,
                         max_sends=3, spill_cap=2048, inject_slots=16)
    rt = Runtime(src)
    rt.declare(tf.Prod, n_prod).declare(tf.Cons, n_cons)
    rt.start()
    cids = rt.spawn_many(tf.Cons, n_cons,
                         last0=np.full(n_cons, -1, np.int32),
                         last1=np.full(n_cons, -1, np.int32),
                         last2=np.full(n_cons, -1, np.int32),
                         last3=np.full(n_cons, -1, np.int32))
    pids = rt.spawn_many(tf.Prod, n_prod,
                         c1=cids[np.asarray([c for c, _ in e1])],
                         c2=cids[np.asarray([c for c, _ in e2])],
                         slot1=np.asarray([s for _, s in e1], np.int32),
                         slot2=np.asarray([s for _, s in e2], np.int32))
    rt.bulk_send(pids, tf.Prod.produce, np.full(n_prod, items, np.int32))
    rt.run(max_steps=40)                      # mid-stream
    assert (np.asarray(rt.state.tail) - np.asarray(rt.state.head)).any()
    path = str(tmp_path / "fifo.npz")
    serialise.save(rt, path)

    # a same-geometry restore is a bit-identical array copy (cannot
    # reorder anything); the FIFO-critical path is the RELAYOUT —
    # grown rings + converted spill entries:
    for okw in (dict(msg_words=2, mailbox_cap=8, batch=2, max_sends=3,
                     spill_cap=512, inject_slots=32),):      # grown
        rt2 = Runtime(RuntimeOptions(**okw))
        rt2.declare(tf.Prod, n_prod + 4).declare(tf.Cons, n_cons + 2)
        rt2.start()
        serialise.restore(rt2, path)
        assert rt2.run(max_steps=500_000) == 0
        st = rt2.cohort_state(tf.Cons)
        bad = st["bad"][:n_cons]
        assert not bad.any(), f"FIFO violations after restore: {bad}"
        for s in range(tf.IN_SLOTS):
            assert (np.asarray(st[f"last{s}"][:n_cons])
                    == items - 1).all()
        assert (np.asarray(st["got"][:n_cons])
                == tf.IN_SLOTS * items).all()
        pst = rt2.cohort_state(tf.Prod)
        assert (np.asarray(pst["seq"][:n_prod]) == items).all()


# ======================================================= the supervisor

def test_supervisor_inprocess_recovers_coded_fatal(tmp_path):
    """A chaos-injected coded fatal mid-run: the supervisor restores
    the newest intact checkpoint into a fresh runtime and the workload
    completes with the unfaulted outcome."""
    prefix = str(tmp_path / "sup")
    attempt = {"n": 0}

    def build():
        attempt["n"] += 1
        rt, ids = ring.build(8, _opts(checkpoint_every_s=60.0,
                                      checkpoint_path=prefix))
        build.ids = ids
        if attempt["n"] == 1:
            testing.fatal_at_boundary(rt, boundary=3, code=42)
        return rt

    def seed(rt):
        rt.send(int(build.ids[0]), ring.RingNode.token, 400)
        rt.checkpoint()                 # the recovery floor

    sup = supervise.Supervisor(build, prefix=prefix, seed=seed,
                               retries=3, backoff_s=0.01)
    assert sup.run() == 0
    assert sup.restarts == 1
    assert sup.failures[0]["code"] == 42
    assert sup.restored_from is not None
    # unfaulted-outcome equality: a clean 400-hop walk over 8 nodes
    # lands exactly 50 passes per node (the analytic oracle); read the
    # recovered terminal world back from its final checkpoint.
    rt_chk, _ = ring.build(8, _opts(checkpoint_every_s=60.0,
                                    checkpoint_path=prefix))
    serialise.restore(rt_chk, serialise.newest_intact(prefix))
    np.testing.assert_array_equal(
        np.asarray(rt_chk.cohort_state(ring.RingNode)["passes"]),
        np.full(8, 50, np.int32))
    rt_chk.stop()


def test_supervisor_refuses_deterministic_poison(tmp_path):
    """The poison rule: the same code at the same world position twice
    in a row raises PoisonError instead of restart-looping."""
    prefix = str(tmp_path / "poison")

    def build():
        rt, ids = ring.build(8, _opts(quiesce_interval=4,
                                      pipeline=False))
        build.ids = ids
        testing.fatal_at_boundary(rt, boundary=1, code=13, every=True)
        return rt

    def seed(rt):
        rt.send(int(build.ids[0]), ring.RingNode.token, 400)

    sup = supervise.Supervisor(build, prefix=prefix, seed=seed,
                               retries=10, backoff_s=0.0)
    with pytest.raises(supervise.PoisonError) as ei:
        sup.run()
    assert ei.value.code == ERROR_CODES["PoisonError"] == 11
    assert len(sup.failures) == 2              # refused on the repeat
    assert sup.failures[0]["code"] == 13


def test_supervisor_noncoded_errors_are_not_swallowed(tmp_path):
    def build():
        rt, _ = ring.build(8, _opts())
        raise RuntimeError("builder exploded")

    sup = supervise.Supervisor(build, prefix=str(tmp_path / "x"))
    with pytest.raises(RuntimeError, match="builder exploded"):
        sup.run()
    with pytest.raises(ValueError):
        supervise.Supervisor(prefix="x")       # neither build nor argv


# ------------------------- subprocess acceptance (kill -> restore -> =)

ACCEPT_SCRIPT = """
import json, os, sys
sys.path.insert(0, {root!r})
from ponyc_tpu.platforms import force_cpu
force_cpu()
import numpy as np
from ponyc_tpu import I32, Ref, Runtime, RuntimeOptions, actor, behaviour
from ponyc_tpu import supervise, testing
from ponyc_tpu.errors import error_code

@actor
class Reporter:
    HOST = True
    n: I32

    @behaviour
    def report(self, st, v: I32):
        return {{**st, "n": st["n"] + v}}

@actor
class Node:
    nxt: Ref["Node"]
    rep: Ref["Reporter"]
    passes: I32

    MAX_SENDS = 2

    @behaviour
    def token(self, st, hops: I32):
        self.send(st["nxt"], Node.token, hops - 1, when=hops > 1)
        self.send(st["rep"], Reporter.report, 1, when=(hops % 128) == 0)
        self.exit(0, when=hops <= 1)
        return {{**st, "passes": st["passes"] + 1}}

MODE = {mode!r}
rt = Runtime(RuntimeOptions(
    mailbox_cap=8, batch=1, max_sends=2, msg_words=1, spill_cap=64,
    inject_slots=8, quiesce_interval=64,
    checkpoint_every_s=0.01, checkpoint_path={prefix!r},
    checkpoint_keep=4,
    watchdog_s=(0.6 if MODE == "wedge" else None),
    analysis_path={apath!r}))
rt.declare(Node, 16).declare(Reporter, 2)
rt.start()
restored = supervise.maybe_restore(rt)
if restored is None:
    ids = rt.spawn_many(Node, 16)
    rep = rt.spawn(Reporter)
    rt.set_fields(Node, ids, nxt=np.roll(ids, -1), rep=rep)
    rt.send(int(ids[0]), Node.token, {hops})
    rt.checkpoint()                    # deterministic recovery floor
    if MODE == "wedge":
        testing.wedge_behaviour(Reporter.report, at_dispatch=3,
                                sleep_s=600.0)
else:
    # faults are one-shot: the recovered child runs clean
    os.environ.pop("PONY_TPU_CHAOS", None)
    testing.chaos.reset()
try:
    code = rt.run()
except Exception as e:
    c = error_code(e)
    if c:
        sys.exit(c)                    # the coded-failure exit contract
    raise
passes = [int(x) for x in rt.cohort_state(Node)["passes"]]
reporter = int(sum(st.get("n", 0) for st in rt._host_state.values()))
rt.stop()
json.dump({{"exit": code, "passes": passes, "reporter": reporter,
           "restored": restored is not None}}, open({out!r}, "w"))
sys.exit(code)
"""


ACCEPT_HOPS = 3000
# One supervised child may run this long (a clean one takes ~5 s). The
# wedge sleeps 600 s and stays so: what ends the wedged child is its own
# watchdog (0.6 s), and where that cannot (the watchdog thread starved,
# a hang in stop() or in the checkpoint writer) the supervisor's kill
# at this bound does, as a failure that counts against `retries`.
ACCEPT_CHILD_S = 60.0


def _accept_script(tmp_path, mode):
    prefix = str(tmp_path / f"{mode}-ring")
    out = str(tmp_path / f"{mode}-out.json")
    code = ACCEPT_SCRIPT.format(
        root=ROOT, mode=mode, prefix=prefix, out=out, hops=ACCEPT_HOPS,
        apath=str(tmp_path / f"{mode}-an.csv"))
    path = str(tmp_path / f"{mode}.py")
    open(path, "w").write(code)
    return path, prefix, out


# the acceptance workload's actor types, mirrored in-process for the
# unfaulted oracle run (same structure as ACCEPT_SCRIPT's)
from ponyc_tpu import I32, Ref, actor, behaviour  # noqa: E402


@actor
class _Reporter:
    HOST = True
    n: I32

    @behaviour
    def report(self, st, v: I32):
        return {**st, "n": st["n"] + v}


@actor
class _Node:
    nxt: Ref["_Node"]
    rep: Ref["_Reporter"]
    passes: I32

    MAX_SENDS = 2

    @behaviour
    def token(self, st, hops: I32):
        self.send(st["nxt"], _Node.token, hops - 1, when=hops > 1)
        self.send(st["rep"], _Reporter.report, 1, when=(hops % 128) == 0)
        self.exit(0, when=hops <= 1)
        return {**st, "passes": st["passes"] + 1}


@pytest.fixture(scope="module")
def clean_baseline():
    """The unfaulted oracle run, in-process (deterministic outcome:
    the subprocess scripts run the structurally identical program)."""
    rt = Runtime(RuntimeOptions(
        mailbox_cap=8, batch=1, max_sends=2, msg_words=1, spill_cap=64,
        inject_slots=8, quiesce_interval=64))
    rt.declare(_Node, 16).declare(_Reporter, 2)
    rt.start()
    ids = rt.spawn_many(_Node, 16)
    rep = rt.spawn(_Reporter)
    rt.set_fields(_Node, ids, nxt=np.roll(ids, -1), rep=rep)
    rt.send(int(ids[0]), _Node.token, ACCEPT_HOPS)
    code = rt.run()
    return {
        "exit": code,
        "passes": [int(x) for x in rt.cohort_state(_Node)["passes"]],
        "reporter": int(sum(st.get("n", 0)
                            for st in rt._host_state.values())),
    }


def test_acceptance_wedged_run_supervised_to_completion(
        tmp_path, clean_baseline):
    """ACCEPTANCE: a wedged behaviour (watchdog code-7 stall) is
    restarted by the supervisor from the last intact checkpoint and
    completes the workload with results equal to the unfaulted run,
    within a seconds-scale deadline."""
    script, prefix, out = _accept_script(tmp_path, "wedge")
    sup = supervise.Supervisor(
        argv=[sys.executable, script], prefix=prefix, retries=3,
        backoff_s=0.05, child_timeout_s=ACCEPT_CHILD_S)
    t0 = time.monotonic()
    code = sup.run()
    elapsed = time.monotonic() - t0
    assert code == 0, sup.failures
    assert sup.restarts >= 1
    assert sup.failures[0]["code"] == ERROR_CODES["PonyStallError"] == 7
    assert sup.restored_from is not None
    assert elapsed < 120            # seconds-scale, not the 600s sleep
    got = json.load(open(out))
    assert got["restored"] is True
    assert got["exit"] == clean_baseline["exit"] == 0
    assert got["passes"] == clean_baseline["passes"]
    assert got["reporter"] == clean_baseline["reporter"]


def test_acceptance_sigkill_mid_flush_supervised_to_completion(
        tmp_path, clean_baseline):
    """ACCEPTANCE: the process is SIGKILLed MID-FLUSH inside a
    checkpoint write (the serialise.py chaos point). The torn write
    never surfaces (tmp + fsync + rename), the supervisor restores the
    newest intact ring snapshot, and the workload completes with the
    unfaulted outcomes."""
    script, prefix, out = _accept_script(tmp_path, "kill")
    env_before = os.environ.get("PONY_TPU_CHAOS")
    os.environ["PONY_TPU_CHAOS"] = "snapshot-mid-flush@3"
    try:
        sup = supervise.Supervisor(
            argv=[sys.executable, script], prefix=prefix, retries=5,
            backoff_s=0.05, child_timeout_s=ACCEPT_CHILD_S)
        code = sup.run()
    finally:
        if env_before is None:
            os.environ.pop("PONY_TPU_CHAOS", None)
        else:
            os.environ["PONY_TPU_CHAOS"] = env_before
    assert code == 0, sup.failures
    assert sup.restarts >= 1
    assert sup.failures[0]["code"] == -9       # SIGKILL
    # every surviving ring file is intact (the torn one never renamed)
    for _seq, f in serialise.list_checkpoints(prefix):
        serialise.verify_snapshot(f)
    got = json.load(open(out))
    assert got["restored"] is True
    assert got["passes"] == clean_baseline["passes"]
    assert got["reporter"] == clean_baseline["reporter"]


# =========================================== observability integration

def test_postmortem_doctor_and_healthz_show_restore_point(tmp_path):
    from ponyc_tpu import flight, metrics
    prefix = str(tmp_path / "pm")
    rt, ids = ring.build(8, _opts(
        checkpoint_every_s=30.0, checkpoint_path=prefix,
        analysis_path=str(tmp_path / "an.csv")))
    hz = metrics.health(rt)
    assert hz["last_checkpoint_age_s"] is None   # nothing written yet
    rt.send(int(ids[0]), ring.RingNode.token, 50)
    rt.run()
    rt.checkpoint()
    rt._ckpt.flush()
    # /healthz: how stale a crash-restore would be
    hz = metrics.health(rt)
    assert hz["last_checkpoint_age_s"] is not None
    assert hz["last_checkpoint_age_s"] < 60
    assert hz["last_checkpoint_path"].startswith(prefix)
    # postmortem block + doctor verdict lead to the restore point
    pm = rt._flight.postmortem("manual")
    assert pm["checkpoint"]["path"]
    assert pm["checkpoint"]["verified"] is True
    assert "restorable from:" in flight.render_postmortem(pm)
    pm["errors"] = [{"class": "SpillOverflowError", "code": 2,
                     "count": 1}]
    line, _detail = flight.diagnose_postmortem(pm)
    assert line.startswith("CRASHED")
    assert "restorable from " + pm["checkpoint"]["path"] in line
    rt.stop()
    # checkpointing off -> the healthz field is None, not absent
    rt2, _ = ring.build(8, _opts())
    hz2 = metrics.health(rt2)
    assert "last_checkpoint_age_s" in hz2
    assert hz2["last_checkpoint_age_s"] is None


# ========================================================== CLI surface

def test_cli_snapshot_and_restore_verdicts(tmp_path, capsys):
    from ponyc_tpu.__main__ import main as cli_main
    path = str(tmp_path / "w.npz")
    rt, ids = ring.build(8, _opts())
    rt.send(int(ids[0]), ring.RingNode.token, 40)
    rt.run()
    serialise.save(rt, path)

    assert cli_main(["snapshot", path]) == 0
    out = capsys.readouterr().out
    assert "INTACT" in out and "RingNode[8]" in out
    assert cli_main(["snapshot", path, "--json"]) == 0
    info = json.loads(capsys.readouterr().out)
    assert info["intact"] and info["format"] == 3
    assert info["steps_run"] == rt.steps_run

    assert cli_main(["restore", path]) == 0
    assert "RESTORABLE" in capsys.readouterr().out

    testing.corrupt_snapshot(path, "bitflip")
    assert cli_main(["snapshot", path]) == 1
    assert cli_main(["restore", path]) == 1
    capsys.readouterr()

    # a RING PREFIX target resolves to the newest intact file
    prefix = str(tmp_path / "r")
    for seq in range(2):
        serialise.save(rt, serialise.checkpoint_file(prefix, seq))
    testing.corrupt_snapshot(serialise.checkpoint_file(prefix, 1),
                             "truncate")
    assert cli_main(["snapshot", prefix]) == 0   # falls back to seq 0
    assert "00000000.ckpt" in capsys.readouterr().out


def test_cli_usage_error_exit_codes(tmp_path, capsys):
    from ponyc_tpu.__main__ import main as cli_main
    assert cli_main(["snapshot"]) == 2                    # no target
    assert cli_main(["snapshot", "a", "b"]) == 2          # two targets
    assert cli_main(["restore"]) == 2
    assert cli_main(["snapshot", str(tmp_path / "nope")]) == 2
    assert cli_main(["supervise"]) == 2                   # no prefix
    assert cli_main(["supervise", "--prefix"]) == 2       # no value
    assert cli_main(["supervise", "--prefix", "p"]) == 2  # no script
    assert cli_main(["supervise", "--retries", "x", "--prefix", "p",
                     "s.py"]) == 2                        # bad int
    assert cli_main(["supervise", "--prefix", "p",
                     str(tmp_path / "nope.py")]) == 2     # no script
    capsys.readouterr()


# =============================================== chaos harness selftest

def test_chaos_hooks_arm_and_disarm():
    c = testing.ChaosHooks()
    fired = []
    c.arm("p", action=lambda: fired.append(1), after=2)
    c.fire("p")
    assert not fired
    c.fire("p")
    assert fired == [1]
    c.fire("p")                       # one-shot: disarmed after firing
    assert fired == [1]
    with pytest.raises(ValueError):
        c.arm("p", after=0)
    with pytest.raises(ValueError):
        c.arm("p", action="explode")
    c.arm("q", action=lambda: fired.append(2))
    c.reset()
    c.fire("q")
    assert fired == [1]


def test_chaos_fatal_poller_fires_once():
    rt, ids = ring.build(8, _opts())
    hook = testing.fatal_at_boundary(rt, boundary=2, code=77)
    rt.send(int(ids[0]), ring.RingNode.token, 500)
    with pytest.raises(PonyError) as ei:
        rt.run()
    assert ei.value.code == 77
    assert hook.fired == 1
