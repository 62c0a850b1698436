"""The mesh cell's readers on a small synthetic trace of four device
planes with one collective, and `route_bytes` by hand at a tiny shape.
The trace is `phase_trace.load()`'s plain data, written out here: times
in nanoseconds, a tick of 1000 ns on every plane."""

import pytest

from benchmarks import mesh_trace, reference_mesh, route_bytes
from benchmarks.layer_metrics import (collective_exposed_pct, collective_ms,
                                      route_fill_pct, route_remote_pct,
                                      shard_skew_pct)

A2A = ("%all_to_all.31 = s32[4,8,1]{1,2,0:T(1,128)} all-to-all(%copy.2), "
       "channel_id=1, replica_groups={{0,1,2,3}}")
PSUM_START = "%all-reduce-start.1 = s32[19]{0} all-reduce-start(%c.87)"
PSUM_DONE = "%all-reduce-done.1 = s32[19]{0} all-reduce-done(%all-reduce-start.1)"
SORT = "%sort.3 = (s32[64]{0}, s32[64]{0}) sort(%a, %b), dimensions={0}"
FUSION = "%fusion.20 = s32[64,2]{0,1:T(2,128)} fusion(%a, %b), kind=kLoop"
WHILE = "%while.1 = (s32[], s32[4]{0}) while(%t), condition=%c, body=%b"


def plane(extra_busy: float):
    """One tick: a `while` round everything; a sort 100..400 (+ extra),
    a synchronous all-to-all 500..600, an asynchronous all-reduce in
    flight 700..800 with a fusion 720..760 running under it."""
    return [[WHILE, 0.0, 1000.0, None],
            [SORT, 100.0, 300.0 + extra_busy, "a/pony/route/sort/sort"],
            [A2A, 500.0, 100.0, "a/pony/route/pony/route/exchange/all_to_all"],
            [PSUM_START, 700.0, 5.0, "a/pony/vote/psum"],
            [FUSION, 720.0, 40.0, "a/pony/vote/add"],
            [PSUM_DONE, 790.0, 10.0, "a/pony/vote/psum"]]


def data(planes):
    return {"device": planes,
            "host": [["segment", 0.0, 1000.0, None, None]]}


def test_collective_time_and_its_exposed_share():
    out = mesh_trace.reduce(data([plane(0.0) for _ in range(4)]))
    assert out["devices"] == 4
    # 100 ns of all-to-all + 100 ns of all-reduce in flight, a plane
    assert out["collective_s"] == pytest.approx(200e-9)
    assert out["by_kind"] == {"all-to-all": pytest.approx(100e-9),
                              "all-reduce": pytest.approx(100e-9)}
    # the fusion covers 40 of the all-reduce's 100; the `while` that
    # contains everything is no other operation
    assert out["exposed_s"] == pytest.approx(160e-9)


def test_the_span_cuts_an_open_collective_and_busy_is_the_union():
    events = [[A2A, 900.0, 300.0, None], [SORT, 0.0, 100.0, None]]
    out = mesh_trace.reduce(data([events]))
    assert out["collective_s"] == pytest.approx(100e-9)   # cut at 1000
    assert out["exposed_s"] == pytest.approx(100e-9)
    assert out["busy_s"] == [pytest.approx(200e-9)]
    assert mesh_trace.reduce({"device": [], "host": []}) is None


def test_skew_is_the_busiest_plane_over_the_mean():
    out = mesh_trace.reduce(data([plane(0.0), plane(0.0), plane(0.0),
                                  plane(0.0)]))
    assert out["busy_s"] == [pytest.approx(1000e-9)] * 4   # the `while`
    # without the enclosing `while` a plane is busy where an operation
    # runs: 300 + 100 + 5 + 40 + 10 (an all-reduce in flight is not the
    # device being busy), one plane 40 more
    bare = [p[1:] for p in (plane(0.0), plane(0.0), plane(0.0), plane(40.0))]
    out = mesh_trace.reduce(data(bare))
    assert out["busy_s"] == [pytest.approx(455e-9)] * 3 + [
        pytest.approx(495e-9)]


def test_readers_on_the_reduction(monkeypatch):
    bare = [p[1:] for p in (plane(0.0), plane(0.0), plane(0.0), plane(40.0))]
    reduced = mesh_trace.reduce(data(bare))
    monkeypatch.setattr(mesh_trace, "of_run", lambda ctx: reduced)
    ctx = {"trace": {"ticks": 2}}
    assert collective_ms.read(ctx) == pytest.approx(1e3 * 200e-9 / 2)
    assert collective_exposed_pct.read(ctx) == pytest.approx(80.0)
    assert shard_skew_pct.read(ctx) == pytest.approx(
        100.0 * (495 / 465 - 1.0))
    # one device, or no collective: nothing to report
    monkeypatch.setattr(mesh_trace, "of_run", lambda ctx: None)
    assert collective_ms.read(ctx) is None
    assert shard_skew_pct.read(ctx) is None


def test_counter_readers():
    route = {"shards": 4, "bucket": 100, "routed": 2400, "remote": 1800,
             "ticks": 3}
    ctx = {"window": {"route": route}}
    # 2,400 entries in 3 ticks x 4 x 4 buckets of 100 slots
    assert route_fill_pct.read(ctx) == pytest.approx(50.0)
    assert route_remote_pct.read(ctx) == pytest.approx(75.0)
    assert route_fill_pct.read({"window": {}}) is None
    assert route_remote_pct.read({"window": {}}) is None


def test_route_bytes_by_hand():
    # an entry of a one-word message: target, sender, behaviour, payload
    assert route_bytes.entry_bytes(1) == 16
    # 8 entries a tick over 4 shards: a shard reads 2, writes 2 into the
    # exchange and writes the 2 it receives: 6 entries of 16 B
    assert route_bytes.tick_bytes_a_shard(8, 4, 1) == 96.0
    assert route_bytes.tick_min_seconds(
        8, 4, 1, {"hbm_bytes_per_s": 96.0}) == pytest.approx(1.0)


def test_collective_names():
    c = mesh_trace.collective_of
    assert c(A2A) == ("all-to-all", None)
    assert c(PSUM_START) == ("all-reduce", "start")
    assert c(PSUM_DONE) == ("all-reduce", "done")
    assert c("%psum.12 = s32[19]{0:T(128)S(1)} all-reduce(%c)") == (
        "all-reduce", None)
    assert c("%all-gather-start = (pred[4], pred[16]) async-start(%p)") == (
        "all-gather", "start")
    assert c("all_to_all.31") == ("all-to-all", None)
    assert c(FUSION) is None and c(WHILE) is None and c(SORT) is None


def test_remote_sends_by_hand():
    """Four actors on two shards (0, 1 | 2, 3), one ping each: a cycle
    whose every hop crosses, then two cycles that stay at home."""
    import numpy as np
    next_slot = np.array([2, 3, 1, 0])
    sent, remote = reference_mesh.remote_sends(
        np.ones(4, np.int64), 8, 2, 3, next_slot=next_slot)
    # 0 -> 2 and 1 -> 3 cross, 2 -> 1 and 3 -> 0 cross too
    assert list(sent) == [4, 4, 4] and list(remote) == [4, 4, 4]
    next_slot = np.array([1, 0, 3, 2])
    sent, remote = reference_mesh.remote_sends(
        np.ones(4, np.int64), 8, 2, 2, next_slot=next_slot)
    assert list(sent) == [4, 4] and list(remote) == [0, 0]
    assert list(reference_mesh.deal(8, 4)) == [0, 2, 4, 6, 1, 3, 5, 7]
