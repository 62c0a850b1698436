"""The plain reference against the engine (CPU backend, 4,096 actors)
and against itself (closed form against tick by tick)."""

import numpy as np
import pytest

from benchmarks import reference

GEOMETRY = dict(mailbox_cap=64, batch=8, spill_cap=4096, msg_words=1,
                max_sends=1)


@pytest.mark.parametrize("traffic", [
    {"recipients": "cycle", "pings": 5, "seeded_every": 1},
    {"recipients": "random", "pings": 5, "seeded_every": 1},
    {"recipients": "cycle", "pings": 5, "seeded_every": 1024},
], ids=["cycle", "random", "sparse"])
def test_engine_matches_reference(traffic):
    from benchmarks.worlds import ubench
    world = ubench.build({"actors": 4096, "runtime_options": GEOMETRY},
                         traffic, seed=11)
    rt = world.rt
    try:
        assert rt.run(max_steps=3) == 0
        assert rt.run(max_steps=9) == 0
        counts = world.counts()
        assert np.array_equal(counts, world.reference(12))
        closed = world.reference_closed(12)
        assert closed is None or np.array_equal(counts, closed)
        assert int(counts.sum()) == rt.counter("n_processed")
        assert rt.counter("n_rejected") == rt.counter("n_mutes") == 0
    finally:
        rt.stop()


def test_ring_matches_reference():
    from benchmarks.worlds import ring
    world = ring.build({"actors": 64, "runtime_options": dict(
        mailbox_cap=8, batch=1, msg_words=1, max_sends=1)},
        {"mode": "lap", "tokens": 1, "laps_per_token": 1}, seed=0)
    try:
        assert world.rt.run() == 0 and world.counts().sum() == 0
        for _ in range(3):          # lap after lap: no sticky exit
            world.start_lap()
            assert world.rt.run() == 0
        assert np.array_equal(world.counts(), world.reference_laps(3))
        assert world.counts().sum() == 3 * 64
    finally:
        world.rt.stop()


def test_full_ring_matches_reference():
    """A ring with a token on every node circulates under the throughput
    mode's contract: `ring-1024.full` needs no code (README)."""
    from benchmarks.worlds import ring
    world = ring.build({"actors": 64, "runtime_options": dict(
        mailbox_cap=8, batch=1, msg_words=1, max_sends=1)},
        {"mode": "throughput", "tokens": 64}, seed=0)
    try:
        assert world.rt.run(max_steps=70) == 0
        assert np.array_equal(world.counts(), world.reference(70))
        assert world.counts().sum() == 70 * 64
    finally:
        world.rt.stop()


@pytest.mark.parametrize("ticks", [0, 1, 5, 31, 32, 33, 64, 100])
@pytest.mark.parametrize("every", [1, 4, 32])
def test_closed_form_is_tick_by_tick(ticks, every):
    n = 32
    gen = np.random.default_rng(ticks * 100 + every)
    order = gen.permutation(n)
    position = np.empty(n, np.int64)
    position[order] = np.arange(n)
    next_slot = np.empty(n, np.int64)
    next_slot[order] = np.roll(order, -1)
    starts = np.arange(0, n, every)
    queue = np.zeros(n, np.int64)
    queue[order[starts]] = 5
    slow, left, _ = reference.ubench_ticks(queue, 8, ticks,
                                           next_slot=next_slot)
    fast = reference.cycle_counts(position, starts, 5, ticks)
    assert np.array_equal(slow, fast)
    assert left.sum() == queue.sum()


def test_random_reference_conserves_and_queues():
    n = 512
    rng = np.random.default_rng(3).integers(1, 2**31 - 1, n).astype(np.uint32)
    pings, queue, _ = reference.ubench_ticks(np.full(n, 5), 8, 20, rng=rng)
    assert queue.sum() == 5 * n               # nothing lost, nothing made
    assert pings.sum() < 20 * 5 * n           # some waited behind a batch
    assert pings.max() > pings.min()


def test_xorshift32_known_values():
    # Marsaglia's 13/17/5 generator from seed 1
    x = np.array([1], np.uint32)
    seen = []
    for _ in range(3):
        x = reference.xorshift32(x)
        seen.append(int(x[0]))
    assert seen == [270369, 67634689, 2647435461]
    assert reference.signed_mod(np.array([2647435461], np.uint32), 1000)[0] \
        == (2647435461 - 2**32) % 1000


def test_ring_passes():
    assert reference.ring_passes(4, 4, 3).tolist() == [3, 3, 3, 3]
    assert reference.ring_passes(4, 6, 1).tolist() == [2, 2, 1, 1]
