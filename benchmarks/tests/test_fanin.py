"""The fan-in's references checked by hand and against themselves, and
the world's sizes. The cell itself runs in `test_harness.py`."""

import json

import numpy as np
import pytest

from benchmarks import reference_fanin as ref
from benchmarks.tests.conftest import ROOT
from benchmarks.worlds import fanin


def test_protocol_on_a_case_worked_by_hand():
    """Two aggregators, ring of 4, drain 1, overloaded above 3, unmute
    at 1. Producers 0-2 report to aggregator 0, producer 3 to 1.

    tick 1  all send item 0; agg 0 takes three (3 queued), nobody hot.
    tick 2  all send item 1; agg 0 drains one, has room for two: takes
            p0's and p1's, rejects p2's into the spill; now 4 queued, so
            hot: p0, p1, p2 muted (3 mutes, 1 rejection).
    tick 3  only p3 runs; agg 0 drains one, the spilled item lands
            (4 queued again), the spill is empty.
    4 - 6   agg 0 drains 4 -> 1 queued; p0-p2 stay muted (more than 1
            queued at each tick's start).
    tick 7  1 queued at the start and nothing spilled: p0-p2 released,
            run, send item 2; agg 0 drains its last old item and takes
            all three."""
    t = ref.Ticks(np.array([0, 0, 0, 1]), 2, mailbox_cap=4, batch=1,
                  overload_occ=3, unmute_occ=1)
    t.advance(2)
    assert t.sent.tolist() == [2, 2, 2, 2]
    assert t.muted.tolist() == [True, True, True, False]
    assert (t.n_rejected, t.n_mutes) == (1, 3)
    assert (t.spill_tgt.tolist(), t.spill_snd.tolist(),
            t.spill_seq.tolist()) == ([0], [2], [1])
    assert (t.tail - t.head).tolist() == [4, 1]
    t.advance(1)
    assert t.sent.tolist() == [2, 2, 2, 3] and len(t.spill_tgt) == 0
    assert (t.tail - t.head).tolist() == [4, 1] and t.n_rejected == 1
    assert t.total.tolist() == [2, 2] and t.seq_sum.tolist() == [0, 1]
    t.advance(3)
    assert t.muted.tolist() == [True, True, True, False]
    assert (t.tail - t.head).tolist() == [1, 1]
    t.advance(1)
    seen = t.observed()
    assert seen["sent"].tolist() == [3, 3, 3, 7]
    assert not seen["muted"].any() and t.n_mutes == 3
    assert seen["total"].tolist() == [6, 6]
    assert seen["seq_sum"].tolist() == [3, 15]      # 0+0+0+1+1+1, 0+..+5
    assert seen["queued"].tolist() == [3, 1]
    assert seen["spilled"].tolist() == [0, 0]


def _state_of(t: ref.Ticks) -> dict:
    """A protocol state in the form `conservation` takes the system's."""
    ring_count, ring_seq_sum = ref.ring_items(t.ring.T, t.head, t.tail)
    return dict(sent=t.sent.copy(), total=t.total.copy(),
                seq_sum=t.seq_sum.copy(), ring_count=ring_count,
                ring_seq_sum=ring_seq_sum, spill_tgt=t.spill_tgt.copy(),
                spill_seq=t.spill_seq.copy(),
                produce_held=np.ones(len(t.out), np.int64),
                muted=t.muted.copy())


def test_conservation_holds_and_catches_a_lost_and_a_duplicated_item():
    out = ref.zipf_wiring(7, 448, 64, 0.99)
    t = ref.Ticks(out, 64, mailbox_cap=64, batch=8, overload_occ=48,
                  unmute_occ=16).advance(40)
    while len(t.spill_tgt) == 0 and t.ticks < 200:   # a tick with a backlog
        t.tick()
    assert t.n_rejected > 0 and len(t.spill_tgt) > 0 and t.muted.any()
    good = ref.conservation(out, 64, **_state_of(t))
    assert good["deficit"] == 0 and all(good["checks"].values())
    hot = int(np.argmax(t.total))

    lost = _state_of(t)
    lost["total"][hot] -= 1                    # an item counted nowhere
    found = ref.conservation(out, 64, **lost)
    assert found["deficit"] == 1
    assert not found["checks"]["conservation_every_aggregator"]

    twice = _state_of(t)
    twice["spill_tgt"] = np.append(twice["spill_tgt"], t.spill_tgt[0])
    twice["spill_seq"] = np.append(twice["spill_seq"], t.spill_seq[0])
    found = ref.conservation(out, 64, **twice)  # a spilled item, twice
    assert found["deficit"] == 1
    assert not found["checks"]["conservation_every_aggregator"]

    swapped = _state_of(t)                     # one lost, another twice:
    swapped["seq_sum"][hot] += 1               # the count cannot tell,
    found = ref.conservation(out, 64, **swapped)    # the sequence sum can
    assert found["deficit"] == 0
    assert not found["checks"]["conservation_every_aggregator"]

    gone = _state_of(t)
    gone["produce_held"][3] = 0
    assert not ref.conservation(out, 64, **gone)["checks"][
        "one_produce_per_producer"]

    stuck = _state_of(t)                       # muted, aggregator drained
    cold = int(np.flatnonzero((np.bincount(out, minlength=64) > 0)
                              & (np.bincount(t.spill_tgt, minlength=64)
                                 == 0))[0])
    stuck["total"][cold] += stuck["ring_count"][cold]
    stuck["seq_sum"][cold] = (stuck["seq_sum"][cold]
                              + stuck["ring_seq_sum"][cold]) & ref.MASK32
    stuck["ring_count"][cold] = stuck["ring_seq_sum"][cold] = 0
    stuck["muted"][np.flatnonzero(out == cold)[0]] = True
    found = ref.conservation(out, 64, **stuck)
    assert found["checks"]["conservation_every_aggregator"]
    assert not found["checks"]["muted_only_behind_work"]


def test_wiring_is_drawn_per_producer_from_the_seed_under_the_zipf():
    a = ref.zipf_wiring(2**31 + 5, 14336, 2048, 0.99)
    b = ref.zipf_wiring(6, 14336, 2048, 0.99)
    assert np.array_equal(a, ref.zipf_wiring(2**31 + 5, 14336, 2048, 0.99))
    assert a.min() >= 0 and a.max() < 2048
    fan_a = np.sort(np.bincount(a, minlength=2048))[::-1]
    fan_b = np.sort(np.bincount(b, minlength=2048))[::-1]
    # another seed is another sample: another hot aggregator, and
    # another profile (independent draws, not one profile permuted)
    assert np.argmax(np.bincount(a)) != np.argmax(np.bincount(b))
    assert not np.array_equal(fan_a, fan_b)
    # ... of the same distribution: rank r expects a share r**-s, and
    # the hottest ranks come out within four standard deviations of it
    share = np.arange(1, 2049) ** -0.99
    want = 14336 * share / share.sum()
    for fan in (fan_a, fan_b):
        assert np.all(np.abs(fan[:16] - want[:16]) <= 4 * np.sqrt(want[:16]))
        assert fan[0] > 8 * fan[15] > 0        # rank 1 against rank 16
        # ... and scatter round it as draws do: chi-square over the 16
        # hottest is about 10 (evenly spaced draws, each rank within one
        # producer of its share, would give under 0.1)
        assert ((fan[:16] - want[:16]) ** 2 / want[:16]).sum() > 2


def test_sizes_follow_actors():
    with open(f"{ROOT}/benchmarks/configs/fanin-zipf.json") as f:
        cfg = json.load(f)
    ppa = cfg["producers_per_aggregator"]
    assert fanin.sizes(cfg["actors"], ppa) == cfg["sizes"]
    assert cfg["runtime_options"]["spill_cap"] == cfg["sizes"]["spill_cap"]
    assert fanin.sizes(2048, ppa) == {"actors": 2048, "aggregators": 256,
                                      "producers": 1792, "spill_cap": 2048}
    assert fanin.sizes(64, ppa) == {"actors": 64, "aggregators": 8,
                                    "producers": 56, "spill_cap": 64}
    with open(f"{ROOT}/benchmarks/traffic/steady.json") as f:
        traffic = json.load(f)
    # the stated size with another ratio is refused before anything is built
    with pytest.raises(ValueError, match="states"):
        fanin.build({**cfg, "producers_per_aggregator": 3}, traffic, 0)
    # and the spill's bound is the configuration's: one entry a producer
    for size in (cfg["sizes"], fanin.sizes(2048, ppa)):
        assert size["producers"] <= size["spill_cap"] < 2 * size["producers"]
