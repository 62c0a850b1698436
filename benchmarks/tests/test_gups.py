"""The GUPS reference checked by hand and against itself, the world's
sizes, and the cell end to end on the CPU at a small `scale`."""

import json

import numpy as np
import pytest

from benchmarks import heap_bytes, reference_gups as ref, run
from benchmarks.tests.conftest import ROOT
from benchmarks.worlds import gups


def test_reference_on_a_case_worked_by_hand():
    """xorshift32 from state 1 gives 270369, then 67634689 (Marsaglia's
    13 / 17 / 5). One streamer, two datums a dispatch, a table of
    2 x 8 words: 270369 = 0x42021 lands on word 0x1 (owner 0, word 1),
    67634689 = 0x4080601 on word 0x1 too, so after one dispatch
    Table[1] = 1 ^ 270369 ^ 67634689 and owner 0 has two datums."""
    assert ref.xorshift32(np.array([1], np.uint32)).tolist() == [270369]
    datums, rng = ref.draw(np.array([1], np.uint32), 2)
    assert datums.ravel().tolist() == [270369, 67634689]
    assert rng.tolist() == [67634689]
    r = ref.Reference(np.array([1]), updaters=2, slice_words=8, chunk=2)
    r.tick()
    want = np.arange(16, dtype=np.uint32)
    want[1] ^= np.uint32(270369) ^ np.uint32(67634689)
    assert np.array_equal(r.table, want)
    assert r.generated.tolist() == [2, 0] and r.done.tolist() == [1]
    # HPCC's verification: the same stream once more gives Table[i] = i
    assert np.array_equal(ref.replay(r.table.copy(), np.array([1]), 2, 1),
                          np.arange(16))
    # a table word the xor leaves alone still equals its index
    table = np.arange(8, dtype=np.uint32)
    ref.scatter(table, [5, 13, 5])     # 5 -> word 5 twice, 13 -> word 5
    assert table.tolist() == [0, 1, 2, 3, 4, 5 ^ 13, 6, 7]


def _system(r: ref.Reference, tick_datums, seed=0):
    """A system that has applied some of the generated datums and still
    queues the rest: (table [updaters, slice], applied, queued owner,
    queued datum), split by a seeded coin."""
    datums = np.concatenate([d.reshape(-1) for d in tick_datums])
    coin = np.random.default_rng(seed).random(len(datums)) < 0.7
    table = np.arange(len(r.table), dtype=np.uint32)
    ref.scatter(table, datums[coin])
    owner = r.owner(datums)
    return (table.reshape(r.updaters, r.slice_words),
            np.bincount(owner[coin], minlength=r.updaters),
            owner[~coin], datums[~coin])


def test_invariant_catches_a_dropped_a_doubled_and_a_misrouted_update():
    r = ref.Reference(ref.seeds(7, 64), updaters=64, slice_words=64, chunk=4)
    seen = []
    for _ in range(6):
        before = r.rng.copy()
        seen.append(ref.draw(before, 4)[0])
        r.tick()
    table, applied, q_owner, q_datum = _system(r, seen)
    assert len(q_owner) > 100 and applied.sum() > 1000
    good = ref.invariant(r, 0, 64, table, applied, q_owner, q_datum)
    assert good == {"words_off": 0, "updaters_off": 0}
    # ... and block by block, as the harness asks
    for lo, hi in ((0, 16), (16, 64)):
        mine = (q_owner >= lo) & (q_owner < hi)
        assert ref.invariant(r, lo, hi, table[lo:hi], applied[lo:hi],
                             q_owner[mine], q_datum[mine]) == good

    dropped = ref.invariant(r, 0, 64, table, applied, q_owner[1:],
                            q_datum[1:])              # a queued one lost
    assert dropped == {"words_off": 1, "updaters_off": 1}

    twice = table.copy()                              # one applied twice
    o, d = int(q_owner[0]), q_datum[0]
    twice[o, int(d) & 63] ^= d
    bumped = applied.copy()
    bumped[o] += 1
    assert ref.invariant(r, 0, 64, twice, bumped, q_owner, q_datum) \
        == {"words_off": 1, "updaters_off": 1}

    astray = q_owner.copy()                           # sent to another owner
    astray[0] = (astray[0] + 1) % 64
    assert ref.invariant(r, 0, 64, table, applied, astray, q_datum) \
        == {"words_off": 2, "updaters_off": 2}

    swapped = table.copy()         # one lost, another twice: the counts
    swapped[o, int(d) & 63] ^= d   # agree, the words do not
    assert ref.invariant(r, 0, 64, swapped, applied, q_owner, q_datum) \
        == {"words_off": 1, "updaters_off": 0}


def test_sizes_follow_the_configuration():
    with open(f"{ROOT}/benchmarks/configs/gups-hpcc.json") as f:
        cfg = json.load(f)
    with open(f"{ROOT}/benchmarks/traffic/stream.json") as f:
        traffic = json.load(f)
    assert gups.sizes(cfg["actors"], cfg["slice_words"]) == cfg["sizes"]
    assert cfg["sizes"]["table_words"] == 2**30
    for key in ("blob_slots", "blob_words"):
        assert cfg["runtime_options"][key] == cfg["sizes"][key]
    assert "delivery" not in cfg["runtime_options"]
    assert gups.sizes(2048, 2048) == {
        "actors": 2048, "updaters": 1024, "streamers": 1024,
        "table_words": 2**21, "blob_slots": 1024, "blob_words": 2048}
    with pytest.raises(ValueError, match="power of two"):
        gups.sizes(1536, 2048)
    # the stated size under another rule is refused before anything is built
    with pytest.raises(ValueError, match="states"):
        gups.build({**cfg, "slice_words": 1024}, traffic, 0)
    # 2,097,152 updates a tick, 13 B each at the least
    assert heap_bytes.updates_per_tick(cfg, traffic) == 2_097_152
    assert heap_bytes.tick_bytes(cfg, traffic) == 2_097_152 * 13


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_at_scale_on_the_cpu(trace, capsys):
    rc = run.main(["--workload", "gups-hpcc.stream", "--seed",
                   str(2**31 + 11), "--seconds", "1", "--trace", str(trace),
                   "--platform", "cpu"], scale={"actors": 512})
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    if trace:
        assert result["metrics"]["compiles_in_window"]["value"] == 0
    else:
        assert result["metrics"]["msgs_per_s"]["value"] > 0
