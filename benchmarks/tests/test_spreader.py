"""The spreader reference checked by hand and against itself, the
world's sizes, the collector's least bytes, and the cell end to end on
the CPU at a small `scale`."""

import json

import numpy as np
import pytest

from benchmarks import gc_bytes, reference_spreader as ref, run
from benchmarks.tests.conftest import ROOT
from benchmarks.worlds import spreader


def test_forest_on_a_case_worked_by_hand():
    """One root, count 1, started at once: tick 1 the root dispatches
    `start` and creates two leaves; tick 2 both dispatch `spread` and
    report 1; tick 3 the root takes both results (two dispatches): a
    tree of 3 actors, and the next tree's two leaves in the same
    dispatch. A tree's period is 2 ticks from then on."""
    f = ref.Forest([0], 1)
    assert f.tick() == {"spawns": 2, "dispatches": 1, "live": 2}
    assert f.tick() == {"spawns": 0, "dispatches": 2, "live": 0}
    assert f.tick() == {"spawns": 2, "dispatches": 2, "live": 2}
    assert (f.runs.tolist(), f.total.tolist()) == ([1], [3])
    assert f.held() == 2 and f.spawned == 4 and f.dispatched == 5
    f.advance_to(3 + 2 * 5)
    assert (f.runs.tolist(), f.total.tolist()) == ([6], [18])
    # a root that waits two self-sends is two ticks behind
    late = ref.Forest([2], 1)
    late.advance_to(5)
    assert late.runs.tolist() == [1] and late.spawned == 4
    # a finite root stops: one tree, nothing held, nobody live
    one = ref.Forest([0], 2, trees=1)
    one.advance_to(12)
    assert (one.runs.tolist(), one.total.tolist()) == ([1], [7])
    assert one.held() == 0 and not one.live.any() and one.spawned == 6


def test_forest_closed_forms_of_the_steady_state():
    """Count 10 on every phase: a tree is 2,047 actors, its non-root
    actors hold their rows 8,144 row-ticks a period (the tick an actor
    reports in counted; 2,046 fewer after each tick's reports), and with
    2 roots a phase every tick of the steady state spawns 2 x 2,046 and
    dispatches 2 x 4,092."""
    assert ref.tree_actors(10) == 2047 and ref.row_ticks(10) == 8144
    phase = ref.phases(2**31 + 5, 40, 20)
    assert sorted(np.bincount(phase).tolist()) == [2] * 20
    assert not np.array_equal(phase, ref.phases(2**31 + 6, 40, 20))
    f = ref.Forest(phase, 10)
    f.advance_to(60)
    for _ in range(20):
        assert f.tick() == {"spawns": 2 * 2046, "dispatches": 2 * 4092,
                            "live": 2 * (8144 - 2046)}
    assert np.array_equal(f.total, f.runs * 2047) and f.runs.min() >= 2


def test_reachable_on_a_graph_worked_by_hand():
    """0 is pinned and names 1; 1 names 2 (dead: its field is not
    followed); 3 -> 4 -> 3 is a cycle nobody reaches; 5 holds a message
    whose Ref argument names 6; 7's only reference comes from dead 2."""
    alive = np.array([1, 1, 0, 1, 1, 1, 1, 1], bool)
    roots = np.array([1, 0, 0, 0, 0, 1, 0, 0], bool)
    field = np.array([1, 2, 7, 4, 3, -1, -1, -1])
    keeps = ref.reachable(alive, roots, [(np.arange(8), field)], [6, -1, 99])
    assert keeps.tolist() == [True, True, True, False, False, True, True,
                              False]
    assert (alive & ~keeps).tolist() == [False] * 3 + [True, True] \
        + [False] * 2 + [True]


def test_invariant_catches_a_lost_result_a_collected_parent_and_a_leak():
    f = ref.Forest(ref.phases(3, 4, 4), 2)
    f.advance_to(9)
    tree = ref.tree_actors(2)
    good = dict(runs=f.runs, total=f.runs * tree, left=f.left,
                n_spawned=f.spawned, n_collected=f.spawned - 6,
                alive=np.array([1] * 4 + [1] * 6 + [0] * 6, bool),
                is_root=np.array([1] * 4 + [0] * 12, bool),
                keeps=np.array([1] * 4 + [1] * 4 + [0] * 8, bool))
    assert ref.invariant(f, **good) == {
        "roots_off": 0, "spawned_off": 0, "rows_off": 0, "lost": 0,
        "garbage": 2}
    short = good["total"].copy()
    short[0] -= 1                           # a result lost on the way up
    assert ref.invariant(f, **{**good, "total": short})["roots_off"] == 1
    behind = good["runs"].copy()
    behind[1] -= 1                          # a tree that never came back
    assert ref.invariant(f, **{**good, "runs": behind,
                               "total": behind * tree})["roots_off"] == 1
    dead = good["alive"].copy()
    dead[5] = False                         # a live actor collected
    found = ref.invariant(f, **{**good, "alive": dead})
    assert found["lost"] == 1 and found["rows_off"] == 1
    assert ref.invariant(f, **{**good, "n_spawned": f.spawned + 2}) \
        == {"roots_off": 0, "spawned_off": 2, "rows_off": 2, "lost": 0,
            "garbage": 2}


def test_sizes_follow_the_configuration():
    with open(f"{ROOT}/benchmarks/configs/spreader-forest.json") as f:
        cfg = json.load(f)
    with open(f"{ROOT}/benchmarks/traffic/churn.json") as f:
        traffic = json.load(f)
    assert spreader.sizes(cfg["actors"], cfg["count"]) == cfg["sizes"]
    assert cfg["sizes"]["roots"] == 640 == traffic["roots"]
    assert traffic["phases"] == 2 * traffic["count"] == cfg["sizes"]["period"]
    for absent in ("delivery", "cd_interval", "batch", "max_sends"):
        assert absent not in cfg["runtime_options"]
    assert spreader.Spreader.BATCH == 2 and spreader.Spreader.MAX_SENDS == 3
    assert spreader.sizes(4096, 6) == {"actors": 4096, "count": 6,
                                       "period": 12, "roots": 24,
                                       "tree_actors": 127}
    # a world cut by its rows alone runs the deepest trees that fit
    assert spreader.sizes(2048, 10) == {"actors": 2048, "count": 6,
                                        "period": 12, "roots": 12,
                                        "tree_actors": 127}
    with pytest.raises(ValueError, match="hold no tree"):
        spreader.sizes(8, 10)
    # the stated size under another mix is refused before anything is built
    with pytest.raises(ValueError, match="the mix states"):
        spreader.build(cfg, {**traffic, "roots": 320}, 0)
    # the least a pass moves: 11 B a row once, 8 B a queued message,
    # 6 B a row a hop with one Ref field
    assert gc_bytes.pass_bytes(1_048_576, 1, 130_944, 10) \
        == 1_048_576 * 11 + 130_944 * 8 + 10 * 1_048_576 * 6


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_at_scale_on_the_cpu(trace, capsys):
    rc = run.main(["--workload", "spreader-forest.churn", "--seed",
                   str(2**31 + 11), "--seconds", "1", "--trace", str(trace),
                   "--platform", "cpu"], scale={"actors": 4096, "count": 6})
    assert rc == 0
    out = capsys.readouterr().out
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    metrics = result["metrics"]
    if trace:
        assert metrics["compiles_in_window"]["value"] == 0
        assert metrics["ticks_per_gc"]["value"] == 11
        assert metrics["gc_hops_per_pass"]["value"] == 5
        assert 0 < metrics["gc_wall_pct"]["value"] < 100
        assert 0 <= metrics["free_rows_low_pct"]["value"] < 100
    else:
        assert metrics["msgs_per_s"]["value"] > 0
