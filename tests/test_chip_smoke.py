"""chip_smoke.py's phase functions at a tiny size on the CPU backend.

The script itself only passes on a TPU (its entry point refuses any
other platform — pinned below); what this file keeps honest between
chip runs is everything else: that each phase still drives the public
entry points end to end and that its checks still hold, so chip time
goes to what the CPU cannot show. One world per phase, sizes chosen so
compiles dominate (seconds each).
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import chip_smoke  # noqa: E402


def test_phase_ubench_main_path():
    secs = chip_smoke.phase_ubench(256, 8)
    assert set(secs) == {"setup_s", "first_call_s", "rest_s", "rest_steps"}


def test_phase_ring_to_quiescence():
    chip_smoke.phase_ring(16, 40)


def test_phase_serve_front_door():
    chip_smoke.phase_serve(8, 4)


def test_phase_formulations_bit_for_bit():
    """2048 actors = a two-block Pallas grid (interpret mode here);
    every formulation must equal plan over every state leaf."""
    secs = chip_smoke.phase_formulations(2048, 2, 2)
    assert set(secs) == {"plan", "cosort", "pallas", "pallas_fused"}


def test_phase_mesh_spreads_state_over_four_devices():
    """The mesh spelling of a phase (every hop crossing a shard, state
    leaves on 4 distinct devices); ubench under a mesh is test_mesh's."""
    chip_smoke.phase_ring(16, 40, mesh_shards=4)


def test_phase_mesh_ubench_takes_the_short_delivery_list(capsys):
    """Phase (e)'s ubench: cycle traffic at the program's own bucket.
    The phase itself checks that every shard of every tick delivered
    over the short list (`n_unpacked`) and that the lookups in the hot
    word (`n_route_pressure`: this geometry keeps every mailbox over its
    overload line) muted nobody."""
    chip_smoke.phase_ubench(256, 8, mesh_shards=4)
    out = capsys.readouterr().out
    assert "took the short delivery list: ok" in out
    assert "looked the hot word up: ok" in out
    assert "and nobody was muted: ok" in out


def test_phase_mesh_fanin_mutes_across_shards_and_ends_clean(capsys):
    """Phase (e)'s fan-in under skew: 200 ticks mid-pressure with
    senders muted behind aggregators of other shards, conserved, then
    quiescent with every item counted and nobody muted."""
    chip_smoke.phase_fanin_mesh(64, 8, 3, mesh_shards=4)
    out = capsys.readouterr().out
    assert "senders on other shards were muted: ok" in out
    assert "nobody left muted: ok" in out


def test_a_failed_check_raises_and_entry_point_refuses_cpu(capsys):
    """Any failed check ends the run (no phase is wrapped in a try that
    continues to exit 0), and on anything but a TPU the entry point
    exits non-zero having printed no result."""
    with pytest.raises(chip_smoke.SmokeFailure, match="lane never ran"):
        chip_smoke.check("lane never ran", False, "min 0")
    capsys.readouterr()
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert out == ""
    assert "not a TPU" in err
    dev = chip_smoke.resolve_device()
    assert dev["platform"] == "cpu" and dev["count"] == 8
    json.dumps(dev)
