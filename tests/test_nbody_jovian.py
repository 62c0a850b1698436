"""The Jovian n-body (`benchmarks/worlds/nbody.py`, the world of the cell
`nbody-jovian.orbit`) against its plain references
(`benchmarks/reference_nbody.py`), on the CPU: 64 systems (320 bodies)
on ONE compiled world.

The deployment is the first whose messages are float records of six
words (`visit(hops: I32, pos: VecF32[3], pm: F32)`) and whose state is
float, so the first whose comparison carries tolerances; the tests hold
the tolerances tight from both sides (float32 passes, float16 fails),
the actor reading to the source's own pairwise step, and the constants
to the two energies the source prints.
"""

import json
import os

import numpy as np
import pytest

from benchmarks import reference_nbody as ref
from benchmarks.modes import throughput_orbit as mode
from benchmarks.worlds import nbody

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SYSTEMS = 64
TICKS = 32
SEED = 2**31 + 51


def _files():
    with open(os.path.join(ROOT, "benchmarks/configs/nbody-jovian.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmarks/traffic/orbit.json")) as f:
        return cfg, json.load(f)


@pytest.fixture(scope="module")
def run():
    """The one world: 32 ticks, one at a time, what it held after each,
    and the reference's float32 state after the same tick."""
    cfg, mix = _files()
    cfg["actors"] = SYSTEMS * ref.BODIES
    cfg["runtime_options"] = {**cfg["runtime_options"], "mailbox_cap": 8,
                              "compile_cache": "off", "tuning_cache": "off"}
    world = nbody.World(cfg, mix, SEED)
    codes, seen, want = [], [], []
    for tick in range(1, TICKS + 1):
        codes.append(world.rt.run(max_steps=1))
        seen.append(world.observed())
        want.append({k: np.copy(v) for k, v in
                     world.reference(tick).items()})
    yield {"world": world, "codes": codes, "seen": seen, "want": want,
           "cfg": cfg, "mix": mix}
    world.rt.stop()


def test_the_world_is_the_configurations_shape(run):
    world, cfg = run["world"], run["cfg"]
    assert (world.systems, world.n, world.live) == (SYSTEMS, 320, 320)
    shape = world.tick_shape()
    assert shape["record_words"] == cfg["sizes"]["record_words"] == 6
    assert shape["state_words"] == cfg["sizes"]["state_words"] == 11
    assert shape["messages"] == shape["dispatching_actors"] == 320
    assert (world.Body.BATCH, world.Body.MAX_SENDS) == (1, 1)
    assert world.rt.opts.msg_words == 5 and world.rt.opts.mesh_shards == 1
    # system 0 is the source's own, every other one perturbed
    assert np.array_equal(world.start["vel"][0, 1:],
                          ref.jovian()["vel"][1:])
    rel = world.start["vel"][1:, 1:] / ref.jovian()["vel"][1:] - 1
    assert 0 < np.abs(rel).max() <= run["mix"]["perturbation"]
    # every size the file states follows from its rules
    full = cfg["sizes"]
    assert full["actors"] == full["systems"] * ref.BODIES == full["live"] \
        == 1_048_575


@pytest.mark.parametrize("tick", range(1, TICKS + 1))
def test_every_body_follows_the_protocol_tick_by_tick(run, tick):
    """Exact integers, floats at the cell's tolerances, on every tick."""
    assert run["codes"][tick - 1] == 0
    seen, want = run["seen"][tick - 1], run["want"][tick - 1]
    got = ref.compare(seen, want)
    assert got["off"] == 0 and all(got["checks"].values()), (tick, got)
    assert (seen["seen"] == tick % 4).all()
    assert (seen["step"] == tick // 4).all() and not seen["bad"].any()


def test_the_comparison_is_tight_float16_fails_it(run):
    """The same 32 ticks with the reference's arithmetic in float16 are
    not the chip's trajectory, by either tolerance and by the energy;
    float64 is (float32's rounding lies far inside the tolerances)."""
    world, seen = run["world"], run["seen"][-1]
    start = world.start
    half = ref.Ticks(start["pos"], start["vel"], start["m"],
                     np.float16).advance(TICKS).observed()
    got = ref.compare(seen, half)
    assert got["checks"]["counters_exact"]
    assert not got["checks"]["positions_within_tolerance"]
    assert not got["checks"]["velocities_within_tolerance"]
    assert got["read"]["position"] > 50 * ref.POS_TOL
    kept = ref.invariant(half, TICKS, start)["checks"]
    assert not kept["energy_every_system"]
    assert not kept["momentum_every_system"]
    double = ref.Ticks(start["pos"], start["vel"], start["m"],
                       np.float64).advance(TICKS).observed()
    got = ref.compare(seen, double)
    assert all(got["checks"].values())
    assert got["read"]["position"] < ref.POS_TOL / 10
    assert got["read"]["velocity"] < ref.VEL_TOL / 10


def test_the_invariant_holds_and_can_fail(run):
    world, seen = run["world"], run["seen"][-1]
    kept = world.invariant(seen, TICKS)
    assert kept["deficit"] == 0 and all(kept["checks"].values()), kept
    assert kept["read"]["steps"] == 8
    assert kept["read"]["energy_drift_max"] < ref.ENERGY_TOL / 10
    with pytest.raises(ValueError, match="step boundary"):
        world.invariant(run["seen"][-2], TICKS - 1)
    # one Jupiter lost its last step's visits (the Sun's among them:
    # 5e-3 of its momentum): its velocity is a step behind
    lost = {k: np.copy(v) for k, v in seen.items()}
    for k in ("vx", "vy", "vz"):
        lost[k][5, 1] = run["seen"][-5][k][5, 1]
    broke = world.invariant(lost, TICKS)
    assert not broke["checks"]["momentum_every_system"]
    assert broke["deficit"] >= 1
    # a token that overtook another, a body a step behind, a NaN
    for key, check in (("bad", "no_token_overtook_another"),
                       ("step", "every_body_at_the_same_step")):
        off = {**seen, key: seen[key] + (np.arange(320).reshape(64, 5) == 7)}
        assert not world.invariant(off, TICKS)["checks"][check]
    nan = {**seen, "x": np.where(np.arange(320).reshape(64, 5) == 9,
                                 np.nan, seen["x"])}
    assert not world.invariant(nan, TICKS)["checks"]["energy_every_system"]
    assert not ref.compare(nan, run["want"][-1])["checks"][
        "positions_within_tolerance"]


def test_the_run_is_clean_and_the_mode_says_correct(run):
    """What `throughput_orbit.finish` holds the run to, on this world."""
    world = run["world"]
    rt = world.rt
    assert rt.steps_run == TICKS
    assert rt.counter("n_processed") == TICKS * world.n
    assert not any(rt.counter(c) for c in
                   ("n_rejected", "n_badmsg", "n_deadletter"))
    depth = np.asarray(rt.state.tail) - np.asarray(rt.state.head)
    assert (depth[world.ids] == 1).all()
    rt.check_invariants()
    plan = {"k": 4, "codes": run["codes"], "reference_ok": True,
            "first": ref.compare(run["seen"][-1], run["want"][-1])}
    win = {"bad_codes": 0, "segment_dispatched": [4 * world.n],
           "segment_s": [1.0], "dispatched": 4 * world.n, "wall_s": 1.0,
           "ticks": 4, "segments": 1}
    out = mode.finish(world, plan, win, None)
    assert all(out["checks"].values()) and out["failed"] == 0, out
    assert out["metrics"]["msgs_per_s"] == 4 * world.n
    # a segment that dispatched less than K x bodies is not correct
    short = mode.finish(world, plan, {**win, "segment_dispatched":
                                      [4 * world.n - 1]}, None)
    assert not short["checks"]["every_segment_dispatched_k_x_bodies"]
    assert short["failed"] == 1


# ---- the references alone: the constants and the actor reading

def test_system_0_prints_the_sources_energies():
    """-0.169075164 before any step and -0.169087605 after 1,000 steps
    of the source's pairwise advance in float64: the constants, the
    units, offset_momentum and the integrator are the source's."""
    start = ref.ensemble(1, 0, 1e-3)
    before = ref.energy(start["pos"], start["vel"], start["m"])[0]
    assert f"{before:.9f}" == "-0.169075164"
    pos, vel = ref.advance_f64(start["pos"], start["vel"], start["m"], 1000)
    after = ref.energy(pos, vel, start["m"])[0]
    assert f"{after:.9f}" == "-0.169087605"
    # the replay works on copies: what it started from is as it was
    assert ref.energy(start["pos"], start["vel"], start["m"])[0] == before
    p, scale = ref.momentum(start["vel"], start["m"])
    assert p[0] < 1e-15 * scale[0]


@pytest.mark.parametrize("seed", [1, 2**31 + 7])
def test_the_rings_visitor_order_is_the_sources_pairwise_advance(seed):
    """In float64 the actor reading (body i pulled by i-1, i-2, i-3, i-4
    in turn, each from its own side) and the source's pair loop agree to
    1e-12 after 1,000 steps: the same pulls in another order."""
    start = ref.ensemble(16, seed, 1e-3)
    a_pos, a_vel = ref.advance_f64(start["pos"], start["vel"], start["m"],
                                   1000)
    r_pos, r_vel = ref.ring_f64(start["pos"], start["vel"], start["m"], 1000)
    assert np.abs(a_pos - r_pos).max() < 1e-12
    assert np.abs(a_vel - r_vel).max() < 1e-12
    # and `Ticks` in float64 IS that ring step: 8 steps, to the bit
    t = ref.Ticks(start["pos"], start["vel"], start["m"],
                  np.float64).advance(32).observed()
    r_pos, r_vel = ref.ring_f64(start["pos"], start["vel"], start["m"], 8)
    for i, k in enumerate(("x", "y", "z")):
        assert np.array_equal(t[k], r_pos[..., i])
        assert np.array_equal(t["v" + k], r_vel[..., i])


def test_the_ensemble_is_the_seeds():
    a, b = ref.ensemble(8, 2**31 + 1, 1e-3), ref.ensemble(8, 2**31 + 1, 1e-3)
    c = ref.ensemble(8, 2**31 + 2, 1e-3)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["vel"], c["vel"])
    assert np.array_equal(a["pos"], c["pos"]) and np.array_equal(a["m"],
                                                                 c["m"])
    p, scale = ref.momentum(a["vel"], a["m"])
    assert (p < 1e-14 * scale).all()
