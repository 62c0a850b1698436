"""Savina's Bank Transaction (`benchmarks/worlds/bank.py`, the world of
the cell `savina-bank.transfers`) against its plain references
(`benchmarks/reference_bank.py`), on the CPU at small sizes.

The deployment is request -> reply round trips through a contended
coordinator: three behaviours live in one cohort, a receive that
depends on the receiver's state (an account in reply mode sets a credit
or a debit aside), a teller that drains a batch a hundred times its
accounts'. The tests cut the teller's BATCH, what it has out and the
ring together with the banks, keeping `in_flight / BATCH` near 2.5 so
that the seeding is out before the first reply is back.
"""

import contextlib
import json
import os

import numpy as np
import pytest

from benchmarks import reference_bank as ref
from benchmarks.modes import throughput_bank as mode
from benchmarks.worlds import bank, ubench
from _hlo import bare_hlo

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BANKS, ACCOUNTS = 8, 40


def _files(config="savina-bank", mix="transfers"):
    with open(os.path.join(ROOT, f"benchmarks/configs/{config}.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, f"benchmarks/traffic/{mix}.json")) as f:
        return cfg, json.load(f)


def _world(seed, *, teller_batch=8, in_flight=20, teller_cap=32,
           lax_credit=False, **options):
    cfg, mix = _files()
    cfg.update(actors=BANKS * (ACCOUNTS + 1), accounts_per_bank=ACCOUNTS,
               teller_batch=teller_batch, teller_mailbox_cap=teller_cap)
    cfg["runtime_options"] = {**cfg["runtime_options"], "mailbox_cap": 16,
                              "compile_cache": "off", "tuning_cache": "off",
                              **options}
    return bank.World(cfg, {**mix, "in_flight": in_flight}, seed,
                      lax_credit=lax_credit)


def _same(seen: dict, want: dict, tick: int) -> None:
    for key in mode.KEYS:
        got, ref_ = np.asarray(seen[key]), np.asarray(want[key])
        off = np.flatnonzero(got != ref_)
        assert off.size == 0, (tick, key, off[:8], got.reshape(-1)[off[:8]],
                               ref_.reshape(-1)[off[:8]])


def _kept(world, seen=None) -> dict:
    kept = world.invariant(world.observed() if seen is None else seen)
    assert kept["deficit"] == 0 and all(kept["checks"].values()), kept
    return kept


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_every_actor_follows_the_protocol_tick_by_tick(seed):
    """24 ticks against `reference_bank.Ticks` on every actor: balances,
    reply mode, what was set aside, every mailbox's depth, the tellers'
    generators; then the invariant after 200."""
    world = _world(seed)
    for tick in range(1, 25):
        assert world.rt.run(max_steps=1) == 0
        _same(world.observed(), world.reference(tick), tick)
    seen = world.observed()
    assert seen["completed"].min() > 0 and seen["requeued"].sum() > 0
    assert (seen["issued"] - seen["completed"] == world.in_flight).all()
    world.rt.run(max_steps=176)
    seen = _kept(world) and world.observed()
    _same(seen, world.reference(200), 200)
    assert world.dispatches(seen) == world.rt.counter("n_processed")
    assert not any(world.rt.counter(c) for c in mode.ERROR_COUNTERS)
    world.rt.check_invariants()
    world.rt.stop()


def test_the_drain_as_a_loop_is_the_same_drain(monkeypatch):
    """Past `engine.DRAIN_UNROLL` selects a cohort's drain runs one take
    as the body of a loop (the cell's tellers: 100 takes from a ring of
    256); forced on every cohort here, the world is the reference's."""
    from ponyc_tpu.runtime import engine
    monkeypatch.setattr(engine, "DRAIN_UNROLL", 0)
    world = _world(7)
    text = _window_text(world.rt)
    assert "pony/drain/while/body" in text
    for tick in range(1, 13):
        world.rt.run(max_steps=1)
        _same(world.observed(), world.reference(tick), tick)
    world.rt.stop()
    monkeypatch.undo()
    world = _world(7)
    assert "pony/drain/while" not in _window_text(world.rt)
    world.rt.stop()


def test_a_credit_that_skips_reply_mode_fails_one_transfer_at_a_time():
    """The check can fail: an account whose `credit` does not honour
    reply mode takes a second transfer while the first is out, and both
    the violation counter and the stray reply show it. Money is still
    conserved: the check that fails is the one that should."""
    world = _world(5, lax_credit=True)
    world.rt.run(max_steps=60)
    seen = world.observed()
    kept = world.invariant(seen)
    assert seen["violations"].sum() > 0 and seen["stray"].sum() > 0
    assert not kept["checks"]["one_transfer_at_a_time"]
    assert kept["checks"]["money_conserved_every_account"]
    assert kept["deficit"] > 0
    _same(seen, world.reference(60), 60)    # the reference breaks alike
    world.rt.stop()


def test_nothing_is_rejected_with_a_whole_ring_out():
    """`in_flight` = the teller's ring, the cell's own ratio: a bank
    never holds more messages than its teller's ring has slots, so
    nothing is rejected or spilled however the transactions bunch."""
    world = _world(9, teller_batch=12, in_flight=32, teller_cap=32)
    for _ in range(4):
        assert world.rt.run(max_steps=50) == 0
        st = world.rt.state
        assert world.rt.counter("n_rejected") == 0
        assert int(np.asarray(st.dspill_count).sum()) == 0
        assert not np.asarray(st.spill_overflow).any()
        _kept(world)
    assert world.observed()["completed"].min() > 100
    world.rt.stop()


def test_a_swamped_teller_mutes_and_releases_as_the_reference_says():
    """With the overload line pulled down to 8 of 32 a teller that still
    holds its seeding mutes the accounts that reply to it: the reference
    models the line (who is muted, the mutes so far) tick by tick, the
    invariant holds with accounts muted, and nobody is stranded."""
    world = _world(11, teller_batch=4, in_flight=32, teller_cap=32,
                   mailbox_cap=64, overload_threshold=0.25,
                   unmute_threshold=0.1)
    muted_ticks = 0
    for tick in range(1, 61):
        world.rt.run(max_steps=1)
        seen = world.observed()
        _same(seen, world.reference(tick), tick)
        muted_ticks += bool(seen["muted"].any())
    assert seen["n_mutes"] > 100 and muted_ticks > 20
    _kept(world, seen)
    while not seen["muted"].any():
        world.rt.run(max_steps=1)
        seen = world.observed()
    assert mode._stranded(world, seen) == 0
    # the check can fail: muted a tick on behind a teller with nothing left
    stuck = {**seen, "teller_queued": np.zeros_like(seen["teller_queued"])}
    assert world.ring(world.Teller) == (32, 8, 3)
    assert ref.stranded(stuck, seen, accounts=ACCOUNTS, unmute_occ=3) \
        == int(seen["muted"].sum()) > 0
    world.rt.stop()


def test_invariant_catches_a_lost_and_a_duplicated_transaction():
    world = _world(2)
    world.rt.run(max_steps=40)
    seen = _kept(world) and world.observed()
    lost = {**seen, "account_queued": seen["account_queued"].copy()}
    lost["account_queued"][np.argmax(seen["account_queued"])] -= 1
    found = world.invariant(lost)
    assert found["deficit"] == 1
    assert not found["checks"]["exactly_once_every_bank"]
    twice = {**seen, "completed": seen["completed"].copy()}
    twice["completed"][0] -= 1              # a reply its teller never counted
    assert not world.invariant(twice)["checks"]["exactly_once_every_bank"]
    short = {**seen, "balance": seen["balance"].copy()}
    short["balance"][7] -= 1
    found = world.invariant(short)
    assert not found["checks"]["money_conserved_every_account"]
    assert found["deficit"] == 1
    world.rt.stop()


def test_sizes_follow_actors_and_a_stays_savinas():
    cfg, mix = _files()
    assert bank.sizes(cfg["actors"], cfg["accounts_per_bank"],
                      mix["in_flight"]) == cfg["sizes"]
    assert cfg["teller_mailbox_cap"] == mix["in_flight"]
    assert ref.sources_of(cfg["accounts_per_bank"], mix["source_share"]) \
        == 800
    # a self-test's scale cuts the banks, never A
    assert bank.sizes(2048, 1000, 256)["banks"] == 2
    with pytest.raises(ValueError, match="hold no bank"):
        bank.sizes(1000, 1000, 256)
    with pytest.raises(ValueError, match="no source"):
        ref.sources_of(9, 0.8)
    # the stated size under another rule is refused before anything is built
    with pytest.raises(ValueError, match="states"):
        bank.build({**cfg, "accounts_per_bank": 500}, mix, 0)


def test_generate_work_keeps_savinas_rules():
    """The source is one of the first 80%, the destination above it,
    the amount 1..amount_max, whatever the generator holds."""
    rng = np.random.default_rng(4).integers(0, 2**32, 50_000,
                                            dtype=np.uint64)
    _, src, dest, amount = ref.generate_work(rng.astype(np.uint32),
                                             1000, 800, 1000)
    assert src.min() == 0 and src.max() == 799
    assert (dest > src).all() and dest.max() == 999
    assert amount.min() == 1 and amount.max() == 1000


def _window_text(rt, compiled=False):
    import jax
    from ponyc_tpu.runtime import engine
    gated = jax.jit(engine.build_multi_step_gated(rt.program, rt.opts))
    lowered = gated.lower(rt.state, *rt._empty_inject, jax.numpy.int32(4),
                          jax.numpy.bool_(True), rt._zero_aux)
    if compiled:
        return lowered.compile().as_text()
    return lowered.as_text(debug_info=True)


def test_each_cohort_has_its_own_dispatch_scope():
    """The lowered window names each cohort's share of `dispatch`
    (`state.cohort_scope`); the drain inside it keeps `pony/drain`."""
    from benchmarks import phase_trace
    from ponyc_tpu.runtime.state import SCOPE_PREFIX, cohort_scope
    world = _world(1)
    text = _window_text(world.rt)
    world.rt.stop()
    for name in ("Teller", "Account"):
        scope = cohort_scope(name)
        assert scope == f"dispatch/cohort/{name}"
        assert f"{SCOPE_PREFIX}/{scope}/" in text, name
    assert f"{SCOPE_PREFIX}/{cohort_scope('Account')}/cond/branch_1_fun/" \
        f"{SCOPE_PREFIX}/drain/" in text
    # as the yardstick reads an op_name: the innermost scope names it
    inner = f"jit(multi)/while/body/{SCOPE_PREFIX}/dispatch/{SCOPE_PREFIX}/" \
        f"{cohort_scope('Account')}/cond/branch_1_fun/"
    assert phase_trace.scope_of(inner + "while/body/select_n") \
        == "dispatch/cohort/Account"
    assert phase_trace.scope_of(inner + f"{SCOPE_PREFIX}/drain/select_n") \
        == "drain"


def _ubench_window(compiled=False):
    cfg, mix = _files("ubench-1m", "random")
    cfg["actors"] = 256
    cfg["runtime_options"] = {**cfg["runtime_options"],
                              "compile_cache": "off", "tuning_cache": "off"}
    world = ubench.build(cfg, mix, 1)
    text = _window_text(world.rt, compiled)
    world.rt.stop()
    return text


def test_the_dispatch_total_of_a_ubench_world_is_unchanged(monkeypatch):
    """What a reader sums under `pony/dispatch` is what it was: with the
    cohort's scope folded back into `dispatch` the window carries the
    same op_names, and compiled it is the same program."""
    import re

    from ponyc_tpu.runtime import engine, state

    def under_dispatch(text):
        names = re.findall(r'op_name="([^"]*)"', text) or \
            re.findall(r'loc\("([^"]*)"', text)
        return sorted(n.replace("/pony/dispatch/cohort/RandomPinger", "")
                      for n in names if "pony/dispatch" in n)

    scoped = _ubench_window()
    assert "pony/dispatch/cohort/RandomPinger/" in scoped
    compiled = _ubench_window(compiled=True)
    monkeypatch.setattr(engine, "cohort_scope", lambda _name: "dispatch")
    folded = _ubench_window()
    assert "dispatch/cohort" not in folded
    assert len(under_dispatch(scoped)) == len(under_dispatch(folded)) > 0
    monkeypatch.setattr(state, "_named_scope",
                        lambda _name: contextlib.nullcontext())
    assert bare_hlo(compiled) == bare_hlo(_ubench_window(compiled=True))
