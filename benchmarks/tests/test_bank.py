"""The bank's reference checked by hand, the cell end to end on the CPU
backend at a tiny size, and the cell's own layer metrics on recorded
contexts."""

import importlib
import json

import numpy as np
import pytest

from benchmarks import phase_trace, reference_bank as ref, run
from benchmarks.tests.conftest import ROOT

CELL = "savina-bank.transfers"
# two banks of Savina's 1,000 accounts; the teller's batch, what it has
# out and the ring cut together (in_flight / BATCH stays near 2.5)
SCALE = {"actors": 2 * 1001, "teller_batch": 12, "teller_mailbox_cap": 32}
NEW = ("tx_per_tick", "round_trip_ticks", "requeue_pct", "teller_depth",
       "dispatch_account_ms", "dispatch_teller_ms")


def reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}").read


def test_eight_transactions_worked_by_hand():
    """One bank of ten accounts from 1,000 each, four transactions out,
    the teller takes one message a tick and an account two. The teller
    issues, in order (source, destination, amount):

        1 (0,1,10)  2 (0,2,20)  3 (1,2,30)  4 (3,4,40)
        5 (0,5,50)  6 (3,9,60)  7 (1,3,70)  8 (2,4,80)

    tick 2  a0 handles credit 1 (-10, debit to a1, waits).
    tick 3  a0 sets credit 2 aside; a1 takes debit 1 (+10), replies.
    tick 4  a0: the reply first (forwards it, stops waiting), THEN
            credit 2 in the same batch (-20, debit to a2, waits again);
            a1 handles credit 3 (-30, debit to a2). a2 now holds both
            debits, a1's first: a1 sent in batch slot 0, a0 in slot 1.
    tick 5  the teller counts reply 1 and issues 5 to a0; a2 takes both
            debits (+30, +20) and replies to a1 and a0; a3 handles
            credit 4. a0 now holds [credit 5, reply]: the teller's send
            is delivered before the accounts'.
    tick 6  a0 sets credit 5 aside (still waiting for 2's reply), then
            forwards that reply; a1 forwards 3's; a4 takes debit 4.
    tick 7  the teller counts 3's reply, issues 6 to a3; a0 handles
            credit 5 at last; a3 forwards 4's reply.
    tick 8  teller: 2's reply, issues 7 to a1; a3 handles credit 6; a5
            takes debit 5, replies.
    tick 9  teller: 4's reply, issues 8 to a2; a0 forwards 5's reply;
            a1 handles credit 7 (debit to a3); a9 takes debit 6 and
            replies to a3, which now holds [debit 7, reply]."""
    script = [[(0, 1, 10), (0, 2, 20), (1, 2, 30), (3, 4, 40), (0, 5, 50),
               (3, 9, 60), (1, 3, 70), (2, 4, 80)]]
    t = ref.Ticks(1, 10, sources=8, amount_max=1000, in_flight=4,
                  teller_batch=1, account_batch=2, teller_ring=(8, 6, 2),
                  account_ring=(4, 3, 1), balance0=1000, rng0=[1],
                  script=script)
    t.advance(4)
    assert t.queued().tolist() == [1, 0, 0, 2, 1, 0, 0, 0, 0, 0, 0]
    assert t.q_kind[t.q_tgt == 0].tolist() == [ref.T_REPLY]
    assert t.q_w[t.q_tgt == 3].tolist() == [[2, 30], [1, 20]]  # a1's, a0's
    assert t.balance[:3].tolist() == [970, 980, 1000]
    assert t.waiting[:3].tolist() == [1, 1, 0]
    assert (t.requeued[0], t.forwarded[0]) == (1, 1)
    t.advance(2)
    assert (int(t.issued[0]), int(t.completed[0])) == (5, 1)
    assert t.queued().tolist() == [2, 1, 0, 0, 1, 0, 0, 0, 0, 0, 0]
    assert t.requeued[0] == 2 and t.waiting[:5].tolist() == [0, 0, 0, 1, 0]
    t.advance(3)
    seen = t.observed()
    assert (seen["issued"].tolist(), seen["completed"].tolist()) == ([8], [4])
    assert seen["balance"].tolist() == [920, 910, 1050, 900, 1040, 1050,
                                        1000, 1000, 1000, 1060]
    assert seen["waiting"].tolist() == [0, 1, 0, 1, 0, 0, 0, 0, 0, 0]
    assert seen["requeued"].tolist() == [2] + [0] * 9
    assert seen["forwarded"].tolist() == [3, 1, 0, 1, 0, 0, 0, 0, 0, 0]
    assert seen["n_out"].tolist() == [3, 2, 0, 2, 0, 0, 0, 0, 0, 0]
    assert seen["out_sum"].tolist() == [80, 100, 0, 100, 0, 0, 0, 0, 0, 0]
    assert seen["n_in"].tolist() == [0, 1, 2, 0, 1, 1, 0, 0, 0, 1]
    assert seen["in_sum"].tolist() == [0, 10, 50, 0, 40, 50, 0, 0, 0, 60]
    assert seen["teller_queued"].tolist() == [1]
    assert seen["account_queued"].tolist() == [0, 0, 1, 2, 0, 0, 0, 0, 0, 0]
    assert not seen["stray"].any() and not seen["violations"].any()
    assert seen["n_mutes"] == 0 and not seen["muted"].any()
    # a3 holds debit 7 in front of 6's reply: a1's row sent before a9's
    assert t.q_kind[t.q_tgt == 4].tolist() == [ref.DEBIT, ref.A_REPLY]
    kept = ref.invariant(seen, banks=1, accounts=10, in_flight=4,
                         amount_max=1000, balance0=1000)
    assert kept["deficit"] == 0 and all(kept["checks"].values()), kept
    # 70 on the wire: what the accounts hold and what is out add up
    assert seen["balance"].sum() + 70 == 10 * 1000


def test_the_reference_refuses_what_it_does_not_model():
    """More out than a ring holds would be rejected by the program; the
    reference says so and does not guess."""
    t = ref.Ticks(1, 10, sources=8, amount_max=9, in_flight=6,
                  teller_batch=6, account_batch=1, teller_ring=(8, 6, 2),
                  account_ring=(4, 3, 1), balance0=100, rng0=[1],
                  script=[[(0, 1, 1)] * 6])
    with pytest.raises(RuntimeError, match="would hold"):
        t.advance(1)


def last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_is_correct(trace, capsys):
    with open(f"{ROOT}/BENCHMARK.json") as f:
        bench = json.load(f)
    rc = run.main(["--workload", CELL, "--seed", str(2**31 + 11),
                   "--seconds", "1", "--trace", str(trace),
                   "--platform", "cpu"], scale=SCALE)
    assert rc == 0
    result = last_line(capsys)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    got = result["metrics"]
    if not trace:
        assert set(got) == {"msgs_per_s", "setup_s"}
        assert got["msgs_per_s"]["value"] > 0
        return
    declared = {m["name"]: m for m in bench["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    assert set(got) <= set(declared)
    assert got["compiles_in_window"]["value"] == 0
    # the counters' metrics need no device; the two device shares do
    assert {"tx_per_tick", "round_trip_ticks", "requeue_pct", "teller_depth",
            "mutes_per_tick"} <= set(got)
    assert 4.0 <= got["round_trip_ticks"]["value"] < 8.0
    assert got["tx_per_tick"]["value"] * got["round_trip_ticks"]["value"] \
        == pytest.approx(2 * 32)
    assert 0 < got["requeue_pct"]["value"] < 50


def test_counter_readers_on_a_recorded_window():
    """A window of the cell's shape (1,024 banks, 256 out each), and a
    window of another mode."""
    ctx = {"window": {"ticks": 128, "transactions": 7_340_032 // 4 // 2,
                      "in_flight": 262_144, "teller_depth": 31.25,
                      "protocol": {"n_processed": 33_000_000,
                                   "requeued": 3_960_000, "n_mutes": 0}}}
    assert reader("tx_per_tick")(ctx) == 7168.0
    assert reader("round_trip_ticks")(ctx) == pytest.approx(36.571, abs=1e-3)
    assert reader("requeue_pct")(ctx) == 12.0
    assert reader("teller_depth")(ctx) == 31.25
    other = {"window": {"ticks": 128, "segment_s": [1.0]}}
    for name in NEW[:4]:
        assert reader(name)(other) is None, name


def test_cohort_readers_on_a_trace_with_and_without_the_scopes(monkeypatch):
    """Each cohort's share of `dispatch` is read off its own scope, and
    below it; a program that names no cohort's share (the parent)
    leaves both metrics out, and `phase_dispatch_ms` reads the same
    either way."""
    def device(account, teller):
        return [[["fusion.1", 0.0, 4000.0,
                  f"jit(multi)/while/body/pony/dispatch/{account}"
                  "cond/branch_1_fun/while/body/select_n"],
                 ["fusion.2", 4000.0, 1000.0,
                  f"jit(multi)/while/body/pony/dispatch/{account}"
                  "cond/branch_1_fun/pony/drain/select_n"],
                 ["fusion.3", 5000.0, 500.0,
                  f"jit(multi)/while/body/pony/dispatch/{teller}"
                  "cond/branch_1_fun/while/body/add"],
                 ["fusion.4", 5500.0, 250.0,
                  "jit(multi)/while/body/pony/dispatch/add"]]]
    host = [["segment", 0.0, 6000.0, None, None]]
    ctx = {"trace": {"ticks": 2}, "window": {}}
    named = phase_trace.reduce(
        {"device": device("pony/dispatch/cohort/Account/",
                          "pony/dispatch/cohort/Teller/"), "host": host}, 2)
    monkeypatch.setattr(phase_trace, "of_run", lambda _ctx: named)
    assert reader("dispatch_account_ms")(ctx) == pytest.approx(2e-3)
    assert reader("dispatch_teller_ms")(ctx) == pytest.approx(0.25e-3)
    total = reader("phase_dispatch_ms")(ctx)
    assert total == pytest.approx((4000 + 1000 + 500 + 250) / 2 * 1e-6)
    parents = phase_trace.reduce({"device": device("", ""), "host": host}, 2)
    monkeypatch.setattr(phase_trace, "of_run", lambda _ctx: parents)
    assert reader("dispatch_account_ms")(ctx) is None
    assert reader("dispatch_teller_ms")(ctx) is None
    assert reader("phase_dispatch_ms")(ctx) == pytest.approx(total)


def test_every_new_metric_is_declared_for_the_cell():
    with open(f"{ROOT}/BENCHMARK.json") as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW:
        assert declared[name]["moves"] == "msgs_per_s"
        assert declared[name]["workloads"] == [CELL]
        assert callable(reader(name))
    for name in ("tick_roofline", "setup_build_s", "setup_cold_launch_s",
                 "phase_dispatch_ms", "mutes_per_tick"):
        assert CELL in declared[name]["workloads"], name
