"""The verify pass (ponyc_tpu/verify.py ≙ verify/fun.c): per-behaviour
effect signatures by probe tracing, budget enforcement, docgen marks,
and the CLI command."""

import pytest

import _child
from ponyc_tpu import (I32, Ref, Runtime, RuntimeOptions, actor,
                       behaviour)
from ponyc_tpu.verify import (VerifyError, behaviour_effects,
                              verify_behaviour, verify_program)


@actor
class Quiet:
    x: I32

    @behaviour
    def set(self, st, v: I32):
        return {**st, "x": v}


@actor
class Busy:
    out: Ref["Quiet"]
    MAX_SENDS = 2
    SPAWNS = {"Quiet": 1}

    @behaviour
    def go(self, st, v: I32):
        self.send(st["out"], Quiet.set, v)
        self.spawn(Quiet.set, v, when=v > 0)
        self.error_int(7, when=v < 0)
        self.exit(0, when=v == 0)
        return st

    @behaviour
    def lazy(self, st, v: I32):
        self.yield_(when=v > 3)
        self.destroy(when=v > 9)
        return st


def test_effect_signatures():
    eff = behaviour_effects(Quiet.set)
    assert eff.sends == 0 and not eff.can_error and not eff.can_exit
    assert eff.marks() == ""

    eff = behaviour_effects(Busy.go)
    assert eff.sends == 2          # explicit send + the spawn's ctor msg
    assert eff.can_error and eff.can_exit
    assert eff.spawns == (("Quiet", 1),)
    assert "may error" in eff.marks() and "spawns Quiet×1" in eff.marks()

    eff = behaviour_effects(Busy.lazy)
    assert eff.can_yield and eff.can_destroy and eff.sends == 0


def test_budget_violation_fails_verify():
    @actor
    class OverBudget:
        out: Ref["Quiet"]
        MAX_SENDS = 1

        @behaviour
        def go(self, st, v: I32):
            self.send(st["out"], Quiet.set, v)
            self.send(st["out"], Quiet.set, v + 1)
            return st

    with pytest.raises(VerifyError, match="MAX_SENDS=1"):
        verify_behaviour(OverBudget.go)


def test_verify_program_reports_all_device_cohorts():
    rt = Runtime(RuntimeOptions(mailbox_cap=8, batch=1, max_sends=2,
                                msg_words=2, inject_slots=8))
    rt.declare(Busy, 1).declare(Quiet, 4).start()
    report = verify_program(rt.program)
    assert set(report) == {"Busy", "Quiet"}
    assert report["Busy"]["go"].can_error
    assert not report["Quiet"]["set"].can_error


def test_docgen_carries_effect_marks():
    from ponyc_tpu.docgen import document_type
    md = document_type(Busy)
    assert "may error" in md and "spawns Quiet×1" in md


def test_host_behaviours_report_no_device_effects():
    @actor
    class H:
        HOST = True
        n: I32

        @behaviour
        def tick(self, st, v: I32):
            return {**st, "n": st["n"] + 1}

    eff = behaviour_effects(H.tick)
    assert eff.marks() == ""


def test_budget_matches_engine_resolution():
    """Budgets resolve exactly as program build does (review finding):
    opts.max_sends is the fallback, MAX_SENDS=0 is falsy -> fallback."""
    @actor
    class ThreeSends:
        out: Ref["Quiet"]

        @behaviour
        def go(self, st, v: I32):
            self.send(st["out"], Quiet.set, v)
            self.send(st["out"], Quiet.set, v)
            self.send(st["out"], Quiet.set, v)
            return st

    # opts.max_sends=3 -> fine, exactly like the engine
    rt = Runtime(RuntimeOptions(mailbox_cap=8, batch=1, max_sends=3,
                                msg_words=2, inject_slots=8))
    rt.declare(ThreeSends, 1).declare(Quiet, 1).start()
    report = verify_program(rt.program)
    assert report["ThreeSends"]["go"].sends == 3
    # standalone default (2) rejects the same behaviour
    with pytest.raises(VerifyError):
        verify_behaviour(ThreeSends.go)
    # ... unless told the real default
    assert verify_behaviour(ThreeSends.go, default_max_sends=3).sends == 3


def test_string_spawns_target_probes_clean():
    """String-form SPAWNS targets with spawn_sync must probe without a
    bogus state-dict error (review finding: the ctor is claim-only in
    the probe)."""
    @actor
    class SKid:
        x: I32

        @behaviour
        def init(self, st, v: I32):
            return {**st, "x": v}

    @actor
    class SParent:
        MAX_SENDS = 1
        SPAWNS = {"SKid": 1}

        @behaviour
        def make(self, st, v: I32):
            self.spawn_sync(SKid.init, v)
            return st

    eff = behaviour_effects(SParent.make)
    assert eff.sync_spawns == ("SKid",)


def test_cli_verify_reports_fail_lines(tmp_path):
    mod = tmp_path / "vmod.py"
    mod.write_text(
        "from ponyc_tpu import I32, Ref, actor, behaviour\n"
        "@actor\n"
        "class Sink:\n"
        "    x: I32\n"
        "    @behaviour\n"
        "    def put(self, st, v: I32):\n"
        "        return {**st, 'x': v}\n"
        "@actor\n"
        "class Bad:\n"
        "    out: Ref['Sink']\n"
        "    MAX_SENDS = 1\n"
        "    @behaviour\n"
        "    def go(self, st, v: I32):\n"
        "        self.send(st['out'], Sink.put, v)\n"
        "        self.send(st['out'], Sink.put, v)\n"
        "        return st\n")
    r = _child.cli(["verify", "vmod"], cwd=tmp_path)
    assert r.returncode == 1, r.stderr[-500:]
    assert "FAIL Bad.go" in r.stdout and "ok   Sink.put" in r.stdout
