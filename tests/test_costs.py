"""The compiler's cost record (costs.capture).

Two layers under test:
1. the capture — XLA's cost/memory analysis of the runtime's REAL
   compiled executables (capture / Runtime.measured_costs /
   opts.cost_capture), memoized, never advancing the world;
2. the operational surfaces — /metrics gauges, the flight-recorder
   postmortem's measured section (gracefully absent where no capture
   ran) and `doctor --postmortem`; none of them carries a verdict.

The symbol-table half of the module is tests/test_window_symbols.py's.
"""

import os

import pytest

from ponyc_tpu import RuntimeOptions, costs
from ponyc_tpu.models import ring


def _opts(**kw):
    base = dict(mailbox_cap=8, batch=1, max_sends=1, msg_words=1,
                spill_cap=64, inject_slots=8)
    base.update(kw)
    return RuntimeOptions(**base)


def _ring(**kw):
    rt, ids = ring.build(8, _opts(**kw))
    return rt, ids


@pytest.fixture(scope="module")
def plain_rt():
    """One started ring world shared by the capture-path tests below —
    each test stays independently runnable (capture compiles on demand)
    but a full-file run pays the build + AOT compiles once."""
    rt, ids = _ring()
    yield rt, ids
    rt.stop()


@pytest.fixture(scope="module")
def cc_rt():
    """One cost_capture=True world shared by the eager-capture /
    postmortem / doctor surface tests."""
    rt, ids = _ring(cost_capture=True)
    yield rt, ids
    rt.stop()


# ------------------------------------------------------ measured capture

def test_capture_reads_real_executables_and_memoizes(plain_rt):
    rt, _ = plain_rt
    steps0 = rt.steps_run
    cap = costs.capture(rt)
    # AOT lowering must not advance the world.
    assert rt.steps_run == steps0
    assert cap["version"] == costs.COST_VERSION
    assert set(cap["executables"]) == {"step", "window"}
    for rec in cap["executables"].values():
        assert "error" not in rec
        # CPU reports both analyses on jaxlib 0.4.x; every field is
        # at worst None, never missing.
        assert {"flops", "bytes_accessed", "peak_bytes"} <= set(rec)
        assert rec["bytes_accessed"] and rec["bytes_accessed"] > 0
    # Memoized: same object back, and measured_costs() is the accessor.
    assert costs.capture(rt) is cap
    assert rt.measured_costs() is cap
    assert rt.measured_costs(force=True) is not cap


def test_cost_capture_option_runs_at_start(cc_rt):
    rt, _ = cc_rt
    # start()'s eager capture is the record measured_costs() returns,
    # and it is the executables' record alone.
    assert rt._costs is not None and rt.measured_costs() is rt._costs
    assert set(rt._costs) == {"version", "backend", "delivery",
                              "executables"}


def test_capture_requires_started_runtime():
    from ponyc_tpu import Runtime
    rt = Runtime(_opts())
    rt.declare(ring.RingNode, 8)
    with pytest.raises(RuntimeError, match="start"):
        costs.capture(rt)


# -------------------------------------------------- operational surfaces

def test_metrics_exports_phases_and_measured_gauges():
    from ponyc_tpu import metrics
    rt, ids = _ring(analysis=1, cost_capture=True)
    rt.send(int(ids[0]), ring.RingNode.token, 20)
    rt.run()
    snap = metrics.snapshot(rt)
    assert snap["phases"]["dispatch"] == 20
    text = metrics.prometheus_text(snap)
    parsed = metrics.parse_prometheus(text)
    assert parsed[("pony_tpu_phase_work_total",
                   (("phase", "delivery"),))] == 20
    assert parsed[("pony_tpu_measured_bytes_accessed",
                   (("executable", "step"),))] > 0
    assert not [k for k in parsed if "divergence" in k[0]]
    rt.stop()


def test_postmortem_carries_and_renders_measured(cc_rt):
    from ponyc_tpu.flight import render_postmortem
    rt, _ = cc_rt
    pm = rt._flight.postmortem("manual")
    assert pm["measured"] is rt._costs
    text = render_postmortem(pm)
    assert "measured [step]" in text
    assert "model vs measured" not in text
    # A postmortem of a run that captured nothing has no "measured"
    # key: render degrades.
    del pm["measured"]
    assert "measured [" not in render_postmortem(pm)


def test_doctor_renders_measured_from_postmortem_file(cc_rt, tmp_path,
                                                      capsys):
    from ponyc_tpu.__main__ import cmd_doctor
    rt, _ = cc_rt
    path = str(tmp_path / "w.postmortem.json")
    rt._flight.dump("manual", path=path, out=open(os.devnull, "w"))
    assert cmd_doctor(["--postmortem", path]) == 0
    assert "measured [step]" in capsys.readouterr().out
