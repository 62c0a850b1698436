"""The reduction from a trace to numbers: on hand-made data whose
answer is known, and on a trace recorded on the v5e."""

import os

import pytest

from benchmarks import reduce_trace

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "recorded_trace.json.gz")


def plane(name, line, events):
    return {"name": name, "lines": [{"name": line, "events": events}]}


def hand_made():
    # nanoseconds. Host: two segments with a gap between them.
    host = plane("/host:CPU", "python3", [
        ["segment", 1000.0, 4000.0],            # 1000..5000
        ["between-segments", 5000.0, 1000.0],   # 5000..6000
        ["segment", 6000.0, 4000.0]])           # 6000..10000
    dev = plane("/device:TPU:0", "XLA Ops", [
        ["early", 0.0, 1500.0],                 # cut to 1000..1500
        ["while", 2000.0, 2000.0],              # 2000..4000, parent of:
        ["fusion.1", 2100.0, 900.0],
        ["fusion.2", 3000.0, 500.0],
        ["fusion.1", 6500.0, 1000.0],           # 6500..7500
        ["late", 9500.0, 2000.0]])              # cut to 9500..10000
    other = plane("/device:TPU:0", "XLA Modules", [["jit_f", 0.0, 12000.0]])
    dev["lines"] += other["lines"]
    return {"planes": [host, dev]}


def test_hand_made_trace():
    r = reduce_trace.reduce(hand_made())
    assert r["devices"] == 1
    assert r["span_s"] == pytest.approx(9000e-9)
    # busy: 500 + 2000 + 1000 + 500
    assert r["busy_s"] == pytest.approx(4000e-9)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(1900e-9)
    assert ops["while"] == pytest.approx(600e-9)      # self time only
    assert ops["fusion.2"] == pytest.approx(500e-9)
    assert r["device_ops"][0][0] == "fusion.1"
    gaps = dict(r["idle_gaps"])
    # idle: 1500..2000, 4000..6500 (middle 5250: between-segments),
    # 7500..9500
    assert gaps["segment"] == pytest.approx(2500e-9)
    assert gaps["between-segments"] == pytest.approx(2500e-9)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["span_s"])


def test_two_devices_are_averaged():
    data = hand_made()
    second = plane("/device:TPU:1", "XLA Ops", [["fusion.1", 1000.0, 9000.0]])
    data["planes"].append(second)
    r = reduce_trace.reduce(data)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((4000e-9 + 9000e-9) / 2)


def test_no_device_plane_reads_nothing():
    data = {"planes": [hand_made()["planes"][0]]}
    assert reduce_trace.reduce(data) is None
    assert reduce_trace.reduce_dir("/nonexistent") is None


def test_no_annotations_uses_the_device_span():
    data = {"planes": [hand_made()["planes"][1]]}
    r = reduce_trace.reduce(data)
    assert r["span_s"] == pytest.approx(11500e-9)
    assert dict(r["idle_gaps"]).keys() == {"unannotated"}


def test_recorded_v5e_trace():
    """A short ring run on the v5e, cut down (README). The numbers below
    were read off this file by hand when it was recorded."""
    data = reduce_trace.load_recorded(RECORDED)
    r = reduce_trace.reduce(data)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["span_s"]
    assert len(r["device_ops"]) <= reduce_trace.TOP
    assert sum(s for _, s in r["idle_gaps"]) + r["busy_s"] == \
        pytest.approx(r["span_s"], rel=1e-6)
    expected = data["expected"]
    assert r["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    assert r["span_s"] == pytest.approx(expected["span_s"], rel=1e-9)
    assert r["device_ops"][0][0] == expected["top_op"]
