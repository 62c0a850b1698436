"""Causal message tracing tests (PROFILE.md §10): on-device trace
propagation through the mailbox ring side lanes, span reassembly into
causal trees, deterministic sampling, the zero-cost-when-off jaxpr
guarantee, the traced-vs-untraced differential, Perfetto flow-event
export, and the `trace` CLI — all tier-1 fast."""

import json
import os

import pytest

from ponyc_tpu import (I32, Ref, Runtime, RuntimeOptions, actor,
                       analysis, behaviour)
from ponyc_tpu.models import ring
from ponyc_tpu.tracing import Tracer, consistent, load_spans, reassemble

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _opts(**kw):
    base = dict(mailbox_cap=8, batch=1, max_sends=1, msg_words=1,
                spill_cap=64, inject_slots=8, analysis=3,
                trace_sample=1)
    base.update(kw)
    return RuntimeOptions(**base)


# A 3-deep causal chain: inject -> Src.go -> Mid.relay -> Sink.take.

@actor
class Sink:
    n: I32

    @behaviour
    def take(self, st, v: I32):
        return {**st, "n": st["n"] + v}


@actor
class Mid:
    out: Ref[Sink]

    @behaviour
    def relay(self, st, v: I32):
        self.send(st["out"], Sink.take, v)
        return st


@actor
class Src:
    out: Ref[Mid]

    @behaviour
    def go(self, st, v: I32):
        self.send(st["out"], Mid.relay, v)
        return st


def _chain(opts):
    rt = Runtime(opts)
    rt.declare(Src, 2).declare(Mid, 2).declare(Sink, 2).start()
    sinks = rt.spawn_many(Sink, 2)
    mids = rt.spawn_many(Mid, 2, out=sinks)
    srcs = rt.spawn_many(Src, 2, out=mids)
    return rt, srcs, mids, sinks


# ------------------------------------------------------- propagation

@pytest.mark.parametrize("delivery", ["plan", "cosort"])
def test_propagation_three_deep_chain(delivery):
    """Acceptance: a sampled injection reassembles into a causal tree
    whose span ticks are consistent (enq <= disp <= retire, children
    nested under parents) across BOTH delivery formulations."""
    rt, srcs, _mids, _sinks = _chain(_opts(delivery=delivery))
    rt.send(int(srcs[0]), Src.go, 7)
    assert rt.run(max_steps=200) == 0
    trees = rt.traces()
    assert len(trees) == 1
    t = next(iter(trees.values()))
    assert t["n_spans"] == 4            # inject + 3 behaviour spans
    assert t["critical_path"] == ["inject", "Src.go", "Mid.relay",
                                  "Sink.take"]
    assert consistent(t)
    # every hop adds latency: the end-to-end number is positive
    assert t["latency"] >= 3
    # explicit nesting walk: each child's enqueue tick is the tick its
    # parent dispatched (the send happened inside that dispatch)
    root = t["roots"][0]
    s = root
    while s.children:
        (c,) = s.children
        assert s.enq <= s.disp <= s.retire
        assert c.enq >= s.disp
        s = c
    assert rt.state_of(int(_sinks[0]))["n"] == 7


def test_fanout_and_fused_dispatch_path():
    """One traced injection fanning out over MAX_SENDS=2 produces one
    tree with two branches; the fused Pallas dispatch path (interpret
    mode on CPU) propagates identically — trace lanes ride the outbox
    layout, not the dispatch implementation."""

    @actor
    class Fan:
        a: Ref[Sink]
        b: Ref[Sink]
        MAX_SENDS = 2

        @behaviour
        def go(self, st, v: I32):
            self.send(st["a"], Sink.take, v)
            self.send(st["b"], Sink.take, v)
            return st

    for fused in (False, True):
        rt = Runtime(_opts(max_sends=2, pallas_fused=fused))
        rt.declare(Fan, 1).declare(Sink, 2).start()
        sinks = rt.spawn_many(Sink, 2)
        fan = rt.spawn(Fan, a=int(sinks[0]), b=int(sinks[1]))
        rt.send(fan, Fan.go, 3)
        assert rt.run(max_steps=100) == 0
        t = next(iter(rt.traces().values()))
        assert t["n_spans"] == 4        # inject + Fan.go + 2×Sink.take
        assert consistent(t)
        fan_span = t["roots"][0].children[0]
        assert fan_span.beh == "Fan.go"
        assert sorted(c.beh for c in fan_span.children) \
            == ["Sink.take", "Sink.take"]


def test_host_behaviour_continues_trace():
    """A traced message delivered to a HOST cohort becomes a host span,
    and the host behaviour's sends continue the chain back onto the
    device — the trace crosses the device/host boundary both ways."""

    @actor
    class HostRelay:
        HOST = True
        out: Ref[Sink]

        @behaviour
        def relay(self, st, v: I32):
            self.send(st["out"], Sink.take, v)
            return st

    rt = Runtime(_opts(msg_words=2))
    rt.declare(HostRelay, 1).declare(Sink, 1).start()
    sink = rt.spawn(Sink)
    hr = rt.spawn(HostRelay, out=sink)
    # inject -> host relay -> device sink: the chain crosses the
    # boundary in both directions.
    rt.send(hr, HostRelay.relay, 5)
    assert rt.run(max_steps=200) == 0
    t = next(iter(rt.traces().values()))
    assert t["critical_path"] == ["inject", "HostRelay.relay",
                                  "Sink.take"]
    assert consistent(t)
    hspan = t["roots"][0].children[0]
    assert hspan.span_id % 2 == 1        # host spans are odd
    assert hspan.children[0].span_id % 2 == 0   # device spans even


# ---------------------------------------------------------- sampling

def test_sampling_deterministic_under_seed():
    a = Tracer(64, seed=7)
    b = Tracer(64, seed=7)
    sa = [a.sample() for _ in range(2048)]
    sb = [b.sample() for _ in range(2048)]
    assert sa == sb
    assert any(sa) and not all(sa)       # ~1-in-64, not degenerate
    c = Tracer(64, seed=8)
    assert [c.sample() for _ in range(2048)] != sa
    # rate sanity: 2048 draws at 1-in-64 ≈ 32 hits
    assert 8 <= sum(sa) <= 128


def test_sampling_deterministic_across_runs():
    """Two identical runs under a fixed seed trace the IDENTICAL set of
    injections — same trace count, same span structure."""
    def run_once():
        rt, ids = ring.build(8, _opts(trace_sample=4, trace_seed=3))
        for i in range(8):
            rt.send(int(ids[i]), ring.RingNode.token, 3)
        rt.run(max_steps=200)
        trees = rt.traces()
        return sorted((tid, t["n_spans"], t["latency"])
                      for tid, t in trees.items())

    first, second = run_once(), run_once()
    assert first == second
    assert 1 <= len(first) < 8           # sampled: some but not all


def test_explicit_trace_ids_and_bulk_send():
    """send(trace=N) / bulk_send(trace=N): the caller's id (the future
    ingress tier's request id) tags the device spans."""
    rt, srcs, _m, _s = _chain(_opts(trace_sample=1000000,
                                    inject_slots=16))
    rt.send(int(srcs[0]), Src.go, 1, trace=77)
    assert rt.run(max_steps=200) == 0
    rt.bulk_send(srcs, Src.go, [2, 2], trace=88)
    assert rt.run(max_steps=200) == 0
    trees = rt.traces()
    assert set(trees) == {77, 88}
    assert trees[77]["critical_path"][-1] == "Sink.take"
    # one root injection, both seeded messages branch under it
    assert trees[88]["n_spans"] == 1 + 2 * 3
    assert consistent(trees[77]) and consistent(trees[88])


# ------------------------------------------------- zero-cost when off

def test_state_carries_no_lanes_when_off():
    for opts in (_opts(trace_sample=0),
                 _opts(analysis=1, trace_sample=8)):
        rt, _ = ring.build(8, opts)
        assert rt.state.trace_buf == {}
        assert rt.state.span_data.size == 0
        assert rt._tracer is None
        with pytest.raises(RuntimeError, match="tracing"):
            rt.traces()


def test_jaxpr_identity_when_off(monkeypatch):
    """Acceptance: with tracing off (analysis<3 or trace_sample=0) the
    step jaxpr is bit-identical to a tracer-free build — proven PR-4
    style by (a) comparing jaxprs across inert knob settings and (b)
    trapping trace_span_lanes, the only source of the lanes."""
    import jax
    import jax.numpy as jnp

    from ponyc_tpu.program import Program
    from ponyc_tpu.runtime import engine, lanes
    from ponyc_tpu.runtime.state import init_state

    def build(analysis, sample):
        opts = RuntimeOptions(mailbox_cap=8, batch=1, max_sends=1,
                              msg_words=1, spill_cap=16, inject_slots=4,
                              analysis=analysis, trace_sample=sample)
        prog = Program(opts)
        prog.declare(ring.RingNode, 8)
        prog.finalize()
        st = init_state(prog, opts)
        step = engine.build_step(prog, opts)
        k = opts.inject_slots
        inj_t = jnp.full((k,), -1, jnp.int32)
        inj_w = jnp.zeros((1 + opts.msg_words + opts.trace_lanes, k),
                          jnp.int32)
        return str(jax.make_jaxpr(step)(st, inj_t, inj_w))

    # trace_sample is inert below analysis 3: bit-identical jaxprs.
    assert build(2, 0) == build(2, 64)
    baseline3 = build(3, 0)

    def boom(*_a, **_k):
        raise AssertionError("trace lanes traced while tracing off")

    monkeypatch.setattr(lanes, "trace_span_lanes", boom)
    assert build(3, 0) == baseline3     # trap unreached, identical
    assert build(2, 64) == build(2, 0)
    with pytest.raises(AssertionError, match="lanes traced"):
        build(3, 1)                     # and it IS the only source


# -------------------------------------------------------- differential

def test_differential_traced_vs_untraced():
    """Acceptance: sampling on changes NOTHING observable — delivery
    order (per-node pass counts), counters and CNF/ACK quiescence
    match an untraced run tick for tick."""
    def run_once(sample):
        rt, ids = ring.build(16, _opts(trace_sample=sample,
                                       inject_slots=16))
        for i in (0, 5, 11):
            rt.send(int(ids[i]), ring.RingNode.token, 20)
        code = rt.run(max_steps=500)
        passes = rt.cohort_state(ring.RingNode)["passes"].tolist()
        return (code, passes, rt.steps_run,
                rt.counter("n_processed"), rt.counter("n_delivered"))

    assert run_once(0) == run_once(1)


# --------------------------------------- span ring bounds / overflow

def test_span_ring_overflow_drops_and_counts():
    rt, ids = ring.build(8, _opts(trace_slots=4, quiesce_interval=64,
                                  pipeline=False))
    rt.send(int(ids[0]), ring.RingNode.token, 40)
    assert rt.run(max_steps=200) == 0
    trees = rt.traces()
    t = next(iter(trees.values()))
    assert rt._tracer.dropped > 0        # ring smaller than the trace
    assert consistent(t)                 # partial tree still consistent
    assert t["n_spans"] < 41


# ------------------------------------- Perfetto / spans.jsonl / CLI

def test_perfetto_flow_event_schema(tmp_path):
    """Acceptance: the Perfetto export carries span slices with flow
    arrows linking sender->receiver spans, plus process/thread name
    metadata for every track (the satellite)."""
    path = str(tmp_path / "an.csv")
    rt, srcs, _m, _s = _chain(_opts(analysis_path=path))
    rt.send(int(srcs[0]), Src.go, 2)
    rt.run(max_steps=200)
    rt.stop()
    spans_path = path + ".spans.jsonl"
    assert os.path.exists(spans_path)
    recs = load_spans(spans_path)
    assert len(recs) == 4
    for r in recs:
        assert set(r) == {"trace", "span", "parent", "beh", "actor",
                          "enq", "disp", "retire"}
    out = str(tmp_path / "t.json")
    analysis.chrome_trace(path, out)
    evs = json.load(open(out))["traceEvents"]
    slices = [e for e in evs if e["ph"] == "X"]
    assert {s["name"] for s in slices} \
        == {"inject", "Src.go", "Mid.relay", "Sink.take"}
    for s in slices:
        assert isinstance(s["ts"], float) and s["dur"] >= 1
    starts = {e["id"]: e for e in evs if e["ph"] == "s"}
    ends = {e["id"]: e for e in evs if e["ph"] == "f"}
    assert len(starts) == 3 and set(starts) == set(ends)  # 3 arrows
    for fid, s in starts.items():
        assert ends[fid]["ts"] >= s["ts"]     # arrow points forward
    # track-name metadata: every tid that appears is labelled
    named = {(e["pid"], e.get("tid")) for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    used = {(e["pid"], e["tid"]) for e in evs
            if e["ph"] in ("X", "s", "f", "i")}
    assert used <= named | {(1, 0)}
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               and "traces" in e["args"]["name"] for e in evs)


def test_trace_cli(tmp_path, capsys):
    from ponyc_tpu.__main__ import main as cli_main
    path = str(tmp_path / "an.csv")
    rt, ids = ring.build(8, _opts(analysis_path=path))
    rt.send(int(ids[0]), ring.RingNode.token, 5)
    rt.run(max_steps=100)
    rt.stop()
    out = str(tmp_path / "cli.json")
    assert cli_main(["trace", path, "-o", out]) == 0
    doc = json.load(open(out))
    assert any(e["ph"] == "s" for e in doc["traceEvents"])
    capsys.readouterr()
    assert cli_main(["trace", "--tree", path + ".spans.jsonl"]) == 0
    tree_out = capsys.readouterr().out
    assert "critical path" in tree_out
    assert "RingNode.token" in tree_out
    # usage errors
    assert cli_main(["trace", "--tree"]) == 2
    assert cli_main(["trace", "--spans"]) == 2
    assert cli_main(["trace", path, "--spans",
                     str(tmp_path / "none.jsonl"), "-o", out]) == 2


def test_top_waiting_for_samples(tmp_path):
    """Satellite: empty, header-only and half-written CSVs render a
    waiting frame instead of crashing."""
    empty = str(tmp_path / "empty.csv")
    open(empty, "w").close()
    assert "waiting for samples" in analysis.top_frame(empty)
    header = str(tmp_path / "h.csv")
    with open(header, "w") as f:
        f.write(",".join(analysis.CSV_COLUMNS) + "\n")
    frame = analysis.top_frame(header)
    assert "waiting for samples" in frame and "no windows" in frame
    partial = str(tmp_path / "p.csv")
    with open(partial, "w") as f:
        f.write(",".join(analysis.CSV_COLUMNS) + "\n")
        f.write("not-a-number,oops")
    assert "waiting for samples" in analysis.top_frame(partial)


def test_top_trace_rows(tmp_path):
    path = str(tmp_path / "an.csv")
    rt, srcs, _m, _s = _chain(_opts(analysis_path=path))
    rt.send(int(srcs[0]), Src.go, 1)
    rt.run(max_steps=200)
    rt.stop()
    frame = analysis.top_frame(path)
    assert "traces: 1" in frame
    assert "Sink.take" in frame


# ------------------------------------------------------- validation

def test_option_validation():
    with pytest.raises(ValueError, match="trace_sample"):
        RuntimeOptions(trace_sample=-1)
    with pytest.raises(ValueError, match="trace_slots"):
        RuntimeOptions(trace_slots=0)
    assert RuntimeOptions(analysis=3, trace_sample=2).tracing
    assert not RuntimeOptions(analysis=2, trace_sample=2).tracing
    assert RuntimeOptions(analysis=3, trace_sample=0).trace_lanes == 0
    assert RuntimeOptions(analysis=3, trace_sample=1).trace_lanes == 2
