"""The tick is a list of phases (ISSUE 43): `engine.tick` calls one
module-level function a phase, each given the static record
(`state.TickStatic`), the state and the earlier phases' results.

  (a) by `ast`: no phase module imports `engine`, `tick` holds no nested
      function, and no name that moved is still importable from `engine`;
  (b) every phase traces ALONE under `jax.eval_shape`, from
      `init_state` of a small two-cohort program that spawns and
      destroys, the static record and the (abstract) results of the
      phases before it: what a phase reads is what its signature says;
  (c) on a four-device mesh at `analysis=1`, where delivery runs over
      the short list and the long one is no longer built a second time
      for `phase_cost_lanes`, the `delivery` lane is a hand count of the
      list's valid entries, with and without a spill that retries;
  and the dump of one small window (`tests/_hlo.py`) is the same text
  twice in one process.
"""

import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _hlo
from ponyc_tpu import Runtime, RuntimeOptions
from ponyc_tpu.program import Program
from ponyc_tpu.runtime import engine, lanes, mute, route, spawn
from ponyc_tpu.runtime.state import PhaseCursor, init_state
from test_lifecycle import Boss, Worker
from test_profiler import Leaf, Worker as Hub, _opts

RUNTIME = os.path.join(_hlo.ROOT, "ponyc_tpu", "runtime")
PHASE_MODULES = ("mute", "spawn", "route", "lanes", "delivery", "gc")
# What moved out of engine.py, and where to.
MOVED = {
    route: ("_route", "_route_pack", "_route_spill", "_unpack_fits",
            "_route_unpack", "_short_plan", "_store_short_plan"),
    lanes: ("_qwait_bucket", "profile_lanes", "phase_cost_lanes",
            "trace_span_lanes"),
    mute: ("LIVE_CONG", "CAN_RECOVER", "RECOVERED", "PRESSURED"),
}


def _tree(module):
    with open(os.path.join(RUNTIME, module + ".py")) as f:
        return ast.parse(f.read())


# ------------------------------------------------------------ (a) by ast

@pytest.mark.parametrize("module", PHASE_MODULES)
def test_no_phase_module_imports_engine(module):
    """Arrows one way: engine -> {phases} -> {state, ops}."""
    for node in ast.walk(_tree(module)):
        if isinstance(node, ast.ImportFrom):
            names = [node.module or ""] + [a.name for a in node.names]
        elif isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        else:
            continue
        assert not any(n.split(".")[-1] == "engine" for n in names), (
            module, node.lineno)


def test_tick_is_its_phases_in_order():
    """`tick` holds no nested function and fits a screen or three; every
    length of the delivery list is route.py's business."""
    tick, = [n for n in _tree("engine").body
             if isinstance(n, ast.FunctionDef) and n.name == "tick"]
    nested = [n for n in ast.walk(tick) if n is not tick and isinstance(
        n, (ast.FunctionDef, ast.Lambda, ast.ClassDef))]
    assert not nested, [n.lineno for n in nested]
    assert tick.end_lineno - tick.lineno + 1 < 200
    words = {n.id for n in ast.walk(_tree("engine"))
             if isinstance(n, ast.Name)} | {
        n.attr for n in ast.walk(_tree("engine"))
        if isinstance(n, ast.Attribute)}
    assert not words & {"l_in", "e_short", "short_list", "_ring_take"}


@pytest.mark.parametrize("home,name", [(m, n) for m, ns in MOVED.items()
                                       for n in ns],
                         ids=lambda v: getattr(v, "__name__", v))
def test_what_moved_left_no_alias_in_engine(home, name):
    assert hasattr(home, name)
    assert not hasattr(engine, name)


# --------------------------------------------- (b) a phase traces alone

def _is_array(x):
    return isinstance(x, (jax.Array, jax.ShapeDtypeStruct, np.ndarray))


def alone(fn, *args):
    """`fn(*args)` traced by itself under `jax.eval_shape`: the array
    leaves of `args` abstract, the rest (ints, None, cohorts, the phase
    cursor) as they are; the result likewise, arrays as shapes."""
    leaves, treedef = jax.tree.flatten(args, is_leaf=lambda x: x is None)
    seen = {}

    def traced(arrays):
        it = iter(arrays)
        out = fn(*jax.tree.unflatten(
            treedef, [next(it) if _is_array(x) else x for x in leaves]))
        seen["leaves"], seen["treedef"] = jax.tree.flatten(
            out, is_leaf=lambda x: x is None)
        return [x for x in seen["leaves"] if _is_array(x)]
    shapes = iter(jax.eval_shape(traced, [x for x in leaves if _is_array(x)]))
    return jax.tree.unflatten(seen["treedef"], [
        next(shapes) if _is_array(x) else x for x in seen["leaves"]])


@pytest.mark.parametrize("analysis", [0, 3])
def test_each_phase_traces_alone(analysis):
    """The tick's phases, one `eval_shape` each, chained by their
    abstract results; the last state is the first's shape, so the chain
    is the whole tick."""
    opts = RuntimeOptions(mailbox_cap=8, batch=1, max_sends=2, msg_words=2,
                          spill_cap=64, inject_slots=8, analysis=analysis)
    prog = Program(opts)
    prog.declare(Worker, 8)
    prog.declare(Boss, 2)
    prog.finalize()
    k = engine.tick_static(prog, opts)
    assert len(k.dispatchers) == 2 and prog.has_device_spawns
    st = jax.eval_shape(lambda: init_state(prog, opts))
    inj_t = jax.ShapeDtypeStruct((opts.inject_slots,), jnp.int32)
    inj_w = jax.ShapeDtypeStruct((1 + opts.msg_words, opts.inject_slots),
                                 jnp.int32)

    w = alone(lambda st: mute.world(k, st), st)
    um = alone(lambda st, w: mute.unmute_pass(k, st, w), st, w)
    rs = alone(lambda st, w, m: spawn.reserve(k, st, w, m), st, w, um.muted)
    d = alone(lambda st, w, rs: engine.dispatch(k, st, w, rs), st, w, rs)
    assert [ch for ch, *_ in d.drain_facts] == (
        prog.device_cohorts if analysis else [])
    cl = alone(lambda st, w, d: spawn.claim(k, st, w, d), st, w, d)

    def routed(*a):
        with PhaseCursor() as phase:
            return route.deliver_routed(k, *a, phase)
    r = alone(routed, st, w, inj_t, inj_w, d.out_entries, cl, d.pool)
    assert r.counts == {} and r.listed_tgt.shape == (
        opts.spill_cap + opts.inject_slots + k.lists.l_in,)
    life = alone(lambda st, d, tail, cl, um: engine.lifecycle(
        k, st, d, tail, cl, um), st, d, r.res.tail, cl, um)
    m = alone(lambda st, life, res, r: mute.bookkeeping(st, life, res, r),
              st, life, r.res, r)
    occ = jax.ShapeDtypeStruct((prog.n_local,), jnp.int32)
    ring = (st.ev_data, jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32))
    if analysis >= 3:
        ring2 = alone(lambda st, w, ring, rows, life, b, occ: lanes.event_ring(
            k, st, w, ring, rows, life, b, occ),
            st, w, ring, d.error_rows, life, m.became, occ)
        assert jax.tree.structure(ring2) == jax.tree.structure(ring)
    aux, counts, _over, wb = alone(
        lambda st, w, d, n, r, life, m, qw, occ: engine.vote(
            k, st, w, d, n, r, life, m, qw, occ),
        st, w, d, cl.n_spawned, r, life, m, st.qwait_hist, occ)
    assert set(aux.spawn) == {"room", "low", "spawned"}
    assert len(counts) == 4 and wb.shape == ()

    st2, aux2 = jax.eval_shape(engine.build_step(prog, opts), st, inj_t,
                               inj_w)
    assert jax.tree.structure(st2) == jax.tree.structure(st)
    assert jax.tree.map(lambda x: (x.shape, x.dtype), aux2) == \
        jax.tree.map(lambda x: (x.shape, x.dtype), aux)


# -------------------- (c) the delivery lane without the second long list

@pytest.mark.parametrize("cap", [32, 8], ids=["all-accepted", "spill-retries"])
def test_mesh_delivery_lane_is_a_hand_count_of_the_list(cap):
    """20 leaves on four shards, poked straight into their rings, send
    to one hub in one tick: 20 valid entries in the hub's shard's list
    (the short list: the arrivals fit). At `mailbox_cap=32` the hub takes
    them all; at 8 it takes what it has room for, and what it rejects is
    listed again from the spill on every later tick until it is in. Then
    5 pokes through the injections: 5 valid entries (every shard lists
    the ones it owns), and their 5 sends."""
    rt = Runtime(_opts(mailbox_cap=cap, batch=2, analysis=1,
                       inject_slots=32, mesh_shards=4))
    rt.declare(Hub, 1).declare(Leaf, 20).start()
    assert rt.program.shards == 4
    assert route.list_sizes(rt.program, rt.opts).short
    hub = rt.spawn(Hub)
    leaves = rt.spawn_many(Leaf, 20, hub=hub)
    rt.bulk_send(leaves, Leaf.poke, np.ones(20, np.int32))
    assert rt.run() == 0

    def listed(arrive):
        """Valid entries over the ticks it takes `arrive` messages for
        the (empty) hub to be in its mailbox: the hub drains `batch`,
        then delivery lists the spill and the arrivals."""
        total, queued, spilled = 0, 0, 0
        while arrive or spilled:
            queued -= min(2, queued)
            offered = spilled + arrive
            total += offered
            taken = min(cap - queued, offered)
            queued, spilled, arrive = queued + taken, offered - taken, 0
        return total
    want = listed(20)
    assert want == (20 if cap == 32 else 20 + 12 + 10 + 8 + 6 + 4 + 2)
    assert rt.profile()["phases"]["delivery"] == want
    assert rt.counter("n_rejected") == want - 20
    for leaf in leaves[:5]:
        rt.send(int(leaf), Leaf.poke, 1)
    assert rt.run() == 0
    assert rt.profile()["phases"]["delivery"] == want + 5 + 5
    assert rt.state_of(hub)["done"] == 25
    # every shard-tick delivered over the short list
    assert rt.counter("n_unpacked") == rt.counter("step_no") > 0
    rt.stop()


# ------------------------------------------------ the dump is one text

def test_the_dump_of_a_window_is_stable(tmp_path):
    """`python tests/_hlo.py DIR` twice is `diff -r` clean: the jaxpr,
    the bare HLO and the op_names of one small window, written twice in
    one process."""
    for side in ("a", "b"):
        assert _hlo.dump(str(tmp_path / side), ["ring"]) == ["ring"]
    for kind in ("jaxpr", "hlo", "ops"):
        a, b = ((tmp_path / side / f"ring.{kind}.txt").read_text()
                for side in ("a", "b"))
        assert a == b and len(a.splitlines()) > 100, kind
    assert "pony/delivery" in (tmp_path / "a" / "ring.ops.txt").read_text()
    assert "metadata=" not in (tmp_path / "a" / "ring.hlo.txt").read_text()
