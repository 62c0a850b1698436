"""`Runtime.window_symbols()` — the compiled window's symbol table
(`ponyc_tpu/costs.py`): the ladder that names an instruction on a
fixture cut from the text compiled for a v5e, the table of the small
benchmark worlds on the CPU backend, and the windows compiled for the
described v5e (no chip).
"""

import collections
import json
import os

import pytest

import _child
import _hlo
from ponyc_tpu import costs
from ponyc_tpu.runtime import state

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "window_symbols.hlo.txt")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@pytest.fixture(scope="module")
def fixture_rows():
    with open(FIXTURE) as f:
        return {r["name"]: r for r in costs.hlo_symbols(f.read())}


@pytest.mark.parametrize("name, scope, how", [
    # its own op_name
    ("fusion.17", "delivery/permute", "own"),
    # a drain fusion: the root is a dynamic-update-slice without one,
    # every select inside is under pony/drain
    ("select_dynamic-update-slice_fusion.1", "drain", "inside"),
    # mute_ref_slots' scatter, re-made by an XLA pass, two fusions deep
    ("fusion.33", "delivery/pressure/mute", "inside"),
    # the merge's prefix sum, between a pad and an add of the same phase
    ("reduce-window", "delivery/plan/bounds", "around"),
    # a scalar the compiler copies for the loop's state
    ("copy.976", None, "none"),
    # a loop whose body holds `rebuild` and `rebuild/compact`
    ("while.58", "delivery/rebuild", "inside"),
    # the loop round the whole tick is no phase's
    ("while.143", None, "none"),
])
def test_the_ladder_names_an_instruction(fixture_rows, name, scope, how):
    row = fixture_rows[name]
    assert (row["scope"], row["how"]) == (scope, how)


def test_a_row_is_an_instruction_that_can_be_a_device_event(fixture_rows):
    """Entry, loop bodies and conditions — not the inside of a fused
    computation, not a reducer, no parameter, tuple or bitcast; and what
    an indexed operation reads or writes."""
    assert set(fixture_rows) == {
        "while.143", "select_dynamic-update-slice_fusion.1", "fusion.17",
        "fusion.33", "pad_bitcast_fusion", "copy.55", "reduce-window",
        "slice.404", "broadcast_add_fusion", "copy.975", "while.58",
        "copy.976", "compare.911", "add.3117", "copy.811"}
    gather, scatter = fixture_rows["fusion.17"], fixture_rows["fusion.33"]
    assert (gather["kind"], gather["s1"], gather["index_count"],
            gather["table_bytes"]) == ("gather", True, 311552, 4 * 311552)
    # the output is plain: the mark is the table's, and `table_s1` says so
    assert gather["shape"] == "s32[311552]{0:T(1024)}" and gather["table_s1"]
    assert scatter["table_s1"]      # into zeros its own fusion made: its output
    assert (scatter["kind"], scatter["s1"], scatter["index_count"],
            scatter["table_bytes"]) == ("scatter", True, 311552, 4 * 262144)
    assert fixture_rows["reduce-window"]["s1"] is None


@pytest.mark.parametrize("op_name, scope", [
    ("jit(multi)/while/body/pony/delivery/cond/branch_1_fun/pony/delivery/"
     "rebuild/jit(_take)/gather", "delivery/rebuild"),
    ("jit(multi)/while/body/pony/dispatch/pony/dispatch/cohort/Account/cond/"
     "branch_1_fun/pony/drain/select_n", "drain"),
    ("jit(multi)/while/body/pony/dispatch/cond", "dispatch"),
    ("jit(multi)/while/body/jit(_where)/select_n", None),
    ("jit(multi)/while", None), (None, None)])
def test_scope_of_is_the_readers_rule(op_name, scope):
    """The program states the rule itself (beside SCOPE_PREFIX) and it
    is `benchmarks/phase_trace.scope_of`'s."""
    from benchmarks import phase_trace
    assert state.scope_of(op_name) == scope == phase_trace.scope_of(op_name)


@pytest.mark.parametrize("world", ["ubench", "fanin", "gups", "spreader",
                                   "mesh4"])
def test_the_table_of_a_world_that_ran(world):
    """After a run the table costs no backend compile (the executable
    that ran is found again), is memoized and does not advance the
    world; every scope is the vocabulary's, and every scope the lowered
    window carries is some row's."""
    import jax
    import jax.numpy as jnp
    from jax import monitoring
    # What an EARLIER world of this worker left in jax's caches is not
    # this one's: after a `mesh_shards=4` runtime (test_delivery_plan's
    # `mesh4-shard` case, when xdist hands that file to the same worker
    # first) the one-chip fan-in and GUPS found a cached helper typed
    # for the mesh and compiled where none should (ROADMAP C9).
    jax.clear_caches()
    rt = _hlo.WINDOWS[world]()
    try:
        rt.run(max_steps=2)
        if world == "spreader":
            rt.gc()
        steps, processed = rt.steps_run, rt.counter("n_processed")
        compiles = []

        def listen(event, _seconds, **_kw):
            compiles.append(event)
        monitoring.register_event_duration_secs_listener(listen)
        try:
            symbols = rt.window_symbols()
        finally:
            monitoring.unregister_event_duration_listener(listen)
        assert COMPILE_EVENT not in compiles
        assert rt.window_symbols()["window"] is symbols["window"]
        assert (rt.steps_run, rt.counter("n_processed")) == (steps, processed)
        assert set(symbols) == ({"window", "gc"} if world == "spreader"
                                else {"window"})
        cohorts = {state.cohort_scope(c.atype.__name__)
                   for c in rt.program.cohorts}
        for program, rows in symbols.items():
            scopes = collections.Counter(r["scope"] for r in rows)
            assert set(scopes) - {None} <= set(state.STEP_SCOPES) | cohorts, \
                program
            assert all((r["scope"] is None) == (r["how"] == "none")
                       for r in rows)
        if world == "spreader":
            assert {r["scope"] for r in symbols["gc"]} >= {
                "gc_mark/roots", "gc_mark/hop", "gc_mark/sweep"}
        lowered = rt._multi_g.lower(
            rt.state, *rt._empty_inject, jnp.int32(4), jnp.bool_(True),
            rt._zero_aux).as_text(debug_info=True)
        named = {r["scope"] for r in symbols["window"]}
        # `pony/spawn` in a world without device spawns is three N-wide
        # operations that XLA may fuse under a neighbour's root
        missing = {s for s in state.STEP_SCOPES
                   if f"{state.SCOPE_PREFIX}/{s}/" in lowered} - named
        assert missing <= {"spawn"}, missing
        assert cohorts <= named
    finally:
        rt.stop()


# The child: a small benchmark world's gated window compiled for the
# DESCRIBED v5e (libtpu's compiler, no chip), its table as JSON.
_FOR_THE_CHIP = """
import json, sys
sys.path[:0] = [{root!r}, {root!r} + "/tests"]
from ponyc_tpu.platforms import force_cpu
force_cpu(8)
try:
    from jax.experimental import topologies
    topologies.get_topology_desc("v5e:2x2", "tpu")
except Exception as e:
    print(json.dumps({{"no_compiler": repr(e)}})); sys.exit(0)
import _hlo
from ponyc_tpu import costs
rt = _hlo._bench_rt({config!r}, {traffic!r}, {actors})
print(json.dumps(costs.hlo_symbols(_hlo.window_texts(rt, chip=True)[1])))
"""
ACTORS = 2048


def v5e_rows(config: str, traffic: str) -> list:
    out = _child.script(
        _FOR_THE_CHIP.format(root=_hlo.ROOT, config=config, traffic=traffic,
                             actors=ACTORS), timeout=110.0,
        env={"ALLOW_MULTIPLE_LIBTPU_LOAD": "1", "TPU_LOG_DIR": "disabled"})
    assert out.returncode == 0, out.stderr[-2000:]
    rows = json.loads(out.stdout.strip().splitlines()[-1])
    if isinstance(rows, dict):
        pytest.skip(f"no TPU compiler here: {rows['no_compiler']}")
    return rows


def test_for_the_chip_every_drain_fusion_is_named():
    """ubench's window: the scan's eight drain fusions write the
    `[batch, w1, N]` batch by `dynamic-update-slice`; seven of the roots
    carry no op_name, and the profiler shows those without a scope.
    The table names all eight `drain`; and a list gather has `S(1)`."""
    rows = v5e_rows("ubench-1m", "cycle")
    drains = [r for r in rows if r["opcode"] == "fusion"
              and "dynamic-update-slice_fusion" in r["name"]
              and r["shape"].startswith(f"s32[8,2,{ACTORS}]")]
    assert len(drains) == 8
    assert {r["scope"] for r in drains} == {"drain"}
    assert sum(r["how"] == "inside" for r in drains) >= 7
    lists = [r for r in rows if r["kind"] == "gather"
             and r["scope"] == "delivery/permute"]
    assert lists and any(r["s1"] for r in lists)
    assert all(r["index_count"] and r["table_bytes"] for r in lists)


def test_for_the_chip_the_ref_tables_scatters_are_the_mute_phases():
    """The fan-in's window: `mute_ref_slots` scatters into the `[k, n]`
    ref table (flat, k = 4 mute slots); an XLA pass re-makes the
    scatters and their fusions' roots lose the op_name. Every one is
    `delivery/pressure/mute` by what it holds."""
    rows = v5e_rows("fanin-zipf", "steady")
    refs = [r for r in rows if r["kind"] == "scatter"
            and r["shape"].startswith(f"s32[{4 * ACTORS}]")]
    assert len(refs) >= 2
    assert {r["scope"] for r in refs} == {"delivery/pressure/mute"}
    assert all(r["table_bytes"] == 4 * 4 * ACTORS for r in refs)
    unnamed = [r["name"] for r in rows
               if r["how"] == "none" and r["opcode"] == "fusion"]
    assert len(unnamed) <= 4, unnamed
