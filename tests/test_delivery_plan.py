"""What a plan miss computes (delivery.py `_compute_plan`, `_bounds`;
ops/segment.py): the permutation and the sorted key from one sort, the
segment bounds from a merge. Both must equal a stable argsort and a
`searchsorted(side="left")` element for element — mailboxes, FIFO,
spill and mutes all hang on them — and the miss branch must hold no
indexed read over the list and no loop."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ponyc_tpu import RuntimeOptions
from ponyc_tpu.ops.segment import (segment_bounds, stable_sort_carrying,
                                   stable_sort_with_keys)
from ponyc_tpu.runtime import state
from ponyc_tpu.runtime.delivery import Entries, deliver


@functools.cache
def _mesh4_shape():
    """One shard's (delivery list, rows) of a 4-shard world."""
    from ponyc_tpu.models import ubench
    opts = RuntimeOptions(mailbox_cap=4, batch=4, max_sends=1, msg_words=1,
                          spill_cap=256, inject_slots=8, mesh_shards=4,
                          tuning_cache="off", compile_cache="off")
    rt, _ids = ubench.build(256, opts, pings=4)
    shape = state.layout_sizes(rt.program, rt.opts)[2], rt.program.n_local
    rt.stop()
    return shape


# (entries E, targets N): fewer entries than targets, many more, the
# ring cell's list (spill + inject + route spill + outbox over 1,024
# nodes), and one shard of a 4-shard mesh (resolved inside the test).
SHAPES = {"e-lt-n": (48, 200), "e-gg-n": (6000, 37), "ring": (9472, 1024),
          "mesh4-shard": None}
INVALID = {"none": 0.0, "some": 0.4, "all": 1.0}


def _keys(e, n, n_levels, invalid, seed):
    """Composite keys as `deliver` builds them: target * n_levels +
    level, `n * n_levels` for an entry that is not delivered. Targets
    0, n // 2 and n - 1 never get a message: empty segments at the
    start, in the middle and at the end."""
    rng = np.random.default_rng(seed)
    live = np.setdiff1d(np.arange(n), [0, n // 2, n - 1])
    tgt = rng.choice(live, e)
    key = tgt * n_levels + rng.integers(0, n_levels, e)
    key[rng.random(e) < invalid] = n * n_levels
    if invalid == 1.0:
        key[:] = n * n_levels
    return key.astype(np.int32)


@pytest.mark.parametrize("invalid", INVALID, ids=lambda k: f"invalid-{k}")
@pytest.mark.parametrize("n_levels", [1, 3], ids=lambda v: f"levels{v}")
@pytest.mark.parametrize("shape", SHAPES)
def test_plan_equals_argsort_and_searchsorted(shape, n_levels, invalid):
    e, n = SHAPES[shape] or _mesh4_shape()
    key = _keys(e, n, n_levels, INVALID[invalid], seed=e + n_levels)
    want_perm = np.argsort(key, kind="stable")
    want_bounds = np.searchsorted(
        key[want_perm], np.arange(n + 1) * n_levels, side="left")
    sorted_key, perm = jax.jit(stable_sort_with_keys)(key)
    bounds = jax.jit(segment_bounds, static_argnums=(1, 2))(
        sorted_key, n, n_levels)
    np.testing.assert_array_equal(perm, want_perm)
    np.testing.assert_array_equal(sorted_key, key[want_perm])
    # a payload rides the same sort (the mesh route's, route._route_pack)
    tag = key * 3 + 1
    np.testing.assert_array_equal(
        jax.jit(stable_sort_carrying)(key, tag, perm)[1:],
        (tag[want_perm], perm[want_perm]))
    np.testing.assert_array_equal(bounds, want_bounds)
    assert bounds.dtype == jnp.int32 and bounds.shape == (n + 1,)
    if INVALID[invalid] < 1.0:
        assert bounds[0] == bounds[1] and bounds[n - 1] == bounds[n]
    else:
        assert not bounds.any()


def test_bounds_hold_the_largest_int32_key():
    """`2 * key + 1` is taken in uint32: a key space that ends at the
    top of int32 neither wraps nor loses its order."""
    top = np.int32(2**31 - 1)
    key = np.array([0, 5, top - 1, top, top], np.int32)
    got = segment_bounds(jnp.asarray(key), 1, int(top))
    np.testing.assert_array_equal(got, [0, 3])


N, E, CAP = 12, 40, 4
LAYOUT = [("A", 0, N, 2)]


def _deliver(tgt, level, n_levels, plan, cosort=False):
    i32 = lambda a: jnp.asarray(a, jnp.int32)
    return deliver(
        {"A": jnp.full((CAP, 2, N), -7, jnp.int32)}, jnp.zeros(N, jnp.int32),
        jnp.zeros(N, jnp.int32), jnp.ones(N, bool),
        Entries(i32(tgt), jnp.full((E,), -1, jnp.int32),
                i32(np.arange(2 * E).reshape(2, E))),
        n_local=N, mailbox_cap=CAP, spill_cap=E, overload_occ=CAP,
        shard_base=jnp.int32(0), cohort_layout=LAYOUT, level=i32(level),
        n_levels=n_levels, plan=plan, cosort=cosort)


def _traffic(seed):
    rng = np.random.default_rng(seed)
    tgt = rng.integers(-1, N + 1, E)        # -1 empty, N out of range
    return tgt, rng.integers(0, 3, E)


@pytest.mark.parametrize("cached", ["no-plan", "stale-plan"])
def test_deliver_returns_the_plan_of_its_keys(cached):
    """Through `deliver` itself, with no plan and through the miss
    branch of the `cond` on a plan of other keys: the plan leaves that
    come back are the argsort / searchsorted of this tick's keys."""
    tgt, level = _traffic(3)
    plan = None
    if cached == "stale-plan":
        old = _deliver(*_traffic(4), 3, None)
        plan = (old.plan_key, old.plan_perm, old.plan_bounds)
    res = jax.jit(lambda: _deliver(tgt, level, 3, plan))()
    valid = (tgt >= 0) & (tgt < N)
    key = np.where(valid, tgt * 3 + level, N * 3)
    np.testing.assert_array_equal(res.plan_key, key)
    perm = np.argsort(key, kind="stable")
    np.testing.assert_array_equal(res.plan_perm, perm)
    np.testing.assert_array_equal(
        res.plan_bounds,
        np.searchsorted(key[perm], np.arange(N + 1) * 3, side="left"))
    # and a hit hands the cached leaves back untouched
    again = _deliver(tgt, level, 3,
                     (res.plan_key, res.plan_perm, res.plan_bounds))
    np.testing.assert_array_equal(again.plan_perm, res.plan_perm)
    np.testing.assert_array_equal(again.plan_bounds, res.plan_bounds)
    np.testing.assert_array_equal(again.buf["A"], res.buf["A"])


def _plan_eqns(cosort):
    """(scope, primitive) of every equation of `deliver`'s jaxpr at or
    below pony/delivery/plan, sub-jaxprs (the miss branch) included."""
    tgt, level = _traffic(5)
    old = _deliver(*_traffic(6), 3, None)
    plan = (old.plan_key, old.plan_perm, old.plan_bounds)
    jaxpr = jax.make_jaxpr(
        lambda: _deliver(tgt, level, 3, plan, cosort=cosort))()
    found = []

    def walk(jp, inherited):
        # A sub-jaxpr's name stacks are relative to its equation's.
        for eqn in jp.eqns:
            own = str(eqn.source_info.name_stack)
            scope = (own[own.rindex("pony/") + 5:] if "pony/" in own
                     else inherited)
            if scope.startswith("delivery/plan"):
                found.append((scope, eqn.params["name"]
                              if eqn.primitive.name == "jit"
                              else eqn.primitive.name))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, scope)
    walk(jaxpr.jaxpr, "")
    return found


@pytest.mark.parametrize("mode", ["plan", "cosort"])
def test_plan_holds_sorts_and_one_pass(mode):
    """No indexed read over the list and no loop at or below
    `delivery/plan` (the sorted key comes out of the sort; the bounds
    are a merge), and exactly one sort directly under it: a trace
    counts the plan's misses by that sort (`plan_miss_pct`), so what
    the bounds run sits one scope down."""
    eqns = _plan_eqns(cosort=(mode == "cosort"))
    prims = {p for _s, p in eqns}
    assert not prims & {"gather", "while", "scatter", "scatter-add",
                        "dynamic_slice", "_take", "searchsorted",
                        "argsort"}, prims
    sorts = [s for s, p in eqns if p == "sort"]
    assert sorts.count("delivery/plan") == 1
    assert sorts.count("delivery/plan/bounds") == 2
    assert "cumsum" in {p for s, p in eqns if s == "delivery/plan/bounds"}


# `deliver` alone, compiled for a described v5e (no chip: libtpu's
# compiler, in a child: tests/_hlo.py), a cached plan handed in: what
# each branch of its choice of length can reach, the reads and writes
# by index and the sorts, each with its result's dimensions. With
# `parent` the prefix is not built (`prefix_len` the identity): the
# program `deliver` was before it held two lengths.
FOR_THE_CHIP = """
sys.path.insert(0, {tests!r})
import _hlo
from ponyc_tpu.runtime import delivery
from ponyc_tpu.runtime.delivery import Entries, deliver
from ponyc_tpu.runtime.state import phase_scope
if {parent}:
    delivery.prefix_len = lambda e: e
n, e, cap, w1 = {n}, {e}, 16, 2
def fn(buf, head, tail, tgt, sender, words, key, perm, bounds):
    with phase_scope("delivery"):
        return deliver(
            {{"A": buf}}, head, tail, head >= 0, Entries(tgt, sender, words),
            n_local=n, mailbox_cap=cap, spill_cap=4096, overload_occ=12,
            shard_base=jnp.int32(0), cohort_layout=[("A", 0, n, w1)],
            plan=(key, perm, bounds))
args = (arg(cap, w1, n), arg(n), arg(n), arg(e), arg(e), arg(w1, e),
        arg(e), arg(e), arg(n + 1))
report = lambda text: dict(
    body=_hlo.branch_ops(text, "pony/delivery/cond"),
    plan=_hlo.branch_ops(text, "pony/delivery/plan/cond"))
"""
CHIP_N = 1 << 12
CHIP_E = 8 * CHIP_N + 2 * 4096 + 8          # ubench-like, 4,096 rows


@functools.cache
def _for_the_chip(parent):
    """{"whole": what the tick over the whole list can reach, "prefix":
    what the tick over the prefix can (its words' gather and its
    delivery sit in two conditionals in turn), "plan": the plan's miss
    branch}, each its operations as (opcode, dims); the branches that
    idle, hand through or hit must reach nothing."""
    import os

    import _hlo
    seen = _hlo.v5e_counts(FOR_THE_CHIP.format(
        tests=os.path.dirname(os.path.abspath(__file__)), parent=parent,
        n=CHIP_N, e=CHIP_E))
    ops = lambda branch: [(op, tuple(dims)) for op, dims in branch]  # noqa: E731
    branches = [ops(b) for cond in seen["body"] for b in cond]
    assert branches.count([]) == (1 if parent else 2)
    (whole,) = [b for b in branches if _of_the_list(b)]
    ((miss, hit),) = seen["plan"]        # a conditional's false branch first
    assert hit == []
    return {"whole": whole, "plan": ops(miss), "prefix": sorted(
        op for b in branches if b and b is not whole for op in b)}


def _of_the_list(ops):
    """Those of a branch's operations that run over the whole list, or
    over the bounds' merge of it (N + 1 + E keys)."""
    return [(op, dims) for op, dims in ops
            if set(dims) & {CHIP_E, CHIP_N + 1 + CHIP_E}]


def test_for_the_chip_the_prefix_branch_reads_nothing_by_the_list():
    """What the chip runs on a tick over the prefix holds no gather,
    sort or scatter of E or N + 1 + E elements: they are of the prefix,
    the same operations at the other length. The one operation over the
    list that such a tick pays is the plan's own sort, which makes the
    prefix — in the plan's miss branch, where the merge for a prefix
    tick is of N + 1 + prefix keys."""
    from ponyc_tpu.runtime.delivery import prefix_len
    short = prefix_len(CHIP_E)
    seen = _for_the_chip(False)
    assert seen["prefix"] and _of_the_list(seen["prefix"]) == []
    assert seen["prefix"] == sorted(
        (op, tuple(short if d == CHIP_E else d for d in dims))
        for op, dims in seen["whole"])
    assert sorted(seen["plan"]) == sorted(
        [("sort", (CHIP_E,))] + [("sort", (CHIP_N + 1 + CHIP_E,))] * 2
        + [("sort", (CHIP_N + 1 + short,))] * 2)


def test_for_the_chip_the_long_branch_is_the_parents():
    """The branch a tick takes when its live entries do not fit the
    prefix holds the operations `deliver` held before it had two
    lengths, one for one."""
    parent = _for_the_chip(True)
    assert _for_the_chip(False)["whole"] == parent["whole"]
    assert parent["prefix"] == []
