"""The mailbox rebuild (delivery.rebuild_tables): arrival ranks gathered
in blocks, a cohort's tables as deep as that cohort's fullest mailbox of
the tick, a block as wide as the rows that have a message in it: full
width while more than an eighth of the cohort's rows are that deep —
one gather of the cohort's rows a rank that some row holds, not always
eight —, compacted to those rows from then on.

Most of tier-1 runs rings of 2-8 slots, which take the one-block form;
these tests drive `deliver()` itself at `mailbox_cap` 16 and 64 against
a NumPy oracle that pushes every arrival into its ring one by one, and
pin the one-block form to a program with no loop and no reduction."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ponyc_tpu.runtime import delivery
from ponyc_tpu.runtime.delivery import Entries, deliver

from _rebuild import block_indices, tick_indices

N, E = 24, 320
LAYOUT = [("Narrow", 0, 16, 2), ("Wide", 16, 24, 4)]   # (type, s0, s1, 1+W)
ONE = [("Wide", 0, N, 4)]                  # a world of one cohort
W1 = 4 + 2                                 # widest payload + trace context
# `deliver` holds its body at two lengths, the list and its prefix
# (delivery.prefix_len); a one-block ring at the one.
LENGTHS = 1 + (delivery.prefix_len(E) < E)


def _forms(acc, layout=LAYOUT):
    """The blocks each cohort must run, 'F' full width or 'C' compacted:
    ceil(its fullest acceptance / 8) of them, block k compacted once the
    rows with acc > 8k fit in M = ceil(rows / 8) — and every later one
    with it, since their count only falls."""
    b = delivery.REBUILD_BLOCK
    out = []
    for _n, s0, s1, _w in layout:
        a, m = acc[s0:s1], -(-(s1 - s0) // b)
        out.append("".join(
            "F" if (a > k * b).sum() > m else "C"
            for k in range(-(-int(a.max()) // b))))
        assert "CF" not in out[-1]
    return out


def _slots(acc, layout=LAYOUT):
    """What `rebuild_slots` must read, the indices the rebuild's gathers
    read: over the blocks each cohort runs, `block_indices` of its rows,
    of those with a message in the block and of the ranks its fullest
    row holds there (`tick_indices`)."""
    return sum(tick_indices(s1 - s0, acc[s0:s1])
               for _n, s0, s1, _w in layout)


def _world(cap, seed, cnt=None, layout=LAYOUT):
    """Tables with recognisable old contents, wrapped monotonic
    counters, and arrivals that make every acceptance from 0 to `cap`:
    actor 0 is empty and is sent `cap` (every block runs), actor 1 is
    sent more than its free space (space-limited), actor 2 nothing."""
    rng = np.random.default_rng(seed)
    occ = rng.integers(0, cap + 1, N)
    occ[0], occ[1], occ[2] = 0, cap - 3, 5
    head = rng.integers(10 * cap, 1000 * cap, N)
    tail = head + occ
    if cnt is None:
        cnt = rng.integers(0, 12, N)
        cnt[0], cnt[1], cnt[2] = cap, 7, 0
        cnt[3] = 0 if seed % 2 else 9
    tgt = np.repeat(np.arange(N), cnt)
    assert tgt.size <= E - 8
    # A few dead-lettered and empty entries between the live ones.
    tgt = np.concatenate([tgt, [-1, N + 3, 5, -1]])
    tgt = np.concatenate([tgt, np.full(E - tgt.size, -1)])
    rng.shuffle(tgt)
    alive = np.ones(N, bool)
    alive[5] = False
    words = rng.integers(1, 1 << 30, (W1, E))
    buf = {name: rng.integers(-99, -1, (cap, w1c, s1 - s0))
           for name, s0, s1, w1c in layout}
    tbuf = {name: rng.integers(-99, -1, (cap, 2, s1 - s0))
            for name, s0, s1, _w in layout}
    return buf, tbuf, head, tail, alive, tgt, words


def _oracle(cap, buf, tbuf, head, tail, alive, tgt, words, layout=LAYOUT):
    """Push arrivals one by one, in list order, while there is room."""
    buf = {k: v.copy() for k, v in buf.items()}
    tbuf = {k: v.copy() for k, v in tbuf.items()}
    tail = tail.copy()
    acc = np.zeros(N, int)
    for j, t in enumerate(tgt):
        if not (0 <= t < N) or not alive[t] or tail[t] - head[t] >= cap:
            continue
        name, s0, _s1, w1c = next(c for c in layout if c[1] <= t < c[2])
        buf[name][tail[t] % cap, :, t - s0] = words[:w1c, j]
        tbuf[name][tail[t] % cap, :, t - s0] = words[W1 - 2:, j]
        tail[t] += 1
        acc[t] += 1
    return buf, tbuf, tail, acc


def _deliver(cap, world, *, cosort, tracing, layout=LAYOUT):
    buf, tbuf, head, tail, alive, tgt, words = world
    i32 = lambda a: jnp.asarray(a, jnp.int32)
    return deliver(
        {k: i32(v) for k, v in buf.items()}, i32(head), i32(tail),
        jnp.asarray(alive),
        Entries(i32(tgt), jnp.full((E,), -1, jnp.int32), i32(words)),
        n_local=N, mailbox_cap=cap, spill_cap=E, overload_occ=cap,
        shard_base=jnp.int32(0), cohort_layout=layout, cosort=cosort,
        trace_buf={k: i32(v) for k, v in tbuf.items()} if tracing else None)


def _same_tables(res, want_buf, want_tbuf, want_tail, tracing):
    """Every table `deliver` returned is the oracle's, bit for bit."""
    np.testing.assert_array_equal(res.tail, want_tail)
    for name in want_buf:
        np.testing.assert_array_equal(res.buf[name], want_buf[name])
    if tracing:
        for name in want_tbuf:
            np.testing.assert_array_equal(res.trace_buf[name],
                                          want_tbuf[name])
    else:
        assert res.trace_buf == {}


@pytest.mark.parametrize("tracing", [False, True], ids=["plain", "traced"])
@pytest.mark.parametrize("mode", ["plan", "cosort"])
@pytest.mark.parametrize("cap", [16, 64])
def test_rebuild_equals_one_by_one_pushes(cap, mode, tracing):
    for seed in (0, 1):
        world = _world(cap, seed)
        want_buf, want_tbuf, want_tail, acc = _oracle(cap, *world)
        assert acc.max() == cap and acc.min() == 0      # every block ran
        assert acc[1] == 3                              # space-limited
        res = _deliver(cap, world, cosort=(mode == "cosort"),
                       tracing=tracing)
        _same_tables(res, want_buf, want_tbuf, want_tail, tracing)
        assert int(res.n_delivered) == acc.sum()
        # Narrow (actor 0) ran every block, Wide what its own took.
        assert int(res.rebuild_slots) == _slots(acc)


# Fullest arrival (Narrow, Wide): what one cohort takes in must not set
# how deep the other's tables are gathered.
DEPTHS = {"narrow-deep": (20, 0), "wide-deep": (1, 17),
          "both-deep": (20, 9), "neither-deep": (3, 2)}


@pytest.mark.parametrize("tracing", [False, True], ids=["plain", "traced"])
@pytest.mark.parametrize("mode", ["plan", "cosort"])
@pytest.mark.parametrize("deep", list(DEPTHS))
def test_rebuild_depth_is_the_cohorts(deep, mode, tracing):
    """A cohort's tables run ceil(its own fullest acceptance / 8) blocks
    — none where it received nothing — and every table still equals the
    one-by-one oracle: a block no longer run carried no message."""
    cap = 64
    cnt = np.zeros(N, int)
    for (_name, s0, s1, _w), most in zip(LAYOUT, DEPTHS[deep]):
        cnt[s0:s1] = np.arange(s1 - s0) % (min(most, 4) + 1)
        cnt[s1 - 2] = most
    buf, tbuf, head, _tail, alive, tgt, words = _world(cap, 2, cnt=cnt)
    world = (buf, tbuf, head, head + 1, alive, tgt, words)   # room for all
    want_buf, want_tbuf, want_tail, acc = _oracle(cap, *world)
    assert (acc[:16].max(), acc[16:].max()) == DEPTHS[deep]
    res = _deliver(cap, world, cosort=(mode == "cosort"), tracing=tracing)
    _same_tables(res, want_buf, want_tbuf, want_tail, tracing)
    assert int(res.rebuild_slots) == _slots(acc)


# A cohort of fewer than 8 rows (M = 1) beside one of 21 (M = 3).
TINY = [("Narrow", 0, 3, 2), ("Wide", 3, 24, 4)]


def _form_cnt(form, cap):
    """Arrivals a row for one form of a tick's blocks, and what each
    cohort of the layout must then run (`_forms`). Narrow has 16 rows
    (M = 2), Wide 8 (M = 1); row 5 is dead and takes nothing."""
    cnt = np.zeros(N, int)
    layout = LAYOUT
    if form == "later-compacted":
        cnt[:16] = np.arange(16) % 5 + 1
        cnt[3] = 12
        cnt[16:] = 2
        want = ["FC", "F"]
    elif form == "later-full":
        cnt[:16] = np.arange(16) % 3
        cnt[[0, 7, 9, 12]] = 10
        cnt[16:] = 9
        want = ["FF", "FF"]
    elif form == "first-compacted":
        cnt[7] = 3
        cnt[11] = 11 if cap > 16 else 8
        want = ["CC" if cap > 16 else "C", ""]
    elif form == "full-then-compacted":
        cnt[:16] = 4
        cnt[[1, 2, 3] if cap > 16 else [2, 3]] = 9
        cnt[3] = cap
        cnt[20] = 1
        want = ["FC" if cap == 16 else "FF" + "C" * (cap // 8 - 2), "C"]
    elif form == "exactly-m":
        cnt[:] = 3
        cnt[[4, 10]] = 9             # Narrow: 2 rows past 8 = M
        cnt[18] = 16                 # Wide: 1 = M
        want = ["FC", "FC"]
    elif form == "m-plus-one":
        cnt[:] = 3
        cnt[[4, 10, 15]] = 9         # Narrow: 3 rows past 8 = M + 1
        cnt[[18, 19]] = 16           # Wide: 2 = M + 1
        want = ["FF", "FF"]
    elif form == "few-rows":
        layout = TINY
        cnt[[0, 2]] = 9              # Narrow, 3 rows, M = 1: 2 past 8
        cnt[1] = 1
        cnt[3:] = 2
        cnt[[8, 9, 23]] = 12         # Wide, 21 rows, M = 3: 3 past 8
        want = ["FF", "FC"]
    elif form == "deepest-few":      # cap 64: a 57-deep acceptance
        cnt[:16] = 2
        cnt[6] = cap - 7
        cnt[16:] = 1
        cnt[22] = cap
        want = ["F" + "C" * (cap // 8 - 1), "F" + "C" * (cap // 8 - 1)]
    elif form == "deepest-many":     # ... by more rows than M: 8 full
        cnt[[0, 1, 2]] = cap - 7
        cnt[8] = 1
        want = ["F" * (cap // 8), ""]
    return cnt, layout, want


FORMS = ["later-compacted", "later-full", "first-compacted",
         "full-then-compacted", "exactly-m", "m-plus-one", "few-rows",
         "deepest-few", "deepest-many"]


@pytest.mark.parametrize("tracing", [False, True], ids=["plain", "traced"])
@pytest.mark.parametrize("mode", ["plan", "cosort"])
@pytest.mark.parametrize("cap", [16, 64])
@pytest.mark.parametrize("form", FORMS)
def test_rebuild_block_is_as_wide_as_its_rows(form, cap, mode, tracing):
    """Block k of a cohort runs full width while more than M of its rows
    have acc > 8k and compacted from then on (k = 0 is no special case);
    whichever it ran, the tables are the one-by-one oracle's and
    `rebuild_slots` the indices those blocks read."""
    cnt, layout, want = _form_cnt(form, cap)
    buf, tbuf, head, _tail, alive, tgt, words = _world(
        cap, 3, cnt=cnt, layout=layout)
    world = (buf, tbuf, head, head.copy(), alive, tgt, words)  # all empty
    want_buf, want_tbuf, want_tail, acc = _oracle(cap, *world, layout=layout)
    assert _forms(acc, layout) == want
    res = _deliver(cap, world, cosort=(mode == "cosort"), tracing=tracing,
                   layout=layout)
    _same_tables(res, want_buf, want_tbuf, want_tail, tracing)
    assert int(res.n_delivered) == acc.sum()
    assert int(res.rebuild_slots) == _slots(acc, layout)


# The cohort's fullest mailbox of the tick: a first full block at every
# width, and (9, 13) a second full block narrower than the first.
FULLEST = [1, 2, 5, 8, 9, 13]


@pytest.mark.parametrize("tracing", [False, True], ids=["plain", "traced"])
@pytest.mark.parametrize("cap", [16, 64])
@pytest.mark.parametrize("most", FULLEST)
def test_full_block_pulls_the_ranks_its_fullest_mailbox_holds(most, cap,
                                                              tracing):
    """Every block of both cohorts runs FULL WIDTH (more rows than M
    are as deep as the fullest) and pulls min(8, most - 8k) ranks, one
    gather of the cohort's rows each: the tables are the one-by-one
    oracle's bit for bit, and `rebuild_slots` is `most` x the rows (the
    dead row 5 among them) — a rank no row holds is not fetched."""
    cnt = np.zeros(N, int)
    cnt[:16] = 1 + np.arange(16) % most
    cnt[[2, 9, 14]] = most              # Narrow: 3 rows that deep, M = 2
    cnt[16:] = 1
    cnt[[17, 22]] = most                # Wide: 2 rows, M = 1
    buf, tbuf, head, _tail, alive, tgt, words = _world(cap, 4, cnt=cnt)
    world = (buf, tbuf, head, head.copy(), alive, tgt, words)
    want_buf, want_tbuf, want_tail, acc = _oracle(cap, *world)
    assert (acc[:16].max(), acc[16:].max()) == (most, most)
    assert _forms(acc) == ["F" * -(-most // 8)] * 2
    res = _deliver(cap, world, cosort=False, tracing=tracing)
    _same_tables(res, want_buf, want_tbuf, want_tail, tracing)
    assert int(res.n_delivered) == acc.sum()
    assert int(res.rebuild_slots) == _slots(acc) == most * N


def test_rebuild_runs_as_many_blocks_as_the_fullest_mailbox():
    """Depth follows the input: 1 message -> 1 block, 9 to one actor ->
    2 blocks, none -> no block, whatever the ring's capacity — of the
    cohort that took them (Narrow, 16 rows); Wide, sent nothing, runs
    none."""
    cap = 64
    buf, tbuf, head, tail, alive, _tgt, words = _world(cap, 0)
    for sent, blocks in ((0, 0), (1, 1), (8, 1), (9, 2), (17, 3)):
        tgt = np.full(E, -1)
        tgt[:sent] = 0
        tgt[100] = 2 if sent else -1
        res = _deliver(cap, (buf, tbuf, head, tail, alive, tgt, words),
                       cosort=False, tracing=False)
        # Two rows at most receive, M = 2 of Narrow's 16: every block
        # is a compacted one, 8 ranks x M.
        assert int(res.rebuild_slots) == (
            blocks * delivery.REBUILD_BLOCK * 2), sent
        assert int(res.n_delivered) == sent + (sent > 0)


def _deliver_jaxpr(cap, layout):
    """`deliver`'s jaxpr over a world of `layout`, tracing on."""
    world = _world(cap, 0, layout=layout)
    return jax.make_jaxpr(
        lambda: _deliver(cap, world, cosort=False, tracing=True,
                         layout=layout))()


def _rebuild_eqns(cap, layout=LAYOUT, inherit=True,
                  scope="pony/delivery/rebuild"):
    """Primitive names (a jitted helper's own name for `jit`) of every
    equation under `scope` in `deliver`'s jaxpr, sub-jaxprs included;
    with `inherit` off, only of those whose OWN name stack holds the
    scope."""
    jaxpr = _deliver_jaxpr(cap, layout)
    names = []

    def walk(jp, inherited):
        # A sub-jaxpr's name stacks are relative to its equation's.
        for eqn in jp.eqns:
            under = inherited or (scope
                                  in str(eqn.source_info.name_stack))
            if under:
                names.append(eqn.params["name"]
                             if eqn.primitive.name == "jit"
                             else eqn.primitive.name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, under and inherit)
    walk(jaxpr.jaxpr, False)
    return names


def test_one_block_ring_has_no_loop_and_no_reduction():
    """`mailbox_cap <= REBUILD_BLOCK`: the slot-plane program, in which
    two more operations are the ring cell's whole 1% bound."""
    tables = 2 * len(LAYOUT)                        # buf + trace_buf
    small = _rebuild_eqns(8)
    assert "while" not in small and "reduce_max" not in small
    assert small.count("_take") == tables
    # One select a table; `_take` and the `%` of `rels` hold one each.
    assert small.count("_where") == tables + tables + 1
    assert "while" not in _rebuild_eqns(8, ONE)


@pytest.mark.parametrize("layout", [ONE, LAYOUT], ids=["one", "two"])
def test_deep_ring_has_two_loops_a_cohort(layout):
    """A ring deeper than a block: one depth (`max(acc)` over the
    cohort's rows) and two loops in turn a COHORT, the full-width
    blocks' and the compacted blocks' — no `cond` round the tables. A
    full block holds the one loop more, over the ranks its fullest row
    holds: one gather a table a rank; a compacted one a sort of the deep
    rows, one (short) gather a table and ONE scatter back to the table's
    lanes for the cohort, its trace side lanes inside the same loops."""
    # buf + trace_buf, in the body at each length
    cohorts, tables = LENGTHS * len(layout), LENGTHS * 2 * len(layout)
    b = delivery.REBUILD_BLOCK
    deep = _rebuild_eqns(16, layout)
    assert deep.count("while") == 3 * cohorts
    assert "cond" not in deep
    assert deep.count("reduce_max") == cohorts
    # The full loop's test counts the rows that deep; nothing else sums.
    assert deep.count("reduce_sum") == cohorts
    assert deep.count("_take") == 2 * tables
    # A rank's plane into the block's pull, nowhere else.
    assert deep.count("dynamic_update_slice") == tables
    # The selects of both loops and the deep rows' `where`; the
    # compacted blocks' `_take`s and the `%` of `rels` hold one each
    # (a rank's `_take` is told its indices are in range: no fill).
    assert deep.count("_where") == (2 * tables * b + cohorts
                                    + tables + LENGTHS)
    # Every loop's body writes its scope itself (a body is a computation
    # of its own): its gathers are named without their loop's help, the
    # compacted blocks' one scope down.
    own = _rebuild_eqns(16, layout, inherit=False)
    assert own.count("_take") == 2 * tables
    compact = _rebuild_eqns(16, layout, inherit=False,
                            scope="pony/delivery/rebuild/compact")
    assert compact.count("_take") == tables
    assert compact.count("sort") == compact.count("scatter") == cohorts
    assert "while" not in compact


def _rank_loops(layout):
    """The `while` equations of `deliver`'s jaxpr that sit inside
    another `while` of the rebuild: the full block's loop over its
    ranks, one a cohort a length."""
    jaxpr = _deliver_jaxpr(16, layout)
    found = []

    def walk(jp, in_block):
        for eqn in jp.eqns:
            block = (eqn.primitive.name == "while" and "pony/delivery/rebuild"
                     in str(eqn.source_info.name_stack))
            if block and in_block:
                found.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, in_block or block)
    walk(jaxpr.jaxpr, False)
    return found


@pytest.mark.parametrize("layout", [ONE, LAYOUT], ids=["one", "two"])
def test_full_block_gathers_one_rank_of_its_rows_at_a_time(layout):
    """The loop inside a full block: its body one gather a table, of the
    cohort's ROWS indices (not 8 x rows), written as one plane of the
    block's pull, with no select by an out-of-range mask (the indices
    are in range as they stand and the gather is told so: the mask's
    compare, copy and select a rank cost the one-chip ubench window its
    compacted pull's `S(1)` table, PERF.md §6, PR 52); and every
    equation of the body and of the test names `pony/delivery/rebuild`
    ITSELF — a loop's computations carry no scope of their own, and
    `mailbox_rebuild_ms` reads by that name."""
    loops = _rank_loops(layout)
    assert len(loops) == LENGTHS * len(layout)
    rows = sorted(LENGTHS * [s1 - s0 for _n, s0, s1, _w in layout])
    seen = []
    for loop in loops:
        test, body = (loop.params["cond_jaxpr"].jaxpr,
                      loop.params["body_jaxpr"].jaxpr)
        for eqn in (*test.eqns, *body.eqns):
            stack = str(eqn.source_info.name_stack)
            assert stack.endswith("pony/delivery/rebuild") or (
                "pony/delivery/rebuild/jit(" in stack), (eqn, stack)
        assert [e.primitive.name for e in test.eqns] == ["lt"]
        takes = [e for e in body.eqns if e.primitive.name == "jit"
                 and e.params["name"] == "_take"]
        writes = [e for e in body.eqns
                  if e.primitive.name == "dynamic_update_slice"]
        assert len(takes) == len(writes) == 2      # mailbox + side lanes
        for take in takes:      # told in range: a gather, no fill's select
            inner = [e.primitive.name for sub in jax.core.jaxprs_in_params(
                take.params) for e in sub.eqns]
            assert "gather" in inner and "select_n" not in inner, inner
        (nn,) = {e.outvars[0].aval.shape[1] for e in takes}
        assert {e.outvars[0].aval.shape[1:] for e in writes} == {
            (delivery.REBUILD_BLOCK, nn)}
        # ... and nothing else by the list: a table's word rows are cut
        # from it once a block, outside (a cohort narrower than the
        # records copied its rows of the whole list a rank: the bank's
        # tellers, 64 ranks a tick, 14 ms).
        assert not any(e.primitive.name in ("while", "cond", "sort",
                                            "scatter", "slice", "squeeze")
                       for e in body.eqns)
        seen.append(nn)
    assert sorted(seen) == rows


# `deliver` alone, compiled for a described v5e (no chip: libtpu's
# compiler, in a child: tests/_hlo.py) at a ring of two blocks: what the
# chip would run for the rebuild, read off the program's own symbol
# table (costs.hlo_symbols).
FOR_THE_CHIP = """
sys.path.insert(0, {tests!r})
import re
import _hlo
from ponyc_tpu import costs
from ponyc_tpu.runtime.delivery import Entries, deliver
from ponyc_tpu.runtime.state import phase_scope
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
def report(text):
    rows = costs.hlo_symbols(text)
    conds = [_hlo.branch_ops(text, scope) for scope in (
        "pony/delivery/cond", "pony/delivery/rebuild/cond",
        "pony/delivery/rebuild/while/body/cond")]
    # a table-shaped copy in a branch or a loop's body (the entry's own
    # is of the argument, which this `jit` does not donate)
    copies, entry = 0, False
    for line in text.splitlines():
        if not line.startswith(" "):
            entry = line.startswith("ENTRY")
        copies += (not entry) and bool(re.search(
            r"= s32\\[%d,%d,%d\\][^ ]* copy\\(" % (cap, w1, n), line))
    return dict(
        gathers=[(r["scope"], r["how"], r["index_count"]) for r in rows
                 if r["kind"] == "gather"
                 and (r["scope"] or "").startswith("delivery/rebuild")],
        unnamed_loops=[r["name"] for r in rows if r["opcode"] == "while"
                       and r["scope"] is None],
        rebuild_conds=sum(len(c) for c in conds[1:]),
        table_writers=[sum(any(op == "scatter" for op, _d in branch)
                           for branch in cond) for cond in conds[0]],
        table_copies=copies)
"""
CHIP_N = 1 << 12
CHIP_E = 8 * CHIP_N + 2 * 4096 + 8          # ubench-like, 4,096 rows


def test_for_the_chip_a_full_block_gathers_its_rows_a_rank():
    """What the chip runs: the full block's gather has `rows` indices at
    either length (the parent's had 8 x rows), the compacted block's
    8 x M = rows, each named by its own `op_name`; no loop without a
    phase; no conditional inside the rebuild, and of the two round it
    (`deliver`'s choice of length) one branch each that rebuilds (told
    by the compacted block's scatter), so none copies the mailbox table
    to write it."""
    import os

    import _hlo
    seen = _hlo.v5e_counts(FOR_THE_CHIP.format(
        tests=os.path.dirname(os.path.abspath(__file__)),
        n=CHIP_N, e=CHIP_E))
    assert sorted(map(tuple, seen["gathers"])) == sorted(
        LENGTHS * [("delivery/rebuild", "own", CHIP_N),
                   ("delivery/rebuild/compact", "own", CHIP_N)])
    assert seen["unnamed_loops"] == []
    assert seen["rebuild_conds"] == 0
    assert seen["table_writers"] == [1] * LENGTHS
    assert seen["table_copies"] == 0
