"""Spreader world: upstream `examples/spreader`, run back to back by
many roots.

One actor type written against the public API (`@actor`, `@behaviour`,
`Ref`, `ctx.spawn`, `Runtime.declare / start / spawn_many / bulk_send /
run / gc`):

  Spreader   `spread(parent, count)` is its constructor (`ctx.spawn`:
             the first message, asynchronous): a leaf (`count == 0`)
             reports `parent.result(1)`, anything else creates two more,
             `spread(this, count - 1)`. `result(i)` adds `i`; on the
             second result it reports `sum + 1` to its parent. A root
             (`parent < 0`, created at set-up by `spawn_many` and
             pinned) then has a whole tree: `runs += 1`, `total += sum +
             1`, and it launches the next tree in the same dispatch.
             `start(wait)` is the roots' own: `wait` self-sends, then
             the first tree. `BATCH = 2`: both results in one tick.
             **No `destroy()` anywhere**: an actor dies only by being
             unreachable, and every row but the roots' is free at
             set-up.

Every size follows from `cfg["actors"]` and `cfg["count"]`: a tree's
period is `2 * count` ticks and its non-root actors live
`reference_spreader.row_ticks(count)` row-ticks a period, so
`actors // (4 * row_ticks)` roots a phase keep a quarter of the rows
live; `roots` = that x the period. A self-test's `scale={"actors":
4096, "count": 6}` cuts the whole world, and so does `{"actors": 2048}`
alone: where the rows hold no tree of `count` on every phase, the trees
are the deepest that fit. At the size the file states,
the derived sizes must be the ones it states, and the traffic mix's.
A mix may give `trees`, the trees a root runs before it stops (the
tier-1 tests' finite worlds); the default outlasts any window.

What `correct` holds the system to is `reference_spreader`: `Forest`
tick by tick, and `invariant` / `reachable` on the chip's own state.
"""

from __future__ import annotations

import sys

import jax.numpy as jnp
import numpy as np

from ponyc_tpu import I32, Ref, Runtime, RuntimeOptions, actor, behaviour

from benchmarks import reference_spreader as ref

TREES = 1 << 30     # trees left on a seeded root: outlasts any window
ERROR_COUNTERS = ("n_rejected", "n_badmsg", "n_deadletter", "n_destroyed")


def _two(ctx, count, when):
    """Create two more, `spread(this, count - 1)`."""
    for _ in range(2):
        ctx.spawn(Spreader.spread, ctx.actor_id, count - 1, when=when)


@actor
class Spreader:
    parent: Ref
    depth: I32      # its `count`: levels still to create below it
    got: I32
    acc: I32
    runs: I32       # a root's: trees completed,
    total: I32      # ... the actors they reported,
    left: I32       # ... and trees still to complete

    SPAWNS = {"Spreader": 2}
    SPAWN_DISPATCHES = 1    # of a tick's two dispatches one spawns at most
    BATCH = 2
    MAX_SENDS = 3           # two constructors and a report or a self-send

    @behaviour
    def start(self, st, wait: I32):
        go = wait <= 0
        self.send(self.actor_id, Spreader.start, wait - 1, when=~go)
        _two(self, st["depth"], go)
        return st

    @behaviour
    def spread(self, st, parent: Ref, count: I32):
        leaf = count <= 0
        self.send(parent, Spreader.result, 1, when=leaf)
        _two(self, count, ~leaf)
        return {**st, "parent": parent, "depth": count}

    @behaviour
    def result(self, st, i: I32):
        got, acc = st["got"] + 1, st["acc"] + i
        done = got == 2
        root = st["parent"] < 0
        fin = done & root
        self.send(st["parent"], Spreader.result, acc + 1, when=done & ~root)
        _two(self, st["depth"], fin & (st["left"] != 1))
        return {**st, "got": jnp.where(done, 0, got),
                "acc": jnp.where(done, 0, acc),
                "runs": st["runs"] + fin,
                "total": st["total"] + jnp.where(fin, acc + 1, 0),
                "left": st["left"] - fin}


def sizes(actors: int, count: int) -> dict:
    """The world's sizes from its two free ones."""
    if count < 1:
        raise ValueError("a tree needs count >= 1")
    while count > 1 and actors < 4 * ref.row_ticks(count):
        count -= 1      # a cut world: the deepest tree that still fits
    period = 2 * count
    per_phase = actors // (4 * ref.row_ticks(count))
    if per_phase < 1:
        raise ValueError(f"{actors} rows hold no tree on every phase")
    return {"actors": actors, "count": count, "period": period,
            "roots": per_phase * period,
            "tree_actors": ref.tree_actors(count)}


class World:
    """One seeded forest and what the churn mode asks of it."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        opts = RuntimeOptions(**cfg["runtime_options"])
        rt = Runtime(opts)
        if "free_rows_low" not in rt.run_loop_stats():
            # a program whose run loop does not watch its rows collects
            # every cd_interval ticks and is refused a row long before:
            # no number, and at once (exit 2, no result line)
            print("benchmarks/worlds/spreader.py: this program's run loop "
                  "has no row-pressure collection (run_loop_stats() lacks "
                  "free_rows_low), so the forest cannot live in its rows "
                  "— no result", file=sys.stderr)
            raise SystemExit(2)
        size = sizes(int(cfg["actors"]), int(cfg["count"]))
        stated = cfg["sizes"]
        if size["actors"] == stated["actors"]:
            if size != stated:
                raise ValueError(f"the configuration states {stated}, its "
                                 f"rules give {size}")
            for key in ("roots", "count"):
                if int(traffic[key]) != size[key]:
                    raise ValueError(f"the mix states {key} "
                                     f"{traffic[key]}, the configuration "
                                     f"{size[key]}")
        if int(traffic["phases"]) != 2 * int(traffic["count"]):
            raise ValueError("the phases are a tree's period, 2 x count")
        self.n, self.count = size["actors"], size["count"]
        self.roots, self.period = size["roots"], size["period"]
        self.tree = size["tree_actors"]
        trees = self.trees = traffic.get("trees")
        self.phase = ref.phases(seed, self.roots, self.period)
        self.forest = self.reference()
        self.live = self.roots              # messages seeded

        rt.declare(Spreader, self.n)
        rt.start()
        self.root_ids = rt.spawn_many(
            Spreader, self.roots, parent=-1, depth=self.count,
            left=TREES if trees is None else int(trees))
        rt.bulk_send(self.root_ids, Spreader.start,
                     self.phase.astype(np.int64))
        self.rt = rt
        self.is_root = np.zeros(self.n, bool)
        self.is_root[self.root_ids] = True

    def reference(self) -> ref.Forest:
        """The protocol from tick 0, for this world's roots."""
        return ref.Forest(self.phase, self.count, self.trees)

    # ---- what the system holds now, read from its state
    def roots_state(self) -> dict:
        cols = self.rt.cohort_state(Spreader)
        return {k: np.asarray(cols[k])[self.root_ids].astype(np.int64)
                for k in ("runs", "total", "left")}

    def queued_refs(self) -> np.ndarray:
        """Ids the Ref arguments of queued and spilled messages name,
        and the spill's targets. Only `spread` carries a Ref (its first
        argument); the occupied ring slots are read on the device, rank
        by rank, so the [cap, words, rows] table never crosses to the
        host."""
        st = self.rt.state
        buf = st.buf[Spreader.__name__]
        cap = buf.shape[0]
        head, occ = st.head, st.tail - st.head
        gid = Spreader.spread.global_id
        rows = jnp.arange(buf.shape[2])
        named = []
        for k in range(int(jnp.max(occ))):
            msg = buf[(head + k) % cap, :, rows]           # [rows, words]
            named.append(np.asarray(jnp.where(
                (k < occ) & (msg[:, 0] == gid), msg[:, 1], -1)))
        tgt = np.asarray(st.dspill_tgt)
        words = np.asarray(st.dspill_words)
        named += [tgt, np.where((tgt >= 0) & (words[0] == gid), words[1], -1)]
        return np.concatenate(named)

    def keeps(self) -> np.ndarray:
        """`reference_spreader.reachable` over the system's state now."""
        st = self.rt.state
        alive = np.asarray(st.alive)
        occ = np.asarray(st.tail) - np.asarray(st.head)
        parent = self.rt.cohort_state(Spreader)["parent"]
        return ref.reachable(
            alive, np.asarray(st.pinned) | (occ > 0) | np.asarray(st.muted),
            [(np.arange(self.n), parent)], self.queued_refs())

    def check(self) -> dict:
        """`reference_spreader.invariant` over the system's state now,
        the reference advanced to the system's tick."""
        rt = self.rt
        self.forest.advance_to(rt.steps_run)
        return ref.invariant(
            self.forest, **self.roots_state(),
            n_spawned=rt.counter("n_spawned"),
            n_collected=rt.counter("n_collected"),
            alive=np.asarray(rt.state.alive), is_root=self.is_root,
            keeps=self.keeps())

    def counters(self) -> dict:
        """The always-on counters the mode follows tick by tick."""
        rt = self.rt
        stats = rt.run_loop_stats()
        return {"spawned": rt.counter("n_spawned"),
                "collected": rt.counter("n_collected"),
                "passes": stats["gc_runs"], "hops": stats["gc_iters"],
                "gc_s": stats["phase_s"].get("gc", 0.0),
                "free_rows_low": stats["free_rows_low"]}

    def held(self) -> int:
        """Messages the world holds: every ring, and the spill."""
        st = self.rt.state
        return int((np.asarray(st.tail, np.int64)
                    - np.asarray(st.head, np.int64)).sum()
                   + np.asarray(st.dspill_count, np.int64).sum())

    def errors(self) -> dict:
        found = {c: self.rt.counter(c) for c in ERROR_COUNTERS}
        found["spawn_fail"] = int(np.asarray(self.rt.state.spawn_fail).any())
        return found

    def tick_shape(self) -> dict:
        """What one steady tick must touch, for min_bytes: a tree takes
        2^(count+2) - 4 dispatches a period of 2 x count ticks, in
        3 x 2^count - 3 actor visits (a `result` pair is one visit);
        records are one header and two payload words."""
        per_tick = self.roots / self.period
        return {"messages": per_tick * ((1 << (self.count + 2)) - 4),
                "dispatching_actors": per_tick * (3 * (1 << self.count) - 3),
                "record_words": 1 + int(self.rt.opts.msg_words),
                "state_words": len(Spreader.field_specs)}


def build(cfg: dict, traffic: dict, seed: int) -> World:
    return World(cfg, traffic, seed)
