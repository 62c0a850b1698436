"""The plain reference for the `spreader-forest` configuration: upstream
`examples/spreader` run back to back by many roots. NumPy and plain
Python only: it imports neither JAX nor the engine.

The protocol (upstream, written down from memory). An actor `Spreader`
is created with `spread(parent, count)`: with `count == 0` it reports
`parent.result(1)`; otherwise it creates two more, `spread(this,
count - 1)`. `result(i)` adds `i`, and on the second result reports
`sum + 1` to its parent. The root has no parent: on its second result a
tree is complete (`runs += 1`, `total += sum + 1`, upstream prints
"<n> actors") and, in this deployment, the root launches the next tree
in the same dispatch. Nobody is ever told to die.

Three things, all integer, all compared exactly:

  Forest       the protocol tick by tick on abstract actors (root, node
               of the binary tree in heap order), with unbounded memory
               and no collector. A message sent in tick t is dispatched
               in tick t + 1; an actor takes both of its results in one
               tick (a batch of two). Per tick: spawns, dispatches, each
               root's `runs` and `total`, and the actors that are still
               owed a message or still owe one (`live`: everything else
               that was ever created is garbage).
  reachable    a breadth-first trace over arrays read from the system:
               the set a correct collector keeps.
  invariant    order-free, from the system's state at any tick.
"""

from __future__ import annotations

import numpy as np


def tree_actors(count: int) -> int:
    """Actors of one tree, the root among them: 2^(count+1) - 1."""
    return (1 << (count + 1)) - 1


def row_ticks(count: int) -> int:
    """Row-ticks one tree's non-root actors hold their rows for: a
    level-L actor is created in tick L - 1 of its tree and reports in
    tick 2 * count - L, so it is in use during 2 * (count - L) + 2 ticks
    (`Forest`'s `live`, counted after a tick's reports, is one tick an
    actor less)."""
    return sum((1 << lv) * (2 * (count - lv) + 2)
               for lv in range(1, count + 1))


def phases(seed: int, roots: int, period: int) -> np.ndarray:
    """[roots] the self-sends root r makes before its first tree: a
    seeded permutation with roots // period roots on every phase of the
    period, so that every tick of the steady state does the same work."""
    if roots % period:
        raise ValueError(f"{roots} roots do not fill {period} phases evenly")
    rng = np.random.default_rng(int(seed) & 0xFFFFFFFFFFFFFFFF)
    return rng.permutation(np.repeat(np.arange(period), roots // period))


class Forest:
    """`roots` trees of `count`, root r starting after `phase[r]`
    self-sends; `trees` (None: without end) trees a root."""

    def __init__(self, phase, count: int, trees: int | None = None):
        self.count = int(count)
        self.phase = np.asarray(phase, np.int64)
        r, m = len(self.phase), tree_actors(self.count)
        self.m = m
        node = np.arange(m)
        self.level = np.floor(np.log2(node + 1)).astype(np.int64)
        self.parent = (node - 1) // 2
        self.inner = node[(self.level < self.count)]
        self.start_q = self.phase.copy()            # root's `start` payload
        self.spread_q = np.zeros((r, m), bool)      # a `spread` is queued
        self.res_n = np.zeros((r, m), np.int64)     # `result`s queued
        self.res_sum = np.zeros((r, m), np.int64)   # ... and their sum
        self.got = np.zeros((r, m), np.int64)
        self.acc = np.zeros((r, m), np.int64)
        self.live = np.zeros((r, m), bool)          # created, not reported
        self.runs = np.zeros(r, np.int64)
        self.total = np.zeros(r, np.int64)
        self.left = np.full(r, -1 if trees is None else int(trees), np.int64)
        self.ticks = self.spawned = self.dispatched = 0

    def _launch(self, who, spread_next):
        """Roots `who` (bool [r]) create their two children."""
        spread_next[who, 1] = spread_next[who, 2] = True
        self.live[who, 1] = self.live[who, 2] = True
        return 2 * int(who.sum())

    def tick(self) -> dict:
        """One tick; the tick's own counts."""
        spread_next = np.zeros_like(self.spread_q)
        res_n = np.zeros_like(self.res_n)
        res_sum = np.zeros_like(self.res_sum)
        dispatches = (int((self.start_q >= 0).sum())
                      + int(self.spread_q.sum()) + int(self.res_n.sum()))
        # the roots' `start`: wait, or launch the first tree
        go = self.start_q == 0
        spawns = self._launch(go, spread_next)
        self.start_q = np.where(self.start_q > 0, self.start_q - 1, -1)
        # `spread`: an inner actor creates two, a leaf reports 1
        inner = self.spread_q[:, self.inner]
        for side in (1, 2):
            kids = 2 * self.inner + side
            spread_next[:, kids] |= inner
            self.live[:, kids] |= inner
        spawns += 2 * int(inner.sum())
        leaves = np.nonzero(self.level == self.count)[0]
        if self.count > 0:
            said = self.spread_q[:, leaves]
            np.add.at(res_n, (slice(None), self.parent[leaves]), said)
            np.add.at(res_sum, (slice(None), self.parent[leaves]), said)
            self.live[:, leaves] &= ~said
        # `result`: add; on the second, report to the parent or finish
        self.got += self.res_n
        self.acc += self.res_sum
        done = (self.got == 2) & (self.res_n > 0)
        nodes = np.arange(1, self.m)
        up = done[:, nodes]
        np.add.at(res_n, (slice(None), self.parent[nodes]), up)
        np.add.at(res_sum, (slice(None), self.parent[nodes]),
                  np.where(up, self.acc[:, nodes] + 1, 0))
        self.live[:, nodes] &= ~up
        fin = done[:, 0]
        self.runs += fin
        self.total += np.where(fin, self.acc[:, 0] + 1, 0)
        self.left = np.where(fin & (self.left > 0), self.left - 1, self.left)
        self.got[done] = 0
        self.acc[done] = 0
        spawns += self._launch(fin & (self.left != 0), spread_next)
        self.spread_q, self.res_n, self.res_sum = spread_next, res_n, res_sum
        self.ticks += 1
        self.spawned += spawns
        self.dispatched += dispatches
        return {"spawns": spawns, "dispatches": dispatches,
                "live": int(self.live.sum())}

    def advance_to(self, ticks: int) -> None:
        while self.ticks < ticks:
            self.tick()

    def held(self) -> int:
        """Messages queued after the last tick."""
        return (int((self.start_q >= 0).sum()) + int(self.spread_q.sum())
                + int(self.res_n.sum()))


def reachable(alive, roots, fields, named) -> np.ndarray:
    """[n] bool: the rows a correct collector keeps. `alive` [n];
    `roots` [n] bool: pinned rows, rows that hold a message, muted rows;
    `fields`: for every Ref field a pair (rows that have the field,
    their targets; -1 = none); `named`: ids the Ref arguments of queued
    and spilled messages, and the spills' targets, name (their holders
    are roots, so the ids are live from the start). A row's fields are
    followed only while it is alive."""
    alive = np.asarray(alive, bool)
    n = len(alive)
    live = np.asarray(roots, bool).copy()
    named = np.asarray(named, np.int64).reshape(-1)
    live[named[(named >= 0) & (named < n)]] = True
    frontier = live.copy()
    while frontier.any():
        reached = np.zeros(n, bool)
        for rows, tgt in fields:
            rows, tgt = np.asarray(rows), np.asarray(tgt, np.int64)
            ok = frontier[rows] & alive[rows] & (tgt >= 0) & (tgt < n)
            reached[tgt[ok]] = True
        frontier = reached & ~live
        live |= frontier
    return live


def invariant(forest: Forest, *, runs, total, left, n_spawned: int,
              n_collected: int, alive, is_root, keeps) -> dict:
    """How far the system's state is off what the protocol allows, after
    `forest.ticks` ticks: `roots_off`, roots whose `runs`, `total` (=
    `runs` x the tree's actors) or trees left differ from the
    reference's; `spawned_off`, |device spawns - the reference's|;
    `rows_off`, |alive non-root rows - (spawned - collected)|; `lost`,
    rows the trace `keeps` (reachable) that are not alive: a live actor
    was collected; `garbage`, alive rows the trace does not keep (what
    the next pass must free; 0 right after one)."""
    runs, total = np.asarray(runs, np.int64), np.asarray(total, np.int64)
    alive, keeps = np.asarray(alive, bool), np.asarray(keeps, bool)
    is_root = np.asarray(is_root, bool)
    tree = tree_actors(forest.count)
    roots_off = int(((runs != forest.runs) | (total != runs * tree)
                     | (total != forest.total)
                     | ((forest.left >= 0)
                        & (np.asarray(left, np.int64) != forest.left)))
                    .sum())
    return {"roots_off": roots_off,
            "spawned_off": abs(int(n_spawned) - forest.spawned),
            "rows_off": abs(int((alive & ~is_root).sum())
                            - (int(n_spawned) - int(n_collected))),
            "lost": int((keeps & ~alive).sum()),
            "garbage": int((alive & ~keeps).sum())}
