"""message-ubench on a mesh: the Pingers of `worlds/ubench.py` in a world
whose actor rows are sharded over `mesh_shards` devices.

Written against the public API only, like its one-chip twin, and with
the same seeded draws (one random cycle, one xorshift state an actor),
but every array here is indexed by an actor's ID OFFSET in the cohort
(`id - base`), not by spawn order. `spawn_many` deals a cohort's rows
round-robin over the shards (`reference_mesh.deal`), so on a mesh the
k-th actor spawned is not the k-th id; the id is what a recipient is
drawn as (`base + rng % n`) and what says which shard an actor lives
on. Stated by id, the world is the same world on any number of shards:
`reference.ubench_ticks` gives every actor's count without knowing the
layout, and `reference_mesh.remote_sends` says how many of the sends
cross shards under this one.

The traffic file chooses `recipients` ("cycle" or "random"), `pings` per
seeded Pinger and `seeded_every`, as for `worlds/ubench.py`.
"""

from __future__ import annotations

import sys

import numpy as np

from ponyc_tpu import Runtime, RuntimeOptions

from benchmarks import reference, reference_mesh
from benchmarks.worlds.ubench import HOPS, CyclePinger, RandomPinger


class World:
    """One seeded Pinger world on a mesh and what the harness may ask."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        n = int(cfg["actors"])
        opts = RuntimeOptions(**cfg["runtime_options"])
        self.n, self.shards = n, max(1, int(opts.mesh_shards))
        self.batch = int(opts.batch)
        self.per_seeded = int(traffic["pings"])
        self.random = traffic["recipients"] == "random"
        if not self.random and traffic["recipients"] != "cycle":
            raise ValueError(f"recipients: {traffic['recipients']!r}")
        if self.per_seeded > self.batch:
            raise ValueError("pings per Pinger above the drain batch: the "
                             "closed-form reference does not hold")
        if n % self.shards:
            raise ValueError(f"{n} actors do not split over {self.shards}")
        self.atype = RandomPinger if self.random else CyclePinger

        gen = np.random.default_rng(seed)
        # One random cycle over all offsets: order[i] sends to order[i+1].
        self.order = gen.permutation(n)
        self.position = np.empty(n, np.int64)
        self.position[self.order] = np.arange(n)
        self.next_slot = np.empty(n, np.int64)
        self.next_slot[self.order] = np.roll(self.order, -1)
        self.rng0 = gen.integers(1, 2**31 - 1, n, dtype=np.int64)
        self.starts = np.arange(0, n, int(traffic["seeded_every"]))
        self.seeded_slots = self.order[self.starts]
        self.live = len(self.seeded_slots) * self.per_seeded

        rt = Runtime(opts)
        rt.declare(self.atype, n)
        rt.start()
        try:
            rt.counter("n_routed")
        except AttributeError:
            # a program that does not count what its route moves cannot
            # be held to the reference's crossings: no number, and at
            # once (exit 2, no result line)
            print("benchmarks/worlds/ubench_mesh.py: this program has no "
                  "route counters (Runtime.counter('n_routed')), so the "
                  "mesh cell's check cannot be made — no result",
                  file=sys.stderr)
            raise SystemExit(2)
        ids = rt.spawn_many(self.atype, n)
        base = int(ids.min())
        # spawn order -> id offset: the round-robin deal, and nothing else
        self.offsets = np.asarray(ids, np.int64) - base
        if not np.array_equal(self.offsets,
                              reference_mesh.deal(n, self.shards)):
            raise RuntimeError("spawn_many did not deal the cohort's ids "
                               "round-robin over the shards: the "
                               "reference's crossings assume it")
        by_id = base + np.arange(n, dtype=np.int64)
        if self.random:
            rt.set_fields(self.atype, by_id, rng=self.rng0, base=base, n=n)
        else:
            rt.set_fields(self.atype, by_id, next_ref=base + self.next_slot)
        seeded_ids = np.sort(base + self.seeded_slots)
        hops = np.full(len(seeded_ids), HOPS, np.int64)
        for _ in range(self.per_seeded):
            rt.bulk_send(seeded_ids, self.atype.ping, hops)
        self.rt, self.ids, self.base = rt, ids, base

    def _seeded_queue(self) -> np.ndarray:
        queue = np.zeros(self.n, np.int64)
        queue[self.seeded_slots] = self.per_seeded
        return queue

    def _traffic(self) -> dict:
        if self.random:
            return {"rng": self.rng0.astype(np.uint32)}
        return {"next_slot": self.next_slot}

    def counts(self) -> np.ndarray:
        """Behaviours each actor has run, by id offset (`cohort_state`
        hands the column back in spawn order)."""
        pings = self.rt.cohort_state(self.atype)["pings"].astype(np.int64)
        out = np.empty(self.n, np.int64)
        out[self.offsets] = pings
        return out

    def reference(self, ticks: int) -> np.ndarray:
        """Per-actor counts after `ticks` ticks, tick by tick, by id
        offset: the layout-free reference."""
        pings, _, _ = reference.ubench_ticks(
            self._seeded_queue(), self.batch, ticks, **self._traffic())
        return pings

    def reference_closed(self, ticks: int):
        """Per-actor counts after any number of ticks where a closed
        form exists (cycle recipients), else None."""
        if self.random:
            return None
        return reference.cycle_counts(self.position, self.starts,
                                      self.per_seeded, ticks)

    def route_reference(self, ticks: int):
        """(sent [ticks], remote [ticks]): what the route's counters
        must have moved in each of the first `ticks` ticks."""
        return reference_mesh.remote_sends(
            self._seeded_queue(), self.batch, self.shards, ticks,
            **self._traffic())

    def tick_shape(self) -> dict:
        """What one steady tick must touch (as `worlds/ubench.py`), and
        for `route_bytes` the words of a routed entry."""
        if self.random:
            actors = self.n * -np.expm1(-self.live / self.n)
        else:
            actors = len(self.seeded_slots)
        return {"messages": self.live, "dispatching_actors": float(actors),
                "record_words": 1 + int(self.rt.opts.msg_words),
                "state_words": len(self.atype.field_specs),
                "shards": self.shards}


def build(cfg: dict, traffic: dict, seed: int) -> World:
    return World(cfg, traffic, seed)
