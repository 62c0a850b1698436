"""The fan-in world on a mesh: `worlds/fanin.py`'s Producers and
Aggregators in one world whose actor rows are sharded over
`mesh_shards` devices, each producer wired under the Zipf to an
aggregator ANYWHERE in the world.

Written against the public API only, with `worlds/fanin.py`'s actors,
draws and readers (its `World` reads the spill shard by shard already);
what a mesh changes is stated here:

  layout      `spawn_many` deals a cohort's rows round-robin over the
              shards (`reference_mesh.deal`), so the k-th aggregator
              spawned lives on shard k % shards, and likewise the k-th
              producer: with the rank -> aggregator permutation drawn
              over the whole world, (shards - 1) / shards of the
              producer -> aggregator edges cross shards. Every array
              here is in spawn order, as `worlds/fanin.py`'s; an actor's
              shard is `id // n_local`.
  reference   `reference_fanin_mesh.Ticks`: the protocol given the
              layout (a producer on another shard than its aggregator
              is muted one tick later than one on the same), and from
              the same run what the route's counters must read.
              `reference_fanin.conservation` is layout-free and is used
              as it is.
  spill       a shard's capacity follows from the configuration's
              `spill_bound_items` (B = 2 items a producer outside a
              mailbox) and the wiring: the power of two at or above
              B x the producers wired to the fullest shard's
              aggregators, never below B x twice a shard's even share
              (`reference_fanin_mesh.spill_capacity`). `spill_bound[s]`
              = B x the producers wired to shard s is what the mode
              holds every shard's spill to after every segment.
"""

from __future__ import annotations

import sys

import numpy as np

from ponyc_tpu import Runtime, RuntimeOptions

from benchmarks import reference_fanin as ref
from benchmarks import reference_fanin_mesh as ref_mesh
from benchmarks import reference_mesh
from benchmarks.worlds import fanin
from benchmarks.worlds.fanin import HOPS, Aggregator, Producer


class World(fanin.World):
    """One wired fan-in world on a mesh and what the mode asks of it."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        options = dict(cfg["runtime_options"])
        self.shards = max(1, int(options.get("mesh_shards", 1)))
        ratio = int(cfg["producers_per_aggregator"])
        self.n = int(cfg["actors"])
        self.a = self.n // (ratio + 1)
        self.p = self.n - self.a
        if self.a < 1 or self.a % self.shards or self.p % self.shards:
            raise ValueError(f"{self.n} actors at {ratio} producers an "
                             f"aggregator do not deal over {self.shards}")
        if int(traffic["items_per_dispatch"]) != 1 \
                or int(traffic["seeded_every"]) != 1:
            raise ValueError("the producer sends one item a dispatch and "
                             "every producer is seeded: the spill's bound "
                             "and both references are stated for that")
        self.live = self.p
        self.out = ref.zipf_wiring(seed, self.p, self.a,
                                   float(traffic["zipf_s"]))
        # the k-th actor of a cohort lands on shard k % shards
        bound = int(cfg["spill_bound_items"])
        wired = ref_mesh.wired_to_shards(
            self.out, np.arange(self.a) % self.shards, self.shards)
        self.spill_bound = bound * wired
        options["spill_cap"] = ref_mesh.spill_capacity(
            bound, self.p, self.shards, int(wired.max()))
        stated = cfg["sizes"]
        mine = {"actors": self.n, "aggregators": self.a, "producers": self.p,
                "spill_cap": ref_mesh.spill_capacity(bound, self.p,
                                                     self.shards, 0)}
        if self.n == stated["actors"] and mine != stated:
            raise ValueError(f"the configuration states {stated}, its "
                             f"ratios and its bound give {mine}")

        rt = Runtime(RuntimeOptions(**options))
        rt.declare(Producer, self.p)
        rt.declare(Aggregator, self.a)
        rt.start()
        try:
            rt.counter("n_remote_mutes")
        except AttributeError:
            # a program whose routing does not mute behind a remote
            # receiver (and so does not count it) cannot hold this
            # world: its spill grows until it overflows. No number, and
            # at once (exit 2, no result line)
            print("benchmarks/worlds/fanin_mesh.py: this program has no "
                  "counter n_remote_mutes: on a mesh it mutes only the "
                  "senders on the receiver's own shard, and this world "
                  "would end in SpillOverflowError — no result",
                  file=sys.stderr)
            raise SystemExit(2)
        self.agg_ids = rt.spawn_many(Aggregator, self.a)
        self.prod_ids = rt.spawn_many(Producer, self.p,
                                      out=self.agg_ids[self.out])
        nl = rt.program.n_local
        for ids, count in ((self.agg_ids, self.a), (self.prod_ids, self.p)):
            offsets = np.asarray(ids, np.int64) - int(np.min(ids))
            dealt = reference_mesh.deal(count, self.shards)
            # a cohort's ids: `count / shards` consecutive ones a shard
            if not np.array_equal(
                    offsets // nl * (count // self.shards) + offsets % nl,
                    dealt):
                raise RuntimeError("spawn_many did not deal the cohort's "
                                   "rows round-robin over the shards: the "
                                   "spill's capacity assumes it")
        rt.bulk_send(self.prod_ids, Producer.produce,
                     np.full(self.p, int(traffic.get("hops", HOPS)),
                             np.int64))
        self.rt = rt
        self.protocol = dict(mailbox_cap=rt.opts.mailbox_cap,
                             batch=rt.opts.batch,
                             overload_occ=rt.opts.overload_occ,
                             unmute_occ=rt.opts.unmute_occ)
        self._ticks = self._new_ticks()

    def _new_ticks(self) -> ref_mesh.Ticks:
        return ref_mesh.Ticks(self.out, self.prod_ids, self.agg_ids,
                              self.rt.program.n_local, **self.protocol)

    def _advanced(self, ticks: int) -> ref_mesh.Ticks:
        """The reference after `ticks` ticks (advanced, never rewound)."""
        if ticks < self._ticks.ticks:
            self._ticks = self._new_ticks()
        return self._ticks.advance(ticks - self._ticks.ticks)

    def reference(self, ticks: int) -> dict:
        return self._advanced(ticks).observed()

    def route_reference(self, ticks: int) -> dict:
        """{counter: what it must read after the first `ticks` ticks}."""
        return self._advanced(ticks).route_counters()

    def spill_by_shard(self) -> np.ndarray:
        """[shards] entries in each shard's receiver spill now."""
        return np.asarray(self.rt.state.dspill_count, np.int64)

    def tick_shape(self) -> dict:
        return {**super().tick_shape(), "shards": self.shards}


def build(cfg: dict, traffic: dict, seed: int) -> World:
    return World(cfg, traffic, seed)
