"""The fan-in's protocol on a mesh, tick by tick: NumPy only, no engine.

`reference_fanin.Ticks` states the protocol for a world on one shard.
On a mesh the actors' rows are dealt over `shards` devices, and two
things come to depend on where an actor lives. This file states the
protocol again with both in it, given the wiring, every actor's id and
the rows a shard holds (`id // n_local` is an actor's shard), and the
protocol's constants. It imports nothing of the engine.

The protocol, one tick:

  0. the hot word  every aggregator as the tick finds it: OVERLOADED if
              its mailbox holds more than `overload_occ` messages or a
              spilled item is waiting for it. (What the tick before left
              behind: the same two facts for which that tick's delivery
              muted the producers on the aggregator's own shard.)
  1. unmute   a muted producer, on whatever shard, is released when its
              aggregator's mailbox holds at most `unmute_occ` messages
              at the start of the tick and no spilled item waits for it;
  2. dispatch every producer that is not muted runs: one item carrying
              `sent`, then `sent += 1`; every aggregator drains
              `min(queued, batch)` items in FIFO order;
  3. route    THE REMOTE MUTE'S RULE: a producer that ran in this tick
              and whose aggregator the tick found OVERLOADED (0) is
              muted now, wherever it lives; its item of this tick still
              travels. For a producer on its aggregator's own shard the
              rule never fires (it was muted at the end of the tick
              before, 5, or released under 1, which an overloaded
              aggregator does not allow); for a producer on another
              shard it is the only way to be muted, and it comes one
              tick after the rejection that caused it;
  4. deliver  per aggregator the arrivals are [spilled items, oldest
              first] then [new items in the order of their producers'
              ids: source shard by source shard, row by row]; it accepts
              `min(arrivals, free slots)` from the front, the rest goes
              to its shard's spill in the same order;
  5. mute     the sender of every arrival that was rejected, or whose
              aggregator now holds more than `overload_occ`, is muted
              IF IT LIVES ON THE AGGREGATOR'S SHARD. A muted producer
              does not run.

What follows for the spill (the configuration's `spill_bound`): a
producer on its aggregator's shard has at most ONE item outside a
mailbox, as on one chip; a producer on another shard at most TWO — the
one that was rejected in tick t and the one it sent in tick t + 1, the
tick whose routing muted it; it then stays muted while anything is
spilled for its aggregator, its own items among them. `spill_peak`
keeps the fullest any shard's spill has been.

The route's counters follow from the same run: a producer that runs
ships two entries (its item and its next `produce` to itself), the item
crosses shards iff producer and aggregator live on different ones; a
shard looks the hot word up in every tick that found any aggregator
OVERLOADED.
"""

from __future__ import annotations

import numpy as np

from benchmarks.reference_fanin import MASK32, segment_rank


class Ticks:
    """The protocol of the module docstring, advanced one tick at a
    time. `out[p]` is producer p's aggregator (index); `prod_ids[p]` and
    `agg_ids[a]` the actors' ids; a shard holds `n_local` consecutive
    ids. Observable: `sent`, `muted` per producer; `total`, `seq_sum`
    (mod 2**32), `head`, `tail` per aggregator; the spill (`spill_tgt`,
    `spill_snd`, `spill_seq`, oldest first per aggregator); counters
    `n_rejected`, `n_mutes` (transitions), `n_remote_mutes` (producers
    muted under rule 3 behind an aggregator of another shard),
    `n_routed`, `n_routed_remote`, `n_lookups` (shard-ticks that looked
    the hot word up)."""

    def __init__(self, out: np.ndarray, prod_ids: np.ndarray,
                 agg_ids: np.ndarray, n_local: int, *, mailbox_cap: int,
                 batch: int, overload_occ: int, unmute_occ: int):
        if overload_occ < 1:
            raise ValueError("a producer (one message queued) would "
                             "count as overloaded")
        self.out = np.asarray(out, np.int64)
        prod_ids = np.asarray(prod_ids, np.int64)
        agg_ids = np.asarray(agg_ids, np.int64)
        self.a, self.cap, self.batch = len(agg_ids), mailbox_cap, batch
        self.overload_occ, self.unmute_occ = overload_occ, unmute_occ
        self.agg_shard = agg_ids // n_local
        self.shards = int(max(prod_ids.max(), agg_ids.max()) // n_local) + 1
        # arrival order at an aggregator: by the producers' ids
        self.by_id = np.argsort(prod_ids, kind="stable")
        self.crosses = (prod_ids // n_local) != self.agg_shard[self.out]
        p = len(self.out)
        self.sent = np.zeros(p, np.int64)
        self.muted = np.zeros(p, bool)
        self.total = np.zeros(self.a, np.int64)
        self.seq_sum = np.zeros(self.a, np.int64)
        self.ring = np.zeros((self.a, mailbox_cap), np.int64)
        self.head = np.zeros(self.a, np.int64)
        self.tail = np.zeros(self.a, np.int64)
        self.spill_tgt = np.zeros(0, np.int64)
        self.spill_snd = np.zeros(0, np.int64)
        self.spill_seq = np.zeros(0, np.int64)
        self.n_rejected = self.n_mutes = self.n_remote_mutes = 0
        self.n_routed = self.n_routed_remote = self.n_lookups = 0
        self.ticks = self.spill_peak = 0

    def tick(self) -> None:
        a, cap, out = self.a, self.cap, self.out
        # 0. the hot word, 1. unmute: on what the tick starts with
        occ0 = self.tail - self.head
        pending = np.bincount(self.spill_tgt, minlength=a)
        overloaded = (occ0 > self.overload_occ) | (pending > 0)
        self.muted &= ~((occ0[out] <= self.unmute_occ) & (pending[out] == 0))
        muted_before = self.muted.copy()
        # 2. dispatch
        run = self.by_id[~self.muted[self.by_id]]
        new_seq = self.sent[run].copy()
        self.sent[run] += 1
        drained = np.minimum(occ0, self.batch)
        for j in range(int(drained.max(initial=0))):
            rows = np.flatnonzero(drained > j)
            self.seq_sum[rows] += self.ring[rows, (self.head[rows] + j) % cap]
        self.seq_sum &= MASK32
        self.total += drained
        self.head += drained
        # 3. route: the remote mute's rule
        self.n_routed += 2 * len(run)
        self.n_routed_remote += int(self.crosses[run].sum())
        if overloaded.any():
            self.n_lookups += self.shards
        behind_hot = run[overloaded[out[run]]]
        self.muted[behind_hot] = True
        self.n_remote_mutes += int(self.crosses[behind_hot].sum())
        # 4. deliver: spilled first, then new in the order of the ids
        tgt = np.concatenate([self.spill_tgt, out[run]])
        snd = np.concatenate([self.spill_snd, run])
        seq = np.concatenate([self.spill_seq, new_seq])
        order = np.argsort(tgt, kind="stable")
        tgt, snd, seq = tgt[order], snd[order], seq[order]
        rank = segment_rank(tgt)
        free = cap - (self.tail - self.head)
        accepted = rank < free[tgt]
        rows = tgt[accepted]
        self.ring[rows, (self.tail[rows] + rank[accepted]) % cap] = \
            seq[accepted]
        self.tail += np.bincount(rows, minlength=a)
        # 5. mute: the aggregator's shard mutes the senders it holds
        hot = (self.tail - self.head) > self.overload_occ
        local = ~self.crosses[snd]
        self.muted[snd[(~accepted | hot[tgt]) & local]] = True
        self.n_mutes += int((self.muted & ~muted_before).sum())
        rejected = ~accepted
        self.spill_tgt, self.spill_snd, self.spill_seq = \
            tgt[rejected], snd[rejected], seq[rejected]
        self.n_rejected += int(rejected.sum())
        self.spill_peak = max(self.spill_peak, int(np.bincount(
            self.agg_shard[self.spill_tgt], minlength=1).max()))
        self.ticks += 1

    def advance(self, ticks: int) -> "Ticks":
        for _ in range(ticks):
            self.tick()
        return self

    def observed(self) -> dict:
        """`reference_fanin.Ticks.observed()`'s keys, in the same form."""
        return {"sent": self.sent.copy(), "muted": self.muted.copy(),
                "total": self.total.copy(), "seq_sum": self.seq_sum.copy(),
                "queued": self.tail - self.head,
                "spilled": np.bincount(self.spill_tgt, minlength=self.a)}

    def route_counters(self) -> dict:
        """What the program's route counters must read after these
        ticks, under the program's names."""
        return {"n_routed": self.n_routed,
                "n_routed_remote": self.n_routed_remote,
                "n_route_pressure": self.n_lookups,
                "n_remote_mutes": self.n_remote_mutes}


def wired_to_shards(out: np.ndarray, agg_shard: np.ndarray,
                    shards: int) -> np.ndarray:
    """[shards] producers wired to each shard's aggregators."""
    return np.bincount(np.asarray(agg_shard)[np.asarray(out)],
                       minlength=shards)


def spill_capacity(bound: int, producers: int, shards: int,
                   fullest_wired: int) -> int:
    """A shard's spill capacity from the bound B (`bound` items a
    producer outside a mailbox): the power of two at or above B x the
    producers wired to the fullest shard's aggregators — and never
    below the power of two at or above B x twice a shard's even share,
    so that every seed of one size compiles the one program (a seed
    whose fullest shard is wired to more than twice the mean takes the
    next power of two)."""
    need = bound * max(int(fullest_wired), 2 * -(-producers // shards))
    return 1 << (need - 1).bit_length()
