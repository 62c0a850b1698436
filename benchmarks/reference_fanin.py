"""Plain references for the fan-in world: NumPy only, no engine.

Producers each hold one `produce` and, when they run, send one item
(their sequence number) to the one aggregator they are wired to;
aggregators count items and sum the sequence numbers. Mailboxes are
bounded, so the world lives in the runtime's backpressure protocol.
Two references decide `correct`, and neither imports `ponyc_tpu` or JAX:

  Ticks          the protocol written down tick by tick (below), for the
                 first ticks of a run and for the tier-1 tests;
  conservation   what must hold after ANY number of ticks, computed from
                 the system's own state: no item lost, none duplicated.

The protocol, one tick (Pony's, as the engine states it for one shard):

  1. unmute   a muted producer is released when its aggregator's mailbox
              held at most `unmute_occ` messages at the start of the
              tick and no spilled message is waiting for it;
  2. dispatch every producer that is not muted runs its `produce`: one
              item carrying `sent`, then `sent += 1` (its next `produce`
              goes to its own mailbox, which never fills); every
              aggregator drains `min(queued, batch)` items in FIFO order;
  3. deliver  per aggregator the arrivals are [spilled items, oldest
              first] then [new items in producer order]; it accepts
              `min(arrivals, free slots)` from the front, the rest goes
              to the spill in the same order;
  4. mute     the sender of every arrival that was rejected, or whose
              aggregator now holds more than `overload_occ`, is muted
              (a producer is never overloaded itself: its mailbox holds
              one message). A muted producer does not run.
"""

from __future__ import annotations

import numpy as np

MASK32 = 0xFFFFFFFF


def zipf_wiring(seed: int, producers: int, aggregators: int,
                s: float) -> np.ndarray:
    """`out[p]`: the aggregator (0-based) producer p reports to. Each
    producer draws its aggregator's rank from Zipf(s) over
    1..aggregators (probability of rank r proportional to r**-s),
    independently, from `seed`; a seeded permutation says which
    aggregator has which rank. Every seed is another sample of the
    deployment: another hot set, and a cold aggregator may come out hot
    by chance."""
    gen = np.random.default_rng(seed)
    cdf = np.cumsum(np.arange(1, aggregators + 1, dtype=np.float64) ** -s)
    ranks = np.minimum(
        np.searchsorted(cdf, gen.random(producers) * cdf[-1], side="right"),
        aggregators - 1)
    return gen.permutation(aggregators)[ranks].astype(np.int64)


def segment_rank(sorted_keys: np.ndarray) -> np.ndarray:
    """Each element's place within its run of equal keys."""
    idx = np.arange(len(sorted_keys))
    first = np.ones(len(sorted_keys), bool)
    first[1:] = sorted_keys[1:] != sorted_keys[:-1]
    return idx - np.maximum.accumulate(np.where(first, idx, 0))


class Ticks:
    """The protocol of the module docstring, advanced one tick at a
    time. Everything observable is an attribute: `sent`, `muted` per
    producer; `total`, `seq_sum` (mod 2**32), `head`, `tail` per
    aggregator; the spill (`spill_tgt`, `spill_snd`, `spill_seq`, oldest
    first per aggregator); the counters `n_rejected` (rejections, a
    re-rejected spilled item counts again) and `n_mutes` (transitions)."""

    def __init__(self, out: np.ndarray, aggregators: int, *, mailbox_cap: int,
                 batch: int, overload_occ: int, unmute_occ: int):
        if overload_occ < 1:
            raise ValueError("a producer (one message queued) would "
                             "count as overloaded")
        self.out = np.asarray(out, np.int64)
        self.a, self.cap, self.batch = aggregators, mailbox_cap, batch
        self.overload_occ, self.unmute_occ = overload_occ, unmute_occ
        p = len(self.out)
        self.sent = np.zeros(p, np.int64)
        self.muted = np.zeros(p, bool)
        self.total = np.zeros(aggregators, np.int64)
        self.seq_sum = np.zeros(aggregators, np.int64)
        self.ring = np.zeros((aggregators, mailbox_cap), np.int64)
        self.head = np.zeros(aggregators, np.int64)
        self.tail = np.zeros(aggregators, np.int64)
        self.spill_tgt = np.zeros(0, np.int64)
        self.spill_snd = np.zeros(0, np.int64)
        self.spill_seq = np.zeros(0, np.int64)
        self.n_rejected = self.n_mutes = self.ticks = 0
        self.spill_peak = 0

    def tick(self) -> None:
        a, cap = self.a, self.cap
        # 1. unmute, on what the tick starts with
        occ0 = self.tail - self.head
        pending = np.bincount(self.spill_tgt, minlength=a)
        self.muted &= ~((occ0[self.out] <= self.unmute_occ)
                        & (pending[self.out] == 0))
        # 2. dispatch
        run = np.flatnonzero(~self.muted)
        new_seq = self.sent[run].copy()
        self.sent[run] += 1
        drained = np.minimum(occ0, self.batch)
        for j in range(int(drained.max(initial=0))):
            rows = np.flatnonzero(drained > j)
            self.seq_sum[rows] += self.ring[rows, (self.head[rows] + j) % cap]
        self.seq_sum &= MASK32
        self.total += drained
        self.head += drained
        # 3. deliver: spilled first, then new in producer order
        tgt = np.concatenate([self.spill_tgt, self.out[run]])
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
        # 4. mute
        hot = (self.tail - self.head) > self.overload_occ
        now_muted = np.zeros(len(self.out), bool)
        now_muted[snd[~accepted | hot[tgt]]] = True
        self.n_mutes += int((now_muted & ~self.muted).sum())
        self.muted |= now_muted
        rejected = ~accepted
        self.spill_tgt, self.spill_snd, self.spill_seq = \
            tgt[rejected], snd[rejected], seq[rejected]
        self.n_rejected += int(rejected.sum())
        self.spill_peak = max(self.spill_peak, len(self.spill_tgt))
        self.ticks += 1

    def advance(self, ticks: int) -> "Ticks":
        for _ in range(ticks):
            self.tick()
        return self

    def observed(self) -> dict:
        """The same keys, in the same form, as the world reads from the
        system (`worlds/fanin.py` `observed()`)."""
        return {"sent": self.sent.copy(), "muted": self.muted.copy(),
                "total": self.total.copy(), "seq_sum": self.seq_sum.copy(),
                "queued": self.tail - self.head,
                "spilled": np.bincount(self.spill_tgt, minlength=self.a)}


def ring_items(buf_seq: np.ndarray, head: np.ndarray,
               tail: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Items waiting in each aggregator's ring, and the sum of their
    sequence numbers mod 2**32. `buf_seq` is [cap, aggregators]: the
    payload word of every ring slot; slot = count % cap."""
    cap = buf_seq.shape[0]
    queued = tail - head
    place = (np.arange(cap)[:, None] - head[None, :]) % cap
    live = place < queued[None, :]
    return queued, (np.where(live, buf_seq, 0).astype(np.int64).sum(axis=0)
                    & MASK32)


def conservation(out: np.ndarray, aggregators: int, *, sent, total, seq_sum,
                 ring_count, ring_seq_sum, spill_tgt, spill_seq,
                 produce_held, muted) -> dict:
    """What must hold after any number of ticks, from the system's own
    state. Per aggregator: items counted + waiting in its ring + waiting
    in the spill = items its producers sent, and the same for the sum of
    the sequence numbers (a producer that sent n items sent 0..n-1; sums
    mod 2**32), so an item lost and another counted twice do not cancel.
    Returns the checks and `deficit`: items unaccounted for, either way.

    out [P] producer -> aggregator; sent, produce_held, muted [P];
    total, seq_sum, ring_count, ring_seq_sum [A]; spill_tgt, spill_seq
    the live spill entries (aggregator, sequence number)."""
    out = np.asarray(out, np.int64)
    sent = np.asarray(sent, np.int64)
    spill_tgt = np.asarray(spill_tgt, np.int64)
    in_spill = np.bincount(spill_tgt, minlength=aggregators)
    spill_sum = np.zeros(aggregators, np.int64)
    np.add.at(spill_sum, spill_tgt, np.asarray(spill_seq, np.int64) & MASK32)
    offered = np.bincount(out, weights=sent, minlength=aggregators) \
        .astype(np.int64)
    # 0 + 1 + .. + (n - 1) mod 2**32 (n < 2**31 fits int64 squared)
    offered_sum = np.zeros(aggregators, np.int64)
    np.add.at(offered_sum, out, ((sent * (sent - 1)) // 2) & MASK32)
    accounted = np.asarray(total, np.int64) + ring_count + in_spill
    accounted_sum = (np.asarray(seq_sum, np.int64) + ring_seq_sum
                     + spill_sum) & MASK32
    holding = np.asarray(ring_count) + in_spill
    return {
        "deficit": int(np.abs(accounted - offered).sum()),
        "checks": {
            "conservation_every_aggregator":
            bool(np.array_equal(accounted, offered))
            and bool(np.array_equal(accounted_sum, offered_sum & MASK32)),
            "one_produce_per_producer":
            bool(np.all(np.asarray(produce_held) == 1)),
            # nobody is muted with its aggregator drained: every mute
            # has work in front of it that will release it
            "muted_only_behind_work":
            bool(np.all(holding[out[np.asarray(muted, bool)]] > 0)),
        },
    }
