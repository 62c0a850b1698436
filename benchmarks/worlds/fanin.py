"""Fan-in world: upstream `examples/fan-in`, many aggregators under a
Zipf.

`models/fanin.py`'s actors, copied against the public API (`@actor`,
`@behaviour`, `Runtime.declare / start / spawn_many / bulk_send / run`):

  Producer    self-driving: `produce(n)` sends one item to `out` and
              `produce(n - 1)` to itself. The item carries `sent`, the
              producer's sequence number, so that a lost item and a
              duplicated one cannot cancel in an aggregator's sums.
              `BATCH = 1`: a producer never holds more than its own
              `produce`; the batch only sizes the outbox.
  Aggregator  `consume(seq)`: `total += 1`, `seq_sum += seq`; no sends;
              drains `runtime_options.batch` a tick.

Every size follows from `cfg["actors"]` by the configuration's ratios
(`producers_per_aggregator`), the spill's capacity too (the power of two
at or above the producers: the proven bound on the spill, see the
configuration file), so a self-test's `scale={"actors": 2048}` cuts the
whole world. At the size the file states, the derived sizes must be the
ones it states. Each producer is wired at set-up to one aggregator, its
rank drawn Zipf(`zipf_s`) from the seed, independently of every other's
(`reference_fanin.zipf_wiring`). A mix may give `hops`, the items a
producer sends before it stops (the tier-1 tests' finite worlds); the
default outlasts any window.
"""

from __future__ import annotations

import numpy as np

from ponyc_tpu import I32, Ref, Runtime, RuntimeOptions, actor, behaviour

from benchmarks import reference_fanin as ref

HOPS = 1 << 30      # `produce`s left on a seeded producer: outlasts any window


@actor
class Aggregator:
    total: I32
    seq_sum: I32

    @behaviour
    def consume(self, st, seq: I32):
        return {**st, "total": st["total"] + 1,
                "seq_sum": st["seq_sum"] + seq}


@actor
class Producer:
    out: Ref
    sent: I32

    BATCH = 1
    MAX_SENDS = 2

    @behaviour
    def produce(self, st, n: I32):
        self.send(st["out"], Aggregator.consume, st["sent"], when=n > 0)
        self.send(self.actor_id, Producer.produce, n - 1, when=n > 0)
        return {**st, "sent": st["sent"] + (n > 0)}


def _at(column, ids) -> np.ndarray:
    """A per-actor column of the state, at the given actor ids."""
    return np.asarray(column)[ids]


def sizes(actors: int, producers_per_aggregator: int) -> dict:
    """The world's sizes from its one free size."""
    aggregators = actors // (producers_per_aggregator + 1)
    producers = actors - aggregators
    if aggregators < 1:
        raise ValueError(f"{actors} actors leave no aggregator")
    return {"actors": actors, "aggregators": aggregators,
            "producers": producers,
            "spill_cap": 1 << (producers - 1).bit_length()}


class World:
    """One wired fan-in world and what the pressure mode asks of it."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        size = sizes(int(cfg["actors"]), int(cfg["producers_per_aggregator"]))
        stated = cfg["sizes"]
        if size["actors"] == stated["actors"] and size != stated:
            raise ValueError(f"the configuration states {stated}, its "
                             f"ratios give {size}")
        if int(traffic["items_per_dispatch"]) != 1 \
                or int(traffic["seeded_every"]) != 1:
            raise ValueError("the producer sends one item a dispatch and "
                             "every producer is seeded: the spill's bound "
                             "and both references are stated for that")
        self.n = size["actors"]
        self.a, self.p = size["aggregators"], size["producers"]
        self.live = self.p
        options = {**cfg["runtime_options"], "spill_cap": size["spill_cap"]}
        self.out = ref.zipf_wiring(seed, self.p, self.a,
                                   float(traffic["zipf_s"]))

        rt = Runtime(RuntimeOptions(**options))
        rt.declare(Producer, self.p)
        rt.declare(Aggregator, self.a)
        rt.start()
        self.agg_ids = rt.spawn_many(Aggregator, self.a)
        self.prod_ids = rt.spawn_many(Producer, self.p,
                                      out=self.agg_ids[self.out])
        rt.bulk_send(self.prod_ids, Producer.produce,
                     np.full(self.p, int(traffic.get("hops", HOPS)),
                             np.int64))
        self.rt = rt
        self.protocol = dict(mailbox_cap=rt.opts.mailbox_cap,
                             batch=rt.opts.batch,
                             overload_occ=rt.opts.overload_occ,
                             unmute_occ=rt.opts.unmute_occ)
        self._ticks = ref.Ticks(self.out, self.a, **self.protocol)

    # ---- what the system holds now, read from its state
    def counts(self) -> np.ndarray:
        """Behaviours each actor has run: producers, then aggregators."""
        rt = self.rt
        return np.concatenate([
            rt.cohort_state(Producer)["sent"].astype(np.int64),
            rt.cohort_state(Aggregator)["total"].astype(np.int64)])

    def spill(self) -> tuple[np.ndarray, np.ndarray]:
        """The live entries of the receiver spill: (aggregator index,
        sequence number). Entries hold the target's row on its shard."""
        st, program = self.rt.state, self.rt.program
        tgt = np.asarray(st.dspill_tgt).astype(np.int64)
        per_shard = len(tgt) // program.shards
        gid = tgt + (np.arange(len(tgt)) // per_shard) * program.n_local
        live = tgt >= 0
        index_of = np.full(program.shards * program.n_local, -1, np.int64)
        index_of[self.agg_ids] = np.arange(self.a)
        agg = index_of[gid[live]]
        if (agg < 0).any():
            raise RuntimeError("a spilled message targets no aggregator")
        return agg, np.asarray(st.dspill_words)[1][live].astype(np.int64)

    def observed(self, spill_tgt=None) -> dict:
        """`reference_fanin.Ticks.observed()`'s keys, from the system
        (`spill_tgt`: the spill's targets, where the caller has them)."""
        rt, st = self.rt, self.rt.state
        agg = rt.cohort_state(Aggregator)
        queued = (_at(st.tail, self.agg_ids).astype(np.int64)
                  - _at(st.head, self.agg_ids))
        return {"sent": rt.cohort_state(Producer)["sent"].astype(np.int64),
                "muted": _at(st.muted, self.prod_ids).astype(bool),
                "total": agg["total"].astype(np.int64),
                "seq_sum": agg["seq_sum"].astype(np.int64) & ref.MASK32,
                "queued": queued,
                "spilled": np.bincount(
                    self.spill()[0] if spill_tgt is None else spill_tgt,
                    minlength=self.a)}

    def conservation(self) -> dict:
        """`reference_fanin.conservation` over the system's state now."""
        rt, st = self.rt, self.rt.state
        spill_tgt, spill_seq = self.spill()
        seen = self.observed(spill_tgt)
        cols = rt.program.by_type[Aggregator].gid_to_col(self.agg_ids)
        payload = np.asarray(st.buf[Aggregator.__name__][:, 1, :])[:, cols]
        head = _at(st.head, self.agg_ids).astype(np.int64)
        ring_count, ring_seq_sum = ref.ring_items(
            payload.astype(np.int64) & ref.MASK32, head, head + seen["queued"])
        held = (_at(st.tail, self.prod_ids).astype(np.int64)
                - _at(st.head, self.prod_ids))
        return ref.conservation(
            self.out, self.a, sent=seen["sent"], total=seen["total"],
            seq_sum=seen["seq_sum"], ring_count=ring_count,
            ring_seq_sum=ring_seq_sum, spill_tgt=spill_tgt,
            spill_seq=spill_seq, produce_held=held, muted=seen["muted"])

    def held(self) -> int:
        """Messages the world holds: every ring, and the spill."""
        st = self.rt.state
        return int((np.asarray(st.tail, np.int64)
                    - np.asarray(st.head, np.int64)).sum()
                   + np.asarray(st.dspill_count, np.int64).sum())

    # ---- the references
    def reference(self, ticks: int) -> dict:
        """The protocol's state after `ticks` ticks, tick by tick (the
        reference is advanced, never rewound)."""
        if ticks < self._ticks.ticks:
            self._ticks = ref.Ticks(self.out, self.a, **self.protocol)
        return self._ticks.advance(ticks - self._ticks.ticks).observed()

    def tick_shape(self) -> dict:
        """What one steady tick must touch, for min_bytes: an aggregator
        with m producers consumes min(m, batch) items a tick and as many
        producers run to refill it, each dispatch one record in and one
        out; both actor types have two state words."""
        served = np.minimum(np.bincount(self.out, minlength=self.a),
                            self.protocol["batch"])
        return {"messages": int(2 * served.sum()),
                "dispatching_actors": float(served.sum()
                                            + np.count_nonzero(served)),
                "record_words": 1 + int(self.rt.opts.msg_words),
                "state_words": len(Producer.field_specs)}


def build(cfg: dict, traffic: dict, seed: int) -> World:
    return World(cfg, traffic, seed)
