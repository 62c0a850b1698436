"""message-ubench worlds: Pingers exchanging `ping(hops_left)`.

Copies of the program's models written against the public API only
(`@actor`, `@behaviour`, `Runtime.declare / start / spawn_many /
set_fields / bulk_send / run`), so a later PR may change
`ponyc_tpu/models/*` without changing the yardstick.

  CyclePinger   `models/ubench.py`'s Pinger: `next_ref` wired as one
                random cycle drawn from the seed.
  RandomPinger  upstream's message-ubench: the recipient is drawn per
                send by a xorshift32 carried in the actor's state (the
                generator `models/gups.py` runs), seeded from the seed.

The traffic file chooses `recipients` ("cycle" or "random"), `pings` per
seeded Pinger and `seeded_every` (1 = every Pinger; k = one Pinger in k,
evenly spaced along the cycle).
"""

from __future__ import annotations

import numpy as np

from ponyc_tpu import I32, Ref, Runtime, RuntimeOptions, actor, behaviour

from benchmarks import reference

# Hops left on a seeded ping: more ticks than any window can run, so no
# ping dies inside one and the world holds its seeded messages forever.
HOPS = 1 << 30


@actor
class CyclePinger:
    next_ref: Ref
    pings: I32

    MAX_SENDS = 1

    @behaviour
    def ping(self, st, n: I32):
        self.send(st["next_ref"], CyclePinger.ping, n - 1, when=n > 0)
        return {**st, "pings": st["pings"] + 1}


@actor
class RandomPinger:
    rng: I32
    base: I32      # global id of the cohort's first slot
    n: I32         # Pingers in the world
    pings: I32

    MAX_SENDS = 1

    @behaviour
    def ping(self, st, n: I32):
        x = st["rng"]                    # xorshift32 on int32 lanes
        x = x ^ (x << 13)
        x = x ^ ((x >> 17) & 0x7FFF)
        x = x ^ (x << 5)
        self.send(st["base"] + x % st["n"], RandomPinger.ping, n - 1,
                  when=n > 0)
        return {**st, "rng": x, "pings": st["pings"] + 1}


class World:
    """One seeded Pinger world and what the harness may ask of it."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        n = int(cfg["actors"])
        self.n = n
        self.batch = int(cfg["runtime_options"]["batch"])
        self.per_seeded = int(traffic["pings"])
        self.random = traffic["recipients"] == "random"
        if not self.random and traffic["recipients"] != "cycle":
            raise ValueError(f"recipients: {traffic['recipients']!r}")
        if self.per_seeded > self.batch:
            raise ValueError("pings per Pinger above the drain batch: the "
                             "closed-form reference does not hold")
        self.atype = RandomPinger if self.random else CyclePinger

        gen = np.random.default_rng(seed)
        # One random cycle over all slots: order[i] sends to order[i+1].
        self.order = gen.permutation(n)
        self.position = np.empty(n, np.int64)
        self.position[self.order] = np.arange(n)
        self.next_slot = np.empty(n, np.int64)
        self.next_slot[self.order] = np.roll(self.order, -1)
        self.rng0 = gen.integers(1, 2**31 - 1, n, dtype=np.int64)
        self.starts = np.arange(0, n, int(traffic["seeded_every"]))
        self.seeded_slots = self.order[self.starts]
        self.live = len(self.seeded_slots) * self.per_seeded

        rt = Runtime(RuntimeOptions(**cfg["runtime_options"]))
        rt.declare(self.atype, n)
        rt.start()
        ids = rt.spawn_many(self.atype, n)
        if not np.array_equal(ids, ids[0] + np.arange(n)):
            raise RuntimeError("cohort ids are not contiguous: "
                               "RandomPinger's base + rng % n needs them so")
        if self.random:
            rt.set_fields(self.atype, ids, rng=self.rng0,
                          base=int(ids[0]), n=n)
        else:
            rt.set_fields(self.atype, ids, next_ref=ids[self.next_slot])
        seeded_ids = np.sort(ids[self.seeded_slots])
        hops = np.full(len(seeded_ids), HOPS, np.int64)
        for _ in range(self.per_seeded):
            rt.bulk_send(seeded_ids, self.atype.ping, hops)
        self.rt, self.ids = rt, ids

    def counts(self) -> np.ndarray:
        """Behaviours each actor has run, in spawn order."""
        return self.rt.cohort_state(self.atype)["pings"].astype(np.int64)

    def reference(self, ticks: int) -> np.ndarray:
        """Per-actor counts after `ticks` ticks, tick by tick."""
        queue = np.zeros(self.n, np.int64)
        queue[self.seeded_slots] = self.per_seeded
        pings, _, _ = reference.ubench_ticks(
            queue, self.batch, ticks,
            next_slot=None if self.random else self.next_slot,
            rng=self.rng0.astype(np.uint32) if self.random else None)
        return pings

    def reference_closed(self, ticks: int):
        """Per-actor counts after any number of ticks where a closed
        form exists (cycle recipients), else None."""
        if self.random:
            return None
        return reference.cycle_counts(self.position, self.starts,
                                      self.per_seeded, ticks)

    def tick_shape(self) -> dict:
        """What one steady tick must touch, for min_bytes: messages
        dispatched, actors that dispatch at least one (exact on a cycle;
        for random recipients the expected share of non-empty mailboxes
        under Poisson arrivals), and the words of a record and a state."""
        if self.random:
            actors = self.n * -np.expm1(-self.live / self.n)
        else:
            actors = len(self.seeded_slots)
        return {"messages": self.live, "dispatching_actors": float(actors),
                "record_words": 1 + int(self.rt.opts.msg_words),
                "state_words": len(self.atype.field_specs)}


def build(cfg: dict, traffic: dict, seed: int) -> World:
    return World(cfg, traffic, seed)
