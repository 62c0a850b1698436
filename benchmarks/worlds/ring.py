"""Token ring: upstream `examples/ring`.

`models/ring.py`'s RingNode WITHOUT `self.exit(0)`: upstream's ring ends
at quiescence, and `exit` is sticky in this runtime — after the first
lap's `exit(0)` every later `run()` would return after one tick and the
token would never travel. Without it `run()` returns 0 by itself when
the token's hops are used up, and the next lap starts from a quiet
world. Written against the public API only.

The traffic file's `mode` decides how the ring is fed:
  lap         nothing is seeded; `start_lap()` sends one token with
              `laps_per_token` laps of hops left (`tokens` must be 1).
  throughput  `tokens` tokens, evenly spaced, are seeded at build with
              hops that outlast any window, and circulate.
"""

from __future__ import annotations

import numpy as np

from ponyc_tpu import I32, Ref, Runtime, RuntimeOptions, actor, behaviour

from benchmarks import reference

HOPS = 1 << 30      # outlasts any window


@actor
class RingNode:
    next_ref: Ref
    passes: I32

    @behaviour
    def token(self, st, hops: I32):
        self.send(st["next_ref"], RingNode.token, hops - 1, when=hops > 1)
        return {**st, "passes": st["passes"] + 1}


class World:
    def __init__(self, cfg: dict, traffic: dict, seed: int):
        del seed                          # the ring has nothing to draw
        n = int(cfg["actors"])
        self.n = n
        self.tokens = self.live = int(traffic["tokens"])
        rt = Runtime(RuntimeOptions(**cfg["runtime_options"]))
        rt.declare(RingNode, n)
        rt.start()
        ids = rt.spawn_many(RingNode, n)
        rt.set_fields(RingNode, ids, next_ref=np.roll(ids, -1))
        self.rt, self.ids = rt, ids
        if traffic["mode"] == "lap":
            if self.tokens != 1:
                raise ValueError("lap mode sends one token per lap")
            self.hops = int(traffic["laps_per_token"]) * n
        else:
            self.starts = np.arange(self.tokens) * (n // self.tokens)
            rt.bulk_send(ids[self.starts], RingNode.token,
                         np.full(self.tokens, HOPS, np.int64))

    def counts(self) -> np.ndarray:
        return self.rt.cohort_state(RingNode)["passes"].astype(np.int64)

    # ---- lap mode
    def start_lap(self) -> int:
        """Seed one lap's token; returns the hops it will make."""
        self.rt.send(int(self.ids[0]), RingNode.token, self.hops)
        return self.hops

    def reference_laps(self, laps: int) -> np.ndarray:
        return reference.ring_passes(self.n, self.hops, laps)

    # ---- throughput mode
    def reference(self, ticks: int) -> np.ndarray:
        return reference.cycle_counts(np.arange(self.n), self.starts, 1, ticks)

    reference_closed = reference

    def tick_shape(self) -> dict:
        return {"messages": self.live, "dispatching_actors": float(self.live),
                "record_words": 1 + int(self.rt.opts.msg_words),
                "state_words": len(RingNode.field_specs)}


def build(cfg: dict, traffic: dict, seed: int) -> World:
    return World(cfg, traffic, seed)
