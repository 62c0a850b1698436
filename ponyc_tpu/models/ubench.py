"""message-ubench — port of the reference's headline throughput benchmark
(`examples/message-ubench/main.pony`: N pinger actors continuously
exchanging ping messages; the metric is actor-messages/sec).

TPU shape: pingers are one cohort; each pinger holds a `next_ref` (a
shuffled permutation so traffic is irregular, like the reference's random
pings) and on `ping(n)` forwards `ping(n-1)` while n > 0. Seeding every
pinger with `pings` messages (≙ the reference's --initial-pings, default
5 there) yields a sustained load of exactly N×pings in-flight messages —
`pings` dispatches per actor per tick with the drain batch widened to
match, so msgs/sec = N × pings / tick (BASELINE.md north star: ≥10× a
32-core CPU at 1M actors on one chip).
"""

from __future__ import annotations

import numpy as np

from .. import I32, Ref, Runtime, RuntimeOptions, actor, behaviour


@actor
class Pinger:
    next_ref: Ref
    pings: I32

    MAX_SENDS = 1      # drain batch comes from opts.batch (>= pings)

    @behaviour
    def ping(self, st, n: I32):
        self.send(st["next_ref"], Pinger.ping, n - 1, when=n > 0)
        return {**st, "pings": st["pings"] + 1}


def cap_for_pings(pings: int, floor: int = 4) -> int:
    """Smallest power-of-two mailbox_cap that holds `pings` in-flight
    messages."""
    return max(floor, 1 << max(0, pings - 1).bit_length())


def build(n_pingers: int, opts: RuntimeOptions | None = None,
          permute: bool = True, seed: int = 0, pings: int = 1):
    """`pings` > 1 sustains that many in-flight messages per pinger (≙ the
    reference's --initial-pings, default 5 there: main.pony OptionSpec);
    opts.batch must be >= pings to drain them and mailbox_cap >= pings to
    hold them."""
    opts = opts or RuntimeOptions(
        mailbox_cap=cap_for_pings(pings, floor=8),
        batch=max(1, pings), max_sends=1, msg_words=1)
    if opts.mailbox_cap < pings:
        raise ValueError("mailbox_cap must be >= pings")
    if opts.batch < pings:
        raise ValueError("opts.batch must be >= pings to sustain them")
    rt = Runtime(opts)
    rt.declare(Pinger, n_pingers)
    rt.start()
    ids = rt.spawn_many(Pinger, n_pingers)
    if permute:
        rng = np.random.default_rng(seed)
        # A single random cycle over all pingers: irregular traffic but
        # every mailbox receives exactly one message per tick (sustained,
        # no hotspots — the steady state the reference's ubench reaches).
        order = rng.permutation(n_pingers)
        nxt = np.empty(n_pingers, np.int64)
        nxt[order] = ids[np.roll(order, -1)]
    else:
        nxt = np.roll(ids, -1)
    rt.set_fields(Pinger, ids, next_ref=nxt)
    return rt, ids


def seed_all(rt: Runtime, ids, hops: int, pings: int = 1):
    """Give every pinger `pings` initial pings carrying `hops` remaining."""
    for _ in range(pings):
        rt.bulk_send(ids, Pinger.ping, np.full(len(ids), hops, np.int64))
