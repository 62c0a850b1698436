"""Plain references for the benchmark's worlds: NumPy only, no engine.

The same traffic on the same seeded world must give the same per-actor
counts as these functions — that comparison decides `correct`. Nothing
here imports `ponyc_tpu` or JAX: the reference is independent of the
code under test.

Semantics modelled (Pony's, as the engine states them): a tick drains
`min(queue, batch)` messages from every mailbox; every drained `ping`
counts once on its receiver and sends one `ping` on; a message sent in
tick t is dispatched in tick t+1 at the earliest. The count a Pinger
ends with does not depend on the order of messages within a tick, so
per-mailbox FIFO is not modelled (identical pings cannot show it).
"""

from __future__ import annotations

import numpy as np


def xorshift32(x: np.ndarray) -> np.ndarray:
    """One step of Marsaglia's xorshift32 on uint32 lanes — the same
    13 / 17 / 5 generator the random world's behaviour runs on int32
    lanes (a logical right shift there, by masking the sign bits)."""
    x = x ^ (x << np.uint32(13))
    x = x ^ (x >> np.uint32(17))
    x = x ^ (x << np.uint32(5))
    return x


def signed_mod(x: np.ndarray, n: int) -> np.ndarray:
    """`x % n` as the device computes it: x read as a signed 32-bit
    word, result non-negative (floor modulo)."""
    return np.mod(x.astype(np.uint32).view(np.int32).astype(np.int64), n)


def ubench_ticks(queue: np.ndarray, batch: int, ticks: int, *,
                 next_slot: np.ndarray | None = None,
                 rng: np.ndarray | None = None):
    """Advance a Pinger world `ticks` ticks; returns (pings, queue, rng).

    queue     [n] messages waiting in each mailbox before the first tick
    next_slot [n] cycle recipients: slot i sends to slot next_slot[i]
    rng       [n] uint32 xorshift state: slot i sends to slot
              signed_mod(next rng, n), one draw per dispatched ping
    Exactly one of next_slot / rng is given. Slots are spawn order."""
    if (next_slot is None) == (rng is None):
        raise ValueError("give next_slot or rng, not both or neither")
    n = len(queue)
    queue = np.asarray(queue, np.int64).copy()
    pings = np.zeros(n, np.int64)
    if rng is not None:
        rng = np.asarray(rng, np.uint32).copy()
    for _ in range(ticks):
        drained = np.minimum(queue, batch)
        pings += drained
        queue -= drained
        if next_slot is not None:
            queue += np.bincount(next_slot, weights=drained,
                                 minlength=n).astype(np.int64)
            continue
        for j in range(int(drained.max(initial=0))):
            sends = drained > j
            rng[sends] = xorshift32(rng[sends])
            queue += np.bincount(signed_mod(rng[sends], n), minlength=n)
    return pings, queue, rng


def cycle_counts(position: np.ndarray, starts: np.ndarray, per_start: int,
                 ticks: int) -> np.ndarray:
    """Closed form of `ubench_ticks` for cycle recipients when every
    seeded mailbox holds `per_start <= batch` messages: each group moves
    one place along the cycle per tick, whole. After `ticks` ticks the
    group seeded at cycle place s has been dispatched at places
    s, s+1, ..., s+ticks-1 (mod n).

    position [n] each slot's place along the cycle
    starts   [g] cycle places of the seeded slots"""
    n = len(position)
    laps, rest = divmod(ticks, n)
    diff = np.zeros(n + 1, np.int64)
    ends = starts + rest
    np.add.at(diff, starts, 1)
    np.add.at(diff, np.minimum(ends, n), -1)
    wrapped = ends > n
    diff[0] += int(wrapped.sum())
    np.add.at(diff, ends[wrapped] - n, -1)
    at_place = np.cumsum(diff[:n]) + laps * len(starts)
    return per_start * at_place[position]


def ring_passes(n_nodes: int, hops_per_token: int, tokens: int) -> np.ndarray:
    """Every token enters at node 0 with `hops_per_token` hops left and
    is passed on until they are used up: node i sees pass number i,
    i+n, ... of each token."""
    laps, rest = divmod(hops_per_token, n_nodes)
    per_token = np.full(n_nodes, laps, np.int64)
    per_token[:rest] += 1
    return per_token * tokens
