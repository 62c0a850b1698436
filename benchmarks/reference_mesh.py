"""What a mesh adds to the plain reference of a Pinger world: NumPy
only, no engine.

`reference.ubench_ticks` states the world without a layout: actor j
holds `queue[j]` pings, drains `min(queue, batch)` a tick and sends one
ping on for each. On a mesh the answer must be the same, whatever shard
an actor and its senders live on; what the layout decides is only which
sends cross from one shard to another. This file states that part:

  deal(n, shards)    where `spawn_many` puts the k-th actor of a cohort
                     of n: the program deals rows round-robin over the
                     shards, so spawn order is not id order
  remote_sends(...)  tick by tick, how many pings the reference sends
                     and how many of them go to another shard: the exact
                     integers the program's route counters must show

Actors are indexed by their id's offset in the cohort (`id - base`)
here, not by spawn order: the id is what a recipient is drawn as
(`base + rng % n`), and `id // (n // shards)` is the shard.
"""

from __future__ import annotations

import numpy as np

from benchmarks.reference import signed_mod, xorshift32


def deal(n: int, shards: int) -> np.ndarray:
    """[n] id offsets in spawn order: the k-th actor spawned lands on
    shard k % shards, in that shard's row k // shards, and a shard holds
    the ids shard * (n // shards) … + n // shards − 1."""
    if n % shards:
        raise ValueError(f"{n} actors do not deal evenly over {shards}")
    k = np.arange(n, dtype=np.int64)
    return (k % shards) * (n // shards) + k // shards


def remote_sends(queue: np.ndarray, batch: int, shards: int, ticks: int, *,
                 next_slot: np.ndarray | None = None,
                 rng: np.ndarray | None = None):
    """(sent [ticks], remote [ticks]) int64: the pings sent in each of
    the first `ticks` ticks, and those whose recipient lives on another
    shard than the sender. Arguments as `reference.ubench_ticks`, every
    array by id offset; the world is one cohort of len(queue) actors
    split evenly, shard s holding offsets s * n/shards … ."""
    if (next_slot is None) == (rng is None):
        raise ValueError("give next_slot or rng, not both or neither")
    n = len(queue)
    if n % shards:
        raise ValueError(f"{n} actors do not split evenly over {shards}")
    per = n // shards
    home = np.arange(n, dtype=np.int64) // per
    queue = np.asarray(queue, np.int64).copy()
    if rng is not None:
        rng = np.asarray(rng, np.uint32).copy()
    else:
        crosses = home != home[next_slot]
    sent = np.zeros(ticks, np.int64)
    remote = np.zeros(ticks, np.int64)
    for t in range(ticks):
        drained = np.minimum(queue, batch)
        queue -= drained
        sent[t] = drained.sum()
        if next_slot is not None:
            remote[t] = drained[crosses].sum()
            queue += np.bincount(next_slot, weights=drained,
                                 minlength=n).astype(np.int64)
            continue
        for j in range(int(drained.max(initial=0))):
            sends = drained > j
            rng[sends] = xorshift32(rng[sends])
            to = signed_mod(rng[sends], n)
            remote[t] += int((to // per != home[sends]).sum())
            queue += np.bincount(to, minlength=n)
    return sent, remote
