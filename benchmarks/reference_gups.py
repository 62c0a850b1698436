"""Plain reference for the GUPS world (HPCC RandomAccess): NumPy only,
no engine.

A table of `table_words` words, `Table[i] = i`, is cut into slices of
`slice_words`; updater k owns slice k (the index's high bits). Every
streamer carries one xorshift32 state and, each time it runs, draws
`chunk` datums; a datum `ran` belongs to the table word
`idx = ran & (table_words - 1)` — owner `idx // slice_words`, word
`idx % slice_words` of its slice — and the update is HPCC's
`Table[idx] ^= ran`. A streamer runs exactly once a tick, so what has
been generated after t ticks is exact whatever the system queues.

Two things decide `correct`, and neither imports `ponyc_tpu` or JAX:

  Reference   the streams and the table, advanced tick by tick;
  invariant   what must hold after ANY number of ticks, whatever order
              same-tick arrivals land in, because xor commutes: for
              every word  table ^ (datums still queued for it) =
              i ^ (datums generated for it); for every updater
              applied + queued = generated for it. The count catches a
              lost and a duplicated update, the xor a misrouted one
              (it lands on another word) and a pair that cancels in the
              count.

HPCC's own verification is `replay`: the same streams applied a second
time give `Table[i] = i` back.
"""

from __future__ import annotations

import numpy as np


def xorshift32(x: np.ndarray) -> np.ndarray:
    """One step of Marsaglia's xorshift32 on uint32 lanes: the 13 / 17 /
    5 generator the streamer runs on int32 lanes (a logical right shift
    there, by masking the sign bits)."""
    x = x ^ (x << np.uint32(13))
    x = x ^ (x >> np.uint32(17))
    x = x ^ (x << np.uint32(5))
    return x


def seeds(seed: int, streamers: int) -> np.ndarray:
    """One non-zero xorshift32 state a streamer, from `seed`."""
    return np.random.default_rng(seed).integers(
        1, 2**31 - 1, streamers, dtype=np.int64).astype(np.uint32)


def draw(rng: np.ndarray, chunk: int) -> tuple[np.ndarray, np.ndarray]:
    """One dispatch of every streamer: (`[chunk, streamers]` datums, the
    states after them)."""
    datums = np.empty((chunk, len(rng)), np.uint32)
    for j in range(chunk):
        rng = xorshift32(rng)
        datums[j] = rng
    return datums, rng


def scatter(table: np.ndarray, datums: np.ndarray) -> None:
    """HPCC's update, in place: `table[ran & (size - 1)] ^= ran`."""
    datums = np.asarray(datums, np.uint32).reshape(-1)
    np.bitwise_xor.at(table, (datums & np.uint32(len(table) - 1))
                      .astype(np.int64), datums)


class Reference:
    """The streams and the table after so many dispatches a streamer.

    `table` [table_words] uint32 is `i ^ (every datum generated for i)`;
    `generated` [updaters] counts the datums generated for each owner;
    `rng`, `done` [streamers] are each streamer's state and dispatches.
    `hops`: dispatches a streamer makes before it stops (None: never)."""

    def __init__(self, rng0: np.ndarray, updaters: int, slice_words: int,
                 chunk: int, hops: int | None = None):
        for size in (updaters, slice_words):
            if size < 1 or size & (size - 1):
                raise ValueError(f"{size} is not a power of two")
        self.updaters, self.slice_words = updaters, slice_words
        self.chunk, self.hops = chunk, hops
        self.rng = np.asarray(rng0, np.uint32).copy()
        self.done = np.zeros(len(self.rng), np.int64)
        self.table = np.arange(updaters * slice_words, dtype=np.uint32)
        self.generated = np.zeros(updaters, np.int64)

    def owner(self, datums: np.ndarray) -> np.ndarray:
        return ((np.asarray(datums, np.uint32)
                 & np.uint32(len(self.table) - 1))
                // np.uint32(self.slice_words)).astype(np.int64)

    def tick(self, who: np.ndarray | None = None) -> None:
        """The streamers `who` (a mask; default: all with hops left)
        each dispatch once."""
        if who is None:
            who = np.ones(len(self.rng), bool) if self.hops is None \
                else self.done < self.hops
        datums, self.rng[who] = draw(self.rng[who], self.chunk)
        self.done[who] += 1
        scatter(self.table, datums)
        self.generated += np.bincount(self.owner(datums).reshape(-1),
                                      minlength=self.updaters)

    def advance_to(self, done: np.ndarray) -> "Reference":
        """Advance every streamer to its own count of dispatches (the
        reference is advanced, never rewound)."""
        done = np.asarray(done, np.int64)
        if (done < self.done).any():
            raise ValueError("the reference cannot be rewound")
        while (self.done < done).any():
            self.tick(self.done < done)
        return self


def replay(table: np.ndarray, rng0: np.ndarray, chunk: int,
           dispatches: int) -> np.ndarray:
    """HPCC's verification: apply the same streams (`dispatches` a
    streamer) to `table` once more, in place; a table that took every
    update exactly once comes back as `Table[i] = i`."""
    rng = np.asarray(rng0, np.uint32).copy()
    for _ in range(dispatches):
        datums, rng = draw(rng, chunk)
        scatter(table, datums)
    return table


def ring_datums(payload: np.ndarray, head: np.ndarray,
                tail: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """The datums waiting in rings: (owner index, datum) of every live
    slot. `payload` is [cap, owners], the payload word of every ring
    slot; a ring's live slots are counts head .. tail - 1, slot =
    count % cap."""
    cap = payload.shape[0]
    place = (np.arange(cap)[:, None] - head[None, :]) % cap
    slot, owner = np.nonzero(place < (tail - head)[None, :])
    return owner.astype(np.int64), payload[slot, owner].astype(np.uint32)


def invariant(ref: Reference, lo: int, hi: int, table: np.ndarray,
              applied: np.ndarray, queued_owner: np.ndarray,
              queued_datum: np.ndarray) -> dict:
    """The order-free invariant on updaters lo .. hi - 1, from the
    system's own state: `table` [hi - lo, slice_words] their slices as
    the system holds them, `applied` [hi - lo] the updates each has
    run, `queued_*` the datums still queued for them anywhere (owner
    index absolute). Returns how many words and how many updaters are
    off it."""
    sw = ref.slice_words
    have = np.array(table, np.uint32).reshape(-1)
    owner = np.asarray(queued_owner, np.int64) - lo
    datum = np.asarray(queued_datum, np.uint32)
    if ((owner < 0) | (owner >= hi - lo)).any():
        raise ValueError("a queued datum outside the block")
    np.bitwise_xor.at(have, owner * sw + (datum & np.uint32(sw - 1))
                      .astype(np.int64), datum)
    counted = np.asarray(applied, np.int64) \
        + np.bincount(owner, minlength=hi - lo)
    return {"words_off": int(np.count_nonzero(
                have != ref.table[lo * sw:hi * sw])),
            "updaters_off": int(np.count_nonzero(
                counted != ref.generated[lo:hi]))}
