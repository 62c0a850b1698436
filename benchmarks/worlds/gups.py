"""GUPS world: upstream `examples/gups_opt` sized by HPCC RandomAccess.

Actors written against the public API (`@actor`, `@behaviour`, `Blob`,
`Runtime.declare / start / blob_store_many / blob_fetch_many /
spawn_many / bulk_send / run`):

  Updater    owns one slice of the table as a heap array: the field
             `table` is the iso handle of a device blob of
             `slice_words` words. `update(datum)`: word `datum &
             (slice_words - 1)` of its slice `^= datum`, read and
             written through `blob_get` / `blob_set`; `applied += 1`.
             No sends; drains `runtime_options.batch` a tick.
  Streamer   self-driving: `apply(n)` draws `chunk` datums from its
             xorshift32 state and sends each to the updater that owns
             `datum & (table_words - 1)` (owner = the index's high
             bits), then `apply(n - 1)` to itself. `BATCH = 1`: it
             never holds more than its own `apply`.

Every size follows from `cfg["actors"]`: half are updaters, half
streamers (one streamer an updater, as upstream); the table is
updaters x `slice_words` words, `Table[i] = i`, built on the device by
ONE bulk blob store and handed to the updaters' fields by `spawn_many`.
A self-test's `scale={"actors": 2048}` cuts the table with the world
(`slice_words` is a shape and stays). At the size the file states, the
derived sizes must be the ones it states. A mix may give `hops`, the
dispatches a streamer makes before it stops (the tier-1 tests' finite
worlds); the default outlasts any window.

What `correct` holds the system to is `reference_gups.invariant`, on
every table word and every updater, read from the chip's own state in
blocks of updaters.
"""

from __future__ import annotations

import functools
import sys

import numpy as np

from ponyc_tpu import (I32, Blob, Runtime, RuntimeOptions, actor,
                       behaviour)

from benchmarks import reference_gups as ref

HOPS = 1 << 30        # `apply`s left on a seeded streamer: outlasts any window
BLOCK_WORDS = 1 << 26  # table words the host compares at a time (256 MiB)
ERROR_COUNTERS = ("n_rejected", "n_badmsg", "n_deadletter", "blob_fail",
                  "blob_budget_fail", "n_blob_remote")


@functools.lru_cache(maxsize=None)
def actor_types(updaters: int, slice_words: int, chunk: int):
    """(Updater, Streamer) for a table of `updaters` slices of
    `slice_words` words, `chunk` updates a streamer's dispatch. The
    sizes are the program's constants, as upstream's are its
    command line's."""
    slice_bits = slice_words.bit_length() - 1

    @actor
    class Updater:
        table: Blob
        applied: I32

        MAX_SENDS = 0

        @behaviour
        def update(self, st, datum: I32):
            w = datum & (slice_words - 1)
            h = st["table"]
            self.blob_set(h, w, self.blob_get(h, w) ^ datum)
            return {**st, "applied": st["applied"] + 1}

    @actor
    class Streamer:
        rng: I32
        done: I32
        base: I32      # global id of the first updater

        BATCH = 1
        MAX_SENDS = chunk + 1

        @behaviour
        def apply(self, st, n: I32):
            go = n > 0
            x = st["rng"]
            for _ in range(chunk):
                x = x ^ (x << 13)            # xorshift32 on int32 lanes
                x = x ^ ((x >> 17) & 0x7FFF)
                x = x ^ (x << 5)
                owner = (x >> slice_bits) & (updaters - 1)
                self.send(st["base"] + owner, Updater.update, x, when=go)
            self.send(self.actor_id, Streamer.apply, n - 1, when=n > 1)
            return {**st, "rng": x, "done": st["done"] + 1}

    return Updater, Streamer


def sizes(actors: int, slice_words: int) -> dict:
    """The world's sizes from its one free size."""
    updaters = actors // 2
    if updaters < 1 or updaters & (updaters - 1):
        raise ValueError(f"{actors} actors: the updaters (half of them) "
                         "must be a power of two")
    return {"actors": actors, "updaters": updaters,
            "streamers": actors - updaters,
            "table_words": updaters * slice_words,
            "blob_slots": updaters, "blob_words": slice_words}


class World:
    """One seeded RandomAccess world and what the heap mode asks of it."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        if not hasattr(Runtime, "blob_store_many"):
            # a program without the bulk blob store cannot build the
            # table: no number, and at once (exit 2, no result line)
            print("benchmarks/worlds/gups.py: this program has no "
                  "Runtime.blob_store_many, so the table cannot be built "
                  "— no result", file=sys.stderr)
            raise SystemExit(2)
        self.slice_words = int(cfg["slice_words"])
        self.chunk = int(traffic["updates_per_dispatch"])
        size = sizes(int(cfg["actors"]), self.slice_words)
        stated = cfg["sizes"]
        if size["actors"] == stated["actors"] and size != stated:
            raise ValueError(f"the configuration states {stated}, its "
                             f"rules give {size}")
        if int(traffic["seeded_every"]) != 1:
            raise ValueError("every streamer is seeded: the reference "
                             "advances all of them")
        self.n, self.u, self.s = (size["actors"], size["updaters"],
                                  size["streamers"])
        self.hops = int(traffic.get("hops", HOPS))
        self.live = self.s
        self.rng0 = ref.seeds(seed, self.s)
        options = {**cfg["runtime_options"],
                   "blob_slots": size["blob_slots"],
                   "blob_words": size["blob_words"]}
        self.Updater, self.Streamer = actor_types(
            self.u, self.slice_words, self.chunk)

        rt = Runtime(RuntimeOptions(**options))
        rt.declare(self.Updater, self.u)
        rt.declare(self.Streamer, self.s)
        rt.start()
        sw = self.slice_words
        # Table[i] = i, i = owner * slice_words + word, made on the device
        handles = rt.blob_store_many(self.u, fill=lambda k, w: k * sw + w)
        self.upd_ids = rt.spawn_many(self.Updater, self.u, table=handles)
        if not np.array_equal(self.upd_ids,
                              self.upd_ids[0] + np.arange(self.u)):
            raise RuntimeError("updater ids are not contiguous: "
                               "base + owner needs them so")
        self.str_ids = rt.spawn_many(
            self.Streamer, self.s, rng=self.rng0.astype(np.int64),
            base=int(self.upd_ids[0]))
        rt.bulk_send(self.str_ids, self.Streamer.apply,
                     np.full(self.s, self.hops, np.int64))
        self.rt, self.handles = rt, handles
        self.blobs_at_setup = rt.counter("n_blob_alloc")
        self._ref = None

    # ---- what the system holds now, read from its state
    def counts(self) -> np.ndarray:
        """Behaviours each actor has run: updaters, then streamers."""
        rt = self.rt
        return np.concatenate([
            rt.cohort_state(self.Updater)["applied"].astype(np.int64),
            rt.cohort_state(self.Streamer)["done"].astype(np.int64)])

    def _at(self, column, ids) -> np.ndarray:
        return np.asarray(column)[ids]

    def queued(self) -> tuple[np.ndarray, np.ndarray]:
        """(owner index, datum) of every update still queued: in the
        updaters' rings and in the receiver spill."""
        st, program = self.rt.state, self.rt.program
        cols = program.by_type[self.Updater].gid_to_col(self.upd_ids)
        payload = np.asarray(
            st.buf[self.Updater.__name__][:, 1, :])[:, cols]
        head = self._at(st.head, self.upd_ids).astype(np.int64)
        tail = self._at(st.tail, self.upd_ids).astype(np.int64)
        owner, datum = ref.ring_datums(payload, head, tail)
        tgt = np.asarray(st.dspill_tgt).astype(np.int64)
        live = tgt >= 0
        if live.any():                    # dspill targets are local rows
            per_shard = len(tgt) // program.shards
            gid = tgt + (np.arange(len(tgt)) // per_shard) * program.n_local
            spilled = gid[live] - int(self.upd_ids[0])
            if ((spilled < 0) | (spilled >= self.u)).any():
                raise RuntimeError("a spilled message targets no updater")
            owner = np.concatenate([owner, spilled])
            datum = np.concatenate([datum, np.asarray(
                st.dspill_words)[1][live].astype(np.uint32)])
        return owner, datum

    def check(self) -> dict:
        """`reference_gups.invariant` over the system's state now: the
        reference advanced to each streamer's `done`, then every table
        word and every updater, in blocks of updaters so that neither
        the chip's table nor the queued datums are ever whole on the
        host beside the reference's."""
        rt = self.rt
        if self._ref is None:
            self._ref = ref.Reference(self.rng0, self.u, self.slice_words,
                                      self.chunk, self.hops)
        streamers = rt.cohort_state(self.Streamer)
        updaters = rt.cohort_state(self.Updater)
        self._ref.advance_to(streamers["done"])
        owner, datum = self.queued()
        order = np.argsort(owner, kind="stable")
        owner, datum = owner[order], datum[order]
        block = max(1, BLOCK_WORDS // self.slice_words)
        off = {"words_off": 0, "updaters_off": 0}
        for lo in range(0, self.u, block):
            hi = min(self.u, lo + block)
            a, b = np.searchsorted(owner, [lo, hi])
            found = ref.invariant(
                self._ref, lo, hi,
                rt.blob_fetch_many(updaters["table"][lo:hi]),
                updaters["applied"][lo:hi], owner[a:b], datum[a:b])
            for key in off:
                off[key] += found[key]
        held = (self._at(rt.state.tail, self.str_ids).astype(np.int64)
                - self._at(rt.state.head, self.str_ids))
        left = np.maximum(self.hops - self._ref.done, 0)
        return {**off, "checks": {
            "invariant_every_word": off["words_off"] == 0,
            "invariant_every_updater": off["updaters_off"] == 0,
            "rng_is_the_reference": bool(np.array_equal(
                streamers["rng"].astype(np.uint32), self._ref.rng)),
            "one_apply_per_streamer": bool(np.array_equal(
                held, np.minimum(left, 1))),
        }}

    def table(self) -> np.ndarray:
        """The whole table as the system holds it, [table_words]
        (small worlds only: the tier-1 tests)."""
        return self.rt.blob_fetch_many(
            self.rt.cohort_state(self.Updater)["table"]) \
            .astype(np.uint32).reshape(-1)

    def held(self) -> int:
        """Messages the world holds: every ring, and the spill."""
        st = self.rt.state
        return int((np.asarray(st.tail, np.int64)
                    - np.asarray(st.head, np.int64)).sum()
                   + np.asarray(st.dspill_count, np.int64).sum())

    def errors(self) -> dict:
        return {c: self.rt.counter(c) for c in ERROR_COUNTERS}

    def tick_shape(self) -> dict:
        """What one steady tick must touch, for min_bytes: every
        streamer dispatches its `apply` and `chunk` updates arrive for
        each; an updater's arrivals are about Poisson(chunk), so the
        share with none is exp(-chunk). Both types' records are one
        header and one payload word; an updater has two state words."""
        updates = self.s * self.chunk
        return {"messages": updates + self.s,
                "dispatching_actors": float(
                    self.s - self.u * np.expm1(-updates / self.u)),
                "record_words": 1 + int(self.rt.opts.msg_words),
                "state_words": len(self.Updater.field_specs)}


def build(cfg: dict, traffic: dict, seed: int) -> World:
    return World(cfg, traffic, seed)
