"""Task Bench world: `-type stencil_1d_periodic -kernel empty`, one actor
a point, every dependency a payload in the device blob pool.

Written against the public API only (`@actor`, `@behaviour`, `Ref`,
`Blob`, `Runtime.declare / start / spawn_many / set_fields /
blob_store_many / bulk_send / run`); a copy, not an import, of anything
under `ponyc_tpu/models`.

  Point   one point of the graph, as Task Bench's Charm++ port has one
          chare a point. `input(src_point, timestep, payload)` is one
          dependency arriving: the behaviour reads the payload's length
          and every word, holds every pair to `(timestep, src_point)`
          and the message to the step the point is about to run
          (`reference_taskbench.wrong_pairs` is `execute_point`'s check;
          a point that fails it counts `bad_inputs`, it does not
          abort), adds the words to `acc` and frees the payload. The
          step's third input runs the task inside its own dispatch, as
          a chare does: the kernel (`empty`: nothing), then one fresh
          payload a dependent, allocated, filled with `pairs` pairs
          `(step, point)` and sent. One behaviour and no second one:
          every branch of a cohort is evaluated in every batch slot.

Point ids are dealt onto rows by a permutation drawn from the seed, and
each point's three dependents onto its three send slots by another, so
the wiring, the plan's permutation and the order in which a point's
inputs arrive differ by seed. Timestep 0 depends on nothing and is the host's: its outputs
are stored by `blob_store_many` and sent by `bulk_send` at set-up, so
the first tick already runs the loop as every later one does.

Every size follows from `cfg["actors"]`, the graph's width; a
self-test's `scale={"actors": 64}` cuts the width and never the payload.
The mix's `steps` is Task Bench's `-steps`: 0 (the cell: the clock cuts
the run), or S >= 2: timesteps 0 .. S - 1, the last of which sends
nothing, so `run()` reaches quiescence with the pool empty.
"""

from __future__ import annotations

import functools
import itertools

import jax.numpy as jnp
import numpy as np

from ponyc_tpu import (I32, Blob, Ref, Runtime, RuntimeOptions, actor,
                       behaviour)

from benchmarks import reference_taskbench as ref

DEPS = ref.DEPS
ORDERS = tuple(itertools.permutations(range(DEPS)))
ERROR_COUNTERS = ("n_rejected", "n_badmsg", "n_deadletter", "blob_fail",
                  "blob_budget_fail", "n_blob_remote")


@functools.lru_cache(maxsize=None)
def point_type(width: int, pairs: int, steps: int):
    """The Point of a graph `width` wide whose dependencies carry
    `pairs` pairs, run for `steps` timesteps (0: for ever). The sizes
    are the program's constants, as Task Bench's are its command
    line's."""
    words = 2 * pairs

    @actor
    class Point:
        dep0: Ref          # the three points that depend on this one,
        dep1: Ref          # in this point's own (seeded) order
        dep2: Ref
        point: I32
        step: I32          # the timestep this point runs next
        seen: I32          # inputs of that step consumed so far
        mask: I32          # bit d: dependency point - 1 + d was one
        bad_inputs: I32
        acc: I32           # wrapping sum of every payload word read

        BATCH = DEPS
        MAX_SENDS = DEPS
        MAX_BLOBS = DEPS
        BLOB_DISPATCHES = 1

        @behaviour
        def input(self, st, src_point: I32, timestep: I32, payload: Blob):
            p, t = st["point"], st["step"]
            which = (src_point - p + 1) % width       # 0, 1, 2: a dependency
            is_dep = which < DEPS
            bit = jnp.where(is_dep, 1 << jnp.where(is_dep, which, 0), 0)
            good = ((self.blob_length(payload) == words) & is_dep
                    & (timestep == t - 1) & ((st["mask"] & bit) == 0))
            total = jnp.int32(0)
            for j in range(pairs):
                a = self.blob_get(payload, 2 * j)
                b = self.blob_get(payload, 2 * j + 1)
                good = good & (a == timestep) & (b == src_point)
                total = total + a + b
            self.blob_free(payload)

            seen = st["seen"] + 1
            fire = seen == DEPS
            # the task: kernel `empty`, then one output a dependent
            # (none from the last of `steps` timesteps)
            out = fire & (t < steps - 1) if steps else fire
            copies = [self.blob_alloc(length=words, when=out)
                      for _ in range(DEPS)]
            for h in copies:
                for j in range(pairs):
                    self.blob_set(h, 2 * j, t, when=out)
                    self.blob_set(h, 2 * j + 1, p, when=out)
            for d, h in enumerate(copies):
                self.send(st[f"dep{d}"], Point.input, p, t, h, when=out)
            return {**st, "step": t + fire,
                    "seen": jnp.where(fire, 0, seen),
                    "mask": jnp.where(fire, 0, st["mask"] | bit),
                    "bad_inputs": st["bad_inputs"] + ~good,
                    "acc": st["acc"] + total}

    return Point


def sizes(width: int) -> dict:
    """The world's sizes from its one free size."""
    return {"actors": width, "messages_per_tick": DEPS * width,
            "live_payloads": DEPS * width, "blob_slots": 7 * width}


class World:
    """One seeded stencil and what `throughput_payload` asks of it."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        for key, want in (("type", "stencil_1d_periodic"),
                          ("dependencies", DEPS), ("kernel", "empty"),
                          ("copies", "iso_per_dependent")):
            if traffic[key] != want:
                raise ValueError(f"{key}: this world is {want!r}")
        self.n = self.width = int(cfg["actors"])
        self.pairs = int(traffic["output_pairs"])
        self.steps = int(traffic.get("steps", 0))
        size = sizes(self.width)
        stated = cfg["sizes"]
        if size["actors"] == stated["actors"] and size != stated:
            raise ValueError(f"the configuration states {stated}, its "
                             f"rules give {size}")
        self.live = size["live_payloads"]      # messages, and payloads
        self.blob_slots = size["blob_slots"]
        options = {**cfg["runtime_options"], "blob_slots": self.blob_slots}
        if int(options["blob_words"]) != 2 * self.pairs:
            raise ValueError("blob_words is the payload: 2 x output_pairs")

        gen = np.random.default_rng(seed)
        w = self.width
        self.point_of_row = gen.permutation(w)
        row_of_point = np.empty(w, np.int64)
        row_of_point[self.point_of_row] = np.arange(w)
        # which dependent rides which send slot, a point
        self.order = np.asarray(ORDERS)[gen.integers(0, len(ORDERS), w)]
        self.Point = point_type(w, self.pairs, self.steps)

        rt = Runtime(RuntimeOptions(**options))
        rt.declare(self.Point, w)
        rt.start()
        ids = rt.spawn_many(self.Point, w, point=self.point_of_row,
                            step=1)
        # dependents[r, k]: the id of the point row r's slot k sends to
        near = ref.dependencies(self.point_of_row, w)
        near = np.take_along_axis(near, self.order, axis=1)
        rt.set_fields(self.Point, ids, **{
            f"dep{k}": ids[row_of_point[near[:, k]]] for k in range(DEPS)})
        # timestep 0, the host's: one copy of (0, point) a dependent
        # (shipped as words: a `fill` closed over the seeded placement
        # would be another program a seed, compiled in every run)
        handles = rt.blob_store_many(DEPS * w, words=ref.fill(
            0, np.tile(self.point_of_row, DEPS), self.pairs))
        for d in range(DEPS):
            to = ids[row_of_point[(self.point_of_row + d - 1) % w]]
            rt.bulk_send(to, self.Point.input, self.point_of_row,
                         np.zeros(w, np.int64), handles[d * w:(d + 1) * w])
        self.rt, self.ids = rt, ids
        self._ref = self._fresh_reference()

    # ---- what the system holds now, read from its state
    def observed(self) -> dict:
        """`reference_taskbench.COLUMNS`, by point id."""
        st = self.rt.cohort_state(self.Point)
        out = {}
        for k in ref.COLUMNS:
            out[k] = np.empty(self.width, np.int32)
            out[k][self.point_of_row] = st[k]
        return out

    def books(self) -> dict:
        """The pool's books, read from the device (outside any clock)."""
        rt = self.rt
        return {"blobs_in_use": rt.blobs_in_use,
                "allocs": rt.counter("n_blob_alloc"),
                "frees": rt.counter("n_blob_free")}

    def held(self) -> int:
        """Messages the world holds: every ring, and the spills."""
        st = self.rt.state
        return int((np.asarray(st.tail, np.int64)
                    - np.asarray(st.head, np.int64)).sum()
                   + np.asarray(st.dspill_count, np.int64).sum()
                   + np.asarray(st.rspill_count, np.int64).sum())

    def errors(self) -> dict:
        return {c: self.rt.counter(c) for c in ERROR_COUNTERS}

    # ---- the reference
    def _fresh_reference(self) -> ref.Stencil:
        return ref.Stencil(self.width, self.pairs, self.steps)

    def reference(self, ticks: int) -> ref.Stencil:
        """The replay after `ticks` ticks (advanced, never rewound)."""
        if ticks < self._ref.ticks:
            self._ref = self._fresh_reference()
        return self._ref.advance(ticks - self._ref.ticks)

    def check(self) -> dict:
        """The chip's own state against the replay after as many ticks,
        on every point, and the pool against its books."""
        want = self.reference(self.rt.steps_run)
        found = ref.compare(self.observed(), want.observed())
        books = self.books()
        mask32 = 0xFFFFFFFF
        return {**found, "books": books, "checks": {
            "every_point_is_the_reference": found["points_off"] == 0,
            "n_processed_is_the_replays":
            self.rt.counter("n_processed") & mask32
            == want.dispatched & mask32,
            "pool_holds_the_live_payloads":
            books["blobs_in_use"] == want.live,
            "allocs_less_frees_is_live":
            (books["allocs"] - books["frees"]) & mask32 == want.live,
            "world_holds_the_live_messages": self.held() == want.live,
        }}

    def tick_shape(self) -> dict:
        """What one steady tick must touch, for min_bytes: every point
        dispatches its three inputs, records of a header and three
        words; its nine state words read and written. The payloads'
        bytes are `payload_bytes.py`'s."""
        return {"messages": self.live, "dispatching_actors": float(self.n),
                "record_words": 1 + int(self.rt.opts.msg_words),
                "state_words": len(self.Point.field_specs)}


def build(cfg: dict, traffic: dict, seed: int) -> World:
    return World(cfg, traffic, seed)
