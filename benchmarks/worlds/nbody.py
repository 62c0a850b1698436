"""n-body world: the Benchmarks Game's Jovian system, an ensemble of it.

Upstream's `examples/n-body/n-body.pony` in the source's own form —
three dimensions, the source's force `dt / (d2 sqrt(d2))` without
softening, its symplectic-Euler `advance`, its constants — written
against the public API only (`@actor`, `@behaviour`, `Ref`, `F32`,
`VecF32`, `Runtime.declare / start / spawn_many / set_fields /
bulk_send / run`). `ponyc_tpu/models/nbody.py` is the library's toy
twin (two dimensions, softened, an arbitrary constant) and is not this.

  Body   one actor a body, a system a ring of five (Sun -> Jupiter ->
         Saturn -> Uranus -> Neptune -> Sun): `next` is the ring's
         successor. A body's (position, mass) travels the ring as a
         token, `visit(hops, pos, pm)`: five payload words, a record of
         six. `visit` pulls the visited body's velocity towards the
         visitor; the fourth visit of a step (`seen == 4`) moves the
         body by its new velocity and sends its OWN token on instead of
         the visitor's, whose round is done. One send a dispatch,
         always. `bad` counts dispatches in which the body's fourth
         visit was not the token on its last hop: a token that overtook
         another.

Four ticks are one step of the source's `advance` for every system at
once; every body receives exactly one token on every tick. The protocol
tick by tick, and the source's own step in float64, are
`reference_nbody.py`'s.

Every size follows from `cfg["actors"]`: `actors // 5` systems. A
self-test's `scale={"actors": 320}` cuts the systems and never the five.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ponyc_tpu import (F32, I32, Ref, Runtime, RuntimeOptions, VecF32, actor,
                       behaviour)

from benchmarks import reference_nbody as ref

BODIES = ref.BODIES
ROUND = BODIES - 1          # visits a step: hops a token lives


def body(dt: float):
    """The Body of one deployment (`dt` is the traffic mix's)."""
    dt = np.float32(dt)

    @actor
    class Body:
        next: Ref
        x: F32
        y: F32
        z: F32
        vx: F32
        vy: F32
        vz: F32
        m: F32
        seen: I32
        step: I32
        bad: I32

        BATCH = 1
        MAX_SENDS = 1

        @behaviour
        def visit(self, st, hops: I32, pos: VecF32[3], pm: F32):
            dx, dy, dz = st["x"] - pos[0], st["y"] - pos[1], st["z"] - pos[2]
            d2 = dx * dx + dy * dy + dz * dz
            mag = dt / (d2 * jnp.sqrt(d2))
            f = pm * mag
            vx, vy, vz = st["vx"] - dx * f, st["vy"] - dy * f, st["vz"] - dz * f
            seen = st["seen"] + 1
            last = seen == ROUND
            x = jnp.where(last, st["x"] + dt * vx, st["x"])
            y = jnp.where(last, st["y"] + dt * vy, st["y"])
            z = jnp.where(last, st["z"] + dt * vz, st["z"])
            # the visitor's token goes on; where its round is done here,
            # the body's own leaves with the position it just took
            self.send(st["next"], Body.visit,
                      jnp.where(last, ROUND, hops - 1),
                      jnp.stack([jnp.where(last, x, pos[0]),
                                 jnp.where(last, y, pos[1]),
                                 jnp.where(last, z, pos[2])]),
                      jnp.where(last, st["m"], pm))
            return {**st, "x": x, "y": y, "z": z, "vx": vx, "vy": vy,
                    "vz": vz, "seen": jnp.where(last, 0, seen),
                    "step": st["step"] + last,
                    "bad": st["bad"] + (last != (hops == 1))}

    return Body


class World:
    """One seeded ensemble and what `throughput_orbit` asks of it."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        for key, want in (("bodies_per_system", BODIES),
                          ("hops_per_token", ROUND), ("tokens_per_body", 1)):
            if int(traffic[key]) != want:
                raise ValueError(f"{key}: the source's system has {want}")
        self.systems = int(cfg["actors"]) // BODIES
        if self.systems < 1:
            raise ValueError(f"{cfg['actors']} actors hold no system of five")
        self.n = self.systems * BODIES
        stated = cfg["sizes"]
        if int(cfg["actors"]) == stated["actors"] and \
                (self.systems, self.n) != (stated["systems"], stated["actors"]):
            raise ValueError(f"the configuration states {stated}, its rules "
                             f"give {self.systems} systems, {self.n} bodies")
        self.live = self.n              # one token a body, for ever
        self.dt = float(traffic["dt"])
        # float64 on the host, cast to float32 once (spawn_many's)
        self.start = ref.ensemble(self.systems, seed,
                                  float(traffic["perturbation"]))
        self.Body = body(self.dt)

        rt = Runtime(RuntimeOptions(**cfg["runtime_options"]))
        rt.declare(self.Body, self.n)
        rt.start()
        pos, vel, m = (np.ascontiguousarray(
            a.reshape(self.n, *a.shape[2:]), np.float32)
            for a in (self.start[k] for k in ("pos", "vel", "m")))
        ids = rt.spawn_many(self.Body, self.n, x=pos[:, 0], y=pos[:, 1],
                            z=pos[:, 2], vx=vel[:, 0], vy=vel[:, 1],
                            vz=vel[:, 2], m=m)
        if not np.array_equal(ids, ids[0] + np.arange(self.n)):
            raise RuntimeError("cohort ids are not contiguous: a system is "
                               "five rows in a row")
        ring = ids.reshape(self.systems, BODIES)
        succ = np.roll(ring, -1, axis=1).reshape(-1)
        rt.set_fields(self.Body, ids, next=succ)
        # set-up: every body's own token to its successor
        rt.bulk_send(succ, self.Body.visit, np.full(self.n, ROUND), pos, m)
        self.rt, self.ids = rt, ids
        self._ticks = self._fresh_reference()

    # ---- what the system holds now, read from its state
    def observed(self) -> dict:
        """`reference_nbody.Ticks.observed()`'s keys, [systems, 5] each."""
        st = self.rt.cohort_state(self.Body)
        return {k: st[k].reshape(self.systems, BODIES)
                for k in (*ref.FLOATS, "m", *ref.COUNTS)}

    def invariant(self, seen: dict, ticks: int) -> dict:
        return ref.invariant(seen, ticks, self.start)

    # ---- the reference
    def _fresh_reference(self) -> ref.Ticks:
        return ref.Ticks(self.start["pos"], self.start["vel"],
                         self.start["m"], np.float32, self.dt)

    def reference(self, ticks: int) -> dict:
        """The protocol's state after `ticks` ticks, tick by tick in
        float32 (the reference is advanced, never rewound)."""
        if ticks < self._ticks.ticks:
            self._ticks = self._fresh_reference()
        return self._ticks.advance(ticks - self._ticks.ticks).observed()

    def tick_shape(self) -> dict:
        """What one steady tick must touch, for min_bytes: every body
        dispatches exactly one visit a tick, a record of six words in
        and one out, its eleven state words read and written."""
        return {"messages": self.live, "dispatching_actors": float(self.n),
                "record_words": 1 + int(self.rt.opts.msg_words),
                "state_words": len(self.Body.field_specs)}


def build(cfg: dict, traffic: dict, seed: int) -> World:
    return World(cfg, traffic, seed)
