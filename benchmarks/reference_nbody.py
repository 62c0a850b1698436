"""The plain reference of `nbody-jovian`: NumPy only, no engine, no JAX.

The source is upstream's `examples/n-body/n-body.pony`, the Pony port of
the Computer Language Benchmarks Game's n-body: the Sun and the four
Jovian planets in three dimensions, seven values a body, `dt` 0.01,
`offset_momentum`, a symplectic-Euler `advance` (every pair's pull onto
both velocities from the positions the step began with, then every
position by its new velocity), and the energy printed before and after.

The deployment reads it as actors (`worlds/nbody.py`): one actor a body,
a system a ring of five (Sun -> Jupiter -> Saturn -> Uranus -> Neptune
-> Sun), every body's (position, mass) travelling the ring as a token,
so that each body meets each other body once a step. Three things here:

  `Ticks`        the protocol tick by tick, vectorised over systems, in
                 the precision asked for (float32: the chip's), with the
                 visitors in the ring's order (i-1, i-2, i-3, i-4) and
                 the expressions written as the world writes them. What
                 the first ticks of a run are compared with.
  `advance_f64`  the source's own pairwise `advance` and `energy` in
                 float64, in the source's pair order. What the energy
                 after any number of steps is compared with, and what
                 pins the constants: system 0 prints -0.169075164
                 before any step and -0.169087605 after 1,000.
  `invariant`    from the system's own state after `ticks` ticks: every
                 body's step counter, that no token overtook another,
                 every system's energy against `advance_f64`'s and its
                 momentum against zero.

**Tolerances, each with its reason.** Both sides of the first comparison
are float32 in the same order, so what differs is the device's `sqrt`
and division (a few ulp a visit) and whether a multiply and an add were
fused: 32 visits a body in 32 ticks. Positions agree within `POS_TOL`
1e-5 of the system's largest |coordinate| and velocities within
`VEL_TOL` 1e-4 of its largest |velocity component|. Read with this
file alone over 4,096 systems: float64 against float32 is 2.4e-7 and
4.0e-7 after 32 ticks, float16 against float32 1.9e-3 and 1.8e-3
(bfloat16 4.1e-3 and 1.2e-2), which fail both; what the engine reads
on the CPU and on the v5e is in PERF.md section 6 (PR 51). The energy
is computed in float64 from the float32 state: `ENERGY_TOL` 5e-5
relative to `advance_f64`'s (float32 against float64 drifts 1.1e-6 in 8
steps and 8.2e-6 in 100, the largest of 4,096 systems; float16 1.3e-3
and 7.1e-3). The momentum a system was offset to is zero: |sum m v|
(its largest component) stays within `MOMENTUM_TOL` 1e-5 of sum |m v|
(float32 reads 1.4e-7 after 8 steps and 1.5e-6 after 100: the two
pulls of a pair are rounded apart and the lean grows with the steps;
float16 7e-4 and 7e-3; one visit of the Sun's lost or doubled is 5e-3
of Jupiter's momentum; a planet's visit to a planet is 1e-6 and is the
counters' and the energy's to find).
"""

from __future__ import annotations

import numpy as np

PI = 3.141592653589793
SOLAR_MASS = 4 * PI * PI
DAYS_PER_YEAR = 365.24
DT = 0.01
BODIES = 5
# Sun, Jupiter, Saturn, Uranus, Neptune: x, y, z, vx (per day), vy, vz,
# mass (in solar masses), the source's literals digit for digit
_TABLE = (
    (0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 1.0),
    (4.84143144246472090e+00, -1.16032004402742839e+00,
     -1.03622044471123109e-01, 1.66007664274403694e-03,
     7.69901118419740425e-03, -6.90460016972063023e-05,
     9.54791938424326609e-04),
    (8.34336671824457987e+00, 4.12479856412430479e+00,
     -4.03523417114321381e-01, -2.76742510726862411e-03,
     4.99852801234917238e-03, 2.30417297573763929e-05,
     2.85885980666130812e-04),
    (1.28943695621391310e+01, -1.51111514016986312e+01,
     -2.23307578892655734e-01, 2.96460137564761618e-03,
     2.37847173959480950e-03, -2.96589568540237556e-05,
     4.36624404335156298e-05),
    (1.53796971148509165e+01, -2.59193146099879641e+01,
     1.79258772950371181e-01, 2.68067772490389322e-03,
     1.62824170038242295e-03, -9.51592254519715870e-05,
     5.15138902046611451e-05),
)
# every pair once, in the order the source's two loops meet them
PAIRS = tuple((i, j) for i in range(BODIES) for j in range(i + 1, BODIES))

POS_TOL = 1e-5
VEL_TOL = 1e-4
ENERGY_TOL = 5e-5
MOMENTUM_TOL = 1e-5


def jovian() -> dict:
    """The source's five bodies in its own units (AU, years, 4 pi^2 a
    solar mass), before `offset_momentum`: `pos` and `vel` [5, 3], `m`
    [5], float64."""
    t = np.asarray(_TABLE, np.float64)
    return {"pos": t[:, 0:3].copy(), "vel": t[:, 3:6] * DAYS_PER_YEAR,
            "m": t[:, 6] * SOLAR_MASS}


def offset_momentum(vel: np.ndarray, m: np.ndarray) -> np.ndarray:
    """The source's `offset_momentum`, per system: the Sun's velocity
    becomes -(sum m v) / SOLAR_MASS, so the system's momentum is zero.
    `vel` is [systems, 5, 3] (the Sun's row is overwritten: the sum is
    over all five, and the source's Sun starts at rest)."""
    p = (vel * m[..., None]).sum(axis=-2)
    out = vel.copy()
    out[..., 0, :] = -p / SOLAR_MASS
    return out


def ensemble(systems: int, seed: int, perturbation: float) -> dict:
    """`systems` copies of the source's system, float64: system 0 is the
    source's own; in every other each planet's three velocity components
    are multiplied by (1 + perturbation * u), u uniform in (-1, 1) from
    a NumPy generator seeded with `seed`; then `offset_momentum`."""
    base = jovian()
    u = np.random.default_rng(seed).uniform(-1.0, 1.0,
                                            (systems, BODIES - 1, 3))
    u[0] = 0.0
    vel = np.broadcast_to(base["vel"], (systems, BODIES, 3)).copy()
    vel[:, 1:, :] *= 1.0 + perturbation * u
    m = np.broadcast_to(base["m"], (systems, BODIES)).copy()
    return {"pos": np.broadcast_to(base["pos"], (systems, BODIES, 3)).copy(),
            "vel": offset_momentum(vel, m), "m": m}


def energy(pos: np.ndarray, vel: np.ndarray, m: np.ndarray) -> np.ndarray:
    """The source's `energy`, per system, in float64 whatever comes in."""
    pos, vel, m = (np.asarray(a, np.float64) for a in (pos, vel, m))
    e = 0.5 * (m * (vel * vel).sum(axis=-1)).sum(axis=-1)
    for i, j in PAIRS:
        d = pos[..., i, :] - pos[..., j, :]
        e = e - m[..., i] * m[..., j] / np.sqrt((d * d).sum(axis=-1))
    return e


def momentum(vel: np.ndarray, m: np.ndarray) -> tuple:
    """(|sum m v|, sum |m v|) per system, in float64: the largest
    component of the vector sum, and the scale it is held against."""
    p = np.asarray(vel, np.float64) * np.asarray(m, np.float64)[..., None]
    return (np.abs(p.sum(axis=-2)).max(axis=-1),
            np.abs(p).sum(axis=(-2, -1)))


def advance_f64(pos, vel, m, steps: int, dt: float = DT) -> tuple:
    """The source's `advance`, `steps` times, on [systems, 5, 3] float64
    copies: for every pair (i, j > i) in the source's order, d = xi - xj,
    mag = dt / (d2 * sqrt(d2)), vi -= d * mj * mag, vj += d * mi * mag;
    then every position += dt * velocity. Returns (pos, vel). Worked
    body-major ([5, 3, systems]: a body's row is contiguous), which is
    the same arithmetic three times as fast at 209,715 systems."""
    pos, vel, m = (np.array(np.moveaxis(np.asarray(a, np.float64), 0, -1),
                            order="C") for a in (pos, vel, m))
    for _ in range(steps):
        for i, j in PAIRS:
            d = pos[i] - pos[j]
            d2 = (d * d).sum(axis=0)
            mag = dt / (d2 * np.sqrt(d2))
            vel[i] -= d * (m[j] * mag)
            vel[j] += d * (m[i] * mag)
        pos += dt * vel
    return np.moveaxis(pos, -1, 0), np.moveaxis(vel, -1, 0)


def ring_f64(pos, vel, m, steps: int, dt: float = DT) -> tuple:
    """The same step in the ring's visitor order, float64: body i meets
    i-1, i-2, i-3, i-4 in turn, each pull computed from its own side
    (d = own - visitor's, v -= d * (visitor's mass * mag)). What shows
    that the actor reading is the source's `advance` (they agree to
    rounding: every pair's two pulls are the same numbers)."""
    pos = np.array(pos, np.float64)
    vel = np.array(vel, np.float64)
    m = np.asarray(m, np.float64)
    for _ in range(steps):
        for back in range(1, BODIES):
            seen = np.roll(pos, back, axis=-2)       # body i sees i - back
            d = pos - seen
            d2 = (d * d).sum(axis=-1)
            mag = dt / (d2 * np.sqrt(d2))
            vel -= d * (np.roll(m, back, axis=-1) * mag)[..., None]
        pos += dt * vel
    return pos, vel


def _to_next(a: np.ndarray) -> np.ndarray:
    """What every body sends, where its ring successor receives it."""
    return np.roll(a, 1, axis=-1)


class Ticks:
    """The protocol, one tick at a time, in `dtype`.

    Every body holds exactly one token between ticks: the one its
    predecessor sent on the tick before (at set-up: the predecessor's
    own, `hops` 4). A tick is one `visit` on every body:

        d = own position - pos;  d2 = dx dx + dy dy + dz dz
        mag = dt / (d2 sqrt(d2));  v -= d (pm mag);  seen += 1
        last = seen == 4
        where last: x += dt v (the new v), seen = 0, step += 1
        bad += last != (hops == 1)
        send to next: (hops - 1, pos, pm), or where last
                      (4, own new position, own mass)

    All arrays are [systems, 5]; the token a body HOLDS is at its own
    index."""

    def __init__(self, pos, vel, m, dtype=np.float32, dt: float = DT):
        self.dtype = np.dtype(dtype)
        pos, vel, m = (np.asarray(a, np.float64).astype(self.dtype)
                       for a in (pos, vel, m))
        self.x, self.y, self.z = (pos[..., k] for k in range(3))
        self.vx, self.vy, self.vz = (vel[..., k] for k in range(3))
        self.m = m
        self.dt = self.dtype.type(dt)
        shape = self.m.shape
        self.seen = np.zeros(shape, np.int64)
        self.step = np.zeros(shape, np.int64)
        self.bad = np.zeros(shape, np.int64)
        self.ticks = 0
        # set-up: every body's own token sits at its successor
        self.t_hops = np.full(shape, BODIES - 1, np.int64)
        self.t_x, self.t_y, self.t_z, self.t_m = (
            _to_next(a) for a in (self.x, self.y, self.z, self.m))

    def tick(self) -> None:
        with np.errstate(over="ignore", invalid="ignore", divide="ignore"):
            dx, dy, dz = self.x - self.t_x, self.y - self.t_y, self.z - self.t_z
            d2 = dx * dx + dy * dy + dz * dz
            mag = self.dt / (d2 * np.sqrt(d2))
            f = self.t_m * mag
            self.vx = self.vx - dx * f
            self.vy = self.vy - dy * f
            self.vz = self.vz - dz * f
            seen = self.seen + 1
            last = seen == BODIES - 1
            self.x = np.where(last, self.x + self.dt * self.vx, self.x)
            self.y = np.where(last, self.y + self.dt * self.vy, self.y)
            self.z = np.where(last, self.z + self.dt * self.vz, self.z)
        self.seen = np.where(last, 0, seen)
        self.step = self.step + last
        self.bad = self.bad + (last != (self.t_hops == 1))
        out = (np.where(last, BODIES - 1, self.t_hops - 1),
               np.where(last, self.x, self.t_x), np.where(last, self.y, self.t_y),
               np.where(last, self.z, self.t_z), np.where(last, self.m, self.t_m))
        (self.t_hops, self.t_x, self.t_y, self.t_z,
         self.t_m) = (_to_next(a) for a in out)
        self.ticks += 1

    def advance(self, ticks: int) -> "Ticks":
        for _ in range(ticks):
            self.tick()
        return self

    def observed(self) -> dict:
        """What `worlds/nbody.py`'s `observed()` reads off the system,
        [systems, 5] each."""
        return {"x": self.x, "y": self.y, "z": self.z, "vx": self.vx,
                "vy": self.vy, "vz": self.vz, "m": self.m,
                "seen": self.seen, "step": self.step, "bad": self.bad}


FLOATS = ("x", "y", "z", "vx", "vy", "vz")
COUNTS = ("seen", "step", "bad")


def _stack(seen: dict, keys) -> np.ndarray:
    return np.stack([np.asarray(seen[k], np.float64) for k in keys], axis=-1)


def compare(seen: dict, want: dict) -> dict:
    """The first ticks' comparison: counters exactly; positions and
    velocities within `POS_TOL` / `VEL_TOL` of the system's largest
    |coordinate| / |velocity component| (the reference's). Returns the
    checks, the largest errors read (for people) and the bodies off."""
    exact = {k: int((np.asarray(seen[k], np.int64)
                     != np.asarray(want[k], np.int64)).sum()) for k in COUNTS}
    out = {"off": sum(exact.values()), "read": {}}
    for name, keys, tol in (("position", FLOATS[:3], POS_TOL),
                            ("velocity", FLOATS[3:], VEL_TOL)):
        got, ref = _stack(seen, keys), _stack(want, keys)
        scale = np.abs(ref).max(axis=(-2, -1), keepdims=True)
        err = np.abs(got - ref) / scale
        # a NaN on either side is an error, never a pass
        err = np.where(np.isfinite(err), err, np.inf)
        out["read"][name] = float(err.max())
        out[name + "_off"] = int((err.max(axis=-1) > tol).sum())
        out["off"] += out[name + "_off"]
    out["checks"] = {"counters_exact": not any(exact.values()),
                     "positions_within_tolerance": out["position_off"] == 0,
                     "velocities_within_tolerance": out["velocity_off"] == 0}
    return out


def invariant(seen: dict, ticks: int, start: dict) -> dict:
    """From the system's own state after `ticks` ticks (a multiple of
    four: a step boundary), and `start`, the ensemble it began from in
    float64: what must hold whatever the trajectory. `deficit` counts
    bodies and systems off, for the result line's `failed`."""
    steps, rest = divmod(int(ticks), BODIES - 1)
    if rest:
        raise ValueError(f"{ticks} ticks is not a step boundary")
    step, idle, bad = (np.asarray(seen[k], np.int64) for k in
                       ("step", "seen", "bad"))
    pos, vel = _stack(seen, FLOATS[:3]), _stack(seen, FLOATS[3:])
    m = np.asarray(seen["m"], np.float64)
    got = energy(pos, vel, m)
    want = energy(*advance_f64(start["pos"], start["vel"], start["m"], steps),
                  start["m"])
    drift = np.abs(got - want) / np.abs(want)
    drift = np.where(np.isfinite(drift), drift, np.inf)
    p, scale = momentum(vel, m)
    lean = np.where(np.isfinite(p), p, np.inf) / scale
    off = {"step": int((step != steps).sum()), "seen": int((idle != 0).sum()),
           "bad": int((bad != 0).sum()),
           "energy": int((drift > ENERGY_TOL).sum()),
           "momentum": int((lean > MOMENTUM_TOL).sum())}
    return {
        "checks": {
            "every_body_at_the_same_step": off["step"] == 0
            and off["seen"] == 0,
            "no_token_overtook_another": off["bad"] == 0,
            "energy_every_system": off["energy"] == 0,
            "momentum_every_system": off["momentum"] == 0},
        "deficit": sum(off.values()),
        "read": {"steps": steps, "energy_drift_max": float(drift.max()),
                 "momentum_lean_max": float(lean.max()),
                 "energy_system_0": float(got.reshape(-1)[0])},
    }
