"""n-body — the library's TOY twin of the reference benchmark
`examples/n-body/` (gravity between bodies; the compute-heavy-float-
behaviour workload), kept for the library's own tests
(`tests/test_models.py`, `tests/test_vec_payloads.py`).

What it is: two dimensions, a softened force (`SOFTEN`), an arbitrary
constant `G`, random bodies, ONE interaction round (accelerations only,
no integrator). What it is not: the source's system. The source's own
form — the Sun and the four Jovian planets in three dimensions, the
force `dt / (d2 sqrt(d2))` without softening, the symplectic-Euler
`advance`, the constants and the two printed energies — is
`benchmarks/worlds/nbody.py` (configuration `nbody-jovian`, cell
`nbody-jovian.orbit`), held to `benchmarks/reference_nbody.py`.

TPU shape: a *systolic ring* of body actors. Each body launches a token
carrying its (position, mass); tokens hop the ring, and every body a token
visits accumulates that body's gravitational contribution into its own
acceleration (≈20 flops per message — behaviour bodies are where the VPU
work lands). After B-1 hops the token expires and the visited body count
completes one interaction round: B tokens in flight give B messages/tick
and the full all-pairs sum after B-1 ticks, without any B²-wide outbox.
"""

from __future__ import annotations

import numpy as np

from .. import F32, I32, Ref, Runtime, RuntimeOptions, VecF32, actor, \
    behaviour

G = 6.674e-3          # scaled constant (unit system is arbitrary here)
SOFTEN = 1e-2


@actor
class Body:
    next_ref: Ref[Body]
    x: F32
    y: F32
    m: F32
    ax: F32
    ay: F32
    seen: I32

    MAX_SENDS = 1
    BATCH = 4

    @behaviour
    def token(self, st, hops: I32, pos: VecF32[2], pm: F32):
        # The visitor's position travels as ONE device-side float vector
        # (pack._VecSpec: k words inside the message — ≙ pony_alloc_msg
        # rich payloads, pony.h:332-360). pos is a [2, lanes] planar
        # block; component reads index axis 0.
        dx = pos[0] - st["x"]
        dy = pos[1] - st["y"]
        r2 = dx * dx + dy * dy + SOFTEN
        inv_r = 1.0 / (r2 ** 0.5)
        f = G * pm * inv_r * inv_r * inv_r
        self.send(st["next_ref"], Body.token, hops - 1, pos, pm,
                  when=hops > 1)
        return {**st,
                "ax": st["ax"] + f * dx,
                "ay": st["ay"] + f * dy,
                "seen": st["seen"] + 1}


def build(n_bodies: int = 256, opts: RuntimeOptions | None = None,
          seed: int = 3):
    opts = opts or RuntimeOptions(mailbox_cap=16, batch=4, max_sends=1,
                                  msg_words=4, spill_cap=1024)
    rt = Runtime(opts)
    rt.declare(Body, n_bodies)
    rt.start()
    rng = np.random.default_rng(seed)
    ids = rt.spawn_many(
        Body, n_bodies,
        x=rng.uniform(-1, 1, n_bodies).astype(np.float32),
        y=rng.uniform(-1, 1, n_bodies).astype(np.float32),
        m=rng.uniform(0.5, 2.0, n_bodies).astype(np.float32))
    rt.set_fields(Body, ids, next_ref=np.roll(ids, -1))
    return rt, ids


def run_round(n_bodies: int = 256,
              opts: RuntimeOptions | None = None) -> Runtime:
    """One full all-pairs interaction round (every token hops B-1 times)."""
    rt, ids = build(n_bodies, opts)
    st = rt.cohort_state(Body)
    # Each body's token starts at its ring successor.
    nxt = np.roll(ids, -1)
    rt.bulk_send(nxt, Body.token,
                 np.full(n_bodies, n_bodies - 1),
                 np.stack([st["x"], st["y"]], axis=1),   # [count, 2] vec col
                 st["m"])
    rt.run(max_steps=4 * n_bodies + 100)
    return rt


def reference_accels(xs, ys, ms):
    """NumPy all-pairs oracle for verification."""
    dx = xs[None, :] - xs[:, None]
    dy = ys[None, :] - ys[:, None]
    r2 = dx * dx + dy * dy + SOFTEN
    inv_r3 = 1.0 / np.sqrt(r2) ** 3
    np.fill_diagonal(inv_r3, 0.0)
    f = G * ms[None, :] * inv_r3
    return (f * dx).sum(1), (f * dy).sum(1)
