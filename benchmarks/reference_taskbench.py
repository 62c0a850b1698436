"""The plain reference of Task Bench's periodic 1-D stencil: NumPy only,
imports neither the engine nor JAX.

Task Bench (Slaughter et al., SC 2020; `core/core.cc`) runs a task
graph of `width` points over a number of timesteps. With `-type
stencil_1d_periodic` the task of point `p` at timestep `t` depends on
the tasks of points `p - 1`, `p`, `p + 1` (mod `width`) at timestep
`t - 1`; timestep 0 depends on nothing. `execute_point` is the source's
own reference of the semantics, and what is written here from memory of
it (benchmarks/configs/taskbench-stencil.json, `assumed`):

  fill    a task's output buffer holds `pairs` pairs, every one
          `(timestep, point)` of the task that wrote it;
  check   before it runs, a task reads every pair of every input and
          holds it to `(timestep - 1, dependency)`; the source aborts on
          the first pair that differs, this one says which differ.

The source's pair is two 64-bit words; here a word is the runtime's
int32 (the configuration's `reduced`): a pair is two words, a buffer
`2 * pairs` of them.

`Stencil` replays the deployment tick by tick: every point consumes its
three inputs, checks them, adds their words to `acc`, and writes one
copy of its output a dependent. What the system must equal after T
ticks, bit for bit (the arithmetic is integer), is `observed()`.
"""

from __future__ import annotations

import numpy as np

DEPS = 3                          # stencil_1d_periodic: p - 1, p, p + 1
COLUMNS = ("step", "acc", "seen", "mask", "bad_inputs")


def dependencies(point, width: int) -> np.ndarray:
    """[..., 3] the points `point` depends on at the timestep before
    (and, the stencil being symmetric, the points that depend on it)."""
    point = np.asarray(point, np.int64)
    return (point[..., None] + np.arange(-1, 2)) % width


def fill(timestep, point, pairs: int) -> np.ndarray:
    """[..., 2 * pairs] the output buffer of task (`timestep`, `point`):
    `pairs` times the pair (timestep, point)."""
    pair = np.stack(np.broadcast_arrays(np.asarray(timestep, np.int32),
                                        np.asarray(point, np.int32)), -1)
    return np.tile(pair, pairs)


def wrong_pairs(timestep, point, inputs, width: int) -> np.ndarray:
    """`execute_point`'s check for the task (`timestep`, `point`):
    `inputs` [..., 3, 2 * pairs] are its dependencies' buffers in the
    order of `dependencies`; [..., 3, pairs] bool says which pairs are
    not (timestep - 1, dependency)."""
    inputs = np.asarray(inputs, np.int32)
    pairs = inputs.shape[-1] // 2
    got = inputs.reshape(*inputs.shape[:-1], pairs, 2)
    want_t = (np.asarray(timestep, np.int64) - 1)[..., None, None]
    want_p = dependencies(point, width)[..., None]
    return (got[..., 0] != want_t) | (got[..., 1] != want_p)


class Stencil:
    """`width` points in lockstep. Timestep 0 is the host's (it depends
    on nothing: its outputs are the seeded messages); from then on a
    tick is a timestep. `steps` is Task Bench's `-steps`: 0 runs for
    ever; S > 0 has timesteps 0 .. S - 1, the last of which writes no
    output, so the pool drains."""

    def __init__(self, width: int, pairs: int, steps: int = 0):
        if width < DEPS:
            raise ValueError(f"a periodic stencil of 3 needs 3 points, "
                             f"not {width}")
        if steps == 1 or steps < 0:
            raise ValueError("steps: 0 (for ever) or at least 2")
        self.width, self.pairs, self.steps = width, pairs, steps
        self.points = np.arange(width)
        self.ticks = 0
        self.step = np.ones(width, np.int32)       # the next to run
        self.acc = np.zeros(width, np.int32)
        self.bad_inputs = np.zeros(width, np.int32)
        self.dispatched = 0
        # what timestep 0 wrote: one copy a dependent
        self.outputs = fill(0, self.points, pairs)
        self.live = DEPS * width

    def tick(self) -> None:
        """One timestep for every point that still has one to run."""
        self.ticks += 1
        if self.live == 0:
            return
        t = self.step.astype(np.int64)
        inputs = self.outputs[dependencies(self.points, self.width)]
        wrong = wrong_pairs(t, self.points, inputs, self.width)
        self.bad_inputs += wrong.any(-1).sum(-1).astype(np.int32)
        self.acc = (self.acc.astype(np.int64)
                    + inputs.astype(np.int64).sum((-1, -2))).astype(np.int32)
        self.dispatched += DEPS * self.width
        last = self.steps and int(t[0]) == self.steps - 1
        self.outputs = None if last else fill(t, self.points, self.pairs)
        self.live = 0 if last else DEPS * self.width
        self.step = (t + 1).astype(np.int32)

    def advance(self, ticks: int) -> "Stencil":
        for _ in range(ticks):
            self.tick()
        return self

    def observed(self) -> dict:
        """Every point's columns by point id; a step's third input
        clears `seen` and `mask`, so between ticks both are 0."""
        zeros = np.zeros(self.width, np.int32)
        return {"step": self.step, "acc": self.acc, "seen": zeros,
                "mask": zeros, "bad_inputs": self.bad_inputs}


def compare(seen: dict, want: dict) -> dict:
    """The system's columns against the reference's, bit for bit:
    `points_off` counts the points on which any column differs, `off`
    the points a column."""
    off = {k: np.asarray(seen[k]).astype(np.int64)
           != np.asarray(want[k]).astype(np.int64) for k in COLUMNS}
    return {"points_off": int(np.logical_or.reduce(list(off.values())).sum()),
            "off": {k: int(v.sum()) for k, v in off.items()}}
