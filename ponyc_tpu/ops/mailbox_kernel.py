"""Pallas TPU kernel for the mailbox drain (the dispatch gather).

≙ the hot half of ponyint_actor_run's message pop loop
(src/libponyrt/actor/actor.c:383-549, messageq.c pops) — and the kernel
BASELINE.json's north star names ("behaviour dispatch ... as a
vmapped/Pallas kernel").

The XLA path (state.ring_take) drains `batch` ring slots per actor
with a static select chain per slot: `batch` separate fusions over the
[cap, w1, N] mailbox block, each re-reading the block from HBM when the
fusion boundary falls badly. This kernel makes the blocking explicit:
one grid step pulls a [cap, w1, LANE] tile of the (planar, actor-minor —
state.py layout note) mailbox table into VMEM ONCE and emits all
`batch` message planes and validity masks from it.

Gating: `RuntimeOptions.pallas` (off by default: compiled by Mosaic on
the v5e and bit-identical to the XLA path there — chip_smoke.py phase
(d) — but not yet timed against it; `interpret=True` runs the same
kernel on CPU for the test suite and is unreachable on a TPU). No
per-lane gather is used anywhere — ring-slot selection is a static
select chain over the small `cap` axis, which is the TPU-legal
formulation (dynamic per-lane indexing does not lower). A cohort the
kernel cannot tile is REFUSED (`refusal`), never quietly run on the XLA
path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


LANE_BLOCK = 1024        # actors per grid step (multiple of 128 lanes)


def _drain_kernel(head_ref, nrun_ref, buf_ref, msgs_ref, valid_ref, *,
                  cap: int, batch: int):
    head = head_ref[:]                        # [1, LB]
    nrun = nrun_ref[:]                        # [1, LB]
    for k in range(batch):
        slot = (head + k) % cap               # [1, LB]
        out = buf_ref[0]                      # [w1, LB]
        for c in range(1, cap):
            out = jnp.where(slot == c, buf_ref[c], out)
        msgs_ref[k] = out
        valid_ref[k] = (nrun > k).astype(jnp.int32)[0]


@functools.partial(jax.jit, static_argnames=("batch", "interpret"))
def drain_msgs(buf, head, n_run, *, batch: int, interpret: bool = False):
    """All actors' next `batch` messages in one pass over the mailbox.

    buf: [cap, w1, N] int32 (planar); head, n_run: [N] int32.
    Returns (msgs [batch, w1, N] int32, valids [batch, N] bool).
    N must be at most LANE_BLOCK or a multiple of it (`refusal`).
    """
    cap, w1, n = buf.shape
    lb = min(LANE_BLOCK, n)
    assert n % lb == 0, (n, lb)
    grid = (n // lb,)
    kernel = functools.partial(_drain_kernel, cap=cap, batch=batch)
    msgs, valid = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, lb), lambda i: (0, i)),
            pl.BlockSpec((1, lb), lambda i: (0, i)),
            pl.BlockSpec((cap, w1, lb), lambda i: (0, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((batch, w1, lb), lambda i: (0, 0, i)),
            pl.BlockSpec((batch, lb), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, w1, n), jnp.int32),
            jax.ShapeDtypeStruct((batch, n), jnp.int32),
        ],
        interpret=interpret,
        name="pony_drain",
    )(head[None, :], n_run[None, :], buf)
    return msgs, valid.astype(jnp.bool_)


def refusal(cohort) -> str | None:
    """Why this kernel cannot drain `cohort` as asked, or None. The grid
    tiles the actor-lane axis in whole LANE_BLOCKs (shared with
    ops.fused_dispatch)."""
    rows = cohort.local_capacity
    if rows > LANE_BLOCK and rows % LANE_BLOCK:
        return (f"cohort {cohort.atype.__name__}: {rows} rows per shard "
                f"is neither <= {LANE_BLOCK} nor a multiple of it (the "
                "kernel's lane block)")
    return None


def interpret_mode() -> bool:
    """Interpret on non-TPU backends so the suite exercises the kernel."""
    return jax.default_backend() != "tpu"
