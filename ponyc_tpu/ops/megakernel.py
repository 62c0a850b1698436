"""Persistent fused window megakernel + the mailbox bandwidth diet.

≙ the whole of ponyint_actor_run's visit — message pop, behaviour
dispatch, GC bookkeeping (src/libponyrt/actor/actor.c:383-664) — as ONE
resident device kernel, where the rest of the engine runs it as a chain
of XLA passes with an HBM round-trip between each.

Two ideas, one module:

1. **The megakernel** (`build_mega_window`): the entire gated window —
   delivery gather → mailbox drain → behaviour dispatch → profiler
   lanes → GC-mark bookkeeping inside the step — executes as one
   `pl.pallas_call` whose body runs the in-window `while` as a
   KERNEL-INTERNAL loop. Today's formulation re-materialises the
   `[cap, w1, N]` mailbox block once per phase per tick
   (ops/mailbox_kernel.py for the drain, ops/fused_dispatch.py for
   dispatch, delivery.py's sort/rebuild, engine.profile_lanes —
   each a separate XLA fusion boundary); here the whole tick body and
   the whole window live inside one kernel scope, so the compiler sees
   a single dataflow region over the mailbox tiles instead of N
   HBM-bounded passes (the Halide "push memory" argument,
   arXiv 2105.12858; actor semantics survive bulk-kernel execution per
   the OpenCL-Actors result, arXiv 1709.07781).

   The kernel body reuses the REAL `engine.build_step` closure and the
   REAL window `while` condition (`engine.aux_go`) — equivalence with
   the XLA scan path is by construction, and the differential/FIFO
   corpora (tests/test_differential.py, tests/test_fifo.py) pin it
   bit-for-bit in interpret mode on the CPU backend.

   **Does not lower on v5e (jax 0.9.0).** Mosaic refuses the kernel at
   lowering (`MOSAIC_REFUSAL` below holds its words), and more refusals
   sit behind that first one: the `pallas_call` has no grid and no
   `BlockSpec`, so all of the window's state is asked into VMEM at
   once, and the replayed jaxpr is full of sort/gather/scatter/while.
   So on a TPU `refusal()` names that reason, `delivery="pallas_mega"`
   raises it at start(), and `delivery="auto"` never enumerates the
   variant. It stays as an interpret-mode vehicle for the equivalence
   corpora only; its deletion is ROADMAP C1's.

2. **The bandwidth diet** (`pack_words`/`unpack_words`): mailbox ring
   records, spill words and trace lanes are int32, but behaviour ids
   and most payload words are small. Records cross the kernel boundary
   packed as an int16 lane plane plus an int32 ESCAPE plane: a word
   that fits int16 (and is not the reserved sentinel) travels in 2
   bytes; the rare wide word travels via the escape plane. The codec
   is LOSSLESS for every int32 value (the sentinel itself is escaped),
   so packing can never change semantics — only bytes moved. Modelled
   hot-path bytes per message drop from 4·w1 to w1·(2 + 4·esc_rate):
   2.0× at a zero escape rate, ≥ 1.8× while fewer than ~5.5% of words
   escape (`modelled_bytes_per_msg`; bench.py records the measured
   escape rate of every run in the BENCH json `kernel` block, and
   PROFILE.md §14 carries the bytes-moved/tick table).

Single-shard only (`refusal`): under a mesh the window's psum votes
cross shards mid-tick, which a single-device kernel scope cannot
express — delivery="pallas_mega" on a sharded program is refused at
start().
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from .mailbox_kernel import interpret_mode

# The escaped sentinel: int16 min. A packed word equal to ESC means
# "read the escape plane". -32768 itself FITS int16 but collides with
# the sentinel, so it is escaped too — the codec is total on int32.
ESC = -32768


# ---------------------------------------------------------------------------
# the record codec (jnp + np twins — serialise.py packs snapshots with
# the numpy spelling, the kernel boundary uses the jax one)


def pack_words(w: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """int32 words → (int16 lane plane, int32 escape plane). Lossless:
    `unpack_words(*pack_words(w)) == w` for every int32 value."""
    w = w.astype(jnp.int32)
    lo = w.astype(jnp.int16)
    fits = (lo.astype(jnp.int32) == w) & (lo != jnp.int16(ESC))
    lo16 = jnp.where(fits, lo, jnp.int16(ESC))
    esc32 = jnp.where(fits, jnp.int32(0), w)
    return lo16, esc32


def unpack_words(lo16: jnp.ndarray, esc32: jnp.ndarray) -> jnp.ndarray:
    return jnp.where(lo16 == jnp.int16(ESC), esc32,
                     lo16.astype(jnp.int32))


def pack_words_np(w: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    w = np.asarray(w, np.int32)
    lo = w.astype(np.int16)
    fits = (lo.astype(np.int32) == w) & (lo != np.int16(ESC))
    lo16 = np.where(fits, lo, np.int16(ESC)).astype(np.int16)
    esc32 = np.where(fits, np.int32(0), w).astype(np.int32)
    return lo16, esc32


def unpack_words_np(lo16: np.ndarray, esc32: np.ndarray) -> np.ndarray:
    return np.where(lo16 == np.int16(ESC), esc32,
                    lo16.astype(np.int32)).astype(np.int32)


def escape_rate(arrays) -> float:
    """Fraction of int32 words that need the escape plane (wide values
    plus the sentinel collision) across `arrays` — the measured input
    to the bytes-per-message model."""
    total = 0
    escaped = 0
    for a in arrays:
        a = np.asarray(a)
        if a.size == 0 or a.dtype != np.int32:
            continue
        lo = a.astype(np.int16)
        fits = (lo.astype(np.int32) == a) & (lo != np.int16(ESC))
        total += a.size
        escaped += int(a.size - np.count_nonzero(fits))
    return escaped / total if total else 0.0


def escape_rate_state(state) -> float:
    """Measured escape rate over the live word tables (mailbox rings +
    spill words) of an RtState — what bench.py records per run."""
    arrs = list(state.buf.values()) + [state.dspill_words,
                                       state.rspill_words]
    arrs += list(state.trace_buf.values())
    return escape_rate([np.asarray(a) for a in arrs])


def record_words(opts) -> int:
    """Ring-record width in words: behaviour id + payload + trace
    lanes (state.py: w1 = 1 + msg_words + trace_lanes)."""
    return 1 + opts.msg_words + getattr(opts, "trace_lanes", 0)


def modelled_bytes_per_msg(opts, esc_rate: float = 0.0) -> Dict[str, Any]:
    """The bandwidth-diet model: hot-path bytes per ring record,
    unpacked (4 bytes/word) vs packed (2 bytes/word + the escape plane
    fetched at the measured escape rate). The acceptance bar is
    ratio ≥ 1.8, which holds while esc_rate ≤ ~5.5%."""
    w1 = record_words(opts)
    unpacked = 4.0 * w1
    packed = w1 * (2.0 + 4.0 * float(esc_rate))
    return {
        "record_words": w1,
        "unpacked_bytes": unpacked,
        "packed_bytes": round(packed, 3),
        "ratio": round(unpacked / packed, 3),
        "escape_rate": round(float(esc_rate), 6),
    }


# ---------------------------------------------------------------------------
# eligibility


# Mosaic's words when the window kernel is lowered for "TPU v5 lite"
# (jax 0.9.0 / libtpu 0.0.34; AOT against the v5e topology and on the
# chip alike). The source is `_decode_refs` materialising a zero-size
# bypass leaf inside the kernel.
MOSAIC_REFUSAL = ("vector types must have positive constant sizes "
                  "but got 3, 0")


def refusal(program, opts) -> Optional[str]:
    """Why delivery="pallas_mega" cannot run as asked, or None.
    Structural preconditions: one shard (the window's mesh psum votes
    cannot cross a single kernel's scope), some device cohort to run,
    and the nested Pallas kernels OFF (a pallas_call inside the
    megakernel's scope would nest kernels — the megakernel IS the fused
    form of both). And the backend: on a TPU the kernel does not lower
    (module docstring), so there the answer is always Mosaic's."""
    if not interpret_mode():
        return ("the window megakernel does not lower on TPU "
                f"(jax {jax.__version__}); Mosaic: {MOSAIC_REFUSAL}")
    if program.shards != 1:
        return f"mesh_shards={program.shards} (single-shard only)"
    if getattr(opts, "pallas", False) is True:
        return "pallas=True would nest a pallas_call inside the kernel"
    if getattr(opts, "pallas_fused", False) is True:
        return ("pallas_fused=True would nest a pallas_call inside the "
                "kernel")
    if not any(ch.behaviours for ch in program.device_cohorts):
        return "no device cohort has behaviours to run"
    return None


# ---------------------------------------------------------------------------
# pytree <-> kernel-operand marshalling
#
# The kernel's I/O is the flattened (state, aux) pytree. Per leaf:
#   - zero-size leaves bypass the kernel (no bytes to move; pallas
#     rejects 0-sized blocks) and are reconstituted outside;
#   - word-table leaves (mailbox rings, spill words, trace lanes —
#     state.PACKED_WORD_FIELDS) cross as (int16, int32-escape) pairs:
#     the bandwidth diet applied exactly where the bytes are;
#   - bool leaves cross as int32 (TPU-friendly lane dtype);
#   - scalars cross as [1] vectors (0-d refs don't block).


class _Role(NamedTuple):
    kind: str            # "bypass" | "packed" | "plain"
    shape: Tuple[int, ...]
    dtype: Any
    was_bool: bool
    was_scalar: bool


def _word_table_mask(state) -> List[bool]:
    """Flattened-leaf mask marking the packable int32 word tables,
    aligned with jax.tree.flatten(state)."""
    import dataclasses
    from ..runtime.state import PACKED_WORD_FIELDS
    mask = jax.tree.map(lambda _: False, state)
    kw = {}
    for f in PACKED_WORD_FIELDS:
        v = getattr(state, f)
        kw[f] = ({k: True for k in v} if isinstance(v, dict) else True)
    mask = dataclasses.replace(mask, **kw)
    return jax.tree_util.tree_leaves(mask)


def _roles(leaves, packed_mask) -> List[_Role]:
    out = []
    for leaf, packed in zip(leaves, packed_mask):
        shape = tuple(leaf.shape)
        size = int(np.prod(shape)) if shape else 1
        if size == 0:
            out.append(_Role("bypass", shape, leaf.dtype, False, False))
        elif packed and leaf.dtype == jnp.int32:
            out.append(_Role("packed", shape, leaf.dtype, False, False))
        else:
            out.append(_Role("plain", shape, leaf.dtype,
                             leaf.dtype == jnp.bool_, shape == ()))
    return out


def _encode(leaves, roles) -> List[jnp.ndarray]:
    ops: List[jnp.ndarray] = []
    for leaf, role in zip(leaves, roles):
        if role.kind == "bypass":
            continue
        if role.kind == "packed":
            lo16, esc32 = pack_words(leaf)
            ops.append(lo16)
            ops.append(esc32)
            continue
        a = leaf
        if role.was_bool:
            a = a.astype(jnp.int32)
        if role.was_scalar:
            a = a.reshape(1)
        ops.append(a)
    return ops


def _operand_structs(roles) -> List[jax.ShapeDtypeStruct]:
    out = []
    for role in roles:
        if role.kind == "bypass":
            continue
        if role.kind == "packed":
            out.append(jax.ShapeDtypeStruct(role.shape, jnp.int16))
            out.append(jax.ShapeDtypeStruct(role.shape, jnp.int32))
            continue
        shape = (1,) if role.was_scalar else role.shape
        dtype = jnp.int32 if role.was_bool else role.dtype
        out.append(jax.ShapeDtypeStruct(shape, dtype))
    return out


def _decode_refs(refs, roles) -> List[jnp.ndarray]:
    """Kernel-side: read operand refs back into the original leaves."""
    leaves: List[jnp.ndarray] = []
    i = 0
    for role in roles:
        if role.kind == "bypass":
            leaves.append(jnp.zeros(role.shape, role.dtype))
            continue
        if role.kind == "packed":
            lo16 = refs[i][...]
            esc32 = refs[i + 1][...]
            i += 2
            leaves.append(unpack_words(lo16, esc32))
            continue
        a = refs[i][...]
        i += 1
        if role.was_scalar:
            a = a.reshape(())
        if role.was_bool:
            a = a.astype(jnp.bool_)
        leaves.append(a)
    return leaves


def _write_refs(refs, roles, leaves) -> None:
    """Kernel-side: write result leaves to the output refs."""
    i = 0
    for leaf, role in zip(leaves, roles):
        if role.kind == "bypass":
            continue
        if role.kind == "packed":
            lo16, esc32 = pack_words(leaf)
            refs[i][...] = lo16
            refs[i + 1][...] = esc32
            i += 2
            continue
        a = leaf
        if role.was_bool:
            a = a.astype(jnp.int32)
        if role.was_scalar:
            a = a.reshape(1)
        refs[i][...] = a
        i += 1


def _decode_outputs(outs, roles) -> List[jnp.ndarray]:
    """Host-side: kernel outputs back into result leaves."""
    leaves: List[jnp.ndarray] = []
    i = 0
    for role in roles:
        if role.kind == "bypass":
            leaves.append(jnp.zeros(role.shape, role.dtype))
            continue
        if role.kind == "packed":
            leaves.append(unpack_words(outs[i], outs[i + 1]))
            i += 2
            continue
        a = outs[i]
        i += 1
        if role.was_scalar:
            a = a.reshape(())
        if role.was_bool:
            a = a.astype(jnp.bool_)
        leaves.append(a)
    return leaves


# ---------------------------------------------------------------------------
# the megakernel window


def build_mega_window(program, opts, step, go_fn, *, forced: bool = False):
    """The gated window (engine.build_multi_step_gated's contract) as
    ONE persistent Pallas kernel; `forced=True` builds the tuner's
    unconditional fori_loop spelling (engine.build_forced_window)
    instead, so calibration times the kernel on the same trip count as
    every other variant.

    `step` is the REAL engine.build_step closure and `go_fn` the REAL
    engine.aux_go — the kernel-internal loop is the same computation
    the XLA path runs, so bit-equivalence is by construction.

    Signature (both spellings): (st, inject_tgt, inject_words, limit,
    force, prev_aux) → (state, last_aux, ticks_run).
    """
    interpret = interpret_mode()

    def window(st, inject_tgt, inject_words, limit, force, prev_aux):
        if forced:
            def fbody(_i, carry):
                s, _aux = carry
                return step(s, inject_tgt, inject_words)

            stf, auxf = lax.fori_loop(0, limit, fbody, (st, prev_aux))
            return stf, auxf, jnp.asarray(limit, jnp.int32)

        def cond(carry):
            _st, aux, i = carry
            first = i == 0
            return (first & (force | go_fn(aux))) | \
                (~first & (i < limit) & go_fn(aux))

        def body(carry):
            s, _aux, i = carry
            first = i == 0
            it = jnp.where(first, inject_tgt, jnp.int32(-1))
            iw = jnp.where(first, inject_words, jnp.int32(0))
            s2, aux2 = step(s, it, iw)
            return (s2, aux2, i + 1)

        return lax.while_loop(cond, body, (st, prev_aux, jnp.int32(0)))

    def mega(st, inject_tgt, inject_words, limit, force, prev_aux):
        limit = jnp.asarray(limit, jnp.int32)
        force = jnp.asarray(force, jnp.bool_)
        args = (st, inject_tgt, inject_words, limit, force, prev_aux)
        in_leaves, in_tree = jax.tree_util.tree_flatten(args)
        packed_mask = _word_table_mask(st)
        # Non-state args never pack: pad the mask to the flat arity.
        packed_mask = packed_mask + [False] * (len(in_leaves)
                                               - len(packed_mask))
        in_roles = _roles(in_leaves, packed_mask)

        out_struct = jax.eval_shape(window, *args)
        out_leaves, out_tree = jax.tree_util.tree_flatten(out_struct)
        out_mask = _word_table_mask(out_struct[0])
        out_mask = out_mask + [False] * (len(out_leaves) - len(out_mask))
        out_roles = _roles(out_leaves, out_mask)

        # Pallas forbids kernels that close over array constants (the
        # step closure bakes the program's routing/layout tables in as
        # literals). Stage the window to a jaxpr ONCE, hand its consts
        # to the kernel as ordinary operands, and replay the jaxpr
        # inside the kernel scope — the whole window body becomes kernel
        # dataflow with no captured arrays.
        def flat_window(*leaves):
            a = jax.tree_util.tree_unflatten(in_tree, leaves)
            return tuple(jax.tree_util.tree_leaves(window(*a)))

        closed = jax.make_jaxpr(flat_window)(*in_leaves)
        consts = [jnp.asarray(c) for c in closed.consts]
        const_roles = _roles(consts, [False] * len(consts))

        def n_operands(roles):
            return sum(0 if r.kind == "bypass"
                       else (2 if r.kind == "packed" else 1)
                       for r in roles)

        n_const = n_operands(const_roles)
        n_in = n_operands(in_roles)

        def kernel(*refs):
            cvals = _decode_refs(refs[:n_const], const_roles)
            leaves = _decode_refs(refs[n_const:n_const + n_in], in_roles)
            res = jax.core.eval_jaxpr(closed.jaxpr, cvals, *leaves)
            _write_refs(refs[n_const + n_in:], out_roles, list(res))

        outs = pl.pallas_call(
            kernel,
            out_shape=_operand_structs(out_roles),
            interpret=interpret,
        )(*(_encode(consts, const_roles) + _encode(in_leaves, in_roles)))
        res_leaves = _decode_outputs(list(outs), out_roles)
        return jax.tree_util.tree_unflatten(out_tree, res_leaves)

    return mega
