"""Front-end API: actor types, behaviours, and the per-dispatch Context.

This is the TPU framework's equivalent of the Pony *language surface* for
actors: an ``actor`` class with ``be`` behaviours (reference: the compiler
lowers each behaviour into a message-send stub + a dispatch case,
src/libponyc/codegen/genfun.c; actor hints tag/priority/batch/main-thread
are lazily read from per-type hint functions, src/libponyrt/actor/
actor.c:398-423 — here they are plain class attributes, resolved at program
build time because the whole actor world is compiled as one XLA program,
the same way reach.c assumes whole-program knowledge).

Behaviours are *pure traced functions*::

    @actor
    class RingNode:
        next_ref: Ref            # per-actor state field (annotation = dtype)
        passes:   I32

        @behaviour
        def token(self, st, hops: I32):
            self.send(st["next_ref"], RingNode.token, hops - 1,
                      when=hops > 0)
            self.exit(0, when=hops <= 0)
            return st

``self`` inside a behaviour is a Context, not the object: it carries the
actor's global id and collects the side effects (sends, exit, yield) that
the engine turns into batched device operations. The state dict ``st`` is
functional — return the updated dict.

The number of ``self.send(...)`` calls per behaviour must be static (it is
traced once); data-dependent sends use ``when=`` masks, exactly as XLA
requires (`lax.cond` under vmap selects, it does not branch).
"""

from __future__ import annotations

import functools
import inspect
from typing import Any, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from .ops import pack
from .ops.pack import (Blob, BlobVal, Bool, Box, F32, I8, I16, I32,  # noqa
                       Iso, Mut, Ref, Tag, Trn, TypeParam, U8, U16,
                       U32, Val, VecF32, VecI32)  # re-exported


class BehaviourDef:
    """A behaviour declaration: dispatch id + typed argument spec.

    ≙ a Pony behaviour's (message id, param list); global ids are assigned
    at program build (≙ reach/paint vtable colouring, reach/paint.c:8-60).
    """

    def __init__(self, fn):
        self.fn = fn
        self.name = fn.__name__
        sig = inspect.signature(fn)
        params = list(sig.parameters.values())[2:]  # drop (self, st)
        self.arg_specs = tuple(
            pack.normalize_annotation(
                p.annotation if p.annotation is not inspect.Parameter.empty
                else I32)
            for p in params)
        self.arg_names = tuple(p.name for p in params)
        # Sendability (≙ safeto.c: behaviour/ctor parameters must be in
        # CAP_SEND {iso, val, tag}, type/cap.c:90): a behaviour call IS
        # a message, so a Trn/Mut/Box parameter could smuggle
        # write-aliased state across an actor boundary.
        for p, spec in zip(params, self.arg_specs):
            m = pack.cap_mode(spec)
            if not pack.cap_sendable(m):
                raise TypeError(
                    f"behaviour {fn.__name__}: parameter {p.name!r} is "
                    f"{spec.__name__} — not sendable; only Iso, Val and "
                    "Tag payloads may cross an actor boundary "
                    "(CAP_SEND, type/cap.c:90; safeto.c)")
        # Source capture (the lint body rules + verify failures point
        # at real file:line; None for exec'd/builtin functions):
        code = getattr(fn, "__code__", None)
        self.source_file: Optional[str] = getattr(code, "co_filename",
                                                  None)
        self.source_line: Optional[int] = getattr(code,
                                                  "co_firstlineno", None)
        # Behaviour-level lint suppressions (``@behaviour(lint_ignore=
        # ("R6",))`` sets fn.LINT_IGNORE so inherited/reified copies —
        # which re-wrap the same fn — keep the suppression).
        self.lint_ignore: Tuple[str, ...] = tuple(
            str(r) for r in getattr(fn, "LINT_IGNORE", ()) or ())
        # Filled in by program build:
        self.global_id: Optional[int] = None
        self.local_id: Optional[int] = None
        self.actor_type: Optional["ActorTypeMeta"] = None

    def __repr__(self):
        owner = self.actor_type.__name__ if self.actor_type else "?"
        return f"<behaviour {owner}.{self.name} gid={self.global_id}>"


def behaviour(fn=None, *, lint_ignore=()):
    """Mark a method as an actor behaviour (≙ Pony ``be``).

    ``@behaviour(lint_ignore=("R6", ...))`` suppresses those lint
    rules for findings attributed to this behaviour (the
    behaviour-level sibling of the type-level ``LINT_IGNORE``)."""
    if fn is None:
        def deco(f):
            if lint_ignore:
                f.LINT_IGNORE = tuple(str(r) for r in lint_ignore)
            return BehaviourDef(f)
        return deco
    return BehaviourDef(fn)


# Alias matching Pony's keyword.
be = behaviour


class ActorTypeMeta(type):
    """Metaclass collecting state fields + behaviours from the class body."""

    def __new__(mcs, name, bases, ns):
        fields: Dict[str, Any] = {}
        inherited: List[BehaviourDef] = []
        for base in bases:
            fields.update(getattr(base, "_fields", {}))
            inherited.extend(getattr(base, "_behaviours", []))
        for key, val in list(ns.get("__annotations__", {}).items()):
            if key.startswith("_") or key.isupper():
                continue
            spec = pack.normalize_annotation(val)
            if spec in pack._NARROW_JNP:
                # State columns are i32/f32 only; letting a narrow marker
                # through would silently give the field signed-i32
                # semantics while the same marker on a message argument
                # arrives at its declared width.
                raise TypeError(
                    f"{name}.{key}: narrow/unsigned widths "
                    f"({spec.__name__}) are message-argument types; "
                    "declare state fields as I32 (or F32) and wrap "
                    "explicitly in the behaviour")
            fields[key] = spec
        own = [val for val in ns.values() if isinstance(val, BehaviourDef)]
        cls = super().__new__(mcs, name, bases, ns)
        # Inherited behaviours get a *fresh* BehaviourDef per subclass:
        # dispatch ids are per-(type, behaviour) slots (≙ paint.c vtable
        # colouring), so sharing one def across types would let finalize()
        # clobber ids. The copy is also set as a class attribute so
        # `Sub.ping` resolves to Sub's slot, not the base's.
        behaviours: List[BehaviourDef] = []
        own_names = {b.name for b in own}
        for b in inherited:
            if b.name in own_names:   # overridden in this class body
                continue
            copy = BehaviourDef(b.fn)
            setattr(cls, copy.name, copy)
            behaviours.append(copy)
        behaviours.extend(own)
        cls._fields = fields
        cls._behaviours = behaviours
        for b in behaviours:
            b.actor_type = cls
        # Scheduling hints (≙ actor.c:398-423 lazy hint fns):
        cls.BATCH = ns.get("BATCH", None)        # msgs per step override
        # Ring slots of this type's mailboxes (a power of two) where
        # RuntimeOptions.mailbox_cap does not fit it: a coordinator that
        # takes a whole window of replies among actors that see two or
        # three messages (every slot of every row is HBM, and a drain
        # or a rebuild pass reads them all).
        cls.MAILBOX_CAP = ns.get("MAILBOX_CAP", None)
        cls.PRIORITY = ns.get("PRIORITY", 0)     # ≙ fork's priority hint
        cls.HOST = ns.get("HOST", False)         # ≙ use_main_thread: runs on host
        cls.TAG = ns.get("TAG", 0)               # ≙ fork's analysis tag
        # Spawn budget (≙ pony_create from behaviour code, actor.c:688):
        # {TargetType_or_name: max ctx.spawn() sites per dispatch}. Spawning
        # is opt-in because reservations cost free-slot compaction per step.
        cls.SPAWNS = ns.get("SPAWNS", {})
        # How many of an actor's ≤batch dispatches per step may spawn
        # (default: all of them). Lowering it shrinks the free-slot window
        # each runnable actor reserves; a step that exceeds it raises
        # SpawnCapacityError (safe, no corruption).
        cls.SPAWN_DISPATCHES = ns.get("SPAWN_DISPATCHES", None)
        # Blob budgets (≙ per-behaviour heap allocations, heap.c):
        # MAX_BLOBS = ctx.blob_alloc sites per dispatch; BLOB_DISPATCHES
        # bounds how many of an actor's ≤batch dispatches per step may
        # allocate (default: all) — each runnable actor statically
        # reserves BLOB_DISPATCHES × MAX_BLOBS pool slots per tick, so
        # lowering it lets a small pool serve many actors.
        cls.MAX_BLOBS = ns.get("MAX_BLOBS", 0)
        cls.BLOB_DISPATCHES = ns.get("BLOB_DISPATCHES", None)
        # Generic actor types (≙ formal type parameters; reify.c):
        # collect TypeParams across fields + behaviour args in first-
        # appearance order. Non-empty → the class must be reified
        # (Cls[Concrete]) before declare/spawn.
        all_specs = list(fields.values())
        for b in behaviours:
            all_specs.extend(b.arg_specs)
        cls._type_params = pack.type_params_of(all_specs)
        cls._reifications = {}
        return cls

    def __getitem__(cls, item):
        """Reify a generic actor type: Cell[I32] substitutes the type
        parameters and yields a CONCRETE actor type with its own cohort
        and behaviour ids (≙ reify.c — each reification is its own
        type; reach.c only ever sees concrete ones). Reifications are
        cached so Cell[I32] is Cell[I32]."""
        params = cls._type_params
        if not params:
            raise TypeError(f"{cls.__name__} is not generic "
                            "(no TypeParam annotations)")
        args = item if isinstance(item, tuple) else (item,)
        args = tuple(pack.normalize_annotation(a)
                     if not isinstance(a, ActorTypeMeta) else a
                     for a in args)
        if len(args) != len(params):
            raise TypeError(
                f"{cls.__name__} takes {len(params)} type argument(s) "
                f"({', '.join(p.name for p in params)}), got {len(args)}")
        # Cache key: actor/marker CLASSES key by object identity (two
        # distinct classes sharing a name must not collide); spec
        # instances key by their canonical name.
        def _key_of(a):
            if isinstance(a, type):
                return a
            if isinstance(a, pack._RefTo) and not isinstance(a.target,
                                                             str):
                return ("Ref", a.target)
            return a.__name__ if hasattr(a, "__name__") else str(a)
        key = tuple(_key_of(a) for a in args)
        hit = cls._reifications.get(key)
        if hit is not None:
            return hit
        mapping = dict(zip(params, args))
        disp = tuple(a.__name__ if hasattr(a, "__name__") else str(a)
                     for a in args)
        name = f"{cls.__name__}[{', '.join(disp)}]"
        ns = {"__annotations__": {}, "__qualname__": name}
        for attr in ("BATCH", "MAILBOX_CAP", "PRIORITY", "HOST", "TAG",
                     "SPAWNS",
                     "SPAWN_DISPATCHES", "MAX_SENDS", "MAX_BLOBS",
                     "BLOB_DISPATCHES"):
            if attr in cls.__dict__:
                ns[attr] = cls.__dict__[attr]
        new = ActorTypeMeta(name, (Actor,), ns)
        new.__name__ = name
        new._fields = {k: pack.substitute(s, mapping)
                       for k, s in cls._fields.items()}
        behaviours = []
        for b in cls._behaviours:
            copy = BehaviourDef(b.fn)
            # Substitute from the CURRENT class's specs (b.arg_specs),
            # not the freshly re-derived signature specs: re-reifying a
            # partial application (Cell[U][I32]) must start from U, not
            # from the template's original parameter.
            copy.arg_specs = tuple(pack.substitute(s, mapping)
                                   for s in b.arg_specs)
            copy.actor_type = new
            setattr(new, copy.name, copy)
            behaviours.append(copy)
        new._behaviours = behaviours
        # Recompute from the SUBSTITUTED specs: a type argument that is
        # itself a TypeParam (partial application, Cell[U]) leaves the
        # result generic — it must still refuse declare().
        sub_specs = list(new._fields.values())
        for b in behaviours:
            sub_specs.extend(b.arg_specs)
        new._type_params = pack.type_params_of(sub_specs)
        cls._reifications[key] = new
        return new

    @property
    def field_specs(cls):
        return cls._fields

    @property
    def behaviour_defs(cls):
        return cls._behaviours


class Actor(metaclass=ActorTypeMeta):
    """Base class for actor types (subclass + annotate fields)."""


def actor(cls):
    """Class decorator: turn a plain class into an actor type."""
    ns = dict(cls.__dict__)
    ns.pop("__dict__", None)
    ns.pop("__weakref__", None)
    return ActorTypeMeta(cls.__name__, (Actor,), ns)


def _heap_scoped(kind: str):
    """One blob op of a behaviour: its handle checks, gathers and
    scatters on the pool carry the device scope `pony/dispatch/heap/
    <kind>` — `get` (a word's or the length's read), `set` (with a
    fresh payload's column, whichever op flushes it:
    BlobPoolView.flush), `alloc` (the slot's books) or `free`
    (runtime.state.STEP_SCOPES; metadata only) —, so a trace names the
    heap's share of the dispatch, which
    is what lies at and below `dispatch/heap`, and splits it by what
    the behaviour asked for."""
    def scope(method):
        @functools.wraps(method)
        def scoped(self, *args, **kwargs):
            from .runtime.state import phase_scope
            with phase_scope(f"dispatch/heap/{kind}"):
                return method(self, *args, **kwargs)
        return scoped
    return scope


# One word an index into the flat pool (lax.scatter, not `.at[]`, which
# casts its indices to int32).
_WORD_SCATTER = lax.ScatterDimensionNumbers(
    update_window_dims=(), inserted_window_dims=(0,),
    scatter_dims_to_operand_dims=(0,))


# One column (every word of a slot) an index into the pool seen as
# [W, nslots]: the updates are [W, lanes], window on dimension 0.
_COLUMN_SCATTER = lax.ScatterDimensionNumbers(
    update_window_dims=(0,), inserted_window_dims=(1,),
    scatter_dims_to_operand_dims=(1,))


def _ascending(key, carried, end, past):
    """(key, carried) sorted by key, STRICTLY ascending — what a scatter
    flagged sorted and unique may be handed. Keys below `end` write and
    are distinct in an honest program; the others are dropped and were
    keyed from `past` (each its own, `end` or more). A breach shows
    after the sort as two equal neighbours: the first keeps its key,
    the others take `past`'s and the vector is sorted once more —
    behind a cond, so an honest program pays one compare."""
    key, carried = lax.sort((key, carried), num_keys=1, is_stable=True)
    dup = jnp.concatenate(
        [jnp.zeros((1,), jnp.bool_), key[1:] == key[:-1]])

    def once_more():
        return tuple(lax.sort(
            (jnp.where(dup | (key >= end), past, key), carried),
            num_keys=1, is_stable=True))

    return lax.cond(jnp.any(dup), once_more, lambda: (key, carried))


class _Column:
    """A payload between its blob_alloc and its flush (BlobPoolView):
    the handle object the alloc returned, the alloc's own local slot
    `idx` (nslots where it did not allocate) and mask `ok`, and the W
    words of every lane as they stand, `rows` — zeros until a blob_set
    of a static word lands. `checked`: the handle's generation and used
    flag as blob_set reads them, gathered at the first set (while the
    column is open nothing can change either: an alloc writes other
    slots, a free flushes first)."""

    __slots__ = ("handle", "idx", "ok", "rows", "checked")

    def __init__(self, handle, idx, ok, words):
        self.handle, self.idx, self.ok = handle, idx, ok
        self.rows = [jnp.zeros(ok.shape, jnp.int32)] * words
        self.checked = None


# One window (the first rows of a slot) an index into those rows of the
# pool seen as [rows, nslots]: _COLUMN_SCATTER the other way, the
# result [rows, lanes].
_WINDOW_GATHER = lax.GatherDimensionNumbers(
    offset_dims=(0,), collapsed_slice_dims=(1,), start_index_map=(1,))

# What a read window costs, in single-word gets of the same lanes (a
# ratio, so the lanes cancel; measured on a v5e, docs/DESIGN.md §7b):
# the gather's fixed part, and a part for every pool word the window
# spans a lane (rows x nslots / lanes: seeing them as [rows, nslots]
# re-tiles them, which is what a large window pays for).
WINDOW_FIXED = 0.8
WINDOW_A_WORD = 0.0105


def window_rows(words, slots_a_lane):
    """How many rows the ONE window holds that stands for static gets of
    `words` of a handle — rows 0 .. max(words): a window starts at the
    pool's first row, where its rows are a prefix of the flat pool — or
    None where the single gets are cheaper: fewer than two of them, or
    fewer than the window's price in gets (over a pool of `slots_a_lane`
    slots a lane of the cohort)."""
    rows = max(words) + 1
    price = WINDOW_FIXED + rows * slots_a_lane * WINDOW_A_WORD
    return rows if len(words) >= max(2, price) else None


class _Window:
    """The static reads of one handle object while nothing writes the
    pool (BlobPoolView): `words`, the word of every such blob_get in
    program order, and — where the view follows a plan that opened a
    window for them — `rows`, the [rows, lanes] words one gather
    read."""

    __slots__ = ("handle", "words", "rows")

    def __init__(self, handle):
        self.handle, self.words, self.rows = handle, [], None


class BlobPoolView:
    """Trace-time working view of the device blob pool for ONE behaviour
    evaluation (see ops.pack.Blob; pool arrays live in runtime.state).

    The planar engine hands each behaviour branch the CURRENT pool
    arrays plus `take` — the lane mask "this lane's batch slot selected
    this behaviour". Every mutation (alloc/set/free) applies eagerly,
    masked by `take & when`, to this view's working copies; because one
    blob has exactly one owner and the take masks of a cohort's
    behaviours are disjoint, sequential application across branches is
    exact — no cross-branch selects, and reads observe this dispatch's
    own earlier writes (read-your-writes).

    One kind of write waits: **a payload is born full.** blob_alloc
    writes the slot's books (`gen`, `used`, `len_`, the counters) at
    once but not its words; it opens a *column* (`_Column`) for the
    handle object it returns — the W words of every lane, zeros. A
    blob_set that is given that very object (found by identity, as a
    pinned handle is) and a static word index lands in the column's row
    under the mask it would have written the pool with, later sets over
    earlier ones, and emits no pool operation. `flush` writes every
    open column, in the order they were opened, each as ONE scatter of
    [W, lanes] into the pool seen as [W, nslots] (word-major: a payload
    is a column of it), and runs before anything that could observe or
    disturb a column: the end of the behaviour's evaluation
    (engine._make_branch), any blob_get, a blob_set whose index is
    traced or whose handle is not an open column's, blob_free.
    blob_length reads `len_`, which is eager, and flushes nothing. That
    is exact: an iso has one owner, so only this lane of this behaviour
    can name the slot; columns open at once hold disjoint slots (the
    reservation's windows: spawn.cohort_blob_resv); and every read of
    the pool's words flushes first — so "the slot's words are zeroed"
    means "by the flush", which writes 0 to every word no set covered.
    A forged handle (an untyped int that names the fresh slot) is not
    the column's object: the op it is given to flushes and then goes
    the eager way, after the column's words as in program order, so no
    program can see the column before it is written.

    One kind of read is made whole: **a payload is read whole.** The
    first blob_get of a handle object at a static word index (a Python
    or NumPy integer in [0, W), the test a column makes of a set) checks
    the handle as every get does and then, where the behaviour's own
    probe says it pays (`reads`, below), gathers the handle's rows up
    to the last one read ONCE — one gather of [rows, lanes] from those
    rows of the pool (word-major: a prefix of it) seen as [rows,
    nslots], indexed by slot, 0 in a lane whose handle is null, stale,
    forged,
    freed or another shard's, the lanes where a single get fills 0 —
    and keeps them as a *read window* (`_Window`) under that object.
    Every later blob_get of the same object at a static word returns a
    row of the window and emits no pool operation. Whatever can change
    the pool's words or what a check said closes every window first: a
    flush that writes a column, a blob_set that scatters, blob_alloc,
    blob_free — of ANY handle, since a forged one may alias the
    window's slot. blob_length and a blob_set folded into a column
    close nothing (neither writes the pool). So a window holds exactly
    what a single get would read where it is used: nothing wrote the
    pool, `gen` or `used` since the gather, and get - set - get of one
    handle sees the set. A get at a traced index, of a handle copied by
    arithmetic (another object), or where no window was opened, goes
    the single-word way.

    Whether a window opens is decided from the behaviour's own reads,
    not by an option: the first get cannot see the gets behind it, so
    the cohort's probe (engine._cohort_dispatch: an abstract trace of
    every behaviour before the real one) runs on a view that only
    RECORDS (given no `reads`: per handle object, in the order first
    read, the static words read until something closed the record:
    `read_log`), and `read_plan()` prices each record (`window_rows`:
    the gets it stands for against the pool words its rows span a
    lane). The real trace's
    view is handed that plan and opens its k-th record as the plan's
    k-th entry says; engine._make_branch holds the trace's own records
    to the plan and raises where they differ.

    A handle is checked — `local`: its slot and its generation against
    the slot's; `live`: the slot's used flag — where an op is given it,
    unless the engine checked it ONCE for the whole cohort dispatch
    (`resolved`; engine._cohort_dispatch, `pinned`): a Blob state field
    that every behaviour of the cohort hands back as the very tracer it
    came in as, in a cohort none of whose behaviours calls blob_alloc or
    blob_free. That is exact: the field holds one value in every batch
    slot (returned by identity), `gen` and `used` are written by alloc
    and free alone (cohorts run one after another, so nothing else
    writes them while this cohort's scan runs), and an iso has one owner
    — the same `h`, `gen`, `used` give the same answer in slot 7 as in
    slot 0, for stale, null, remote and forged handles too. A handle
    that is any other object (a message argument, a computed value, a
    field some behaviour overwrites) is checked where it is used.

    ≙ the reference's actor heap + pony_alloc_msg payloads
    (pony.h:332-360): alloc on the owning actor, move by message."""

    __slots__ = ("data", "used", "len_", "gen", "base", "nslots", "words",
                 "take",
                 "resv", "claims", "frees", "fail", "budget_fail", "n_alloc",
                 "n_free", "n_remote", "alloced", "budget_over", "resolved",
                 "columns", "sets_folded", "sets_alone", "reads",
                 "windows", "read_log", "gets")

    def __init__(self, data, used, len_, gen, base, take, resv,
                 budget_over=None, resolved=None, reads=()):
        self.data = data            # [W*B] i32, word-major (working copy)
        self.used = used            # [B] bool
        self.len_ = len_            # [B] i32
        self.gen = gen              # [B] i32 slot generations (ABA guard)
        self.base = base            # traced i32: this shard's first handle
        self.nslots = used.shape[0]
        self.words = data.shape[0] // max(1, self.nslots)   # W
        self.take = take            # [lanes] bool
        self.resv = resv            # [sites, lanes] i32 handles, or None
        self.claims = 0             # trace-time alloc-site counter
        self.frees = 0              # trace-time free-site counter
        self.columns = []           # open _Columns, in the order opened
        self.sets_folded = 0        # trace-time: blob_sets a column took
        self.sets_alone = 0         # trace-time: blob_sets that scattered
        self.reads = reads          # the probe's read_plan(); given
        #   none, this view records and opens no window
        self.windows = []           # the _Windows nothing has closed yet
        self.read_log = []          # every _Window, in the order opened
        self.gets = 0               # trace-time: blob_gets
        self.resolved = resolved    # pack.RefTypes or None: handle tracer
        #   -> (slot, ok, used) checked once, before the batch scan
        self.fail = jnp.bool_(False)     # sticky: wanted a slot, pool empty
        self.budget_fail = jnp.bool_(False)  # sticky: wanted a slot but
        #   the dispatch was past its BLOB_DISPATCHES reservation budget
        self.budget_over = budget_over   # [lanes] bool or None — lanes
        #   whose reservation window was withheld for budget (engine's
        #   used-counter walk), used to blame alloc failures on the
        #   right knob (blob_slots vs BLOB_DISPATCHES)
        self.n_alloc = jnp.int32(0)
        self.n_free = jnp.int32(0)
        self.n_remote = jnp.int32(0)     # Blob args that arrived off-shard
        self.alloced = self.take & False   # [lanes] did this dispatch alloc
        #   (drives the engine's blob_dispatches used-counter walk)

    def local(self, h):
        """(local slot index, validity mask). The handle's generation
        bits must match the slot's current generation (ABA guard: a
        stale handle to a recycled slot is dead, ops.pack encoding).
        Invalid handles map to the UPPER sentinel `nslots` — JAX
        normalises negative indices NumPy-style even under
        mode="drop"/"fill", so -1 would silently address the last slot;
        an out-of-range-high index is what those modes actually
        drop/fill."""
        got = self._checked(h)
        if got:
            return got[0], got[1]
        h = jnp.asarray(h, jnp.int32)
        hl = pack.blob_slot(h) - self.base
        ok = (h >= 0) & (hl >= 0) & (hl < self.nslots)
        hs = jnp.where(ok, hl, self.nslots)
        ok = ok & (jnp.take(self.gen, hs, mode="fill", fill_value=-1)
                   == pack.blob_gen_of(h))
        return jnp.where(ok, hl, self.nslots), ok

    def live(self, h, hl):
        """Is slot `hl` (of handle `h`, from `local`) allocated: a read
        of a freed, stale or forged slot yields 0 and a write to it is
        dropped, never another blob's words."""
        got = self._checked(h)
        if got:
            return got[2]
        return jnp.take(self.used, hl, mode="fill", fill_value=False)

    def _checked(self, h):
        """(slot, ok, used) of a handle checked before the scan, found
        by identity (before jnp.asarray can hand back another object);
        None for any other."""
        return self.resolved and self.resolved.lookup(h)

    def at(self, word, slot, ok):
        """Flat index of (word, local slot) where `ok`, else one past
        the end — what mode="fill" fills and mode="drop" drops."""
        from .runtime.state import pool_index
        return jnp.where(ok, pool_index(self.nslots, word, slot),
                         self.data.shape[0])

    def ordered(self, word, slot, ok, value):
        """The writes of one blob_set as (keys u32, values i32), flat and
        STRICTLY ascending by key — what a scatter flagged sorted and
        unique may be handed. A lane that writes is keyed by its flat
        index; one that does not by `len(data) + lane`, its own and past
        the end, so the dropped lanes sort behind every write and
        FILL_OR_DROP drops them. u32 holds a pool of 2^31 - 1 words plus
        the lanes.

        Writing lanes are distinct by iso ownership. A handle forged or
        copied through an untyped int breaks that, and shows after the
        sort as two equal neighbours: the lowest lane keeps the word,
        the others are re-keyed past the end and the vector is sorted
        once more — behind a cond, so an honest program pays one compare
        over the lanes."""
        from .runtime.state import pool_index
        size, n = self.data.shape[0], ok.size
        if size + n > 1 << 32:
            raise ValueError(
                f"blob_set: {n} lanes past a pool of {size} words do not "
                "fit an unsigned 32-bit key")
        past = jnp.uint32(size) + lax.iota(jnp.uint32, n)
        flat = pool_index(self.nslots, word, slot).astype(jnp.uint32)
        return _ascending(
            jnp.where(ok, flat, past.reshape(ok.shape)).reshape(-1),
            value.reshape(-1), size, past)

    def names_a_row(self, word):
        """Is `word` a Python or NumPy integer in [0, W): an index the
        trace can read, so a column or a read window can hold its row."""
        return (isinstance(word, (int, np.integer))
                and not isinstance(word, bool) and 0 <= word < self.words)

    def column_of(self, h, word):
        """The open column a blob_set of `word` to `h` folds into: `h`
        is the object its blob_alloc returned and `word` a Python or
        NumPy integer that names one of its rows; None for any other."""
        if not self.names_a_row(word):
            return None
        for col in self.columns:
            if col.handle is h:
                return col
        return None

    def flush(self):
        """Write every open column into the pool, in the order they were
        opened, and close them (and, having written, every read
        window): one column scatter each, under `dispatch/heap/set`
        whoever asks (the scope is absolute).

        The lanes are sorted by slot once a column — a lane that did not
        allocate keyed `nslots + lane`, its own and past the end, so it
        sorts behind every slot and FILL_OR_DROP drops it — carrying the
        lane, and the column's rows follow by one gather; the scatter is
        told sorted and unique, which `_ascending` makes true here as it
        does for `ordered`: two lanes handed one slot (a free list that
        names it twice) show as equal neighbours, the lowest lane keeps
        the slot whole and the others' columns are dropped."""
        if not self.columns:
            return
        from .runtime.state import phase_scope
        cols, self.columns = self.columns, []
        self.close()
        lanes = cols[0].ok.size
        past = jnp.int32(self.nslots) + lax.iota(jnp.int32, lanes)
        with phase_scope("dispatch/heap/set"):
            for col in cols:
                key, lane = _ascending(
                    jnp.where(col.ok, col.idx, past).reshape(-1),
                    lax.iota(jnp.int32, lanes), self.nslots, past)
                rows = jnp.take(
                    jnp.stack([jnp.broadcast_to(r, (lanes,))
                               for r in col.rows]), lane,
                    axis=1, mode="clip")
                self.data = lax.scatter(
                    self.data.reshape(self.words, self.nslots),
                    key[:, None], rows, _COLUMN_SCATTER,
                    indices_are_sorted=True, unique_indices=True,
                    mode=lax.GatherScatterMode.FILL_OR_DROP).reshape(-1)

    def close(self):
        """Close every read window: the pool's words, `gen` or `used`
        are about to change."""
        self.windows = []

    def windowed(self, h, word):
        """Word `word` of `h` out of its read window ([lanes] i32), or
        None where this blob_get goes the single-word way: a traced or
        out-of-range `word`, or a handle whose reads the plan gave no
        window. The first static get of a handle object opens its
        record, and — where the plan says so — checks the handle and
        gathers its rows; the later ones cost a slice of the result."""
        self.gets += 1
        if not self.names_a_row(word):
            return None
        win = next((w for w in self.windows if w.handle is h), None)
        if win is None:
            win = _Window(h)
            self.windows.append(win)
            self.read_log.append(win)
            k = len(self.read_log) - 1
            rows = self.reads[k] if k < len(self.reads) else None
            if rows is not None:
                hl, ok = self.local(h)
                ok = ok & self.live(h, hl)
                # word-major: rows 0 .. rows-1 are a prefix of the pool
                win.rows = lax.gather(
                    self.data[:rows * self.nslots].reshape(rows, self.nslots),
                    jnp.where(ok, hl, self.nslots)[..., None],
                    _WINDOW_GATHER, (rows, 1),
                    mode=lax.GatherScatterMode.FILL_OR_DROP, fill_value=0)
        win.words.append(int(word))
        # (a word past the rows the plan gave is a trace that reads
        # otherwise than its probe: it goes alone here and
        # engine._make_branch, which compares the two, raises)
        if win.rows is None or word >= win.rows.shape[0]:
            return None
        return win.rows[word]

    def read_plan(self):
        """What this evaluation's static reads are worth, a record an
        entry in the order opened: the rows of its window, or None where
        its gets go alone (window_rows, at this pool's slots a lane)."""
        slots_a_lane = self.nslots / jnp.size(self.take)
        return tuple(window_rows(w.words, slots_a_lane)
                     for w in self.read_log)

    def read_facts(self):
        """How this evaluation's blob_gets go under its own plan: the
        windows it opens, the gets they stand for, the gets that gather
        a word a lane."""
        paid = [len(w.words) for w, rows in zip(self.read_log,
                                                self.read_plan()) if rows]
        return {"windows": len(paid), "gets_windowed": sum(paid),
                "gets_alone": self.gets - sum(paid)}


class Context:
    """Per-dispatch effect collector, passed as ``self`` to behaviours.

    ≙ pony_ctx_t + the send/exit runtime entry points (pony_sendv
    actor.c:773, pony_exitcode start.c:345). All effects are masked arrays;
    the engine pads them to the type's static send budget.
    """

    __slots__ = ("actor_id", "msg_words", "sends", "exit_flag", "exit_code",
                 "yield_flag", "destroy_flag", "spawn_fail", "_spawn_resv",
                 "spawn_claims", "destroy_called", "error_flag",
                 "error_code", "error_loc", "error_called", "ref_types",
                 "_spawn_meta", "sync_inits", "_effected", "cap_moves",
                 "cap_types", "exit_called", "yield_called", "_blob",
                 "kept")

    def __init__(self, actor_id, msg_words: int, spawn_resv=None,
                 spawn_meta=None, blob=None):
        self.actor_id = actor_id          # traced i32 scalar (global id)
        self.msg_words = msg_words
        self.sends: List[Tuple[Any, Any, Any]] = []   # (target, words, when)
        self.exit_flag = jnp.bool_(False)
        self.exit_code = jnp.int32(0)
        self.yield_flag = jnp.bool_(False)
        self.destroy_flag = jnp.bool_(False)
        self.spawn_fail = jnp.bool_(False)
        self.destroy_called = False      # trace-time: did destroy() run?
        self.error_flag = jnp.bool_(False)
        self.error_code = jnp.int32(0)
        self.error_loc = jnp.int32(0)
        self.error_called = False        # trace-time: did error_int() run?
        self.exit_called = False         # trace-time: did exit() run?
        self.yield_called = False        # trace-time: did yield_() run?
        # {target type name: [n_sites] i32 reserved global ids} for this
        # dispatch; None entries = -1 (no free slot was available).
        self._spawn_resv = spawn_resv or {}
        # {target type name: [claimed refs so far]} (engine canonicalises).
        self.spawn_claims: Dict[str, List[Any]] = {
            t: [] for t in self._spawn_resv}
        # Trace-time typed-ref provenance; the engine tags the typed
        # state fields and typed args into it before dispatch.
        self.ref_types = pack.RefTypes()
        # Trace-time iso-move discipline (≙ type/alias.c consume rules).
        self.cap_moves = pack.CapMoves()
        # Capability provenance of traced values (≙ the cap half of the
        # type checker; engine tags declared Iso/Val/Tag fields + args).
        self.cap_types = pack.CapTypes()
        # {target type name: field_specs} for sync construction.
        self._spawn_meta = spawn_meta or {}
        # {target type name: {site index: (state dict, ok mask)}}.
        self.sync_inits: Dict[str, Dict[int, Any]] = {}
        self._effected = False    # trace-time: any exit()/yield_() call
        # Device blob pool view (None = pool disabled or host dispatch).
        self._blob: Optional[BlobPoolView] = blob
        # Trace-time: the state fields the behaviour handed back as the
        # very objects it was given (engine.eval_behaviour fills it).
        self.kept: frozenset = frozenset()

    # -- messaging (≙ pony_sendv, actor.c:773-834) --
    def _send_checks(self, target, behaviour_def: BehaviourDef, args):
        """Trace-time sendability + capability discipline for one send,
        shared by the real send and the verify/lint probe
        (verify._ProbeContext) so whole-program lint enforces exactly
        what the engine's trace would.

        Sendability (≙ type/safeto.c + expr/call.c): a behaviour call
        must exist on the receiver's type, and ref-typed params only
        accept matching refs. Typed provenance rides on tracer identity
        (pack.RefTypes) — a directly-forwarded typed field or argument
        is checked; derived values are untyped (gradual). Fails the
        TRACE (build time), not as a runtime badmsg."""
        owner = behaviour_def.actor_type.__name__
        tn = self.ref_types.lookup(target)
        if tn is not None and tn != owner:
            raise TypeError(
                f"sendability: ref typed Ref[{tn}] cannot receive "
                f"{owner}.{behaviour_def.name} — declare the field/arg "
                f"as Ref[{owner}] or fix the wiring")
        for spec, a in zip(behaviour_def.arg_specs, args):
            want = pack.ref_target(spec)
            got = self.ref_types.lookup(a)
            if want is not None and got is not None and got != want:
                raise TypeError(
                    f"sendability: {owner}.{behaviour_def.name} expects "
                    f"Ref[{want}] but was passed a Ref[{got}]")
        # Iso move discipline (≙ cap.c/alias.c/safeto.c consume rules):
        # a moved handle may never be used again this dispatch, and an
        # Iso-parameter send IS a move. Capability provenance must also
        # cover the parameter's declared mode (≙ is_cap_sub_cap: a
        # shared val cannot be passed where a unique iso is required).
        where = f"{owner}.{behaviour_def.name} send"
        for spec, a in zip(behaviour_def.arg_specs, args):
            if pack.concrete_null_handle(a):
                continue                  # 0/-1 sentinel: no payload
            prev = self.cap_moves.was_moved(a)
            if prev is not None:
                raise TypeError(
                    f"capability: use-after-move — payload already moved "
                    f"by {prev} is passed to {where}")
            src = self.cap_types.lookup(a)
            want = pack.cap_mode(spec)
            if not pack.cap_store_ok(src, want):
                raise TypeError(
                    f"capability: {where} declares its parameter "
                    f"{want.capitalize()} but was passed a {src} "
                    f"payload — a {src} value cannot grant the rights "
                    f"{want} requires (is_cap_sub_cap, type/cap.c)")
        for spec, a in zip(behaviour_def.arg_specs, args):
            if pack.concrete_null_handle(a):
                continue
            want = pack.cap_mode(spec)
            # The payload SHIPS whenever it rides a capability-typed
            # parameter; if the sender's value is unique (iso — by
            # declared parameter mode or by provenance), shipping it is
            # a MOVE, including the legal iso→val/tag downgrades. The
            # sender provably loses it either way.
            if want == "iso" or (want is not None
                                 and self.cap_types.lookup(a) == "iso"):
                self.cap_moves.move(a, where)

    def send(self, target, behaviour_def: BehaviourDef, *args, when=True):
        if not isinstance(behaviour_def, BehaviourDef):
            raise TypeError("second argument to send() must be a behaviour "
                            "(e.g. SomeActor.some_behaviour)")
        if behaviour_def.global_id is None:
            raise RuntimeError(
                f"{behaviour_def} not registered in a Program yet")
        self._send_checks(target, behaviour_def, args)
        payload = pack.pack_args(behaviour_def.arg_specs, args, self.msg_words)
        # Planar-aware: payload is [W] (all-constant args) or [W, R]
        # (lane vectors); the gid row matches its trailing shape.
        gid_row = jnp.full((1,) + payload.shape[1:],
                           behaviour_def.global_id, jnp.int32)
        words = jnp.concatenate([gid_row, payload], axis=0)
        self.sends.append((jnp.asarray(target, jnp.int32), words,
                           jnp.asarray(when, jnp.bool_)))

    # -- lifecycle --
    def spawn(self, ctor: BehaviourDef, *args, when=True):
        """Create an actor of the constructor's type and send it `ctor` as
        its first message (≙ pony_create, actor.c:688-734 — in Pony
        ``create`` *is* an async behaviour, so construction here is exactly
        "claim a slot, deliver the constructor message").

        Returns the new actor's ref (traced i32), usable immediately in
        this behaviour's sends/state. The spawner's class must declare
        ``SPAWNS = {TargetType: n_sites}``; slots come from the *same
        shard* as the spawner (≙ pony_create allocating on the creating
        scheduler's thread). If no free slot was available the ref is -1,
        the sticky `spawn_fail` flag raises host-side, and the masked
        constructor send drops harmlessly.
        """
        tname, ref, ok = self._claim_slot(ctor, when, "spawn")
        self.send(ref, ctor, *args, when=ok)
        # The returned ref is typed (provenance-tagged): storing it in a
        # mistyped Ref[T] field or sending it a foreign behaviour fails
        # at build.
        return self.ref_types.tag(jnp.where(ok, ref, jnp.int32(-1)), tname)

    def _claim_slot(self, ctor, when, what: str):
        """Shared spawn preamble: budget checks + slot claim bookkeeping
        (≙ pony_create's allocation, actor.c:688-734). Returns
        (target type name, reserved ref, ok mask)."""
        if not isinstance(ctor, BehaviourDef):
            raise TypeError(f"{what}() takes a constructor behaviour "
                            "(e.g. Worker.init)")
        tname = ctor.actor_type.__name__
        resv = self._spawn_resv.get(tname)
        if resv is None:
            raise RuntimeError(
                f"{tname} is not in this actor type's SPAWNS declaration; "
                f"add SPAWNS = {{{tname}: n}} to the spawning class")
        used = len(self.spawn_claims[tname])
        if used >= resv.shape[0]:
            raise RuntimeError(
                f"more than SPAWNS[{tname}]={resv.shape[0]} spawns in one "
                "behaviour dispatch; raise the declared budget")
        ref = resv[used]
        w = jnp.asarray(when, jnp.bool_)
        ok = w & (ref >= 0)
        self.spawn_claims[tname].append(jnp.where(ok, ref, jnp.int32(-1)))
        self.spawn_fail = self.spawn_fail | (w & (ref < 0))
        return tname, ref, ok

    def spawn_sync(self, ctor: BehaviourDef, *args, when=True):
        """Spawn with a SYNCHRONOUS constructor (≙ the fork's
        pony_sendv_synchronous_constructor, actor.c:836-848): the
        constructor behaviour runs *inside this dispatch* on the
        newborn's zeroed state, and the resulting fields are written when
        the slot is claimed — so same-step sends to the new ref find a
        fully constructed actor next tick, with no ordering convention.

        The constructor must be PURE construction: returning the initial
        state only. Effects inside it (send/spawn/exit/destroy/yield/
        error) raise at build — an effectful create needs the async
        `spawn`, whose constructor message is a real dispatch.
        """
        tname, ref, ok = self._claim_slot(ctor, when, "spawn_sync")
        specs = self._spawn_meta.get(tname)
        if specs is None:
            raise RuntimeError(
                "spawn_sync is only available in device behaviours")
        used = len(self.spawn_claims[tname]) - 1   # site just claimed
        self._ctor_arg_checks(ctor, args, tname)
        # Run the constructor NOW on zeroed defaults (≙ the synchronous
        # field assignment), in a throwaway context that must stay inert.
        cctx = Context(ref, self.msg_words)
        zero = {f: (jnp.int32(-1) if pack.is_ref(s) else
                    jnp.float32(0) if s is pack.F32 else jnp.int32(0))
                for f, s in specs.items()}
        st2 = ctor.fn(cctx, zero, *args)
        if st2 is None or set(st2.keys()) != set(specs.keys()):
            raise TypeError(
                f"sync constructor {ctor} must return the full state dict "
                f"({sorted(specs)})")
        if (cctx.sends or cctx.destroy_called or cctx.error_called
                or any(cctx.spawn_claims.values()) or cctx._effected):
            raise TypeError(
                f"sync constructor {ctor} performs effects; effects need a "
                "real dispatch — use ctx.spawn (async constructor message)")
        for f, s in specs.items():
            want = pack.ref_target(s)
            got = self.ref_types.lookup(st2[f])
            if want is not None and got is not None and got != want:
                raise TypeError(
                    f"sendability: sync constructor {ctor} stores a "
                    f"Ref[{got}] into field {f!r} declared Ref[{want}]")
            # Cap lattice applies to the newborn's fields too (the
            # OUTER provenance map: values flow from the spawner's
            # args/fields through the constructor).
            if pack.concrete_null_handle(st2[f]):
                continue
            src = self.cap_types.lookup(st2[f])
            dst = pack.cap_mode(s)
            if not pack.cap_store_ok(src, dst):
                raise TypeError(
                    f"capability: sync constructor {ctor} stores a "
                    f"{src} payload into field {f!r} declared "
                    f"{dst.capitalize()} — a {src} value cannot grant "
                    f"the rights {dst} requires (is_cap_sub_cap)")
            # The newborn is ANOTHER actor: a spawner-provenance value
            # landing in its fields crosses an actor boundary, so it
            # must be sendable — a trn/ref/box could otherwise smuggle
            # a write-aliased payload out (CAP_SEND, safeto.c).
            if src is not None and not pack.cap_sendable(src):
                raise TypeError(
                    f"capability: sync constructor {ctor} moves a "
                    f"{src} payload into the newborn's field {f!r} — "
                    f"{src} is not sendable; only iso/val/tag cross an "
                    "actor boundary (CAP_SEND, type/cap.c:90)")
        self.sync_inits.setdefault(tname, {})[used] = (st2, ok)
        return self.ref_types.tag(jnp.where(ok, ref, jnp.int32(-1)), tname)

    def _ctor_arg_checks(self, ctor: BehaviourDef, args, tname: str):
        """Constructor arguments obey the same sendability + capability
        rules as a send (≙ expr/call.c parameter checks): a typed ref
        arg must match, a cap-typed arg must satisfy the store lattice,
        and handing a unique to the newborn is a MOVE. Shared with the
        verify/lint probe (verify._ProbeContext.spawn_sync), which
        claims the slot but never runs the constructor."""
        where = f"{tname}.{ctor.name} spawn_sync"
        for spec, a in zip(ctor.arg_specs, args):
            want = pack.ref_target(spec)
            got = self.ref_types.lookup(a)
            if want is not None and got is not None and got != want:
                raise TypeError(
                    f"sendability: {tname}.{ctor.name} expects Ref[{want}] "
                    f"but was passed a Ref[{got}]")
            if pack.concrete_null_handle(a):
                continue
            prev = self.cap_moves.was_moved(a)
            if prev is not None:
                raise TypeError(
                    f"capability: use-after-move — payload already moved "
                    f"by {prev} is passed to {where}")
            cwant = pack.cap_mode(spec)
            src = self.cap_types.lookup(a)
            if not pack.cap_store_ok(src, cwant):
                raise TypeError(
                    f"capability: {where} declares its parameter "
                    f"{cwant.capitalize()} but was passed a {src} "
                    f"payload — a {src} value cannot grant the rights "
                    f"{cwant} requires (is_cap_sub_cap, type/cap.c)")
        for spec, a in zip(ctor.arg_specs, args):
            if pack.concrete_null_handle(a):
                continue
            cwant = pack.cap_mode(spec)
            if cwant == "iso" or (cwant is not None
                                  and self.cap_types.lookup(a) == "iso"):
                self.cap_moves.move(a, where)

    def destroy(self, when=True):
        """Mark *this* actor for destruction at the end of the step: slot
        freed, queued messages discarded, later sends dead-letter.

        The reference never destroys explicitly — ORCA/cycle GC collects
        (gc/cycle.c); this framework has that too (runtime.gc()). destroy()
        is the cheap opt-out for protocols that know their own lifetime.
        Refs held elsewhere dangle (and the slot may be reused by a later
        spawn) — the documented divergence from ORCA's safety.
        """
        self.destroy_called = True
        self.destroy_flag = self.destroy_flag | jnp.asarray(when, jnp.bool_)

    def exit(self, code=0, when=True):
        """Request program termination (≙ pony_exitcode + quiescent stop)."""
        self._effected = True
        self.exit_called = True
        w = jnp.asarray(when, jnp.bool_)
        self.exit_flag = self.exit_flag | w
        self.exit_code = jnp.where(w, jnp.asarray(code, jnp.int32),
                                   self.exit_code)

    def yield_(self, when=True):
        """Stop draining this actor's mailbox for the rest of the step
        (≙ the fork's ponyint_actor_yield, actor.c:675-679)."""
        self._effected = True
        self.yield_called = True
        self.yield_flag = self.yield_flag | jnp.asarray(when, jnp.bool_)

    def error_int(self, code, when=True):
        """Record an int-coded error on this actor (≙ the fork's
        pony_error_int / pony_error_code, pony.h:622-665 — errors are
        *values*, not unwinding). The actor keeps running (a Pony
        behaviour must handle its own errors; the code here is the
        observable residue): the latest nonzero code is queryable via
        Runtime.last_error() and surfaces in the analysis dump."""
        self.error_called = True
        # Trace-time raise site (≙ the fork's __error_loc): the Python
        # call site interns into a host-side table; the device carries
        # only the i32 site id.
        from .errors import caller_loc, register_error_site
        site = register_error_site(caller_loc())
        w = jnp.asarray(when, jnp.bool_)
        self.error_flag = self.error_flag | w
        self.error_code = jnp.where(w, jnp.asarray(code, jnp.int32),
                                    self.error_code)
        self.error_loc = jnp.where(w, jnp.int32(site), self.error_loc)

    # -- device blob pool (≙ actor-heap message payloads; see
    # ops.pack.Blob and BlobPoolView) --
    def _require_blob(self, what: str) -> "BlobPoolView":
        if self._blob is None:
            raise RuntimeError(
                f"{what}: the device blob pool is disabled — set "
                "RuntimeOptions.blob_slots and blob_words (> 0); host "
                "behaviours have no device pool")
        return self._blob

    def _blob_guard(self, h, what: str):
        """Trace-time iso discipline shared by the blob ops: touching a
        handle after it was moved (sent, or freed) is use-after-move."""
        prev = self.cap_moves.was_moved(h)
        if prev is not None:
            raise TypeError(
                f"capability: use-after-move — blob handle already moved "
                f"by {prev} is passed to {what}")

    @_heap_scoped("alloc")
    def blob_alloc(self, length=None, when=True):
        """Claim a fresh device blob; returns its handle ([lanes] i32,
        -1 where `when` is false or the pool had no free slot — the
        sticky blob-fail flag then raises host-side, like spawn_fail).
        The slot's words read 0 until set; `length` (default: the pool
        width) records the logical word count read back by
        blob_length(). The class must declare ``MAX_BLOBS = n`` (allocs
        per dispatch).

        The slot's books are written here; its words are not. The
        handle returned opens a column on the view (BlobPoolView): the
        blob_sets that follow to THIS object at static word indices
        fill it, and the payload reaches the pool whole — zeros where
        nothing was set — in one scatter, before any read of the pool,
        any other write, any free, and at the latest when the behaviour
        returns. Fill the handle you were given: a copy made by
        arithmetic (`jnp.where(c, h, -1)`) is another object, and each
        set to it flushes and scatters one word a lane.
        ≙ pony_alloc / pony_alloc_msg on the owning actor's heap."""
        b = self._require_blob("blob_alloc")
        if b.resv is None:
            raise RuntimeError(
                "blob_alloc: declare MAX_BLOBS = n on the allocating "
                "actor class (the per-dispatch alloc budget)")
        if b.claims >= b.resv.shape[0]:
            raise RuntimeError(
                f"more than MAX_BLOBS={b.resv.shape[0]} blob_alloc calls "
                "in one behaviour dispatch; raise the declared budget")
        b.close()
        slot = b.resv[b.claims]                # reserved global SLOT ids
        b.claims += 1
        w = jnp.asarray(when, jnp.bool_)
        ok = w & b.take & (slot >= 0)
        wanted = w & b.take & (slot < 0)
        # Blame the right knob: a lane whose whole reservation window
        # was withheld (dispatch count past BLOB_DISPATCHES) failed on
        # BUDGET; a lane holding a real window that still read -1 found
        # the POOL's compacted free list exhausted.
        if b.budget_over is not None:
            b.budget_fail = b.budget_fail | jnp.any(wanted & b.budget_over)
            b.fail = b.fail | jnp.any(wanted & ~b.budget_over)
        else:
            b.fail = b.fail | jnp.any(wanted)
        idx = jnp.where(ok, slot - b.base, b.nslots)  # OOB-high → dropped
        # Bump the slot generation and bake it into the handle (ABA
        # guard): any still-circulating handle from the slot's previous
        # life now mismatches and reads null.
        newgen = (jnp.take(b.gen, idx, mode="fill", fill_value=0)
                  + 1) & pack.BLOB_GEN_MASK
        b.gen = b.gen.at[idx].set(newgen, mode="drop")
        h = pack.blob_handle(slot, newgen)
        b.used = b.used.at[idx].set(True, mode="drop")
        wpool = b.words
        ln = (jnp.int32(wpool) if length is None
              else jnp.clip(jnp.asarray(length, jnp.int32), 0, wpool))
        b.len_ = b.len_.at[idx].set(
            jnp.broadcast_to(ln, idx.shape), mode="drop")
        b.n_alloc = b.n_alloc + jnp.sum(ok.astype(jnp.int32))
        b.alloced = b.alloced | ok
        h2 = jnp.where(ok, h, jnp.int32(-1))
        self.cap_types.tag(h2, "iso")
        b.columns.append(_Column(h2, idx, ok, wpool))
        return h2

    @_heap_scoped("get")
    def blob_get(self, h, i):
        """Read word `i` of blob `h` ([lanes] i32; 0 for null/-1 handles,
        out-of-range words, or handles owned by another shard). Floats:
        ``ctx.blob_get(h, i).view(jnp.float32)``.

        A payload is read whole: where a behaviour reads several words
        of the handle it was GIVEN (the same object — a copy made by
        arithmetic is another) at Python or NumPy integer indices, with
        no blob_set that scatters, blob_alloc or blob_free between
        them, the first get gathers the rows they span in one operation
        and the others read the result (BlobPoolView: the read window;
        `for i in range(W): self.blob_get(h, i)` is one gather). A
        traced `i` gathers one word a lane, as does a lone get or a
        pair too far apart to be worth the rows between them."""
        b = self._require_blob("blob_get")
        self._blob_guard(h, "blob_get")
        b.flush()
        row = b.windowed(h, i)
        if row is not None:
            return row
        hl, ok = b.local(h)
        ok = ok & b.live(h, hl)
        i = jnp.asarray(i, jnp.int32)
        ok = ok & (i >= 0) & (i < b.words)
        return jnp.take(b.data, b.at(i, hl, ok), mode="fill", fill_value=0)

    @_heap_scoped("get")
    def blob_length(self, h):
        """Logical word count recorded at blob_alloc ([lanes] i32; 0 for
        null/remote handles)."""
        b = self._require_blob("blob_length")
        self._blob_guard(h, "blob_length")
        hl, _ok = b.local(h)
        return jnp.take(b.len_, hl, mode="fill", fill_value=0)

    def blob_freeze(self, h):
        """Freeze an owned (iso) blob into shared-immutable VAL (≙
        Pony's consume-to-val — `recover val` / trn→val freeze): the
        returned handle aliases freely, so one dispatch may send it to
        MANY readers (declare the parameter ``BlobVal``); writes and
        frees reject at trace; the slot is reclaimed by the GC mark
        pass once no live field/message/host root references it.
        Idempotent on already-val handles."""
        self._require_blob("blob_freeze")
        self._blob_guard(h, "blob_freeze")
        src = self.cap_types.lookup(h)
        if src == "val":
            return h
        self.cap_types.tag(h, "val")
        return h

    @_heap_scoped("set")
    def blob_set(self, h, i, v, when=True):
        """Write word `i` of blob `h` (i32; masked by `when`). Only the
        owner holds the handle (iso), so lanes never collide; writes are
        visible to this dispatch's later blob_get calls and to the
        handle's next owner after a send. Floats: pass
        ``value.view(jnp.int32)``.

        A set to the handle a blob_alloc of this behaviour returned (the
        same object), at a Python or NumPy integer `i`, while that
        payload's column is open, lands in the column under the very
        mask below and emits no pool operation (BlobPoolView). Any other
        set flushes the open columns, closes the read windows (the get
        after it reads the pool again, and sees this set) and then
        writes the pool: the lanes
        are scattered in the order of their flat pool index and XLA is
        told so: its TPU scatter of single words is one update after
        another unless the indices are declared sorted AND unique
        (BlobPoolView.ordered makes both true)."""
        b = self._require_blob("blob_set")
        self._blob_guard(h, "blob_set")
        if self.cap_types.lookup(h) == "val":
            raise TypeError(
                "capability: blob_set on a frozen (val) blob — "
                "shared-immutable payloads cannot be written "
                "(≙ val's deny-write, type/cap.c)")
        col = b.column_of(h, i)
        if col is not None:
            if col.checked is None:
                hl, okh = b.local(h)
                col.checked = okh & b.live(h, hl)
            ok = jnp.asarray(when, jnp.bool_) & b.take & col.checked
            col.rows[i] = jnp.where(
                ok, jnp.broadcast_to(jnp.asarray(v, jnp.int32), ok.shape),
                col.rows[i])
            b.sets_folded += 1
            return
        b.flush()
        b.close()
        b.sets_alone += 1
        hl, okh = b.local(h)
        i = jnp.asarray(i, jnp.int32)
        ok = (jnp.asarray(when, jnp.bool_) & b.take & okh
              & (i >= 0) & (i < b.words) & b.live(h, hl))
        v = jnp.broadcast_to(jnp.asarray(v, jnp.int32), ok.shape)
        key, v = b.ordered(i, hl, ok, v)
        b.data = lax.scatter(
            b.data, key[:, None], v, _WORD_SCATTER, indices_are_sorted=True,
            unique_indices=True, mode=lax.GatherScatterMode.FILL_OR_DROP)

    @_heap_scoped("free")
    def blob_free(self, h, when=True):
        """Release blob `h` back to the pool. Explicit free is the fast
        path; blobs whose owner died (or whose handle moved off-shard)
        are swept by the next Runtime.gc() mark pass (≙ the owner's
        heap dying with the actor, gc.c/heap.c). Freeing is a MOVE:
        later use of the handle in this dispatch is rejected at trace.
        A free closes every read window (BlobPoolView): a forged alias
        of the freed slot reads 0 afterwards, as it would word by
        word."""
        b = self._require_blob("blob_free")
        self._blob_guard(h, "blob_free")
        if self.cap_types.lookup(h) == "val":
            raise TypeError(
                "capability: blob_free on a frozen (val) blob — shared "
                "payloads have no single owner to free them; the GC "
                "mark pass reclaims unreferenced val blobs")
        b.flush()
        b.close()
        h = jnp.asarray(h, jnp.int32)
        hl, okh = b.local(h)
        ok = jnp.asarray(when, jnp.bool_) & b.take & okh & b.live(h, hl)
        idx = jnp.where(ok, hl, b.nslots)           # OOB-high → dropped
        b.frees += 1
        b.used = b.used.at[idx].set(False, mode="drop")
        b.len_ = b.len_.at[idx].set(0, mode="drop")
        b.n_free = b.n_free + jnp.sum(ok.astype(jnp.int32))
        self.cap_moves.move(h, "blob_free")
