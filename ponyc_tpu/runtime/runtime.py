"""Host driver: runtime construction, spawning, host↔device messaging and
the run-to-quiescence loop.

≙ the reference's runtime bootstrap and lifecycle
(src/libponyrt/sched/start.c: pony_init parses flags and sizes the world,
pony_start runs schedulers until quiescence, pony_get_exitcode returns the
program's code) plus the host side of actor creation
(pony_create, actor/actor.c:688-734) and external sends (pony_sendv from
non-actor context).

The host loop is deliberately thin: it issues ONE fused device dispatch
per iteration (engine.build_multi_step_gated — a lax.while_loop advancing
up to `quiesce_interval` ticks that self-terminates the moment host
attention is needed), then reads back a few scalars to decide termination —
the TPU analog of the CNF/ACK quiescence vote (scheduler.c:303-480).
Host-resident actors (HOST=True types — the main-thread/ASIO-side actors
of the reference, scheduler.c:179-190, asio/asio.c) are drained at those
window boundaries; the early window stop keeps their reaction latency at
one tick, as if steps were dispatched singly.
"""

from __future__ import annotations

import collections
import functools
import os
import signal
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..api import ActorTypeMeta, BehaviourDef
from ..config import RuntimeOptions
from ..errors import ERROR_CODES, PonyError, PonyStallError, error_code
from ..ops import pack
from ..program import Program
from . import engine
from .controller import WindowController
from .state import LIST_COUNTERS, RtState, init_state, pool_index


# The run loop's phases (ISSUE 24). One mechanism does three things at a
# phase boundary: the watchdog stamp (flight.py), a span on the
# profiler's clock (`pony:<phase>`, in the same .xplane.pb as the device
# operations) and the seconds the phase took (run_loop_stats()
# ["phase_s"], self time: a child's seconds are not its parent's too).
# The top-level phases cover the whole of run(): enter, dispatching,
# wait, host-work, quiescent, exit. The children of host-work — outbox
# (host-cohort mail), pollers, gc, checkpoint, analysis — leave the
# watchdog's stamp as it is: the watchdog's vocabulary does not change.
PHASE_STAMPS = {"dispatching": "dispatching", "wait": "in-flight",
                "host-work": "host-work", "quiescent": "quiescent"}
# Outside run() (ISSUE 35) every public call that touches the device is
# a phase of the same mechanism (`_api_phase`): start, spawn, set-fields,
# bulk-send, blob-store, blob-fetch, read (state columns and the other
# array reads), counter (one scalar), stop. They stamp nothing, so the
# watchdog reads what it read; called from a host behaviour inside run()
# they nest under outbox like any child. send() only appends to a host
# deque and stays bare. dispatching and the host-work family stay
# LEAVES of the run loop's own making: benchmarks/phase_trace.py sums a
# window's host cost from their self times by name.
API_PHASES = ("start", "spawn", "set-fields", "bulk-send", "blob-store",
              "blob-fetch", "read", "counter", "stop")
RUN_PHASES = ("enter", "dispatching", "wait", "host-work", "outbox",
              "pollers", "gc", "checkpoint", "analysis", "quiescent",
              "exit") + API_PHASES
_API_PHASES = frozenset(API_PHASES)


@functools.partial(jax.jit, static_argnames=("bw", "bsl", "run"))
def _fetch_blobs(data, slots, *, bw: int, bsl: int, run: bool):
    """Every word of the pool's global `slots`, [len(slots), bw], from
    the flat blob_data. `run`: the slots are adjacent and of one shard,
    so each word is one slice. Compiled once a shape (a whole-table read
    in blocks calls it block after block)."""
    shard, local = slots // bsl, slots % bsl
    words = jnp.arange(bw, dtype=jnp.int32)
    if run:
        first = shard[0] * (bw * bsl) + pool_index(bsl, words, local[0])
        got = jax.vmap(lambda a: jax.lax.dynamic_slice(
            data, (a,), slots.shape))(first)
    else:
        got = data[shard[None, :] * (bw * bsl) + pool_index(
            bsl, words[:, None], local[None, :])]
    return got.T


class _PhaseSpan:
    """One phase as a context (`Runtime._phase`). With no profiler
    session it costs two clock reads, a list push and pop and two dict
    adds (seconds and calls; a third for a phase of API_PHASES); the
    annotation is built only while one is recording."""

    __slots__ = ("rt", "name", "meta", "note", "t0", "child_s")

    def __init__(self, rt, name, meta):
        self.rt, self.name, self.meta = rt, name, meta
        self.note = None
        self.child_s = 0.0

    def __enter__(self):
        rt = self.rt
        stamp = PHASE_STAMPS.get(self.name)
        if stamp is not None:
            rt._stamp(stamp)
        if TraceAnnotation.is_enabled():
            self.note = TraceAnnotation("pony:" + self.name, **self.meta)
            self.note.__enter__()
        rt._phase_stack.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        rt = self.rt
        stack = rt._phase_stack
        while stack and stack.pop() is not self:
            pass        # an interrupt may have left a child behind
        own = dt - self.child_s
        rt._phase_s[self.name] += own
        rt._phase_n[self.name] += 1
        if self.name in _API_PHASES:
            # itemised, in ms, for the next window record's outside_ms
            out = rt._rl_outside
            out[self.name] = out.get(self.name, 0.0) + own * 1e3
        if stack:
            stack[-1].child_s += dt
        if self.note is not None:
            self.note.__exit__(*exc)
        return False


def _api_phase(name: str, meta=None):
    """Make a public Runtime method the phase `name` (API_PHASES): the
    span `pony:<name>` while a profiler session records, its self
    seconds and calls in run_loop_stats()["phase_s"] / ["phase_n"]
    always. `meta(self, *args, **kw)` gives the span's meta (count=,
    blobs=, words=) and is evaluated only while a session records."""
    def decorate(fn):
        @functools.wraps(fn)
        def call(self, *args, **kw):
            m = meta(self, *args, **kw) \
                if meta is not None and TraceAnnotation.is_enabled() else {}
            with _PhaseSpan(self, name, m):
                return fn(self, *args, **kw)
        return call
    return decorate


class SpillOverflowError(RuntimeError):
    """The bounded overflow spill was exceeded — raise mailbox_cap or
    spill_cap, or let backpressure mute faster (lower overload_threshold)."""

    code = ERROR_CODES["SpillOverflowError"]


class AmbientAuth:
    """Root authority (≙ env.root: AmbientAuth). Obtained only from
    Runtime.ambient_auth(); narrower capability tokens check for it.
    The sentinel token (same pattern as files.FilesAuth) makes direct
    construction impossible, so holding `rt` alone does not mint it."""

    _token = object()

    def __init__(self, rt, token=None):
        if token is not AmbientAuth._token:
            raise PermissionError(
                "obtain AmbientAuth via rt.ambient_auth(), not directly")
        self._rt = rt


class SpawnCapacityError(RuntimeError):
    """A device-side ctx.spawn() wanted a slot but its cohort window had
    none free — raise the target cohort's declared capacity (or let GC /
    destroy() return slots faster)."""

    code = ERROR_CODES["SpawnCapacityError"]


class BlobCapacityError(RuntimeError):
    """A device-side ctx.blob_alloc() wanted a pool slot but its window
    had none free — raise RuntimeOptions.blob_slots, or free blobs
    (ctx.blob_free) faster. ≙ pony_alloc exhausting the heap."""

    code = ERROR_CODES["BlobCapacityError"]


class HostContext:
    """Effect collector for host-resident behaviours (≙ running an actor on
    the main-thread scheduler, scheduler.c:1030-1035)."""

    def __init__(self, rt: "Runtime", actor_id: int):
        self.rt = rt
        self.actor_id = actor_id
        self.exit_flag = False
        self.exit_code = 0
        self.yield_flag = False
        self.trace_ctx = None   # (trace_id, span_id) of this dispatch
        #   when causal tracing followed the message here — sends
        #   below continue the chain (PROFILE.md §10)

    def send(self, target, behaviour_def, *args, when=True):
        if when:
            self.rt.send(int(target), behaviour_def, *args,
                         trace=self.trace_ctx)

    def exit(self, code=0, when=True):
        if when:
            self.exit_flag = True
            self.exit_code = int(code)

    def yield_(self, when=True):
        if when:
            self.yield_flag = True


def _host_pack_args(specs, args, msg_words):
    words = np.zeros((msg_words,), np.int32)
    if len(args) != len(specs):
        raise TypeError(f"behaviour takes {len(specs)} args, got {len(args)}")
    off = 0
    for spec, v in zip(specs, args):
        if isinstance(spec, pack._VecSpec):
            dt = np.float32 if spec.base is pack.F32 else np.int32
            arr = np.asarray(v, dt).reshape(-1)
            if arr.shape[0] != spec.n:
                raise TypeError(f"argument for {spec.__name__} must have "
                                f"{spec.n} elements, got {arr.shape[0]}")
            words[off:off + spec.n] = arr.view(np.int32)
            off += spec.n
        elif spec is pack.F32:
            words[off] = np.float32(v).view(np.int32)
            off += 1
        elif spec is pack.Bool:
            words[off] = np.int32(bool(v))
            off += 1
        elif spec is pack.U32:
            words[off] = np.asarray(v, np.int64).astype(
                np.uint32).view(np.int32)
            off += 1
        elif spec in pack._NARROW_JNP:
            dt = pack.narrow_np_map()[spec]
            # astype wraps out-of-range values to the declared width
            # (np scalar constructors would raise instead).
            words[off] = np.asarray(v, np.int64).astype(dt).astype(np.int32)
            off += 1
        else:
            words[off] = np.int32(v)
            off += 1
    return words


def _host_unpack_args(specs, words):
    out = []
    off = 0
    for spec in specs:
        if isinstance(spec, pack._VecSpec):
            blk = np.asarray(words[off:off + spec.n], np.int32)
            out.append(blk.view(np.float32) if spec.base is pack.F32
                       else blk)
            off += spec.n
            continue
        w = np.int32(words[off])
        off += 1
        if spec is pack.F32:
            out.append(float(w.view(np.float32)))
        elif spec is pack.Bool:
            out.append(bool(w))
        elif spec is pack.U32:
            out.append(int(w.view(np.uint32)))
        elif spec in pack._NARROW_JNP:
            out.append(int(w.astype(pack.narrow_np_map()[spec])))
        else:
            out.append(int(w))
    return tuple(out)


class Runtime:
    """A live actor world bound to one program layout.

    Typical use::

        rt = Runtime(opts)
        rt.declare(RingNode, 1024)
        rt.start()                       # ≙ pony_init: freeze + allocate
        refs = rt.spawn_many(RingNode, next_ref=..., passes=...)
        rt.send(refs[0], RingNode.token, 1000)
        code = rt.run()                  # ≙ pony_start: run to quiescence
    """

    def __init__(self, opts: Optional[RuntimeOptions] = None):
        self._opts_defaulted = opts is None
        self.opts = opts or RuntimeOptions()
        self.program = Program(self.opts)
        self.state: Optional[RtState] = None  # via the property below
        self._step = None
        self._inject_q: collections.deque = collections.deque()
        # Host fast lane (opts.host_fastpath): host-sender → host-target
        # messages, dispatched at host boundaries without touching the
        # device mailbox table (≙ inject_main, scheduler.c:179-190).
        self._host_fast_q: collections.deque = collections.deque()
        # Device-pool blob handles the HOST currently owns (blob_store
        # not yet sent/freed) — GC roots for the blob sweep (gc.py).
        self._host_blobs: set = set()
        self._free: Dict[str, List[int]] = {}
        self._host_state: Dict[int, Dict[str, Any]] = {}
        self._exit_code = 0
        self._exit_requested = False
        self._device_dirty = True     # force the first window of a run
        self._idle_boundaries = 0     # lifetime skipped host-only
        #   boundaries; feeds the cd_interval GC cadence so host-heavy
        #   phases still collect (steps_run freezes while skipping)
        self._noisy = 0          # ≙ asio noisy_count keeping runtime alive
        self._bridge_pollers: List[Any] = []   # asio backends (bridge/)
        self.steps_run = 0
        self.totals = collections.Counter()    # lifetime stats (host ints)
        # Host-cohort behaviour runs by global id (the host twin of the
        # device beh_runs matrix — host behaviours dispatch here, so the
        # device counters never see them; profile() merges both).
        self._beh_host_runs: collections.Counter = collections.Counter()
        self._last_counters: Dict[str, int] = {}
        self._gc_fn = None
        self._freelist_key = None   # None = stale; "synced" = cache valid
        self._ref_mask = None
        self._ever_released = False
        self._last_gc_step = 0
        self._next_gc = self.opts.gc_initial   # ≙ heap.c next_gc
        # Row pressure (programs with device spawns; StepAux.spawn): the
        # least room any tick has left, and the step of the last pass it
        # asked for (one pass an aux: a gated-out window hands the same
        # aux back).
        self._free_rows_low = 2**31 - 1
        # The pool's books as the windows' aux brought them (StepAux.
        # pool): slots claimed and released up to the last retired
        # window, Python integers summed from mod-2^32 differences (the
        # device's own count stands at the first window).
        self._pool_books: Dict[str, int] = {}
        self._row_gc_step = -1
        self._spawned_at_gc = 0     # device spawns when the last pass ran
        self._row_bytes = None      # see _spawned_bytes
        self._host_errors: Dict[int, int] = {}
        self._host_error_locs: Dict[int, str] = {}
        self._tracer = None      # tracing.Tracer, set by start() when
        #   opts.tracing (analysis >= 3 and trace_sample > 0)
        self.tuning_record: Optional[Dict[str, Any]] = None   # set by
        #   start() when quiesce_interval is "auto":
        #   {"quiesce_interval": {source (cache/default), initial,
        #   bounds}} (tuning.resolve_quiesce_interval)
        # ---- adaptive run loop (PROFILE.md §9) ----
        self._controller: Optional[WindowController] = None  # window
        #   sizer, created at start() (fixed lo==hi when
        #   quiesce_interval is a concrete int)
        self._qi_auto = False         # quiesce_interval was "auto"
        self._qi_loaded = 0           # the initial window resolve() gave
        self._state_epoch = 0         # monotonic state-write stamp: the
        #   pipelined retire clears _device_dirty only when NO host
        #   write landed since that window's dispatch (a write after
        #   dispatch is invisible to the window's aux)
        self._last_retire_t: Optional[float] = None
        self._rl_tile_t: Optional[float] = None   # the newest flight
        #   window record's retire: where the next record's time starts.
        #   NOT reset at run() entry, so the time between two run()
        #   calls has an owner (since_prev_ms)
        self._rl_seq = 0              # windows dispatched: the `window`
        #   the spans of one window share
        self._rl_retired_seq = 0      # ... of the newest retired one
        self._rl_wall_ns = 0          # sum of the windows' wall_ms
        self._phase_s = dict.fromkeys(RUN_PHASES, 0.0)
        self._phase_n = dict.fromkeys(RUN_PHASES, 0)
        self._phase_stack: List[_PhaseSpan] = []
        self._rl_outside: Dict[str, float] = {}   # ms by API phase
        #   since the newest window record's retire: the next sync-point
        #   window's outside_ms (the itemisation of its since_prev_ms)
        self._cold_window = True      # _multi_g has not run since
        #   start(): its next launch traces, lowers and compiles or
        #   reloads (the run loop calls no other executable)
        self._cold_s = 0.0            # ... what those launches took,
        self._cold_n = 0              #   and how many there were
        # Run-loop telemetry (run_loop_stats()): windows retired, how
        #   many dispatches rode behind an in-flight window, cumulative
        #   host-imposed device-idle gap, re-queued gated-out injects.
        self._rl_windows = 0
        self._rl_pipelined = 0
        self._rl_synced = 0
        self._rl_gap_ns = 0
        self._rl_requeued = 0
        # ---- operational observability (PROFILE.md §11) ----
        self._flight = None           # flight.FlightRecorder (start())
        self._watchdog = None         # flight.Watchdog when watchdog_s
        self._metrics = None          # metrics.MetricsServer when
        #   metrics_port is not None
        self._ckpt = None             # serialise.Checkpointer when
        #   checkpoint_every_s is set (durable worlds, PROFILE.md §12)
        self._costs = None            # costs.capture memo — measured
        #   cost/memory analysis of the compiled executables (ISSUE 19)
        # costs.window_symbols: each launched program's first launch as
        # shapes + shardings (taken at the cold launch), its `Compiled`
        # and its symbol table, by costs.LAUNCHED's names
        self._launch_specs: Dict[str, Any] = {}
        self._compiled: Dict[str, Any] = {}
        self._symbols: Dict[str, list] = {}
        self._last_run_crashed = False  # run() exited exceptionally:
        #   stop() must NOT overwrite the ring's newest snapshot with
        #   the post-crash world (the supervisor restores the last
        #   intact PRE-crash checkpoint)
        self._wd_epoch = 0            # phase-stamp progress counter
        self._wd_stamp = ("idle", 0, time.monotonic())  # (phase,
        #   epoch, t): one tuple assignment per transition — the cheap
        #   progress evidence the watchdog thread reads
        # Coded runtime errors raised/caught on this runtime, keyed
        # (class_name, int code) — the errors.ERROR_CODES metrics label
        # and the postmortem's error section.
        self._error_counts: collections.Counter = collections.Counter()
        self._last_aux = None         # newest RETIRED window's host-side
        #   StepAux (numpy scalars): the zero-extra-fetch telemetry feed
        #   for edge consumers — the serving tier's admission controller
        #   (serve.py) reads qw_p99/n_muted_now here
        self._serve = None            # serve.Server when a front door is
        #   attached (metrics/flight surface the serving block)

    # Any state assignment — including a caller handing back what
    # rt._step returned — conservatively invalidates the cached
    # freelists; internal writers that provably keep them consistent
    # restore _freelist_key after assigning.
    @property
    def state(self) -> Optional[RtState]:
        return self._state

    @state.setter
    def state(self, v) -> None:
        self._state = v
        self._freelist_key = None
        # Any host-side state write may have created device work the
        # last window's aux cannot know about (bulk_send's direct
        # mailbox writes, restore(), flag flips) — the run loop's
        # host-only-boundary skip must not trust stale quiescence.
        self._device_dirty = True
        # Write stamp for the pipelined run loop: a window's aux is
        # authoritative at retire only if this counter still matches
        # its at-dispatch value (no write raced the in-flight window).
        self._state_epoch = getattr(self, "_state_epoch", 0) + 1

    # ---- construction (≙ pony_init) ----
    def declare(self, atype: ActorTypeMeta, capacity: int) -> "Runtime":
        self.program.declare(atype, capacity)
        return self

    @_api_phase("start", lambda self: {"shards": self.program.shards})
    def start(self) -> "Runtime":
        # ≙ pony_init, split so the operational pieces (the always-on
        # flight recorder + optional stall watchdog, PROFILE.md §11)
        # arm BEFORE the first device-touching call: a backend init
        # that never returns then trips the watchdog — postmortem on
        # disk, int-coded PonyStallError raised — instead of hanging
        # forever.
        self._apply_defaults_and_pin()
        from .. import flight as _flight
        self._flight = _flight.FlightRecorder(
            self, self.opts.flight_windows)
        self._stamp("backend-init")
        if self.opts.watchdog_s is not None:
            self._watchdog = _flight.Watchdog(self, self.opts.watchdog_s)
            self._watchdog.start()
        try:
            self._start_world()
        except KeyboardInterrupt:
            stall = self._stall_from_interrupt()
            if stall is not None:
                raise stall from None
            raise
        if self.opts.cost_capture:
            # Record XLA's own cost/memory analysis of the just-built
            # executables so the postmortem and every metrics scrape
            # carry it. Opt-in: it AOT-compiles step+window once more
            # (lower() only — the world does not advance).
            from .. import costs as _costs
            _costs.capture(self, force=True)
        if self.opts.metrics_port is not None:
            from .. import metrics as _metrics
            self._metrics = _metrics.MetricsServer(
                self, self.opts.metrics_port)
            self._metrics.update_now(self)
        if self.opts.checkpoint_every_s is not None:
            from .. import serialise as _serialise
            self._ckpt = _serialise.Checkpointer(self)
        self._stamp("idle")
        return self

    def _apply_defaults_and_pin(self) -> None:
        # ≙ Main_runtime_override_defaults_oo (start.c:99,214): a declared
        # actor type may override runtime defaults — applied only when the
        # caller didn't pass explicit options (explicit flags win, exactly
        # like the reference's CLI > Main-override > default ordering).
        if self._opts_defaulted:
            import dataclasses as _dc
            overrides = {}
            for atype, _cap in self.program._declared:
                overrides.update(getattr(atype, "RUNTIME_DEFAULTS", {}))
            if overrides:
                self.opts = _dc.replace(self.opts, **overrides)
                self.program.opts = self.opts
                self.program.shards = max(1, self.opts.mesh_shards)
        if self.opts.pin >= 0:   # ≙ --ponypin (start.c:75-94): pin the
            # host driver thread (the "scheduler" of this runtime)
            try:
                self._pre_pin_affinity = os.sched_getaffinity(0)
                os.sched_setaffinity(0, {self.opts.pin})
            except OSError as e:
                raise ValueError(
                    f"cannot pin host thread to core {self.opts.pin}: "
                    f"{e}") from None

    def _start_world(self) -> None:
        # Persistent compile cache (tuning.enable_compile_cache): lands
        # before the first jit of this runtime so warm starts reload
        # executables instead of re-lowering.
        from .. import tuning
        tuning.enable_compile_cache(self.opts.compile_cache)
        self.program.finalize()
        self.state = init_state(self.program, self.opts)
        if self.program.shards > 1:
            from ..parallel.mesh import make_mesh, shard_state
            self.mesh = make_mesh(self.program.shards)
            self.state = shard_state(self.state, self.mesh)
        else:
            self.mesh = None
        # A requested kernel that cannot serve this program is an error
        # HERE, before the first trace.
        engine.check_kernels(self.program, self.opts)
        # Adaptive quiesce window (runtime/controller.py): resolve the
        # "auto" initial value through the tuning cache (a previous
        # run's converged window for this layout), then hand the bounds
        # to the controller. A concrete int pins lo == hi — the fixed
        # pre-adaptive window through the same code path.
        qi = self.opts.quiesce_interval
        self._qi_auto = qi == "auto"
        if self._qi_auto:
            qi, qi_rec = tuning.resolve_quiesce_interval(
                self.program, self.opts)
            lo = self.opts.quiesce_interval_min
            hi = self.opts.quiesce_interval_max
            self.tuning_record = {"quiesce_interval": qi_rec}
        else:
            qi = max(1, int(qi))
            lo = hi = qi
        self._qi_loaded = qi
        self._controller = WindowController(qi, lo, hi)
        import dataclasses as _dc
        self.opts = _dc.replace(self.opts, quiesce_interval=qi)
        self.program.opts = self.opts
        self._step = engine.jit_step(self.program, self.opts, self.mesh)
        # The window (tick 0 gated on-device by the previous window's
        # aux): the one program the run loop launches.
        self._multi_g = engine.jit_multi_step_gated(
            self.program, self.opts, self.mesh)
        self._cold_window = True
        self._zero_aux = engine.zero_aux(self.program)
        # Inject buffers carry the trace side lanes when causal tracing
        # is on (two trailing rows: trace_id, parent_span — PROFILE §10).
        w1 = 1 + self.opts.msg_words + self.opts.trace_lanes
        k = self.opts.inject_slots
        self._empty_inject = (jnp.full((k,), -1, jnp.int32),
                              jnp.zeros((w1, k), jnp.int32))
        if self.opts.tracing:
            from ..tracing import Tracer
            self._tracer = Tracer(
                self.opts.trace_sample, self.opts.trace_seed,
                beh_names=[f"{b.actor_type.__name__}.{b.name}"
                           for b in self.program.behaviour_table])
        else:
            self._tracer = None
        for cohort in self.program.cohorts:
            self._free[cohort.atype.__name__] = list(
                range(cohort.capacity - 1, -1, -1))

    # ---- spawning (≙ pony_create, actor.c:688-734) ----
    def spawn(self, atype: ActorTypeMeta, **fields) -> int:
        return int(self.spawn_many(atype, 1, **{
            k: np.asarray([v]) for k, v in fields.items()})[0])

    @_api_phase("spawn", lambda self, atype, count, **_f:
                {"count": int(count)})
    def spawn_many(self, atype: ActorTypeMeta, count: int,
                   **fields) -> np.ndarray:
        """Allocate `count` slots of a cohort and set initial state columns.

        Field values may be scalars (broadcast) or [count] arrays. Returns
        the global actor ids. This is the host-side mass-create path the
        benchmarks use (the reference creates actors one pony_create at a
        time; batch creation is the idiomatic TPU equivalent).
        """
        if self.state is None:
            raise RuntimeError("call start() before spawn()")
        cohort = self.program.by_type[atype]
        unknown = set(fields) - set(atype.field_specs)
        if unknown:
            raise TypeError(f"{atype.__name__} has no fields {unknown}")
        self._check_ref_fields(atype, fields)
        self._move_blob_fields(atype, fields)
        if not cohort.host and (self.program.has_device_spawns
                                or self.steps_run):
            # Device-side spawn/destroy/GC may have claimed or freed slots
            # behind the host freelist's back. Sync from device truth at
            # most once per world mutation (the state setter invalidates
            # _freelist_key): a setup loop of spawn calls with no steps in
            # between pays one device fetch, not one per call.
            if self._freelist_key is None:
                self._rebuild_freelists()
        fkey = self._freelist_key
        free = self._free[atype.__name__]
        if len(free) < count:
            raise RuntimeError(
                f"cohort {atype.__name__} capacity exhausted "
                f"({cohort.capacity} declared)")
        slots = np.array([free.pop() for _ in range(count)], np.int32)
        ids = np.asarray(cohort.slot_to_gid(slots), np.int32)
        cols = np.asarray(cohort.slot_to_col(slots), np.int32)
        if cohort.host:
            for i, gid in enumerate(ids):
                st = {}
                for fname in atype.field_specs:
                    default = (-1 if pack.is_ref(atype.field_specs[fname])
                               else 0)
                    v = fields.get(fname, default)
                    v = np.asarray(v)
                    st[fname] = v.reshape(-1)[i % max(v.size, 1)].item() \
                        if v.ndim else v.item()
                self._host_state[int(gid)] = st
        else:
            ts = dict(self.state.type_state[atype.__name__])
            for fname, spec in atype.field_specs.items():
                if fname in fields:
                    val = jnp.asarray(fields[fname]).astype(ts[fname].dtype)
                    val = jnp.broadcast_to(val, (count,) if val.ndim == 0
                                           else val.shape)
                else:
                    # Reused slots must not leak a previous life's state.
                    val = jnp.full((count,),
                                   pack.null_word(spec),
                                   ts[fname].dtype)
                ts[fname] = ts[fname].at[cols].set(val)
            new_ts = dict(self.state.type_state)
            new_ts[atype.__name__] = ts
            self.state = self._replace(type_state=new_ts)
        self.state = self._replace(
            alive=self.state.alive.at[ids].set(True),
            # The caller now holds these refs: GC roots until release().
            pinned=self.state.pinned.at[ids].set(True))
        # Our own pops/sets kept the cached freelists consistent.
        self._freelist_key = fkey
        return ids

    def _rebuild_freelists(self) -> None:
        """Refresh every device cohort's freelist from device truth.

        A slot is free only if it is dead, its queue is drained, AND no
        message addressed to it is parked in either spill tier — the same
        free_ok condition the device spawn path enforces (spawn.free_mask, step
        1b). Reclaiming a row with a stale spilled message would deliver a
        previous life's message to the newborn."""
        st = self.state
        alive, head, tail, dsp, rsp = (
            np.asarray(x) for x in jax.device_get(
                (st.alive, st.head, st.tail, st.dspill_tgt, st.rspill_tgt)))
        n = self.program.total
        nl = self.program.n_local
        s_cap = self.opts.spill_cap
        spill_hit = np.zeros((n,), bool)
        shard = np.arange(dsp.shape[0]) // s_cap   # dspill targets: local
        ok = dsp >= 0
        spill_hit[shard[ok] * nl + dsp[ok]] = True
        ok = (rsp >= 0) & (rsp < n)                # rspill targets: global
        spill_hit[rsp[ok]] = True
        free_ok = ~alive & (tail - head == 0) & ~spill_hit
        for cohort in self.program.cohorts:
            if cohort.host:
                continue
            # Highest slot first, matching the initial freelist order.
            all_slots = np.arange(cohort.capacity - 1, -1, -1)
            gids = np.asarray(cohort.slot_to_gid(all_slots))
            self._free[cohort.atype.__name__] = [
                int(s) for s, g in zip(all_slots, gids) if free_ok[g]]
        self._freelist_key = "synced"

    # ---- GC pinning (≙ ORCA's external rc: an actor is born with one
    # reference owned by its creator, actor.c:688-734) ----
    def _set_flag_column(self, column: str, ids, value: bool) -> None:
        """Set a per-actor bool flag column host-side. Flag flips never
        affect slot freedom, so the spawn freelist cache survives."""
        ids = np.asarray(ids, np.int32).reshape(-1)
        fkey = self._freelist_key
        col = getattr(self.state, column)
        self.state = self._replace(**{column: col.at[ids].set(value)})
        self._freelist_key = fkey

    def release(self, ids) -> None:
        """Drop the host's reference(s): the actors become collectable as
        soon as they are unreachable and message-quiet (gc.py)."""
        self._set_flag_column("pinned", ids, False)
        self._ever_released = True

    def pin(self, ids) -> None:
        """(Re-)pin actors as host-held GC roots."""
        self._set_flag_column("pinned", ids, True)

    def apply_backpressure(self, ids) -> None:
        """Mark actors UNDER_PRESSURE (≙ pony_apply_backpressure,
        src/libponyrt/actor/actor.c:1137-1162): senders to these actors
        mute on send until release_backpressure(), regardless of mailbox
        occupancy — the hook for pressure the runtime cannot see (a
        stalled socket, a full external queue). stdlib/backpressure.py
        wraps this with the reference package's auth-token surface."""
        self._set_flag_column("pressured", ids, True)
        # Raise the mesh-wide "any pressure" gate bit on every shard so
        # the next tick's (otherwise-skipped) pressured all_gather runs;
        # the per-tick vote keeps it honest from then on (engine.vote).
        self.state = self._replace(world_bits=self.state.world_bits | 1)

    def release_backpressure(self, ids) -> None:
        """Clear UNDER_PRESSURE (≙ pony_release_backpressure); muted
        senders release on the next unmute pass once the receiver is
        also under the occupancy threshold."""
        self._set_flag_column("pressured", ids, False)

    def gc(self) -> int:
        """Run one collection: trace reachability from the roots, free
        everything unreached (≙ ORCA + the cycle detector in one pass —
        see gc.py). Returns the number of actors collected."""
        if self.state is None:
            raise RuntimeError("call start() first")
        if self._gc_fn is None:
            from . import gc as gc_mod
            self._gc_fn = gc_mod.jit_gc(self.program, self.opts, self.mesh)
            self._ref_mask = gc_mod.build_ref_arg_mask(
                self.program, self.opts.msg_words)
            self._blob_mask = gc_mod.build_blob_arg_mask(
                self.program, self.opts.msg_words)
        # Host-side roots: refs in host-actor state dicts and in pending
        # inject messages (they will reach the device eventually).
        extra = np.zeros((self.program.total,), bool)
        for aid, stt in self._host_state.items():
            cohort = self.program.cohort_of(aid)
            for fname, spec in cohort.atype.field_specs.items():
                if pack.is_ref(spec):
                    v = int(stt.get(fname, -1))
                    if 0 <= v < self.program.total:
                        extra[v] = True
        import itertools
        n_blob_total = self.program.shards * self.opts.blob_slots
        blob_roots = np.zeros((n_blob_total,), bool)
        for h in self._host_blobs:
            slot = pack.blob_slot(int(h))
            if h >= 0 and 0 <= slot < n_blob_total:
                blob_roots[slot] = True
        for t, w, *_ in itertools.chain(self._inject_q,
                                        self._host_fast_q):
            if 0 <= t < self.program.total:
                extra[t] = True
            gid = int(w[0])
            if 0 <= gid < self._ref_mask.shape[0]:
                for i in np.nonzero(self._ref_mask[gid])[0]:
                    v = int(w[1 + i])
                    if 0 <= v < self.program.total:
                        extra[v] = True
                for i in np.nonzero(self._blob_mask[gid])[0]:
                    v = int(w[1 + i])
                    slot = pack.blob_slot(v)
                    if v >= 0 and 0 <= slot < n_blob_total:
                        blob_roots[slot] = True
        args = (self.state, jnp.asarray(extra), jnp.asarray(blob_roots))
        if "gc" not in self._launch_specs:
            from .. import costs as _costs
            self._launch_specs["gc"] = _costs.launch_specs(*args)
        self.state, facts = self._gc_fn(*args)
        # the pass's answer, waited for here: its seconds are this
        # phase's (`gc`), not the next counter read's
        n, converged, iters, n_swept, free_before, born = map(
            int, jax.device_get(facts))
        self.totals["gc_runs"] += 1
        self._spawned_at_gc = born & 0xFFFFFFFF
        if TraceAnnotation.is_enabled():
            # the pass's outcome, known only now: a zero-length child
            # that closes the `pony:gc` span it ran under
            with TraceAnnotation("pony:gc", collected=n, hops=iters,
                                 free_before=free_before):
                pass
        # GC window stats for the profiler (analysis.window / profile()):
        # passes run, trace iterations, blob slots reclaimed; actors
        # collected ride the device n_collected counter.
        self.totals["gc_iters"] += iters
        self.totals["gc_swept_blobs"] += n_swept
        if not converged:
            self.totals["gc_aborted"] += 1
        if self._flight is not None:
            self._flight.event("gc", collected=n, iters=iters,
                               swept=n_swept, converged=bool(converged),
                               free_before=free_before)
        # Growth-triggered accounting reset (≙ heap.c's next_gc update
        # after a collection) — here so every collection path, manual
        # included, clears the allocation-pressure signal consistently.
        heap = getattr(self, "_heap", None)
        if heap is not None:
            heap.bytes_since_gc = 0
            self._next_gc = max(self.opts.gc_initial,
                                int(heap.bytes_live * self.opts.gc_factor))
        return n

    def _replace(self, **kw) -> RtState:
        import dataclasses as _dc
        return _dc.replace(self.state, **kw)

    @_api_phase("set-fields", lambda self, atype, ids, **_f:
                {"count": int(np.size(ids))})
    def set_fields(self, atype: ActorTypeMeta, ids, **fields):
        """Overwrite state columns for existing actors (host-side poke,
        e.g. wiring refs once ids are known). ids are global actor ids."""
        cohort = self.program.by_type[atype]
        self._check_ref_fields(atype, fields)
        self._move_blob_fields(atype, fields)
        if cohort.host:
            for i, aid in enumerate(np.asarray(ids).reshape(-1)):
                st = self._host_state.setdefault(int(aid), {})
                for fname, v in fields.items():
                    v = np.asarray(v).reshape(-1)
                    st[fname] = v[i % v.size].item()
            return
        cols = jnp.asarray(cohort.gid_to_col(np.asarray(ids)))
        ts = dict(self.state.type_state[atype.__name__])
        for fname, v in fields.items():
            col = ts[fname]
            val = jnp.asarray(v).astype(col.dtype)
            ts[fname] = col.at[cols].set(val)
        new_ts = dict(self.state.type_state)
        new_ts[atype.__name__] = ts
        fkey = self._freelist_key
        self.state = self._replace(type_state=new_ts)
        self._freelist_key = fkey   # column writes don't affect freedom

    # ---- sendability checks (capability-lite; ≙ type/safeto.c +
    # expr/call.c: a send must name a behaviour the receiver's type has,
    # and Ref[T]-typed slots may only hold ids of T's cohort). Device-side
    # wiring is verified at trace time (engine._make_branch /
    # api.Context.send); these are the host-boundary twins. Out-of-range
    # ids stay permissive — they dead-letter on device, as documented. ----
    def _check_send_target(self, target: int, bdef: BehaviourDef) -> None:
        if 0 <= target < self.program.total:
            owner = self.program.cohort_of(int(target)).atype.__name__
            want = bdef.actor_type.__name__
            if owner != want:
                raise TypeError(
                    f"sendability: actor {target} is a {owner}; it cannot "
                    f"receive {want}.{bdef.name}")

    def _check_ids_in_cohort(self, v, want: str, what: str) -> None:
        """Vectorised membership: every in-world id in `v` must fall in
        cohort `want`'s rows. Cohorts are contiguous per-shard local-row
        ranges (shard-major slots), so this is two compares on id % nl —
        array speed even for benchmark-scale wiring."""
        v = np.asarray(v, np.int64).reshape(-1)
        nl = self.program.n_local
        c = self.program.by_type_name(want)
        lid = v % max(nl, 1)
        bad = ((v >= 0) & (v < self.program.total)
               & ((lid < c.local_start) | (lid >= c.local_stop)))
        if bad.any():
            x = int(v[bad][0])
            owner = self.program.cohort_of(x).atype.__name__
            raise TypeError(
                f"sendability: {what} expects Ref[{want}] but id {x} "
                f"is a {owner}")

    def _check_ref_args(self, specs, args, what: str) -> None:
        for spec, v in zip(specs, args):
            want = pack.ref_target(spec)
            if want is not None:
                self._check_ids_in_cohort(v, want, what)

    def _check_ref_fields(self, atype: ActorTypeMeta, fields) -> None:
        for fname, v in fields.items():
            want = pack.ref_target(atype.field_specs.get(fname))
            if want is not None:
                self._check_ids_in_cohort(
                    v, want, f"field {atype.__name__}.{fname}")

    def _check_host_iso_blob(self, h: int) -> None:
        """An iso Blob handle leaving the host must be host-OWNED
        (present in _host_blobs): blob_store() mints ownership, host
        delivery of an iso Blob arg transfers it. Anything else —
        double-send, a stale handle, a forged int — is an aliased move,
        rejected loudly like HostHeap.send_iso and the device trace's
        use-after-move (null/-1 rides freely)."""
        if h >= 0 and h not in self._host_blobs:
            from ..hostmem import CapabilityError
            raise CapabilityError(
                f"capability: aliased move — iso blob handle {h} is not "
                "owned by the host (already sent, freed, or never "
                "obtained via blob_store/host delivery); an iso is "
                "moved-unique — use a BlobVal parameter for shared "
                "payloads")

    # ---- external sends (≙ pony_sendv from outside the runtime) ----
    def _trace_context(self, trace):
        """Resolve a send's causal-trace context to (trace_id,
        parent_span) or (-1, 0) (untraced). `trace` spellings: None =
        the deterministic sampler decides (1-in-trace_sample); an int =
        an explicit caller trace id (the bridge/ingress tier tying a
        socket request to its device spans — always traced, root span
        get-or-created); a (trace_id, span_id) tuple = continue an
        existing span (host-behaviour propagation)."""
        tr = self._tracer
        if tr is None:
            return -1, 0
        step = self.steps_run
        if isinstance(trace, tuple):
            return int(trace[0]), int(trace[1])
        if trace is not None:
            tid = int(trace)
            return tid, tr.root_span(tid, step)
        if tr.sample():
            return tr.begin(step)
        return -1, 0

    def send(self, target: int, behaviour_def: BehaviourDef, *args,
             trace=None):
        if behaviour_def.global_id is None:
            raise RuntimeError(f"{behaviour_def} not part of this program")
        self._check_send_target(int(target), behaviour_def)
        self._check_ref_args(behaviour_def.arg_specs, args,
                             f"{behaviour_def.actor_type.__name__}."
                             f"{behaviour_def.name}")
        tlanes = self.opts.trace_lanes
        words = np.zeros((1 + self.opts.msg_words + tlanes,), np.int32)
        words[0] = behaviour_def.global_id
        words[1:1 + self.opts.msg_words] = _host_pack_args(
            behaviour_def.arg_specs, args, self.opts.msg_words)
        tctx = None
        if tlanes:
            tid, psid = self._trace_context(trace)
            words[-2], words[-1] = tid, psid
            if tid >= 0:
                tctx = (tid, psid)
        # Iso payload discipline at the host boundary (≙ the gc.c send
        # handler moving ownership with the message): mark the handle in
        # flight — peeking it now is use-after-send, re-sending it is an
        # aliased move (hostmem.HostHeap). AFTER packing validated, so a
        # failed send can never poison the handle.
        heap = getattr(self, "_heap", None)
        if heap is not None:
            for spec, a in zip(behaviour_def.arg_specs, args):
                # Blob handles share the iso MODE but live in the device
                # pool, not the HostHeap — their move discipline is the
                # trace/device side (api.BlobPoolView), never send_iso.
                if (pack.cap_mode(spec) == "iso"
                        and not pack.is_blob(spec) and int(a) > 0):
                    heap.send_iso(int(a))
        if self.opts.blob_slots > 0:
            # A sent ISO blob handle is MOVED off the host: it stops
            # being a GC root here (the in-flight message keeps it
            # alive until the receiver owns it — gc.py's marks). A VAL
            # (shared) handle ALIASES: the host keeps its root until
            # rt.blob_release(h), so it can keep sending/fetching it.
            # Moving a handle the host does NOT own (double-send, stale
            # or forged int) is an aliased move — loud, matching
            # HostHeap.send_iso and the device path's use-after-move
            # (every legitimately host-sendable iso blob is in
            # _host_blobs: blob_store() puts it there, and host
            # delivery of an iso Blob arg transfers it there).
            for spec, a in zip(behaviour_def.arg_specs, args):
                if pack.is_blob(spec) and not pack.is_blob_val(spec):
                    self._check_host_iso_blob(int(a))
                    self._host_blobs.discard(int(a))
        # Host senders (the API and host behaviours both run here) to
        # host targets take the fast lane; everything else rides the
        # device inject path. Per-sender-pair FIFO holds: a given
        # sender's messages to a given receiver always take ONE lane.
        if (self.opts.host_fastpath
                and 0 <= int(target) < self.program.total
                and self.program.cohort_of(int(target)).host):
            # Fast-lane messages never touch the device, so the trace
            # context rides the queue entry instead of word lanes.
            self._host_fast_q.append((int(target), words, tctx))
        else:
            self._inject_q.append((int(target), words))

    @_api_phase("bulk-send", lambda self, targets, *_a, **_k:
                {"count": int(np.size(targets))})
    def bulk_send(self, targets, behaviour_def: BehaviourDef, *arg_cols,
                  trace=None):
        """Mass-enqueue one message per (distinct) target directly into the
        device mailboxes — the setup path for benchmark-scale seeding
        (injecting 1M messages through the per-step inject buffer would
        take thousands of steps). Targets must be unique within one call.

        `trace` (causal tracing on only): an explicit caller trace id —
        every seeded message joins that trace (one root, N branches;
        the ingress tier's batched-request hook). None = untraced (the
        sampler never fires here: sampling one message of a bulk seed
        would attribute the whole batch's cost to it).
        """
        targets = np.asarray(targets, np.int64)
        if len(np.unique(targets)) != len(targets):
            raise ValueError("bulk_send targets must be distinct; use "
                             "send() for repeated targets")
        self._check_ids_in_cohort(
            targets, behaviour_def.actor_type.__name__,
            f"bulk_send target of {behaviour_def.actor_type.__name__}."
            f"{behaviour_def.name}")
        self._check_ref_args(behaviour_def.arg_specs, arg_cols,
                             f"{behaviour_def.actor_type.__name__}."
                             f"{behaviour_def.name}")
        # ISO blob columns MOVE off the host exactly like send() args
        # (the handles stop being GC roots; in-flight mailbox words keep
        # the blobs alive until the receivers own them); VAL columns
        # alias — the host keeps its roots until rt.blob_release. Same
        # ownership check as send(): moving a handle the host does not
        # own raises before any column is consumed.
        if self.opts.blob_slots > 0:
            for spec, col in zip(behaviour_def.arg_specs, arg_cols):
                if pack.is_blob(spec) and not pack.is_blob_val(spec):
                    for a in np.asarray(col).reshape(-1):
                        self._check_host_iso_blob(int(a))
            for spec, col in zip(behaviour_def.arg_specs, arg_cols):
                if pack.is_blob(spec) and not pack.is_blob_val(spec):
                    for a in np.asarray(col).reshape(-1):
                        self._host_blobs.discard(int(a))
        k = len(targets)
        words = np.zeros((k, 1 + self.opts.msg_words), np.int32)
        words[:, 0] = behaviour_def.global_id
        specs = behaviour_def.arg_specs
        if len(arg_cols) != len(specs):
            raise TypeError(
                f"behaviour takes {len(specs)} args, got {len(arg_cols)}")
        off = 1
        for spec, col in zip(specs, arg_cols):
            col = np.asarray(col)
            if isinstance(spec, pack._VecSpec):
                # One [count, n] column block per vector argument; the
                # layout is validated, not reinterpreted — a transposed
                # block would silently interleave components otherwise.
                if col.shape != (k, spec.n):
                    raise TypeError(
                        f"bulk_send column for {spec.__name__} must have "
                        f"shape ({k}, {spec.n}), got {col.shape}")
                dt = np.float32 if spec.base is pack.F32 else np.int32
                blk = np.ascontiguousarray(col.astype(dt))
                words[:, off:off + spec.n] = blk.view(np.int32)
                off += spec.n
            elif spec is pack.F32:
                words[:, off] = col.astype(np.float32).view(np.int32)
                off += 1
            else:
                words[:, off] = col.astype(np.int32)
                off += 1
        # Per-cohort mailbox tables (state.py): all targets live in ONE
        # cohort (checked above); write its table at its own depth and
        # width (the packed words beyond it are zeros by construction —
        # this behaviour's args fit the cohort's width). Advanced
        # indices (slot, col) pair up, the word axis rides.
        cname = behaviour_def.actor_type.__name__
        cohort = self.program.by_type_name(cname)
        tail = self.state.tail
        t_at = np.asarray(tail[targets])
        occ = t_at - np.asarray(self.state.head[targets])
        if (occ >= cohort.mailbox_cap).any():
            full = targets[occ >= cohort.mailbox_cap]
            raise RuntimeError(
                f"bulk_send would overflow {len(full)} full mailbox(es) "
                f"(first target {int(full[0])}); drain with run() first or "
                "raise mailbox_cap")
        slot = t_at % cohort.mailbox_cap
        cols = np.asarray(cohort.gid_to_col(targets))
        w1c = 1 + cohort.msg_words
        new_cbuf = self.state.buf[cname].at[slot, :, cols].set(
            jnp.asarray(words[:, :w1c]))
        extra = {}
        if self._tracer is not None:
            # Stamp (or CLEAR — ring slots are recycled, a stale lane
            # would adopt a previous message's trace) the trace side
            # lanes for every written slot.
            lanes = np.full((k, 2), -1, np.int32)
            lanes[:, 1] = 0
            if trace is not None:
                tid = int(trace)
                lanes[:, 0] = tid
                lanes[:, 1] = self._tracer.root_span(tid, self.steps_run)
            extra["trace_buf"] = {
                **self.state.trace_buf,
                cname: self.state.trace_buf[cname].at[slot, :, cols].set(
                    jnp.asarray(lanes))}
        if cname in self.state.qwait_enq:
            # Profiler enqueue stamp (analysis >= 1): bulk_send bypasses
            # the in-step delivery that normally writes it, so stamp the
            # current tick here — queue-wait deltas for host-seeded
            # messages then measure from the seeding boundary.
            extra["qwait_enq"] = {
                **self.state.qwait_enq,
                cname: self.state.qwait_enq[cname].at[slot, cols].set(
                    jnp.int32(self.steps_run))}
        self.state = self._replace(
            buf={**self.state.buf, cname: new_cbuf},
            tail=tail.at[targets].add(1), **extra)

    def _drain_inject(self):
        tgt, words, _consumed = self._drain_inject_tracked()
        return tgt, words

    def _drain_inject_tracked(self):
        """Like _drain_inject, but also returns the consumed (target,
        words) pairs IN ORDER, so the pipelined run loop can re-queue
        them verbatim when a gated-out window (ticks_run == 0) never
        applied its injections."""
        if not self._inject_q:
            return (*self._empty_inject, [])
        k = self.opts.inject_slots
        w1 = 1 + self.opts.msg_words + self.opts.trace_lanes
        tgt = np.full((k,), -1, np.int32)
        words = np.zeros((w1, k), np.int32)   # planar: word-major
        # Host-side flow control: at most one drain-batch per target per
        # step, so a burst (e.g. timer events queued during a long XLA
        # compile) can never outrun the receiver and trip the bounded
        # device spill. Held-back messages keep their per-target FIFO
        # order in the deque — the host queue is the unbounded tier the
        # reference gets from pool-backed mailboxes (messageq.c).
        taken: Dict[int, int] = {}
        quota: Dict[int, int] = {}
        held: List[Any] = []
        consumed: List[Any] = []
        i = 0
        while i < k and self._inject_q:
            t, w = self._inject_q.popleft()
            q = quota.get(t)
            if q is None:
                # Out-of-world targets have no cohort: any batch quota
                # works — the device path drops them (sends stay
                # permissive out of range; they dead-letter, as
                # _check_send_target documents).
                q = quota[t] = (self.program.cohort_of(t).batch
                                if 0 <= t < self.program.total
                                else self.opts.batch)
            c = taken.get(t, 0)
            if c >= q:
                held.append((t, w))
                continue
            taken[t] = c + 1
            consumed.append((t, w))
            tgt[i] = t
            words[:, i] = w
            i += 1
        self._inject_q.extendleft(reversed(held))
        return jnp.asarray(tgt), jnp.asarray(words), consumed

    # ---- asio bridge hooks (≙ asio/asio.c noisy accounting) ----
    def add_noisy(self):
        self._noisy += 1

    def remove_noisy(self):
        self._noisy = max(0, self._noisy - 1)

    def register_poller(self, poller):
        """poller.poll(rt) is called at every host boundary; it may inject
        messages (timers/sockets/stdin — the bridge package uses this)."""
        self._bridge_pollers.append(poller)

    def attach_bridge(self):
        """Create (once) and register the ASIO bridge for this runtime
        (≙ ponyint_asio_start, asio/asio.c:47-56)."""
        if getattr(self, "bridge", None) is None:
            from ..bridge import Bridge
            self.bridge = Bridge(self)
            self.register_poller(self.bridge)
        return self.bridge

    def attach_net(self):
        """Create (once) the TCP/UDP layer (≙ packages/net over
        lang/socket.c) on top of the bridge."""
        if getattr(self, "net", None) is None:
            from ..net import Net
            self.net = Net(self)
        return self.net

    def attach_resolver(self):
        """Create (once) the async DNS resolver (≙ the addrinfo surface
        of lang/socket.c, delivered as actor messages)."""
        if getattr(self, "resolver", None) is None:
            from ..net.dns import Resolver
            self.resolver = Resolver(self)
        return self.resolver

    def attach_processes(self):
        """Create (once) the child-process monitor (≙ packages/process
        over lang/process.c)."""
        if getattr(self, "procs", None) is None:
            from ..process import Processes
            self.procs = Processes(self)
        return self.procs

    @property
    def heap(self):
        """Host object heap for rich message payloads (hostmem.py)."""
        h = getattr(self, "_heap", None)
        if h is None:
            from ..hostmem import HostHeap
            h = self._heap = HostHeap()
        return h

    def files_auth(self):
        """Root file-system capability (≙ env.root AmbientAuth handed to
        the Main actor; see files.py)."""
        from ..files import FilesAuth
        return FilesAuth(FilesAuth._token)

    def ambient_auth(self) -> "AmbientAuth":
        """The root authority object (≙ env.root: AmbientAuth,
        packages/builtin/ambient_auth.pony). Narrower tokens —
        stdlib.backpressure.ApplyReleaseBackpressureAuth,
        stdlib.signals auth, capsicum rights — derive from it so a
        library can be handed only the power it needs."""
        return AmbientAuth(self, AmbientAuth._token)

    # ---- host-cohort dispatch (≙ main-thread scheduler path; on a mesh,
    # each shard's host-row tail range is gathered and drained here — the
    # multi-chip analog of inject_main, scheduler.c:179-190) ----
    @property
    def _host_rows(self) -> np.ndarray:
        """Global ids of all host-cohort mailbox rows (every shard's tail
        range), cached after start()."""
        rows = getattr(self, "_host_rows_cache", None)
        if rows is None:
            fh, nl = self.program.first_host_row, self.program.n_local
            p = self.program.shards
            rows = np.concatenate(
                [s * nl + np.arange(fh, nl) for s in range(p)]) \
                if fh < nl else np.zeros((0,), np.int64)
            self._host_rows_cache = rows
        return rows

    def _drain_host(self) -> bool:
        rows = self._host_rows
        if rows.size == 0:
            return False
        rows_j = jnp.asarray(rows)
        head = np.asarray(self.state.head[rows_j])
        tail = np.asarray(self.state.tail[rows_j])
        pending = tail - head
        if not pending.any():
            return False
        # Per-cohort mailbox tables: fetch each HOST cohort's table once
        # (at its own width) and read messages via cohort-local columns.
        host_bufs: Dict[str, np.ndarray] = {}
        host_tbufs: Dict[str, np.ndarray] = {}   # trace side lanes
        new_head = head.copy()
        for i in np.nonzero(pending)[0]:
            aid = int(rows[int(i)])
            cohort = self.program.cohort_of(aid)
            cname = cohort.atype.__name__
            cbuf = host_bufs.get(cname)
            if cbuf is None:
                cbuf = host_bufs[cname] = np.asarray(
                    self.state.buf[cname])       # [cap, w1_c, capacity]
                if self._tracer is not None:
                    host_tbufs[cname] = np.asarray(
                        self.state.trace_buf[cname])  # [cap, 2, cap_c]
            col = int(cohort.gid_to_col(aid))
            consumed = 0
            for k in range(int(pending[i])):
                slot = (head[i] + k) % cohort.mailbox_cap
                msg = cbuf[slot, :, col]
                tctx = None
                if self._tracer is not None:
                    tlane = host_tbufs[cname][slot, :, col]
                    if int(tlane[0]) >= 0:
                        tctx = (int(tlane[0]), int(tlane[1]))
                consumed += 1
                ctx = self._dispatch_host_msg(aid, cohort, int(msg[0]),
                                              msg[1:], trace_ctx=tctx)
                if ctx is not None and ctx.yield_flag:
                    break
            new_head[i] = head[i] + consumed
        self.state = self._replace(
            head=self.state.head.at[rows_j].set(jnp.asarray(new_head)))
        return True

    def _dispatch_host_msg(self, aid: int, cohort, gid: int, payload,
                           trace_ctx=None):
        """Dispatch ONE message to a host-resident actor — shared by the
        device-mailbox drain above and the fast lane below so their
        semantics (iso receive, PonyError residue, exit/yield flags,
        counters) cannot drift. Returns the HostContext, or None for a
        badmsg. `trace_ctx` = the message's (trace_id, parent_span)
        when causal tracing followed it here: the dispatch becomes a
        HOST span and the behaviour's sends continue the chain."""
        bdef = (self.program.behaviour_table[gid]
                if 0 <= gid < len(self.program.behaviour_table)
                else None)
        if bdef is None or bdef.actor_type is not cohort.atype:
            self.totals["badmsg"] += 1
            return None
        ctx = HostContext(self, aid)
        if trace_ctx is not None and self._tracer is not None:
            tid, psid = trace_ctx
            sid = self._tracer.host_span(tid, psid, gid, aid,
                                         self.steps_run)
            ctx.trace_ctx = (tid, sid)
        st = self._host_state.get(aid, {})
        args = _host_unpack_args(bdef.arg_specs, payload)
        heap = getattr(self, "_heap", None)
        if heap is not None:
            # Delivery completes the iso move: the receiver may
            # peek/unbox now (≙ the gc.c recv handler).
            for spec, a in zip(bdef.arg_specs, args):
                if pack.cap_mode(spec) == "iso" and int(a) > 0:
                    heap.receive(int(a))
        if self.opts.blob_slots > 0:
            # An iso Blob delivered to a HOST actor completes its move
            # HERE: the host now owns the handle (GC root; legitimately
            # re-sendable — _check_host_iso_blob accepts it).
            for spec, a in zip(bdef.arg_specs, args):
                if (pack.is_blob(spec) and not pack.is_blob_val(spec)
                        and int(a) >= 0):
                    self._host_blobs.add(int(a))
        if self._flight is not None:
            # Recent-host-mail lane of the black box (bounded ring).
            self._flight.mail(aid, f"{cohort.atype.__name__}."
                                   f"{bdef.name}")
        try:
            st2 = bdef.fn(ctx, st, *args)
        except PonyError as e:
            # ≙ a behaviour-local `try...else` (fork int-coded
            # errors): record the code, actor continues.
            self._host_errors[aid] = e.code
            self._host_error_locs[aid] = e.loc
            self.totals["host_errors"] += 1
            self._error_counts[("PonyError", e.code)] += 1
            st2 = st
        self._host_state[aid] = st2 if st2 is not None else st
        self.totals["host_processed"] += 1
        if self.opts.analysis >= 1:
            self._beh_host_runs[int(gid)] += 1
        if ctx.exit_flag:
            self._exit_code = ctx.exit_code
            self._exit_requested = True
        return ctx

    def _drain_host_fast(self, budget: int) -> bool:
        """Dispatch queued fast-lane messages (host→host sends) up to
        `budget`; leftovers keep the run loop busy. A target with no
        host state was never spawned — dead-letter, matching the device
        path's to-dead drop."""
        q = self._host_fast_q
        if not q:
            return False
        n = 0
        yielded = set()      # actors that yield_()ed: stop their batch
        held = []            # their remaining messages, order preserved
        while q and n < budget:
            aid, w, tctx = q.popleft()
            if aid in yielded:
                held.append((aid, w, tctx))
                continue
            n += 1
            if aid not in self._host_state:
                self.totals["deadletter_host"] += 1
                continue
            cohort = self.program.cohort_of(aid)
            ctx = self._dispatch_host_msg(
                aid, cohort, int(w[0]),
                w[1:1 + self.opts.msg_words], trace_ctx=tctx)
            if ctx is not None and ctx.yield_flag:
                # ≙ the device drain honouring yield mid-batch
                # (actor.c:675-679): this actor processes nothing more
                # this boundary; its queue order is preserved.
                yielded.add(aid)
            if self._exit_requested:
                break
        q.extendleft(reversed(held))
        return True

    # ---- the run loop (≙ pony_start → scheduler run → quiescence) ----
    #
    # PIPELINED + ADAPTIVE since PROFILE.md §9: the loop keeps ONE
    # window in flight and dispatches the next one BEHIND it before
    # fetching its aux, so the host boundary (outbox drain, host
    # behaviours, pollers, GC cadence, the analysis writer) overlaps
    # device compute instead of serialising against it. Exactness is
    # the device's job, not the host's: the speculative window's tick 0
    # is gated ON DEVICE by the in-flight window's aux
    # (engine.build_multi_step_gated), so when the one-window-stale aux
    # turns out to demand host attention — host mail, exit, fatal
    # flags, or quiescence — the speculated window is an identity pass
    # (0 ticks, aux passed through, injections re-queued) and the loop
    # falls back to the synchronous confirm dispatch. A "quiet" vote
    # therefore never terminates the run unless no tick ran after it —
    # the CNF/ACK semantics (scheduler.c:303-480) are unchanged and the
    # differential/FIFO oracles hold message-for-message
    # (tests/test_run_loop.py proves it against the forced synchronous
    # loop). Window length adapts via self._controller
    # (runtime/controller.py): grow on full-budget quiet windows,
    # shrink on host-attention cuts and queue-wait p99 pressure.

    def _stamp(self, phase: str) -> None:
        """Advance the watchdog phase stamp (flight.py): one int bump +
        one tuple assignment, readable atomically from any thread. The
        run loop stamps every phase transition (dispatching / in-flight
        / host-work / quiescent / idle), so 'no stamp within the
        deadline' is exactly 'no progress'."""
        self._wd_epoch += 1
        self._wd_stamp = (phase, self._wd_epoch, time.monotonic())

    def _phase(self, name: str, **meta) -> _PhaseSpan:
        """`with self._phase("dispatching", window=n): ...` — the
        phase as a nested context (see RUN_PHASES above): stamps the
        watchdog where the phase is one of its own, is the profiler span
        `pony:<name>` carrying `meta` (window=<sequence number>,
        ticks=<k> where known, cold=1 on a first launch), and adds its
        self seconds to run_loop_stats()["phase_s"] and one call to
        ["phase_n"]."""
        return _PhaseSpan(self, name, meta)

    def _fatal(self, exc):
        """Record a coded runtime error (metrics label + postmortem
        evidence) on its way out; returns `exc` so raise sites stay
        one-liners."""
        self._error_counts[(type(exc).__name__, error_code(exc))] += 1
        if self._flight is not None:
            self._flight.event("error", cls=type(exc).__name__,
                               code=error_code(exc), message=str(exc))
        return exc

    def _stall_from_interrupt(self):
        """A pending KeyboardInterrupt may be the watchdog's doing
        (flight.Watchdog.trip interrupts the main thread after dumping
        the postmortem): convert it to the int-coded stall error, or
        return None for a genuine Ctrl-C."""
        wd = self._watchdog
        if wd is None or wd.tripped is None:
            return None
        t = wd.tripped
        return self._fatal(PonyStallError(
            f"runtime stalled: phase {t['phase']!r} made no progress "
            f"for {t['age_s']}s (deadline {t['deadline_s']}s; "
            f"postmortem: {t.get('postmortem') or '(unwritten)'})",
            phase=t["phase"], postmortem=t.get("postmortem", "")))

    def _defer_signals(self):
        """Block SIGINT/SIGTERM delivery across the donation-critical
        dispatch region: `self._multi_g` consumes (donates) the current
        state buffers, so an interrupt raised between the call and the
        state re-assignment would leave self.state pointing at deleted
        buffers — the classic donated-buffer-reuse crash. Blocked
        signals deliver the instant the mask is restored (a Ctrl-C
        still lands within one dispatch call). Returns the previous
        mask, or None where masking is unavailable."""
        try:
            return signal.pthread_sigmask(
                signal.SIG_BLOCK, {signal.SIGINT, signal.SIGTERM})
        except (AttributeError, ValueError, OSError):
            return None

    def _restore_signals(self, prev) -> None:
        if prev is not None:
            signal.pthread_sigmask(signal.SIG_SETMASK, prev)

    def _dispatch_window(self, budget: int, force: bool, prev_aux,
                         pipelined: bool) -> Dict[str, Any]:
        """Dispatch one gated window and start the non-blocking host
        copy of its control scalars; returns the in-flight record for
        _retire_window. `pipelined` windows ride behind an unretired
        one (gate live, host exposed no device idle); sync-point
        windows are accounted against the host gap — the wall time
        from the previous retire to this dispatch's START (from then on
        the window is the device's; the call itself may run the compute
        inline on XLA:CPU's synchronous path, which must not read as
        host-imposed idle), the quantity
        benchmarks/layer_metrics/host_gap_pct.py reads.

        The first launch since start() traces, lowers and compiles or
        reloads the window: its span carries cold=1 and its seconds go
        to run_loop_stats()["cold_dispatch_s"]. The span stays a LEAF
        (see API_PHASES above)."""
        now = time.perf_counter()
        if pipelined:
            self._rl_pipelined += 1
            gap_ns = 0      # dispatched while the previous window ran
            outside: Dict[str, float] = {}
        else:
            self._rl_synced += 1
            gap_ns = 0 if self._last_retire_t is None else \
                max(0, int((now - self._last_retire_t) * 1e9))
            # what the API phases took since the newest record's retire
            outside, self._rl_outside = self._rl_outside, {}
        self._rl_seq += 1
        seq = self._rl_seq
        cold = self._cold_window
        meta = {"window": seq, "shards": self.program.shards}
        if cold:
            meta["cold"] = 1
        with _PhaseSpan(self, "dispatching", meta):
            inj_t, inj_w, consumed = self._drain_inject_tracked()
            mask = self._defer_signals()
            args = (self.state, inj_t, inj_w, jnp.int32(max(1, budget)),
                    np.bool_(force), prev_aux)
            if cold:
                from .. import costs as _costs
                self._launch_specs["window"] = _costs.launch_specs(*args)
            try:
                st2, aux, kdev = self._multi_g(*args)
                self.state = st2
                epoch = self._state_epoch
            finally:
                self._restore_signals(mask)
        dispatch_s = time.perf_counter() - now
        if cold:
            self._cold_window = False
            self._cold_s += dispatch_s
            self._cold_n += 1
        # From here the window is the device's: the watchdog deadline
        # now covers device completion, not host dispatch latency.
        self._stamp("in-flight")
        # Start the device→host DMA of the control scalars now; the
        # retire's device_get then waits on data already in motion
        # instead of issuing the request after the window completes.
        for leaf in jax.tree.leaves((aux, kdev)):
            try:
                leaf.copy_to_host_async()
            except AttributeError:
                pass
        return {"aux": aux, "k": kdev, "budget": int(budget),
                "consumed": consumed, "gap_ns": gap_ns, "epoch": epoch,
                "pipelined": pipelined, "seq": seq, "t_dispatch": now,
                "dispatch_s": dispatch_s, "outside": outside}

    def _retire_window(self, win: Dict[str, Any]):
        """Fetch an in-flight window's (ticks_run, aux) and fold it into
        host accounting. A gated-out window (0 ticks) changed nothing:
        its injections go back to the FRONT of the queue in order, and
        no counters/controller/analysis state moves. Returns (k, aux as
        host scalars)."""
        seq = win["seq"]
        t_wait = time.perf_counter()
        with self._phase("wait", window=seq):
            k, a = jax.device_get((win["k"], win["aux"]))
        now = self._last_retire_t = time.perf_counter()
        self._rl_retired_seq = seq
        k = int(k)
        if a.spawn:
            self._free_rows_low = min(self._free_rows_low,
                                      int(a.spawn["low"]))
        # The fetch returned: the device answered, the host boundary
        # work for this window starts now (watchdog phase evidence).
        with self._phase("host-work", window=seq, ticks=k):
            if k == 0:
                if win["consumed"]:
                    self._inject_q.extendleft(reversed(win["consumed"]))
                    self._rl_requeued += len(win["consumed"])
                return 0, a
            self._account_window(win, k, a, t_wait, now)
        return k, a

    def _account_window(self, win: Dict[str, Any], k: int, a,
                        t_wait: float, now: float) -> None:
        """_retire_window's host accounting for a window that ran k > 0
        ticks and whose fetch began at t_wait and returned at now."""
        # The window just observed (and advanced) true device state;
        # its aux is authoritative for the quiescence-skip decision
        # UNLESS a host-side write landed after its dispatch (the
        # epoch moved) — such a write is invisible to this aux.
        if self._state_epoch == win["epoch"]:
            self._device_dirty = False
        self._last_aux = a
        self.steps_run += k
        if self.opts.debug_checks:
            self.check_invariants()
        # aux counters are cumulative int32; accumulate mod-2^32
        # deltas so fetch cadence doesn't matter (< 2^31 events per
        # window).
        for key, cur in (("processed", int(a.n_processed) & 0xFFFFFFFF),
                         ("delivered", int(a.n_delivered) & 0xFFFFFFFF)):
            last = self._last_counters.get(key, 0)
            self.totals[key] += (cur - last) & 0xFFFFFFFF
            self._last_counters[key] = cur
        for key, leaf in a.pool.items():    # cumulative, as above
            have = self._pool_books.get(key, 0)
            self._pool_books[key] = have + ((int(leaf) - have) & 0xFFFFFFFF)
        self._rl_windows += 1
        self._rl_gap_ns += win["gap_ns"]
        # Where this window's wall clock went. The records of
        # consecutive windows tile the clock: a sync-point window owns
        # [previous retire, its dispatch) as since_prev and [its
        # dispatch, its retire) as wall; a pipelined one was dispatched
        # behind the previous window, so since_prev is 0 and its wall
        # counts from the previous retire.
        # outside_ms itemises since_prev: the API phases (a counter()
        # read, a cohort_state()) that ran in it. The first record has no
        # record before it, so set-up's calls are in phase_s alone.
        tile = self._rl_tile_t
        outside = win["outside"] if tile is not None else {}
        if win["pipelined"] and tile is not None:
            since_prev, t_from = 0.0, tile
        else:
            t_from = win["t_dispatch"]
            since_prev = 0.0 if tile is None else max(0.0, t_from - tile)
        wall = max(0.0, now - t_from)
        self._rl_tile_t = now
        self._rl_outside = {}
        self._rl_wall_ns += int(wall * 1e9)
        # Controller: a full-budget exit with no host attention grows
        # the window; a host-attention cut (or queue-wait pressure via
        # the qw_p99 aux lane) shrinks it; early quiescence holds.
        attention = bool(a.host_pending) or bool(a.exit_flag) \
            or bool(a.spill_overflow) or bool(a.spawn_fail) \
            or bool(a.blob_fail) or bool(a.blob_budget_fail)
        self._controller.observe(k, win["budget"], attention,
                                 qw_p99=int(a.qw_p99))
        # Flight recorder (PROFILE.md §11): the black box retains this
        # window's already-fetched control scalars — host ints only,
        # one bounded-deque append; no extra device traffic.
        if self._flight is not None:
            self._flight.window(self.steps_run, k, win["budget"],
                                win["gap_ns"] / 1e3, win["pipelined"], a,
                                wall_ms=wall * 1e3,
                                wait_ms=min(wall, now - t_wait) * 1e3,
                                since_prev_ms=since_prev * 1e3,
                                dispatch_ms=win["dispatch_s"] * 1e3,
                                outside_ms=outside)
        if getattr(self, "_analysis", None) is not None:
            with self._phase("analysis", window=win["seq"]):
                self._analysis.window(a, ticks=k,
                                      gap_us=win["gap_ns"] / 1e3)
        if self._metrics is not None:
            self._metrics.maybe_update(self)

    def _spawned_bytes(self, a) -> int:
        """Bytes of actor memory (mailbox ring + fields, the leanest
        spawn target's) the device has spawned since the last pass: what
        a pass could at most find to free."""
        if self._row_bytes is None:
            self._row_bytes = min(
                4 * (tc.mailbox_cap * (1 + tc.msg_words)
                     + len(tc.atype.field_specs))
                for tc in map(self.program.by_type_name,
                              self.program.spawn_target_names))
        born = (int(a.spawn["spawned"]) - self._spawned_at_gc) & 0xFFFFFFFF
        return born * self._row_bytes

    def _fatal_checks(self, a) -> None:
        if bool(a.spill_overflow):
            raise self._fatal(SpillOverflowError(
                f"spill overflow at step {self.steps_run}"))
        if bool(a.spawn_fail):
            raise self._fatal(SpawnCapacityError(
                f"device spawn found no free slot by step "
                f"{self.steps_run}"))
        if bool(a.blob_fail):
            raise self._fatal(BlobCapacityError(
                f"device blob_alloc found no free pool slot by step "
                f"{self.steps_run} — the pool is exhausted: raise "
                "RuntimeOptions.blob_slots, or free blobs "
                "(ctx.blob_free) faster"))
        if bool(a.blob_budget_fail):
            raise self._fatal(BlobCapacityError(
                f"device blob_alloc exceeded its per-tick reservation "
                f"budget by step {self.steps_run} — more allocating "
                "dispatches than BLOB_DISPATCHES in one tick (free "
                "pool slots may remain): raise the actor class's "
                "BLOB_DISPATCHES (or lower its batch)"))

    @staticmethod
    def _clean_busy(a) -> bool:
        """Host-side twin of engine.aux_go: the retired aux votes
        "device busy, zero host attention" — the only state worth
        speculating a window behind."""
        return (bool(a.device_pending) and not bool(a.host_pending)
                and not bool(a.exit_flag) and not bool(a.spill_overflow)
                and not bool(a.spawn_fail) and not bool(a.blob_fail)
                and not bool(a.blob_budget_fail)
                and not (a.spawn and int(a.spawn["room"]) < 0))

    def run(self, max_steps: Optional[int] = None) -> int:
        if self.state is None:
            raise RuntimeError("call start() first")
        # pony:enter / pony:exit: what run() does before its first
        # dispatch and after its last retire, so that the phases cover
        # the whole of run().
        self._phase_stack.clear()
        with self._phase("enter"):
            if self.opts.analysis >= 1 and getattr(self, "_analysis",
                                                   None) is None:
                from .. import analysis as _analysis_mod
                _analysis_mod.attach(self)
            # A request_exit() fired BEFORE run() (signal handler, input
            # callback between runs) must be honoured, not discarded — the
            # flag is consumed at the break below, never cleared on entry.
            max_steps = max_steps or self.opts.max_steps
            ctrl = self._controller
            pipelining = bool(self.opts.pipeline)
            idle_polls = 0
            steps_this_run = 0
            skipped_boundaries = 0
            a = None          # newest RETIRED aux; None forces a first window
            win = None        # the one in-flight (unretired) window
            self._last_retire_t = None   # host_gap_us: inside one run()
            self._last_run_crashed = False
            # SIGQUIT = dump the flight recorder and keep running (the
            # operator's "what is it doing RIGHT NOW" key, ^\ on a tty;
            # SIGTERM/SIGUSR1 stay the analysis dump's, PROFILE.md §8).
            prev_quit = None
            if self._flight is not None and hasattr(signal, "SIGQUIT"):
                def _quit_dump(_signum, _frame):
                    self._flight.dump(reason="SIGQUIT")
                try:
                    prev_quit = signal.signal(signal.SIGQUIT, _quit_dump)
                except ValueError:      # not the main thread: skip
                    prev_quit = None
        try:
            while True:
                if win is None:
                    # A boundary where the device is provably quiescent
                    # and nothing needs injecting is HOST-ONLY: skip the
                    # device dispatch entirely (≙ idle schedulers
                    # staying asleep while the main-thread scheduler
                    # works, scheduler.c:527-746). Sound because with no
                    # injects and no pending device work, a window could
                    # neither dispatch nor deliver anything — device
                    # facts in `a` cannot change. Skipped boundaries
                    # count against max_steps so a runaway host program
                    # stays bounded exactly like a device one.
                    if (a is not None and not bool(a.device_pending)
                            and not bool(a.host_pending)
                            and not self._inject_q
                            and not getattr(self, "_device_dirty", True)):
                        skipped_boundaries += 1
                        self._idle_boundaries += 1
                        # fall through to the host boundary below
                    else:
                        # Sync-point dispatch: the host knows everything
                        # it needs (force=True runs tick 0 whatever the
                        # carried aux says — host-side writes may have
                        # created work the previous aux cannot see).
                        budget = ctrl.window
                        if max_steps is not None:
                            budget = min(budget, max_steps - steps_this_run
                                         - skipped_boundaries)
                        win = self._dispatch_window(
                            max(1, budget), force=True,
                            prev_aux=a if a is not None else self._zero_aux,
                            pipelined=False)
                        continue    # top: pipeline behind it, then retire
                else:
                    # Pipeline refill: dispatch the NEXT window behind
                    # the in-flight one BEFORE fetching its aux — the
                    # device never idles across the boundary. Safe at
                    # any speed: its tick 0 is gated on-device by the
                    # in-flight aux, so it self-cancels if that window
                    # ends needing host attention or quiet.
                    spec = None
                    # A due checkpoint suppresses the next speculation:
                    # the following boundary then has no in-flight
                    # window, which is exactly the quiescent-consistent
                    # point the snapshot needs (delay bounded by ONE
                    # window).
                    ckpt_due = (self._ckpt is not None
                                and self._ckpt.due())
                    if pipelining and not ckpt_due \
                            and a is not None and self._clean_busy(a):
                        budget = ctrl.window
                        if max_steps is not None:
                            budget = min(budget,
                                         max_steps - steps_this_run
                                         - skipped_boundaries
                                         - win["budget"])
                        if budget >= 1:
                            spec = self._dispatch_window(
                                budget, force=False, prev_aux=win["aux"],
                                pipelined=True)
                    k, a = self._retire_window(win)
                    steps_this_run += k
                    win = spec
                # ---- host boundary for `a` (overlaps `win`'s device
                # execution when the pipeline kept one in flight) ----
                seq = self._rl_retired_seq
                with self._phase("host-work", window=seq):
                    self._fatal_checks(a)
                    if bool(a.exit_flag):
                        self._exit_code = int(a.exit_code)
                        break
                    if bool(a.host_pending):
                        with self._phase("outbox", window=seq):
                            self._drain_host()
                    if self._bridge_pollers:
                        with self._phase("pollers", window=seq):
                            for p in self._bridge_pollers:
                                p.poll(self)
                    # Fast lane: host→host messages (including any the
                    # drains and pollers just produced) dispatch NOW,
                    # without waiting a device window per hop (≙
                    # inject_main staying on the main-thread scheduler).
                    if self._host_fast_q:
                        with self._phase("outbox", window=seq):
                            self._drain_host_fast(
                                self.opts.host_fastpath_budget)
                    # Periodic collection (≙ the cycle detector triggered
                    # off the scheduler-0 idle path every
                    # --ponycdinterval, scheduler.c:976-989) — only when
                    # something can actually be garbage: a host ref was
                    # released or actors spawn on device. Host-heap
                    # allocation pressure schedules a collection EARLY
                    # (≙ the per-actor heap's growth-triggered GC, heap.c
                    # next_gc with --ponygcinitial/--ponygcfactor,
                    # start.c:204-209).
                    heap = getattr(self, "_heap", None)
                    heap_pressure = (heap is not None and
                                     heap.bytes_since_gc > self._next_gc)
                    # Rows are this runtime's actor memory, and a world
                    # that creates actors outgrows them long before the
                    # cadence: the device ended the window because the
                    # NEXT tick's spawn reservations would outrun the
                    # free rows (StepAux.spawn "room" < 0, engine.aux_go)
                    # — collect now, before that tick is launched (≙
                    # next_gc, in rows; derived, no option: as a heap
                    # is not collected before gc_initial bytes have been
                    # allocated, rows are not before the actors spawned
                    # since the last pass hold that much). If the pass
                    # frees too little, the tick's spawn is refused and
                    # SpawnCapacityError says so.
                    row_pressure = bool(
                        a.spawn and int(a.spawn["room"]) < 0
                        and self.steps_run > self._row_gc_step
                        and self._spawned_bytes(a) >= self.opts.gc_initial)
                    # Cadence counts device steps + skipped host-only
                    # boundaries (steps_run freezes while boundaries are
                    # skipped; host-heavy phases must still collect
                    # periodically).
                    eff_step = self.steps_run + self._idle_boundaries
                    if (not self.opts.noblock
                            and (self._ever_released
                                 or self.program.has_device_spawns)
                            and (heap_pressure or row_pressure
                                 or (self.opts.cd_interval > 0
                                     and eff_step - self._last_gc_step
                                     >= self.opts.cd_interval))):
                        self._last_gc_step = eff_step
                        self._row_gc_step = self.steps_run
                        with self._phase("gc", window=seq):
                            self.gc()
                    # Periodic crash-safe checkpoint (PROFILE.md §12):
                    # the world is quiescent-consistent here whenever no
                    # window is in flight (retired state + host queues =
                    # exactly what serialise captures); the device→host
                    # copy runs now, the file write rides the background
                    # writer behind the next window. Never lets a
                    # checkpointing failure take down the run it exists
                    # to protect.
                    if self._ckpt is not None and win is None:
                        with self._phase("checkpoint", window=seq):
                            try:
                                self._ckpt.tick(self, in_flight=False)
                            except Exception as e:      # noqa: BLE001
                                self.totals["checkpoint_errors"] += 1
                                if self._flight is not None:
                                    self._flight.event(
                                        "checkpoint_failed",
                                        error=f"{type(e).__name__}: {e}")
                    if self._exit_requested:
                        self._exit_requested = False    # consume it
                        break
                # A dirty device (host-side state write since the last
                # window — e.g. bulk_send's direct mailbox writes from a
                # host behaviour) is not provably quiet: stay busy so the
                # next iteration runs a window before quiescence can hold.
                busy = (bool(a.device_pending) or bool(a.host_pending)
                        or bool(self._inject_q) or bool(self._host_fast_q)
                        or getattr(self, "_device_dirty", False))
                if not busy:
                    if win is not None:
                        # A speculated window may still be in flight; `a`
                        # voted quiet, so its gate closed it to an
                        # identity pass — retire (cheap) before deciding
                        # termination from a fully-synced world.
                        k2, a2 = self._retire_window(win)
                        steps_this_run += k2
                        win = None
                        if k2 or self._inject_q:
                            # Device disagreed (ticks ran), or the
                            # gated-out window handed back injections:
                            # not quiet after all.
                            if k2:
                                a = a2
                            continue
                    terminating = (self._noisy == 0
                                   and (not self._bridge_pollers
                                        or idle_polls > 2))
                    if terminating:
                        # Cleanup ticks ON THE TERMINATION PATH ONLY: the
                        # unmute pass lags the drain that satisfies it by
                        # one tick, so a program can quiesce with cosmetic
                        # mute-flag residue. Bounded — pressure a host
                        # never released legitimately holds mutes and must
                        # not livelock termination; a merely-waiting
                        # (noisy) program never pays these ticks. These
                        # are the SYNCHRONOUS CONFIRM dispatches the
                        # pipelined loop falls back to at quiescence.
                        cleanup = 0
                        while (bool(a.any_muted) and cleanup < 3
                               and (max_steps is None
                                    or steps_this_run + skipped_boundaries
                                    < max_steps)):
                            cw = self._dispatch_window(
                                1, force=True, prev_aux=a, pipelined=False)
                            k2, a = self._retire_window(cw)
                            steps_this_run += k2
                            cleanup += 1
                        break  # quiescent: terminate (≙ ACK'd CNF token)
                    idle_polls += 1
                    # Waiting on external events (timers/fds): BLOCK on
                    # the asio queue when a bridge is attached — the
                    # native epoll thread wakes us the instant an event
                    # lands (≙ a suspended scheduler woken by the ASIO
                    # thread, scheduler.c:1427-1476) — else back off
                    # exponentially (≙ the fork's scaling_sleep,
                    # scheduler.c:918-935). The cap only bounds non-asio
                    # pollers' cadence (process reaping, resolver
                    # completions).
                    waiter = next((p for p in self._bridge_pollers
                                   if hasattr(p, "wait")), None)
                    # Waiting on the outside world is a HEALTHY steady
                    # state: the watchdog disarms on this phase (a
                    # quiet timer-driven service is not a stall).
                    with self._phase("quiescent"):
                        if waiter is not None:
                            waiter.wait(0.02)
                        else:
                            time.sleep(min(0.002,
                                           2e-5 * (1 << min(idle_polls, 7))))
                else:
                    idle_polls = 0
                if max_steps is not None \
                        and steps_this_run + skipped_boundaries >= max_steps:
                    break
        except KeyboardInterrupt:
            # The interrupt may be the watchdog's (flight.Watchdog
            # trips by signalling the main thread after dumping the
            # postmortem): surface the int-coded stall, not a bare ^C.
            stall = self._stall_from_interrupt()
            if stall is not None:
                raise stall from None
            raise
        finally:
            # Interrupt safety (KeyboardInterrupt/SIGTERM mid-pipeline,
            # and every fatal raise above): an in-flight window's output
            # IS self.state — sync it, fold its aux into the counters,
            # and drain any host-cohort mail it surfaced, so a stopped
            # run loses no host-outbox messages and the runtime stays
            # consistent for a restart (no donated-buffer reuse).
            import sys as _sys
            with self._phase("exit"):
                # A tripped watchdog means the device (or a host phase) is
                # WEDGED: retiring the in-flight window or refreshing the
                # metrics snapshot would block on the very hang we are
                # converting to an error — skip device-touching teardown
                # and let the PonyStallError out (the runtime is not
                # restartable after a stall; the postmortem is the value).
                stalled = (self._watchdog is not None
                           and self._watchdog.tripped is not None)
                if win is not None and not stalled:
                    k2, a2 = self._retire_window(win)
                    steps_this_run += k2
                    if bool(a2.host_pending):
                        self._drain_host()
                if _sys.exc_info()[0] is not None \
                        and not isinstance(_sys.exc_info()[1], PonyStallError):
                    # Interrupted between boundaries: host→host messages
                    # already queued on the fast lane would otherwise be
                    # stranded until the next run() — deliver them now
                    # (bounded by the normal per-boundary budget). Normal
                    # exits skip this: quiescent termination proves the
                    # lane empty, and an exit() break stops the world as
                    # the synchronous loop always has. A watchdog STALL
                    # also skips it: the wedged behaviour may be ON this
                    # lane, and re-dispatching it would hang the unwind.
                    self._drain_host_fast(self.opts.host_fastpath_budget)
                if prev_quit is not None:
                    try:
                        signal.signal(signal.SIGQUIT, prev_quit)
                    except ValueError:
                        pass
                self._stamp("idle")
                # Crash postmortem (PROFILE.md §11): any exceptional exit
                # dumps the black box. Stall trips already dumped (the
                # watchdog thread wrote it before interrupting us).
                exc = _sys.exc_info()[1]
                self._last_run_crashed = (exc is not None
                                          and not isinstance(exc, SystemExit))
                if (exc is not None and self._flight is not None
                        and not isinstance(exc, (SystemExit,
                                                 PonyStallError))):
                    self._flight.dump(
                        reason=f"crash: {type(exc).__name__}: {exc}",
                        error_code=error_code(exc))
                if self._metrics is not None and not stalled:
                    self._metrics.update_now(self)
        # Persist a converged adaptive window for warm starts (PR 1
        # tuning-cache machinery): only a steady controller with real
        # evidence writes, and only when the value actually moved.
        if (self._qi_auto and ctrl.state == "steady"
                and self._rl_windows >= 8
                and ctrl.window != self._qi_loaded):
            from .. import tuning
            with self._phase("exit"):
                tuning.store_quiesce_interval(self.program, self.opts,
                                              ctrl.window)
            self._qi_loaded = ctrl.window
        return self._exit_code

    def run_loop_stats(self) -> Dict[str, Any]:
        """Observable run-loop telemetry (dump(), `top`, the benchmark's
        layer metrics — host_gap_pct.py reads host_gap_us_total): windows
        retired, pipelined vs sync-point dispatches, the cumulative
        host-imposed device-idle gap, the windows' summed wall clock
        (dispatch start to retire; a pipelined window's counts from the
        retire before it), the seconds spent in each phase and the
        calls of each (RUN_PHASES: run()'s and the API calls' outside
        it; self time, cumulative), the cold launches (the first of the
        window's executable since start(): trace + lower + compile or
        reload) and their seconds, re-queued gated-out injections, the
        collector's passes and trace hops (`gc_runs`, `gc_iters`; the
        actors are the device's `n_spawned` / `n_collected`, counter()),
        `free_rows_low` (a program with device spawns: the least free
        rows, net of the next tick's reservations, any tick has left;
        None before the first window), `pool` (a program whose window
        can allocate or free blobs, state.counts_pool: the slots claimed
        and released up to the last retired window, `allocs` / `frees`,
        and their difference `blobs_in_use` at its end — read with the
        window's aux, no fetch of their own; None elsewhere and before
        the first window) and the controller snapshot."""
        return {
            "windows": self._rl_windows,
            "pipelined_dispatches": self._rl_pipelined,
            "sync_dispatches": self._rl_synced,
            "host_gap_us_total": self._rl_gap_ns / 1e3,
            "windows_wall_s": self._rl_wall_ns / 1e9,
            "phase_s": dict(self._phase_s),
            "phase_n": dict(self._phase_n),
            "cold_dispatches": self._cold_n,
            "cold_dispatch_s": self._cold_s,
            "injects_requeued": self._rl_requeued,
            "gc_runs": self.totals["gc_runs"],
            "gc_iters": self.totals["gc_iters"],
            "free_rows_low": (self._free_rows_low
                              if self._free_rows_low < 2**31 - 1 else None),
            "pool": ({"allocs": self._pool_books["alloc"],
                      "frees": self._pool_books["free"],
                      "blobs_in_use": (self._pool_books["alloc"]
                                       - self._pool_books["free"])}
                     if self._pool_books else None),
            "controller": (self._controller.snapshot()
                           if self._controller is not None else None),
        }

    def checkpoint(self, path: Optional[str] = None) -> Optional[str]:
        """Write one on-demand snapshot: to `path` (synchronous,
        serialise.save) or into the periodic ring (async write;
        requires checkpoint_every_s — returns the queued file's path).
        Call between runs/steps only, like serialise.save."""
        from .. import serialise as _serialise
        if path is not None:
            _serialise.save(self, path)
            return path
        if self._ckpt is None:
            raise RuntimeError(
                "no checkpoint ring configured: pass path=, or set "
                "RuntimeOptions.checkpoint_every_s/checkpoint_path")
        seq = self._ckpt.checkpoint(self, force=True)
        return _serialise.checkpoint_file(self._ckpt.prefix, seq)

    def checkpoint_stats(self) -> Optional[Dict[str, Any]]:
        """Checkpointer telemetry (PROFILE.md §12): capture/write costs
        and the newest restorable snapshot; None when checkpointing is
        off."""
        return self._ckpt.stats() if self._ckpt is not None else None

    def request_exit(self, code: int = 0) -> None:
        """Ask the run loop to stop at the next host boundary (≙
        pony_exitcode + the quiescent stop, start.c:345 — but callable
        from host-side code outside any behaviour, e.g. an input
        handler or signal callback)."""
        self._exit_code = int(code)
        self._exit_requested = True

    @_api_phase("stop")
    def stop(self, postmortem: bool = False) -> int:
        """Tear down auxiliaries (≙ pony_stop, start.c:332-351): emit the
        analysis summary, stop the writer thread, close the bridge, and
        stop the watchdog/metrics threads. ``postmortem=True``
        additionally dumps the flight recorder (the on-demand black-box
        read — path lands in ``rt._flight.last_dump``)."""
        if postmortem and self._flight is not None:
            self._flight.dump(reason="stop(postmortem=True)")
        a = getattr(self, "_analysis", None)
        if a is not None:
            a.summary()
            a.close()
            self._analysis = None
        b = getattr(self, "bridge", None)
        if b is not None:
            b.close()
            self.bridge = None
            self._bridge_pollers = [p for p in self._bridge_pollers
                                    if p is not b]
        wd = self._watchdog
        stalled_wd = wd is not None and wd.tripped is not None
        if self._ckpt is not None:
            if not stalled_wd and not self._last_run_crashed:
                # Final checkpoint on clean teardown — the fast-start
                # restore source. Skipped after a stall (capture would
                # hang on the wedged device) and after ANY crashed
                # run: the ring's newest snapshot must stay the last
                # intact PRE-crash world, or the supervisor would
                # restore straight back into the failure.
                try:
                    self._ckpt.checkpoint(self, force=True)
                except Exception:                  # noqa: BLE001
                    self.totals["checkpoint_errors"] += 1
            self._ckpt.close()
            self._ckpt = None
        if wd is not None:
            wd.close()
            self._watchdog = None
        if self._metrics is not None:
            if wd is None or wd.tripped is None:
                # A stalled device would hang this last snapshot fetch.
                self._metrics.update_now(self)
            self._metrics.close()
            self._metrics = None
        return self._exit_code

    # ---- introspection (≙ ponyint_actor_num_messages, actor.c:666; and
    # the analysis dump hooks, analysis.c) ----
    def queue_depth(self, actor_id: int) -> int:
        return int(self.state.tail[actor_id] - self.state.head[actor_id])

    def last_error(self, actor_id: int) -> int:
        """Latest int-coded error on an actor, 0 = none (≙ the fork's
        __error_code(); device via ctx.error_int, host via PonyError)."""
        if self.program.cohort_of(actor_id).host:
            return self._host_errors.get(int(actor_id), 0)
        return int(self.state.last_error[actor_id])

    def last_error_loc(self, actor_id: int) -> str:
        """Source location of the latest error (≙ the fork's
        __error_loc): the Python file:line of the ctx.error_int call
        site (device) or the PonyError raise site (host); "?" = none."""
        from ..errors import error_site
        if self.program.cohort_of(actor_id).host:
            return self._host_error_locs.get(int(actor_id), "?")
        return error_site(int(self.state.last_error_loc[actor_id]))

    def total_memory(self) -> Dict[str, int]:
        """Process + device memory accounting (≙ the fork's
        @ponyint_total_memory, DIVERGENCE.md: the runtime knows its
        OS-visible memory use). Returns bytes: host RSS, device state
        (the actor world's HBM footprint), and the native pool's live
        block count."""
        try:    # current RSS (Linux); peak via getrusage as fallback
            with open("/proc/self/statm") as f:
                rss_bytes = (int(f.read().split()[1])
                             * (os.sysconf("SC_PAGE_SIZE")))
        except OSError:
            import resource
            rss_bytes = resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss * 1024
        dev = 0
        if self.state is not None:
            dev = sum(leaf.nbytes for leaf in jax.tree.leaves(self.state))
        try:
            from .. import native
            pool_live, pool_recycled = native.pool_stats()
        except Exception:                     # noqa: BLE001 — lib unbuilt
            pool_live = pool_recycled = 0
        return {"host_rss_bytes": int(rss_bytes),
                "device_state_bytes": dev,
                "pool_live_blocks": int(pool_live),
                "pool_recycled_blocks": int(pool_recycled)}

    def check_invariants(self) -> None:
        """Debug-build queue/flag invariants (≙ well_formed_msg_chain +
        messageq_size_debug, actor.c:57-92 / messageq.c:15-27 — the
        reference compiles these in for debug builds; call this from
        tests or enable opts.debug_checks to run it at every aux fetch).
        Raises AssertionError with the first violated invariant."""
        st = jax.device_get(self.state)
        occ = st.tail - st.head
        c = np.tile(np.concatenate(
            [np.full(ch.local_capacity, ch.mailbox_cap)
             for ch in self.program.cohorts]), self.program.shards)
        assert (occ >= 0).all(), "mailbox occupancy negative (head>tail)"
        assert (occ <= c).all(), "mailbox occupancy exceeds capacity"
        alive = np.asarray(st.alive)
        muted = np.asarray(st.muted)
        assert not (muted & ~alive).any(), "dead actor still muted"
        assert (np.asarray(st.mute_refs)[:, ~muted] == -1).all(), \
            "unmuted actor holds a mute ref"
        dead_occ = occ[~alive]
        assert (dead_occ == 0).all(), "dead actor with queued messages"
        for name in ("dspill", "rspill"):
            tgts = np.asarray(getattr(st, name + "_tgt"))
            cnt = int(np.asarray(getattr(st, name + "_count")).sum())
            assert cnt <= tgts.shape[0], f"{name} count exceeds capacity"

    @staticmethod
    def _fetch(arr) -> np.ndarray:
        """Host-read a runtime array. On a multi-PROCESS mesh the shards
        live on other hosts, so fetching is a collective
        (process_allgather) — every rank must read at the same program
        point, which the SPMD host-driver contract already requires
        (tests/_dist_worker.py)."""
        if (hasattr(arr, "is_fully_addressable")
                and not arr.is_fully_addressable):
            from jax.experimental import multihost_utils
            arr = multihost_utils.process_allgather(arr, tiled=True)
        return np.asarray(arr)

    @_api_phase("counter")
    def counter(self, name: str) -> int:
        """Sum a per-shard runtime counter (n_processed, n_delivered,
        n_rejected, n_badmsg, n_deadletter, n_mutes; the route's
        n_routed, n_routed_remote, n_unpacked, n_route_pressure,
        n_route_prefix (the shard-ticks whose route spill read the
        sorted entries' prefix alone), n_remote_mutes — 0 on one chip,
        where nothing is routed and the state holds no such leaf;
        n_prefix, the ticks delivered over the list's prefix — 0 where
        there is no such leaf, state.counts_prefix) over the mesh."""
        if name in LIST_COUNTERS:
            leaf = self.state.route_counts.get(name)
            return 0 if leaf is None else int(self._fetch(leaf).sum())
        return int(self._fetch(getattr(self.state, name)).sum())

    @functools.cached_property
    def _pinned_handles(self) -> Dict[str, List[str]]:
        """A fact of the compiled program, worked out once a runtime
        (a program is frozen by then: profile() needs start())."""
        return engine.pinned_handles(self.program, self.opts)

    @functools.cached_property
    def _born_full(self) -> Dict[str, Dict[str, int]]:
        """Another: engine.born_full."""
        return engine.born_full(self.program, self.opts)

    @_api_phase("read")
    def profile(self) -> Dict[str, Any]:
        """Structured per-behaviour/per-cohort telemetry report — the
        host face of the on-device profiler matrix (lanes.profile_lanes;
        ≙ reading back the fork's per-actor --ponyanalysis records).
        Requires opts.analysis >= 1 (at level 0 the lanes compile away
        and there is nothing to read). One small device fetch; call it
        at window boundaries, not per tick.

        Returns::

            {"steps": int,
             "behaviours": {"Type.beh": {"runs", "delivered",
                                         "rejected"}},   # cumulative
             "cohorts": {"Type": {"queue_wait_hist": [QW_BUCKETS ints],
                                  "queue_wait_p50": int,   # ticks (2^k
                                  "queue_wait_p99": int,   #  bucket lo)
                                  "mute_ticks": int,
                                  "pinned_handles": [field names],
                                  "born_full": {"allocs", "sets_folded",
                                                "sets_alone", "windows",
                                                "gets_windowed",
                                                "gets_alone"}}},
                         # Blob fields whose handle the dispatch checks
                         # once, not once a message (a fact of the
                         # compiled program: engine.pinned_handles);
                         # blob_alloc sites, the blob_sets a fresh
                         # payload's column took and the ones that
                         # wrote the pool; the read windows opened, the
                         # blob_gets they answered and the ones that
                         # gathered a word a lane (another:
                         # engine.born_full)
             "phases": {"delivery": int, "drain": int, "dispatch": int,
                        "gc_mark": int,       # cumulative work units
                        "rebuild": int},      # indices the rebuild read
             "totals": {"processed", "delivered", "rejected", "badmsg",
                        "deadletter", "mutes", "host_processed"},
             "gc": {"passes", "collected", "blob_slots_reclaimed",
                    "trace_iters", "aborted"}}

        Device behaviours' runs sum to counter("n_processed") and
        delivered sums to counter("n_delivered") for well-formed traffic
        (badmsg deliveries are attributable to no behaviour); host
        behaviours report their host-dispatch counts."""
        if self.opts.analysis < 1:
            raise RuntimeError(
                "Runtime.profile() needs RuntimeOptions.analysis >= 1 "
                "(the telemetry lanes compile to constants at level 0)")
        if self.state is None:
            raise RuntimeError("call start() first")
        from ..analysis import hist_percentile
        from .state import N_PHASES, PHASE_NAMES, QW_BUCKETS
        p = self.program.shards
        nb = len(self.program.behaviour_table)
        nd = len(self.program.device_cohorts)
        runs = self._fetch(self.state.beh_runs).reshape(p, nb).sum(0)
        deliv = self._fetch(
            self.state.beh_delivered).reshape(p, nb).sum(0)
        rej = self._fetch(self.state.beh_rejected).reshape(p, nb).sum(0)
        mt = self._fetch(
            self.state.coh_mute_ticks).reshape(p, nd).sum(0)
        hist = self._fetch(self.state.qwait_hist).reshape(
            p, nd, QW_BUCKETS).sum(0)
        behaviours = {}
        for g, bdef in enumerate(self.program.behaviour_table):
            name = f"{bdef.actor_type.__name__}.{bdef.name}"
            behaviours[name] = {
                "runs": int(runs[g]) + self._beh_host_runs.get(g, 0),
                "delivered": int(deliv[g]),
                "rejected": int(rej[g]),
            }
        cohorts = {}
        pinned, born = self._pinned_handles, self._born_full
        for di, ch in enumerate(self.program.device_cohorts):
            h = [int(x) for x in hist[di]]
            cohorts[ch.atype.__name__] = {
                "queue_wait_hist": h,
                "queue_wait_p50": hist_percentile(h, 0.50),
                "queue_wait_p99": hist_percentile(h, 0.99),
                "mute_ticks": int(mt[di]),
                "pinned_handles": pinned[ch.atype.__name__],
                "born_full": born[ch.atype.__name__],
            }
        ph = self._fetch(self.state.phase_cost).reshape(
            p, N_PHASES).sum(0)
        return {
            "steps": self.steps_run,
            "behaviours": behaviours,
            "cohorts": cohorts,
            "phases": {name: int(ph[i])
                       for i, name in enumerate(PHASE_NAMES)},
            "totals": {
                "processed": self.counter("n_processed"),
                "delivered": self.counter("n_delivered"),
                "rejected": self.counter("n_rejected"),
                "badmsg": self.counter("n_badmsg"),
                "deadletter": self.counter("n_deadletter"),
                "mutes": self.counter("n_mutes"),
                "host_processed": self.totals.get("host_processed", 0),
            },
            "gc": {
                "passes": self.totals.get("gc_runs", 0),
                "collected": self.counter("n_collected"),
                "blob_slots_reclaimed": self.totals.get(
                    "gc_swept_blobs", 0),
                "trace_iters": self.totals.get("gc_iters", 0),
                "aborted": self.totals.get("gc_aborted", 0),
            },
        }

    def measured_costs(self, force: bool = False) -> Dict[str, Any]:
        """Measured, not modelled (costs.capture, ISSUE 19): XLA's own
        ``cost_analysis()`` / ``memory_analysis()`` of this runtime's
        REAL compiled step and pipelined-window executables — flops,
        bytes accessed, argument/output/temp/peak bytes per executable.
        Lazy and memoized (first call AOT-compiles each executable once
        more; the world does not advance); ``opts.cost_capture=True``
        runs it eagerly at start(). Works on CPU and TPU — fields a
        backend doesn't report degrade to None."""
        from .. import costs as _costs
        return _costs.capture(self, force=force)

    def window_symbols(self) -> Dict[str, list]:
        """What a profiler trace's device operations are, by the
        program's own names (costs.window_symbols): for each program
        the run loop launches — `"window"` and, once a collection pass
        has run, `"gc"` — a row for every instruction of the COMPILED
        text that can be a device event: `name` (`fusion.238`),
        `opcode`, `shape`, `kind` (gather / scatter / sort / collective
        / other), the phase `scope` (state.STEP_SCOPES) with `how` it
        was found (own / inside / around / none), and for a gather or a
        scatter whether its table or output was dealt `S(1)` (`s1`; the
        table alone: `table_s1`; `table_bytes`, `index_count`). Join a trace's event to a row by
        instruction name and output shape (PROFILE.md §16). Lazy and
        memoized; after a run the executable is found again, not
        compiled, and the world does not advance."""
        from .. import costs as _costs
        return _costs.window_symbols(self)

    def traces(self) -> Dict[int, Dict[str, Any]]:
        """Reassembled causal traces (PROFILE.md §10): drains the
        device span ring, merges host spans (injection roots, host-
        cohort dispatches) and returns one causal tree per trace id —
        ``{trace_id: {"roots", "spans", "n_spans", "latency",
        "critical_path"}}`` with latency in device ticks (max retire −
        min enqueue over the trace). Requires tracing on
        (``analysis >= 3`` and ``trace_sample > 0``); sample with
        ``RuntimeOptions(trace_sample=N)`` or pass an explicit id via
        ``send(..., trace=...)`` / ``bulk_send(..., trace=...)``."""
        if self._tracer is None:
            raise RuntimeError(
                "Runtime.traces() needs causal tracing on: "
                "RuntimeOptions(analysis=3, trace_sample=N) (the trace "
                "lanes compile away otherwise)")
        from ..tracing import reassemble
        self._tracer.drain(self)
        return reassemble(self._tracer.spans)

    @_api_phase("read", lambda self, actor_id: {"words": len(
        self.program.cohort_of(actor_id).atype.field_specs)})
    def state_of(self, actor_id: int) -> Dict[str, Any]:
        cohort = self.program.cohort_of(actor_id)
        if cohort.host:
            return dict(self._host_state.get(actor_id, {}))
        col = int(cohort.gid_to_col(actor_id))
        ts = self.state.type_state[cohort.atype.__name__]
        # Addressable arrays: slice on device (one element crosses the
        # wire, not the column); only a multi-process mesh pays the
        # collective whole-array fetch.
        return {k: (np.asarray(v[col]).item()
                    if getattr(v, "is_fully_addressable", True)
                    else self._fetch(v)[col].item())
                for k, v in ts.items()}

    def _blob_slot_of(self, handle: int, what: str) -> int:
        """Decode + validate a handle host-side (range, allocation,
        generation — a stale handle to a recycled slot rejects)."""
        bsl = self.opts.blob_slots
        slot = pack.blob_slot(int(handle))
        if handle < 0 or not (0 <= slot < self.program.shards * bsl):
            raise IndexError(f"{what}: blob handle {handle} out of range")
        if not bool(self._fetch(self.state.blob_used)[slot]):
            raise KeyError(f"{what}: blob handle {handle} is not "
                           "allocated")
        if (int(self._fetch(self.state.blob_gen)[slot])
                & pack.BLOB_GEN_MASK) != pack.blob_gen_of(int(handle)):
            raise KeyError(f"{what}: blob handle {handle} is STALE — "
                           "its slot was recycled (generation mismatch)")
        return slot

    def _blob_flat(self, slots) -> np.ndarray:
        """Where the words of the pool's global `slots` lie in the flat
        blob_data, [blob_words, len(slots)] (state.pool_index inside a
        shard's block, the blocks shard-major)."""
        bw, bsl = self.opts.blob_words, self.opts.blob_slots
        shard, local = np.divmod(np.asarray(slots, np.int64), bsl)
        return (shard * (bw * bsl) + pool_index(
            bsl, np.arange(bw, dtype=np.int64)[:, None], local))

    @_api_phase("blob-fetch", lambda self, handle: {"blobs": 1})
    def blob_fetch(self, handle: int) -> np.ndarray:
        """Host-side read of a device blob's logical words (≙ receiving
        a message payload on the main-thread scheduler). Raises on null/
        unallocated/stale handles."""
        slot = self._blob_slot_of(handle, "blob_fetch")
        ln = int(self._fetch(self.state.blob_len)[slot])
        at = self._blob_flat([slot])[:ln, 0]
        data = self.state.blob_data
        # Addressable pools: gather on device (the blob crosses the
        # wire, not the pool).
        return (np.asarray(data[at])
                if getattr(data, "is_fully_addressable", True)
                else self._fetch(data)[at])

    @_api_phase("blob-fetch", lambda self, handles:
                {"blobs": int(np.size(handles))})
    def blob_fetch_many(self, handles) -> np.ndarray:
        """blob_fetch's bulk twin: every word of every handle's blob,
        [count, blob_words] (logical lengths are not applied), in ONE
        device gather — the blobs cross the wire, not the pool. Raises
        on a null, unallocated or stale handle, as blob_fetch does."""
        h = np.asarray(handles, np.int64).reshape(-1)
        bw, bsl = self.opts.blob_words, self.opts.blob_slots
        slots = pack.blob_slot(h)
        if (h < 0).any() or (slots >= self.program.shards * bsl).any():
            raise IndexError("blob_fetch_many: a handle out of range")
        if not self._fetch(self.state.blob_used)[slots].all():
            raise KeyError("blob_fetch_many: a handle is not allocated")
        if ((self._fetch(self.state.blob_gen)[slots] & pack.BLOB_GEN_MASK)
                != pack.blob_gen_of(h)).any():
            raise KeyError("blob_fetch_many: a STALE handle — its slot "
                           "was recycled (generation mismatch)")
        # adjacent slots of one shard (a fresh pool's) are `blob_words`
        # strided runs, read as slices; anything else word by word
        run = (h.size > 0 and slots[0] // bsl == slots[-1] // bsl
               and np.array_equal(slots, slots[0] + np.arange(h.size)))

        data = self.state.blob_data
        if not getattr(data, "is_fully_addressable", True):
            data = self._fetch(data)
        return np.asarray(_fetch_blobs(data, slots.astype(np.int32),
                                       bw=bw, bsl=bsl, run=bool(run)))

    @_api_phase("blob-store", lambda self, *_a, **_k: {"blobs": 1})
    def blob_store(self, words, length: Optional[int] = None,
                   near: Optional[int] = None) -> int:
        """Host-side blob allocation between steps (≙ the embedder
        building a message payload, pony.h pony_alloc_msg): claims a
        free pool slot, writes `words` (i32, ≤ blob_words), returns the
        handle — typically then sent as a Blob argument. The HOST owns
        the blob until the send moves it.

        `near`: an actor id whose SHARD should own the slot. Host
        INJECTIONS bypass the routing that migrates device-to-device
        blobs, so allocate on the receiver's shard or the handle
        arrives unreadable (null + n_blob_remote)."""
        if self.opts.blob_slots <= 0:
            raise RuntimeError("blob pool disabled: set "
                               "RuntimeOptions.blob_slots/blob_words")
        w = np.asarray(words, np.int32).reshape(-1)
        if w.shape[0] > self.opts.blob_words:
            raise ValueError(
                f"{w.shape[0]} words > blob_words={self.opts.blob_words}")
        used = self._fetch(self.state.blob_used)
        bsl = self.opts.blob_slots
        if near is not None:
            tgt_shard = int(near) // self.program.n_local
            used = used[tgt_shard * bsl:(tgt_shard + 1) * bsl]
            off = tgt_shard * bsl
        else:
            off = 0
        free = np.flatnonzero(~used)
        if free.size == 0:
            raise BlobCapacityError(
                "host blob_store: pool exhausted"
                + (f" on shard {near // self.program.n_local}"
                   if near is not None else ""))
        slot = off + int(free[0])
        full = np.zeros((self.opts.blob_words,), np.int32)
        full[:w.shape[0]] = w
        ln = w.shape[0] if length is None else int(length)
        if not 0 <= ln <= self.opts.blob_words:
            raise ValueError(
                f"length={ln} outside [0, blob_words="
                f"{self.opts.blob_words}]")
        shard = slot // self.opts.blob_slots
        st = self.state
        gen = (int(self._fetch(st.blob_gen)[slot]) + 1) \
            & pack.BLOB_GEN_MASK
        self.state = self._replace(
            blob_data=st.blob_data.at[self._blob_flat([slot])[:, 0]].set(
                jnp.asarray(full)),
            blob_used=st.blob_used.at[slot].set(True),
            blob_len=st.blob_len.at[slot].set(jnp.int32(ln)),
            blob_gen=st.blob_gen.at[slot].set(jnp.int32(gen)),
            n_blob_alloc=st.n_blob_alloc.at[shard].add(1))
        handle = pack.blob_handle(slot, gen)
        self._host_blobs.add(handle)    # GC root until sent/freed
        return handle

    @_api_phase("blob-store", lambda self, count, *_a, **_k:
                {"blobs": int(count)})
    def blob_store_many(self, count: int, words=None, *, fill=None,
                        length: Optional[int] = None,
                        near: Optional[int] = None) -> np.ndarray:
        """blob_store's bulk twin (≙ spawn_many for the actor heap):
        claims the `count` lowest free pool slots and writes them in ONE
        device operation. Returns the [count] handles, host-owned like
        blob_store's until a send, spawn_many or set_fields moves them
        into an actor (`spawn_many(T, count, table=handles)`).

        The words come from `words` ([count, w] i32, w <= blob_words,
        shipped from the host) or from `fill(k, w)`, evaluated on the
        device: a jnp function of the blob's index in this call, k
        [1, count], and the word's index, w [blob_words, 1] (a table too
        large to ship — `fill=lambda k, w: k * 2048 + w`). Neither:
        zeroed blobs. `length` and `near` are blob_store's."""
        if self.opts.blob_slots <= 0:
            raise RuntimeError("blob pool disabled: set "
                               "RuntimeOptions.blob_slots/blob_words")
        if words is not None and fill is not None:
            raise ValueError("give words or fill, not both")
        count, bw, bsl = int(count), self.opts.blob_words, \
            self.opts.blob_slots
        if words is not None:
            words = np.asarray(words, np.int32).reshape(count, -1)
            if words.shape[1] > bw:
                raise ValueError(
                    f"{words.shape[1]} words > blob_words={bw}")
        ln = ((bw if words is None else words.shape[1])
              if length is None else int(length))
        if not 0 <= ln <= bw:
            raise ValueError(
                f"length={ln} outside [0, blob_words={bw}]")
        used = self._fetch(self.state.blob_used)
        off = 0
        if near is not None:
            off = (int(near) // self.program.n_local) * bsl
            used = used[off:off + bsl]
        free = np.flatnonzero(~used)
        if free.size < count:
            raise BlobCapacityError(
                f"host blob_store_many: {count} blobs wanted, "
                f"{free.size} pool slots free"
                + (f" on shard {near // self.program.n_local}"
                   if near is not None else ""))
        slots = (off + free[:count]).astype(np.int32)
        gens = ((self._fetch(self.state.blob_gen)[slots] + 1)
                & pack.BLOB_GEN_MASK).astype(np.int32)
        allocs = np.bincount(slots // bsl, minlength=self.program.shards)
        # A whole shard's pool (a fresh one's, word-major: exactly the
        # [blob_words, count] block) is one slice update in place;
        # anything else is one scatter of single words.
        whole = count == bsl and int(slots[0]) % bsl == 0
        at = None if whole else self._blob_flat(slots)

        def store(data, used, len_, gen, n_alloc, slots, gens, vals, at):
            if fill is not None:
                vals = jnp.broadcast_to(jnp.asarray(fill(
                    jnp.arange(count, dtype=jnp.int32)[None, :],
                    jnp.arange(bw, dtype=jnp.int32)[:, None]),
                    jnp.int32), (bw, count))
            if whole:
                data = jax.lax.dynamic_update_slice(
                    data, vals.reshape(-1), (slots[0] * bw,))
            else:
                data = data.at[at].set(vals)
            return (data, used.at[slots].set(True),
                    len_.at[slots].set(jnp.int32(ln)),
                    gen.at[slots].set(gens),
                    n_alloc + jnp.asarray(allocs, jnp.int32))

        vals = None
        if words is not None:
            vals = np.zeros((bw, count), np.int32)
            vals[:words.shape[1]] = words.T
        elif fill is None:
            fill = lambda k, w: 0           # noqa: E731 — zeroed blobs
        st = self.state
        data, used_d, len_d, gen_d, n_alloc = jax.jit(
            store, donate_argnums=(0, 1, 2, 3, 4))(
            st.blob_data, st.blob_used, st.blob_len, st.blob_gen,
            st.n_blob_alloc, slots, gens, vals, at)
        self.state = self._replace(
            blob_data=data, blob_used=used_d, blob_len=len_d,
            blob_gen=gen_d, n_blob_alloc=n_alloc)
        handles = np.asarray(pack.blob_handle(slots, gens), np.int32)
        self._host_blobs.update(handles.tolist())   # roots until moved
        return handles

    def _move_blob_fields(self, atype: ActorTypeMeta, fields) -> None:
        """An iso Blob handle the host stores into an actor's field has
        MOVED there (≙ send()'s iso args): the field is its GC root from
        now on, the host's goes."""
        if not self._host_blobs:
            return
        for fname, v in fields.items():
            spec = atype.field_specs.get(fname)
            if pack.is_blob(spec) and not pack.is_blob_val(spec):
                self._host_blobs.difference_update(
                    np.asarray(v).reshape(-1).tolist())

    def blob_free_host(self, handle: int) -> None:
        """Host-side release of a blob the host owns (e.g. fetched and
        finished with). Double frees and stale handles reject (counter
        integrity + ABA guard)."""
        slot = self._blob_slot_of(handle, "blob_free_host")
        shard = slot // self.opts.blob_slots
        st = self.state
        self.state = self._replace(
            blob_used=st.blob_used.at[slot].set(False),
            blob_len=st.blob_len.at[slot].set(0),
            n_blob_free=st.n_blob_free.at[shard].add(1))
        self._host_blobs.discard(int(handle))

    def blob_store_str(self, text: str, near: Optional[int] = None
                       ) -> int:
        """Store a UTF-8 string as a device blob (4 bytes/word): the
        `String val`-style payload path; pair with blob_fetch_str.
        blob_len records WORDS (the pool's logical unit); the byte
        count is recovered by stripping the zero-padding of the final
        word, so U+0000 in the text is rejected here rather than
        silently truncated on the way back."""
        raw = text.encode("utf-8")
        if b"\x00" in raw:
            raise ValueError(
                "blob_store_str: NUL (U+0000) in text is "
                "indistinguishable from word padding; store raw words "
                "with blob_store instead")
        if len(raw) > 4 * self.opts.blob_words:
            raise ValueError(
                f"{len(raw)} bytes > 4*blob_words="
                f"{4 * self.opts.blob_words}")
        padded = raw + b"\x00" * (-len(raw) % 4)
        words = np.frombuffer(padded, np.int32) if padded else \
            np.zeros((0,), np.int32)
        return self.blob_store(words, near=near)

    def blob_fetch_str(self, handle: int) -> str:
        """Read back a blob_store_str payload."""
        words = np.ascontiguousarray(self.blob_fetch(handle), np.int32)
        return words.tobytes().rstrip(b"\x00").decode("utf-8")

    def blob_release(self, handle: int) -> None:
        """Drop the host's GC ROOT on a handle without freeing the
        slot — the val-blob release path (device readers may still hold
        it; the next gc() reclaims it once nobody does). For a handle
        the host exclusively owns, blob_free_host frees immediately."""
        self._host_blobs.discard(int(handle))

    @property
    @_api_phase("read", lambda self: {"words": int(
        self.state.blob_used.size)})
    def blobs_in_use(self) -> int:
        """Currently allocated pool slots (leak diagnostic: orphaned
        blobs — owner died, or handle moved off-shard — persist only
        until the next rt.gc(), whose mark pass sweeps them)."""
        return int(self._fetch(self.state.blob_used).sum())

    @_api_phase("read", lambda self, atype: {"words": sum(
        int(v.size) for v in
        self.state.type_state[atype.__name__].values())})
    def cohort_state(self, atype: ActorTypeMeta) -> Dict[str, np.ndarray]:
        """State columns in *slot order* (spawn order), whatever the shard
        layout."""
        cohort = self.program.by_type[atype]
        cols = np.asarray(
            cohort.slot_to_col(np.arange(cohort.capacity)), np.int64)
        return {k: self._fetch(v)[cols]
                for k, v in self.state.type_state[atype.__name__].items()}

    @property
    def exit_code(self) -> int:
        return self._exit_code
