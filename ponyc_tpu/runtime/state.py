"""Device-resident runtime state: the struct-of-arrays actor world.

≙ the reference's per-actor structs flattened across all actors:
  - pony_actor_t fields (flags, priority, batch, mute counters —
    src/libponyrt/actor/actor.h:35-69) become columns over [N] actors;
  - each actor's messageq_t (intrusive MPSC list, actor/messageq.c) becomes
    one row of a dense [N, cap, words] ring-buffer table with monotonically
    increasing head/tail counts (occupancy = tail - head; physical slot =
    count % cap);
  - the scheduler's unbounded pool-backed queues have no static-shape
    analog, so overflow goes to bounded *spill* tables retried next step
    (SURVEY.md §7 hard part (a): capacity-bounded mailboxes with spill).

TPU-first memory layout (the round-3 redesign): the actor/entry axis is
the MINOR-MOST (last) dimension of every multi-dimensional array. XLA:TPU
maps the last dim onto the 128 vector lanes and pads it up — a
[N, cap, words] mailbox table (actor-major, the CPU-obvious layout) pads
its `words`-sized minor dim to 128 lanes, inflating physical traffic up
to 64× and making the dispatch/delivery path run at ~1/30 of HBM speed
(measured on-chip, round 3). With [cap, words, N] the million-actor axis
fills the lanes, small static dims (ring slot, payload word) become the
major axes iterated at trace time, and every hot op is a full-width
vector op over [N]. Sharding therefore also rides the LAST axis (see
state_partition_specs): actor rows are shard-major within it
(program.py), per-shard scalars are [P] vectors, spill tables per-shard
[P*S]. With P == 1 this is exactly the single-chip layout. Two spills
exist because a message can be stuck in two different places on a mesh:

  - rspill ("route spill", sender side): the per-destination all_to_all
    bucket was full — the message hasn't left its source shard yet; targets
    are global ids.
  - dspill ("delivery spill", receiver side): it reached the target shard
    but the target mailbox was full; targets are local rows. This is the
    only spill that exists on a single chip.

Everything lives in one pytree so a whole scheduler tick is a single jitted
function application; host↔device traffic per step is a handful of scalars.

Counts are int32: a single actor overflows after 2^31 lifetime messages —
acceptable for now, and noted here deliberately.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import re
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..config import RuntimeOptions
from ..ops import pack
from ..program import Program

# Queue-wait histogram geometry (the profiler, lanes.profile_lanes):
# bucket k counts dispatched messages that waited [2^k, 2^(k+1)) ticks
# between delivery (enqueue stamp) and dispatch; the last bucket is
# open-ended (>= 2^(QW_BUCKETS-1)). Power-of-two buckets keep the
# on-device update a handful of compares (≙ the DTrace scripts'
# quantize() aggregations over the fork's USDT probes).
QW_BUCKETS = 16

# Per-phase window telemetry (ISSUE 19):
# one work-unit counter per scheduler-tick phase, accumulated on device
# in lanes.phase_cost_lanes. Work units are DETERMINISTIC per-phase
# tallies (delivery-list entries gathered, ring slots drained,
# behaviours dispatched, GC bookkeeping rows touched, mailbox slots the
# rebuild gathered) — not wall time — so every dispatch formulation
# produces bit-identical lanes; wall/bytes attribution is the measured
# layer's job (costs.py).
# New lanes go at the END: a snapshot written with fewer restores with
# the missing ones at zero (serialise.py).
PHASE_NAMES = ("delivery", "drain", "dispatch", "gc_mark", "rebuild")
N_PHASES = len(PHASE_NAMES)

# Named scopes on the tick's phases (ISSUE 24): every operation of the
# step carries `pony/<phase>` in its HLO op_name, so a profiler trace
# names device time by phase instead of by fusion number. One
# vocabulary: where a phase coincides with PHASE_NAMES the scope carries
# that name. A scope is written ABSOLUTE (`pony/delivery/rebuild`, not
# `rebuild` inside `pony/delivery`): lax.cond / while put their own
# segments between nested scopes, so a reader takes what follows the
# LAST `pony` segment of an op_name (benchmarks/phase_trace.py). Scopes
# are metadata only — the optimised HLO with `_named_scope` stubbed out
# is the same program (tests/test_profiler.py).
SCOPE_PREFIX = "pony"
STEP_SCOPES = ("unmute", "spawn", "drain", "dispatch", "dispatch/heap",
               # below `dispatch/heap` (a handle checked once a dispatch
               # keeps the bare scope: engine, `pinned`): what a
               # behaviour asked of the pool (api._heap_scoped), and the
               # engine's share — the free list's compaction and the
               # windows handed to the allocating dispatches
               "dispatch/heap/get", "dispatch/heap/set",
               "dispatch/heap/alloc", "dispatch/heap/free",
               "dispatch/heap/reserve",
               "route",
               # a mesh only (route._route): the one sort by
               # destination shard that carries the entries, a
               # contiguous masked slice of them a destination, the
               # all_to_alls, overflow + link mutes — below them the
               # lookup of the sorted targets in the mesh-wide hot word
               # and the mutes it and a full link trigger —; then
               # (deliver_routed) the received buckets' fills and their
               # joining front to front for the short delivery list
               "route/sort", "route/bucket", "route/exchange",
               "route/spill", "route/spill/lookup", "route/spill/mute",
               "route/unpack",
               "delivery", "delivery/plan", "delivery/plan/bounds",
               "delivery/permute", "delivery/rebuild",
               "delivery/rebuild/compact", "delivery/pressure",
               "delivery/pressure/spill", "delivery/pressure/mute",
               "gc_mark", "mute", "vote",
               # a program with device spawns; the collector's own program
               "spawn/free", "spawn/reserve", "spawn/claim",
               "gc_mark/roots", "gc_mark/hop", "gc_mark/sweep")
_named_scope = jax.named_scope      # the one seam the tests stub
# Segments of an op_name that JAX writes itself: a scope's path ends at
# the first one, so no scope may be named like one.
_JAX_SEGMENT = re.compile(
    r"^(cond|while|body|scan|branch_\d+_fun|cond_fun|body_fun|pjit|"
    r"closed_call|core_call|checkpoint|remat|shard_map|custom_jvp_call|"
    r"custom_vjp_call|.*\(.*)$")


def scope_of(op_name):
    """The phase an HLO `op_name` belongs to: what follows its LAST
    `pony` segment, up to the first segment JAX wrote itself and without
    the final segment, which names the primitive — `jit(multi)/while/
    body/pony/delivery/cond/branch_1_fun/pony/delivery/rebuild/gather`
    is `delivery/rebuild`. None for an op_name under no scope. The rule
    the scopes are written for (absolute, above); every reader of a
    trace or of a compiled text applies this one."""
    if not op_name:
        return None
    segments = op_name.split("/")
    if SCOPE_PREFIX not in segments:
        return None
    last = len(segments) - 1 - segments[::-1].index(SCOPE_PREFIX)
    path = []
    for seg in segments[last + 1:-1]:
        if _JAX_SEGMENT.match(seg):
            break
        path.append(seg)
    return "/".join(path) or None


def phase_scope(path: str, when: bool = True):
    """Context manager: the traced operations inside belong to phase
    `path` (one of STEP_SCOPES, a `cohort_scope`, or `analysis` for the
    opt-in lanes). Not `when` a static fact says the operations inside
    are dead in this program (the pool's reservations in a world that
    allocates nothing): a name no compiled operation carries would only
    show in the lowered text."""
    if not when:
        return contextlib.nullcontext()
    return _named_scope(f"{SCOPE_PREFIX}/{path}")


def cohort_scope(type_name: str) -> str:
    """The scope of ONE cohort's share of `dispatch`:
    `dispatch/cohort/<actor type>`, below `dispatch`, so what a reader
    sums under `pony/dispatch` is what it was. The drain inside it keeps
    `pony/drain` and the pool's operations `pony/dispatch/heap`: scopes
    are absolute, the innermost names the operation."""
    return f"dispatch/cohort/{type_name}"


# Arrival ranks one rebuild block gathers for every actor AT MOST (a
# full block pulls as many as its cohort's fullest mailbox holds of it:
# delivery.rebuild_tables): a vreg's sublanes, and RuntimeOptions'
# default `batch` — an actor that keeps taking in more than it drains
# is under pressure, not in steady state.
REBUILD_BLOCK = 8


def rows_of(program: Program, attr: str):
    """A cohort's own `attr` (`mailbox_cap`, `overload_occ`,
    `unmute_occ`: program.Cohort) for every local row. The plain int
    where all cohorts agree — every program none of whose types states a
    MAILBOX_CAP, so its window is the program it was — else an [n_local]
    int32 vector, cohort by cohort, built where it is traced (broadcasts
    and a concatenation, no literal of a million words)."""
    values = [getattr(ch, attr) for ch in program.cohorts]
    if len(set(values)) <= 1:
        return values[0]
    return jnp.concatenate([jnp.full((ch.local_capacity,), v, jnp.int32)
                            for ch, v in zip(program.cohorts, values)])


def pool_index(nslots: int, word, slot):
    """Where word `word` of slot `slot` lies in ONE shard's flat blob
    pool of `nslots` slots (RtState.blob_data): word-major, slots on the
    lanes. Broadcasts; jnp or numpy."""
    return word * nslots + slot


def ring_take(buf_rows, slot):
    """Pull ring-slot `slot[r]` of every actor r: [cap, w1, R] × [R] →
    [w1, R]. The per-lane index varies only over the small static `cap`
    axis, so a static select chain keeps every op a full-width vector op
    (a gather along a tiny major axis would defeat the lane layout —
    see the layout note above)."""
    cap = buf_rows.shape[0]
    out = buf_rows[0]
    for c in range(1, cap):
        out = jnp.where((slot == c)[None, :], buf_rows[c], out)
    return out


# What a tick knows before it is traced, worked out once
# (engine.tick_static) and handed to every phase. `p` shards of `nl` rows,
# first host row `fh`, spill_cap `s_cap` (a mailbox's capacity is its
# cohort's: program.Cohort.mailbox_cap, `rows_of`); `lists`:
# route.ListSizes; `dispatchers`: (run_cohort, cohort) a device cohort.
# `pri_rank`, `n_levels` — delivery priority levels (see
# delivery.deliver): 0 = receiver spill, 1 = host inject, 2+k = sender
# cohort with k-th highest PRIORITY (≙ the fork's actor priority hint
# ordering contenders).
# `cohort_layout` — per-cohort mailbox widths tiling the local row space
# (ALL cohorts, device + host): delivery rebuilds each table at its own
# width.
# `blob_route` — blob migration over the mesh: (mask, iso mask) iff some
# behaviour ROUTES a Blob argument (static mask) and the pool is live
# (see route._route), else None. Iso-mode positions MOVE (source slot
# freed); val-mode (frozen, shared) positions COPY — other readers keep
# the source.
TickStatic = collections.namedtuple(
    "TickStatic", "program opts p nl fh s_cap lists pri_rank n_levels "
    "cohort_layout blob_route dispatchers")


class PhaseCursor:
    """The step is a straight line of phases: `phase("route")` closes
    the scope that was open and opens the next, so the step's body is
    not re-indented under a dozen `with` blocks. Itself a context
    manager that closes the last scope, whatever ends the trace."""

    def __init__(self):
        self._open = None

    def __call__(self, path: str) -> None:
        self._close()
        self._open = phase_scope(path)
        self._open.__enter__()

    def _close(self) -> None:
        if self._open is not None:
            scope, self._open = self._open, None
            scope.__exit__(None, None, None)

    def __enter__(self):
        return self

    def __exit__(self, *_exc):
        self._close()
        return False

# Span-ring record rows (causal tracing, PROFILE.md §10): the layout is
# owned by tracing.py so the host reassembler and the device writer can
# never drift. (trace_id, span_id, parent_span, behaviour_gid,
# actor_gid, enqueue_tick, dispatch_tick, retire_tick.)
from ..tracing import SPAN_ROWS  # noqa: E402  (after QW_BUCKETS on purpose)


def layout_sizes(program: Program, opts: RuntimeOptions):
    """Static per-shard sizes shared by route.list_sizes and init_state:
    (e_out, bucket, n_delivery_entries).

    e_out — outbox entries one shard can emit per tick;
    bucket — per-destination all_to_all bucket (mesh only);
    n_delivery_entries — rows in one shard's delivery list
    (receiver-spill + host inject + incoming), which is also the length
    of the cached delivery plan (see delivery.py). On a mesh that is
    the LONG list, the received buckets as they come (`shards * bucket`
    incoming), and the plan's length; where that is longer than one
    shard's outbox a tick whose arrivals fit delivers over the short
    list, `s + inject + e_out + s` entries (route.list_sizes), and
    keeps its plan in the front of the same arrays."""
    e_out = sum(ch.local_capacity * ch.batch * ch.max_sends
                for ch in program.device_cohorts)
    s = opts.spill_cap
    p = program.shards
    if p > 1:
        if opts.route_bucket > 0:
            bucket = opts.route_bucket
        else:
            # Worst case one shard receives everything; keep buckets at
            # outbox-size/shards ×4 (overflow is safe — it parks in the
            # route spill; opts.route_bucket overrides).
            bucket = max(16, min(e_out + s, 4 * (e_out + s) // p))
        incoming = p * bucket
    else:
        bucket = 0
        incoming = s + e_out          # route-spill passthrough + outbox
    return e_out, bucket, s + opts.inject_slots + incoming


def record_words(opts: RuntimeOptions) -> int:
    """Words in one mailbox ring record: behaviour id + payload +
    trace lanes (w1 below)."""
    return 1 + opts.msg_words + opts.trace_lanes


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RtState:
    """The complete device state of the actor world (one pytree)."""

    # Mailboxes (≙ messageq.c): one lane per actor, device and host
    # cohorts; ring slot and payload word are the (small, static) major
    # axes — see the layout note in the module docstring. PER-COHORT
    # word width (≙ per-type pony_msg_t sizes, genfun.c): each type's
    # table is [cap, 1+W_c, capacity] where W_c = min(opts.msg_words,
    # the cohort's widest behaviour) — a narrow type's million mailboxes
    # stop paying the widest type's HBM footprint. Keys = type names;
    # the last axis is the cohort's shard-major slot axis (like
    # type_state columns). Spills/inject/outbox keep the global width.
    buf: Dict[str, jnp.ndarray]  # {type: [cap, 1+W_c, capacity]} int32
    head: jnp.ndarray         # [N] int32, monotonic pop count
    tail: jnp.ndarray         # [N] int32, monotonic push count

    # Per-actor scheduling flags (≙ actor.h:59-69 flag bits).
    alive: jnp.ndarray        # [N] bool — slot occupied (≙ !PENDINGDESTROY)
    muted: jnp.ndarray        # [N] bool — ≙ FLAG_MUTED; skipped by dispatch
    mute_refs: jnp.ndarray    # [K, N] int32 — global ids of the muting
    #                              receivers (possibly off-shard), slotted
    #                              by ref % K; -1 = empty slot. ≙ the
    #                              mutemap receiver-set per sender
    #                              (mutemap.c; scheduler.c:1478-1635):
    #                              release only when all recover.
    mute_age: jnp.ndarray     # [N] int32 — consecutive ticks spent muted
    #                              (0 when unmuted). Past opts.mute_age_limit
    #                              the unmute pass force-releases: the
    #                              lockstep deadlock-breaker for
    #                              mutual-mute cycles/chains (the
    #                              reference's pre-0.36 backpressure can
    #                              deadlock here; bounded queues + spill
    #                              make periodic release safe for us)
    mute_ovf: jnp.ndarray     # [N] bool — more distinct muters than slots
    #                              (hash collision); release deferred until
    #                              the shard is globally quiet
    pinned: jnp.ndarray       # [N] bool — host holds a ref (GC root,
    #                              ≙ ORCA external rc; see runtime/gc.py)
    pressured: jnp.ndarray    # [N] bool — ≙ FLAG_UNDER_PRESSURE
    #                              (pony_apply_backpressure,
    #                              actor.c:1137-1162): the actor declared
    #                              itself under external pressure; its
    #                              senders mute on send until released

    # Receiver-side overflow spill (local-row targets).
    dspill_tgt: jnp.ndarray    # [P*S] int32 local row, -1 = empty slot
    dspill_sender: jnp.ndarray  # [P*S] int32 sender *global* id (-1 = host)
    dspill_words: jnp.ndarray  # [1+W, P*S] int32
    dspill_count: jnp.ndarray  # [P] int32

    # Sender-side routing spill (global-id targets; used when P > 1).
    rspill_tgt: jnp.ndarray    # [P*S] int32 global id, -1 = empty slot
    rspill_sender: jnp.ndarray  # [P*S] int32 sender global id
    rspill_words: jnp.ndarray  # [1+W, P*S] int32
    rspill_count: jnp.ndarray  # [P] int32
    # Which list delivery ran over and what the route moved, cumulative
    # per shard (list_counters: a leaf only where its path is built).
    # "n_prefix" [P] int32 — the ticks on which this shard delivered
    # over the PREFIX of its list (delivery.deliver: the live entries
    # fitted a quarter of it; 0 in cosort, which has the one length);
    # no leaf where every ring is one rebuild block (counts_prefix).
    # A mesh only, nothing being routed on one chip: "n_routed" [P]
    # int32 — entries this shard placed
    # in an all_to_all bucket (a message counts once, in the tick it
    # ships: one parked in the route spill counts when its retry does);
    # "n_routed_remote" [P] int32 — those of them whose bucket went to
    # ANOTHER shard; "n_unpacked" [P] int32 — the ticks on which this
    # shard delivered over the short list (route._route_unpack: what
    # arrived fitted one shard's outbox); "n_route_pressure" [P] int32 —
    # the ticks on which this shard looked its sorted entries' targets
    # up in the mesh-wide hot word (route._route_spill: world bit 0 or 3
    # was set; 0 on a mesh where nobody declares pressure and nobody is
    # overloaded); "n_route_prefix" [P] int32 — those ticks, and the
    # ticks of a link overflow, on which this shard's valid entries
    # fitted the PREFIX of the sorted ones and `_route_spill` read no
    # further (a quarter: delivery.prefix_len; = "n_route_pressure" in
    # a world whose entries always fit and whose links never overflow;
    # a quiet tick reads neither length and counts 0; state only, not a
    # StepAux leaf); "n_remote_mutes" [P] int32 — the senders routing
    # muted behind a receiver on ANOTHER shard (overloaded, under
    # declared pressure, or at the end of a full link). Read through
    # Runtime.counter(), which sums them over the mesh like n_processed.
    route_counts: Dict[str, jnp.ndarray]

    spill_overflow: jnp.ndarray  # [P] bool — a spill overflowed (fatal)

    # Program-wide control (≙ pony_exitcode / quiescence token state).
    exit_flag: jnp.ndarray    # [P] bool
    exit_code: jnp.ndarray    # [P] int32
    step_no: jnp.ndarray      # [P] int32

    # Telemetry accumulators (≙ --ponyanalysis counters, analysis.c);
    # int32 per shard, host accumulates mod-2^32 deltas.
    n_processed: jnp.ndarray  # [P] int32 — behaviours dispatched
    n_delivered: jnp.ndarray  # [P] int32 — messages accepted into mailboxes
    n_rejected: jnp.ndarray   # [P] int32 — capacity rejections (→ spill)
    n_badmsg: jnp.ndarray     # [P] int32 — wrong-type behaviour ids dropped
    n_deadletter: jnp.ndarray  # [P] int32 — sends to dead/unspawned slots
    n_mutes: jnp.ndarray      # [P] int32 — mute transitions
    n_spawned: jnp.ndarray    # [P] int32 — device-side ctx.spawn() claims
    n_destroyed: jnp.ndarray  # [P] int32 — ctx.destroy() completions
    spawn_fail: jnp.ndarray   # [P] bool — sticky: a wanted spawn had no slot
    n_collected: jnp.ndarray  # [P] int32 — actors freed by GC (gc.py)
    last_error: jnp.ndarray   # [N] int32 — latest ctx.error_int code
    #                              (0 = none; ≙ fork's pony_error_code)
    last_error_loc: jnp.ndarray  # [N] int32 — trace-site id of that
    #                              error (errors.error_site resolves it;
    #                              ≙ fork's __error_loc string table)
    n_errors: jnp.ndarray     # [P] int32 — error_int events

    # Per-event trace ring (analysis level 3; ≙ the fork's per-event
    # analysis rows, analysis.c:587-692): row0 = event id (analysis.py
    # EVENT_NAMES), row1 = actor gid, row2 = step. Zero-length when
    # analysis < 3 (the lanes compile away).
    ev_data: jnp.ndarray      # [3, P*EV] int32
    ev_count: jnp.ndarray     # [P] int32 — valid entries since last drain
    ev_dropped: jnp.ndarray   # [P] int32 — lifetime overflow drops

    # Per-behaviour profiler (analysis level >= 1; ≙ the fork's
    # per-actor --ponyanalysis records, analysis.h:16-31 — per
    # (cohort, behaviour) here because the cohort IS the TPU unit of
    # attribution). All cumulative int32, indexed by GLOBAL behaviour
    # id (which encodes the cohort: each type owns a contiguous gid
    # range) or by device-cohort index. Zero-length when analysis < 1
    # so every lane compiles away (lanes.profile_lanes is never even
    # traced at level 0 — the zero-cost-when-off discipline).
    beh_runs: jnp.ndarray       # [P*NB] int32 — dispatches per behaviour
    beh_delivered: jnp.ndarray  # [P*NB] int32 — mailbox acceptances per
    #                               behaviour (host-cohort deliveries
    #                               included: the host drains them)
    beh_rejected: jnp.ndarray   # [P*NB] int32 — capacity rejections by
    #                               target behaviour (per-tick semantics
    #                               match n_rejected: a parked message
    #                               re-rejected next tick counts again)
    coh_mute_ticks: jnp.ndarray  # [P*ND] int32 — muted actor-ticks per
    #                               device cohort (the integral of
    #                               muted_now over ticks)
    qwait_hist: jnp.ndarray     # [P*ND*QW_BUCKETS] int32 — queue-wait
    #                               histogram per device cohort: bucket k
    #                               = waited [2^k, 2^(k+1)) ticks from
    #                               delivery to dispatch
    qwait_enq: Dict[str, jnp.ndarray]  # {type: [cap, capacity]} int32 —
    #                               enqueue-step stamp per ring slot
    #                               (device cohorts; {} when analysis<1)
    phase_cost: jnp.ndarray     # [P*N_PHASES] int32 — cumulative
    #                               per-phase work units (PHASE_NAMES
    #                               order: delivery gather entries,
    #                               mailbox ring slots drained,
    #                               behaviours dispatched, GC-mark
    #                               bookkeeping rows). Zero-length when
    #                               analysis < 1

    # Causal tracing (analysis >= 3 AND trace_sample > 0; PROFILE.md
    # §10; ≙ the fork's per-event rows following one message
    # send→dispatch, analysis.c:587-692). {} / zero-length when off —
    # the whole subsystem compiles away (lanes.trace_span_lanes is
    # never traced; tests/test_tracing.py pins jaxpr identity).
    trace_buf: Dict[str, jnp.ndarray]  # {type: [cap, 2, capacity]}
    #                               per-ring-slot (trace_id,
    #                               parent_span) side lanes, written by
    #                               delivery with the SAME gather as the
    #                               payload rebuild; -1 = untraced.
    #                               ALL cohorts (the host drain reads
    #                               host-cohort lanes to continue
    #                               traces through host behaviours)
    span_data: jnp.ndarray    # [SPAN_ROWS, P*TS] int32 — span ring
    #                               (tracing.SPAN_ROWS rows; TS =
    #                               opts.trace_slots), drained by the
    #                               analysis writer / Runtime.traces()
    span_count: jnp.ndarray   # [P] int32 — valid entries since drain
    span_dropped: jnp.ndarray  # [P] int32 — lifetime overflow drops
    span_next: jnp.ndarray    # [P] int32 — monotonic span-id counter
    #                               (device ids: even, unique across
    #                               shards — see tracing.py)

    # Cached delivery plan (see delivery.py): when consecutive ticks carry
    # the same (target, level) key vector — any topology-stable traffic —
    # the sort permutation and segment bounds are reused instead of
    # re-sorted. The TPU analog of the reference's O(1) pointer-based
    # mailbox push (messageq.c:102-160): the "pointer" is a delivery plan
    # amortised across ticks.
    plan_key: jnp.ndarray     # [P*E] int32, -1 = invalid (forces replan)
    plan_perm: jnp.ndarray    # [P*E] int32 stable-sort permutation
    plan_bounds: jnp.ndarray  # [P*(n_local+1)] int32 segment bounds

    # Device blob pool (≙ actor-heap message payloads — pony_alloc_msg
    # and per-type object graphs, pony.h:332-360; see ops.pack.Blob and
    # api.Context.blob_*): message payloads wider than msg_words live
    # here and ride messages as moved-unique HANDLES (global id =
    # shard * blob_slots + slot; -1 null). Planar like every hot array,
    # word index major, blob slot minor (lanes) — and FLAT: word w of a
    # shard's slot s lies at w*BS + s of the shard's block (shard-major,
    # so the one axis shards). A behaviour reads and writes single words
    # (api.Context.blob_get / blob_set), which the chip does in place on
    # a 1-D array only: of a [W, BS] table it first makes a flat copy,
    # the whole pool a batch slot (PERF.md, PR 33). `pool_index` below
    # is the one place that knows the order. Zero-size when
    # RuntimeOptions.blob_slots == 0 — all plumbing compiles away.
    blob_data: jnp.ndarray    # [P*blob_words*BS] int32 payload words
    blob_used: jnp.ndarray    # [P*BS] bool — slot allocated
    blob_len: jnp.ndarray     # [P*BS] int32 — logical word count
    blob_gen: jnp.ndarray     # [P*BS] int32 — slot generation, bumped on
    #   each alloc and carried in the HANDLE's high bits (ops.pack
    #   BLOB_GEN_SHIFT): a stale handle to a recycled slot mismatches
    #   and reads null — ABA protection for the iso discipline's
    #   dynamic escape hatches (forged ints, post-sweep stragglers)
    blob_fail: jnp.ndarray    # [P] bool — sticky: an alloc found the
    #   POOL exhausted (no free slot in the compacted free list —
    #   raise RuntimeOptions.blob_slots or free faster)
    blob_budget_fail: jnp.ndarray  # [P] bool — sticky: an alloc fell
    #   outside the actor's per-tick reservation BUDGET (more
    #   allocating dispatches than BLOB_DISPATCHES, with free slots
    #   possibly plentiful — raise the class's BLOB_DISPATCHES). Kept
    #   separate from blob_fail so the host error names the right knob
    #   (≙ SpawnCapacityError naming its own)
    n_blob_alloc: jnp.ndarray   # [P] int32 — lifetime allocs
    n_blob_free: jnp.ndarray    # [P] int32 — lifetime frees
    n_blob_remote: jnp.ndarray  # [P] int32 — Blob args that arrived
    #   undereferenceable: host-injected off-shard handles (allocate
    #   with blob_store(near=...)), or migration drops when the
    #   receiving shard's pool was full (loud data loss, never
    #   corruption)
    n_blob_moved: jnp.ndarray   # [P] int32 — blobs that MIGRATED in
    #   with a routed message (route._route: payload rides the
    #   all_to_all, fresh local slot + generation at the receiver)

    # Mesh-wide world facts from the previous tick's packed vote, stored
    # shard-uniform: bit0 = any pressured, bit1 = any muted, bit2 = any
    # route-spill entries, bit3 (a mesh only) = any row overloaded (over
    # its overload line, or anything parked in a receiver spill). They
    # gate the per-tick all_gathers/psums the
    # backpressure machinery needs only when those states exist — a quiet
    # mesh tick runs collective-free except routing + one vote
    # (≙ idle costing ~nothing, the fork's README.md:8-10 thesis).
    world_bits: jnp.ndarray   # [P] int32

    # Per-type state columns: {type_name: {field: [cohort.capacity] array}}
    # (leading axis shard-major; see Cohort.slot_to_col).
    type_state: Dict[str, Dict[str, jnp.ndarray]]


# The route's counters (RtState.route_counts), a mesh only; and every
# name that dict may hold (list_counters: which of them a program has).
ROUTE_COUNTERS = ("n_routed", "n_routed_remote", "n_unpacked",
                  "n_route_pressure", "n_route_prefix", "n_remote_mutes")
LIST_COUNTERS = ROUTE_COUNTERS + ("n_prefix",)


def counts_prefix(program: Program) -> bool:
    """Whether the state counts the ticks delivered over the list's
    prefix ("n_prefix": delivery.deliver, module docstring 2b). Not
    where one rebuild block covers every ring: that window holds
    delivery at the one length, is counted in operations (delivery.py,
    4d) and stays the program it was."""
    cap = rows_of(program, "mailbox_cap")
    return not (isinstance(cap, int) and cap <= REBUILD_BLOCK)


def counts_pool(program: Program) -> bool:
    """Whether the window's aux carries the pool's books (engine.
    StepAux.pool): a pool some device cohort can allocate in or free a
    message's payload from — it declares MAX_BLOBS, or a behaviour of
    it takes a Blob argument. A pool whose handles are state fields set
    at build time alone (GUPS's table) has no such cohort, and its
    window stays the program it was."""
    return program.opts.blob_slots > 0 and any(
        ch.blob_sites or any(pack.is_blob(s) for b in ch.behaviours
                             for s in b.arg_specs)
        for ch in program.device_cohorts)


def list_counters(program: Program) -> tuple:
    """The leaves of RtState.route_counts for this program."""
    return ((ROUTE_COUNTERS if program.shards > 1 else ())
            + (("n_prefix",) if counts_prefix(program) else ()))


# The int32 word tables that serialise.save(packed=True) stores as an
# int16 lane plane + an int32 escape plane: mailbox ring records, both
# spill word tables and the per-message trace lanes (behaviour ids and
# most payload words are small). Listed here, next to the layout they
# describe.
PACKED_WORD_FIELDS = ("buf", "dspill_words", "rspill_words", "trace_buf")


def init_state(program: Program, opts: RuntimeOptions) -> RtState:
    """Allocate the zeroed actor world for a finalized program."""
    assert program.frozen, "finalize() the Program first"
    n = program.total
    p = program.shards
    # Spill tables carry the full in-flight word width: payload plus
    # the (trace_id, parent_span) lanes when tracing is on — a parked
    # message must keep its causal context across the retry.
    w1 = 1 + opts.msg_words + opts.trace_lanes
    s = opts.spill_cap * p
    _, _, n_entries = layout_sizes(program, opts)
    i32 = jnp.int32
    # Profiler matrix sizes: zero when analysis < 1 (lanes compile away).
    nb = len(program.behaviour_table) if opts.analysis >= 1 else 0
    nd = len(program.device_cohorts) if opts.analysis >= 1 else 0

    type_state: Dict[str, Dict[str, Any]] = {}
    for cohort in program.cohorts:
        fields = {}
        for fname, spec in cohort.atype.field_specs.items():
            from ..ops.pack import F32, null_word
            dtype = jnp.float32 if spec is F32 else jnp.int32
            # Ref/blob fields default to -1 ("no actor"/"no blob" — id 0
            # is real for both; the GC tracer treats >= 0 as an edge).
            fields[fname] = jnp.full((cohort.capacity,),
                                     null_word(spec), dtype)
        type_state[cohort.atype.__name__] = fields

    return RtState(
        buf={cohort.atype.__name__:
             jnp.zeros((cohort.mailbox_cap, 1 + cohort.msg_words,
                        cohort.capacity), i32)
             for cohort in program.cohorts},
        head=jnp.zeros((n,), i32),
        tail=jnp.zeros((n,), i32),
        alive=jnp.zeros((n,), jnp.bool_),
        muted=jnp.zeros((n,), jnp.bool_),
        mute_refs=jnp.full((opts.mute_slots, n), -1, i32),
        mute_age=jnp.zeros((n,), i32),
        mute_ovf=jnp.zeros((n,), jnp.bool_),
        pinned=jnp.zeros((n,), jnp.bool_),
        pressured=jnp.zeros((n,), jnp.bool_),
        dspill_tgt=jnp.full((s,), -1, i32),
        dspill_sender=jnp.full((s,), -1, i32),
        dspill_words=jnp.zeros((w1, s), i32),
        dspill_count=jnp.zeros((p,), i32),
        rspill_tgt=jnp.full((s,), -1, i32),
        rspill_sender=jnp.full((s,), -1, i32),
        rspill_words=jnp.zeros((w1, s), i32),
        rspill_count=jnp.zeros((p,), i32),
        route_counts={name: jnp.zeros((p,), i32)
                      for name in list_counters(program)},
        spill_overflow=jnp.zeros((p,), jnp.bool_),
        exit_flag=jnp.zeros((p,), jnp.bool_),
        exit_code=jnp.zeros((p,), i32),
        step_no=jnp.zeros((p,), i32),
        n_processed=jnp.zeros((p,), i32),
        n_delivered=jnp.zeros((p,), i32),
        n_rejected=jnp.zeros((p,), i32),
        n_badmsg=jnp.zeros((p,), i32),
        n_deadletter=jnp.zeros((p,), i32),
        n_mutes=jnp.zeros((p,), i32),
        n_spawned=jnp.zeros((p,), i32),
        n_destroyed=jnp.zeros((p,), i32),
        spawn_fail=jnp.zeros((p,), jnp.bool_),
        n_collected=jnp.zeros((p,), i32),
        last_error=jnp.zeros((n,), i32),
        last_error_loc=jnp.zeros((n,), i32),
        n_errors=jnp.zeros((p,), i32),
        ev_data=jnp.zeros(
            (3, p * (opts.analysis_events if opts.analysis >= 3 else 0)),
            i32),
        ev_count=jnp.zeros((p,), i32),
        ev_dropped=jnp.zeros((p,), i32),
        beh_runs=jnp.zeros((p * nb,), i32),
        beh_delivered=jnp.zeros((p * nb,), i32),
        beh_rejected=jnp.zeros((p * nb,), i32),
        coh_mute_ticks=jnp.zeros((p * nd,), i32),
        qwait_hist=jnp.zeros((p * nd * QW_BUCKETS,), i32),
        qwait_enq=({ch.atype.__name__:
                    jnp.zeros((ch.mailbox_cap, ch.capacity), i32)
                    for ch in program.device_cohorts}
                   if opts.analysis >= 1 else {}),
        phase_cost=jnp.zeros(
            (p * (N_PHASES if opts.analysis >= 1 else 0),), i32),
        trace_buf=({ch.atype.__name__:
                    jnp.full((ch.mailbox_cap, 2, ch.capacity), -1, i32)
                    for ch in program.cohorts}
                   if opts.tracing else {}),
        span_data=jnp.zeros(
            (SPAN_ROWS, p * (opts.trace_slots if opts.tracing else 0)),
            i32),
        span_count=jnp.zeros((p,), i32),
        span_dropped=jnp.zeros((p,), i32),
        span_next=jnp.zeros((p,), i32),
        plan_key=jnp.full((p * n_entries,), -1, i32),
        plan_perm=jnp.zeros((p * n_entries,), i32),
        plan_bounds=jnp.zeros((p * (program.n_local + 1),), i32),
        world_bits=jnp.zeros((p,), i32),
        blob_data=jnp.zeros((p * opts.blob_words * opts.blob_slots,), i32),
        blob_used=jnp.zeros((p * opts.blob_slots,), jnp.bool_),
        blob_len=jnp.zeros((p * opts.blob_slots,), i32),
        blob_gen=jnp.zeros((p * opts.blob_slots,), i32),
        blob_fail=jnp.zeros((p,), jnp.bool_),
        blob_budget_fail=jnp.zeros((p,), jnp.bool_),
        n_blob_alloc=jnp.zeros((p,), i32),
        n_blob_free=jnp.zeros((p,), i32),
        n_blob_remote=jnp.zeros((p,), i32),
        n_blob_moved=jnp.zeros((p,), i32),
        type_state=type_state,
    )


def geometry_descriptor(program: Program, opts: RuntimeOptions):
    """The layout facts a snapshot must carry so a restore can re-lay-out
    the SoA arrays into a DIFFERENT geometry (serialise.py): everything
    that sizes an array without changing program STRUCTURE. Cohorts are
    in declaration order (behaviour gids depend on it — covered by the
    structural fingerprint); slots are the geometry-independent actor
    identity (slot s of cohort C is the same actor whatever the shard
    count or capacity)."""
    assert program.frozen
    return {
        "shards": program.shards,
        "n_local": program.n_local,
        "total": program.total,
        "mailbox_cap": opts.mailbox_cap,
        "msg_words": opts.msg_words,
        "trace_lanes": opts.trace_lanes,
        "spill_cap": opts.spill_cap,
        "mute_slots": opts.mute_slots,
        "blob_slots": opts.blob_slots,
        "blob_words": opts.blob_words,
        "analysis": opts.analysis,
        "trace_slots": opts.trace_slots if opts.tracing else 0,
        "analysis_events": (opts.analysis_events
                            if opts.analysis >= 3 else 0),
        "cohorts": [{
            "name": c.atype.__name__,
            "capacity": c.capacity,
            "local_capacity": c.local_capacity,
            "local_start": c.local_start,
            "host": bool(c.host),
            "msg_words": c.msg_words,
            # its own ring depth, where it states one (a snapshot of a
            # program that states none reads as it always did)
            **({"mailbox_cap": c.mailbox_cap}
               if c.mailbox_cap != opts.mailbox_cap else {}),
        } for c in program.cohorts],
    }


def state_partition_specs(program: Program, opts: RuntimeOptions):
    """PartitionSpec pytree matching RtState: every array shards its
    LAST axis over the 'actors' mesh axis (the lane/actor dimension —
    see the layout note above); leading static dims replicate."""
    from jax.sharding import PartitionSpec as P
    shapes = jax.eval_shape(lambda: init_state(program, opts))
    return jax.tree.map(
        lambda leaf: P(*([None] * (len(leaf.shape) - 1) + ["actors"])),
        shapes)
