"""Delivery/dispatch autotuner: the formulation A/B as code
(PROFILE.md §6).

The engine has formulation choices with no shape- or hardware-independent
winner: delivery as a cached stable-sort plan + permutation gathers
("plan") vs one multi-operand co-sort ("cosort"); the mailbox drain as an
XLA select-chain vs a Pallas kernel (`pallas`); dispatch as planar XLA vs
the fused Pallas kernel (`pallas_fused`). CAF's OpenCL actor backend
reached the same conclusion for behaviour offload (Wahlster et al.,
arXiv:1709.07781 — the runtime must pick the execution configuration
per workload), as did Halide's schedule search (arXiv:2105.12858): the
choice is a measurement, not a design constant.

So ``RuntimeOptions(delivery="auto")`` (and ``pallas="auto"`` /
``pallas_fused="auto"``) defers the choice to ``Runtime.start()``:

1. enumerate the eligible concrete variants (`variants`);
2. time each on a synthetic busy workload built from the program's REAL
   cohort shapes (`make_workload`) with a `lax.fori_loop` window over
   the real step (`engine.build_forced_window`) — in-executable ticks
   divided by trip count, so the per-call launch cost divides out;
3. pick the minimum (`decide`) and record the full table;
4. persist the decision in an on-disk cache keyed by (platform, jax
   version, cohort layout, geometry) so steady-state starts skip
   calibration entirely (`load_cached`/`store_cached`).

Semantics are untouched by construction: calibration runs on throwaway
copies of the state, and the only thing "auto" changes is which already-
equivalence-tested formulation executes (tests/test_differential.py and
tests/test_delivery_modes.py are the oracle that they agree).

The synthetic workload seeds every device mailbox full of the cohort's
first behaviour and parks a full receiver-spill aimed at one victim
actor, so both the dispatch path (planar evaluation of every behaviour)
and the delivery path (full-width sort + rebuild, with real accepted
messages every tick) stay busy for the whole window. The measured regime
re-sorts every tick (spill contents shift), i.e. it prices "plan" at its
cache-MISS cost — conservative for plan, exact for cosort; the recorded
table says so.

Also here: `enable_compile_cache` wires jax's persistent compilation
cache for Runtime/bench/chip_smoke.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import statistics
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .config import RuntimeOptions, auto_fields

# Option fields a variant may override — the tuner must never touch a
# field that changes Program layout or state shapes (the calibration
# template and the runtime's real jitted step share both).
VARIANT_FIELDS = ("delivery", "pallas", "pallas_fused")


# ---------------------------------------------------------------------------
# cache locations

# Both caches default to ONE fixed place under the checkout (git-ignored
# `.cache/`): never `~`, a temp name, a pid or a time. The directory is
# part of jax's cache key handling (a directory that moves never hits),
# and two checkouts on one machine must not inherit each other's
# formulation choice or converged window.
CACHE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".cache", "ponyc_tpu")


def tuning_cache_dir(opts: RuntimeOptions) -> Optional[str]:
    """The tuning-decision cache directory: opts.tuning_cache is
    "auto" ($PONY_TPU_TUNING_CACHE, else CACHE_ROOT/tuning), "off"
    (None), or an explicit directory."""
    setting = opts.tuning_cache
    if setting in ("", "auto"):
        setting = os.environ.get("PONY_TPU_TUNING_CACHE", "") \
            or os.path.join(CACHE_ROOT, "tuning")
    if setting.lower() in ("off", "0"):
        return None
    return setting


def enable_compile_cache(setting: str = "auto") -> Optional[str]:
    """Turn on jax's persistent compilation cache ("off" leaves jax
    alone). Returns the directory in use, or None.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache can be placed
    from outside: jax reads the variable itself and this function sets
    no directory at all. Otherwise the directory is the fixed
    CACHE_ROOT/xla. Idempotent; call before the first compile that
    should be cached (Runtime.start() does).

    CPU guard: on the CPU backend the cache stays off unless
    PONY_TPU_COMPILE_CACHE_FORCE=1 (the re-test hook). Re-tested on
    jaxlib 0.9.0 (PR 21): single-device worlds reload soundly (fuzz,
    ring, gc, run-loop and differential suites pass cold and warm — the
    jaxlib 0.4.37 state corruption is gone), but a RELOADED meshed
    executable deadlocks its own collectives — on a warm cache
    tests/test_mesh_pressure.py::test_programmatic_backpressure_on_mesh
    dies in rendezvous.cc ("Expected 4 threads to join the rendezvous,
    but only 2 of them arrived") — and every reload logs a 2 KB
    cpu_aot_loader feature-mismatch line. The start-up this cache
    attacks is the accelerator's anyway."""
    if setting == "off":
        return None
    import jax
    if jax.default_backend() == "cpu" and os.environ.get(
            "PONY_TPU_COMPILE_CACHE_FORCE", "0") != "1":
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CACHE_ROOT, "xla")
        if jax.config.jax_compilation_cache_dir != path:
            os.makedirs(path, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", path)
    # Start-up is what the cache is for: keep every executable, not
    # only the slow-to-compile ones (a world's set-up runs dozens of
    # small scatter/gather programs besides the step and the window).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # The key covers the HLO's metadata too. jax leaves it out by
    # default, and an executable reloaded from an entry that another
    # build wrote then carries THAT build's op_names: a profile would
    # show the tick's phases (`pony/<phase>`, state.STEP_SCOPES) under
    # stale names, or under none (seen on the v5e, PR 24: an entry
    # written by a build without the scopes served a nameless window).
    # The price is a recompile when only line numbers moved.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path


# ---------------------------------------------------------------------------
# variant enumeration


def _dispatching(program):
    """The device cohorts a dispatch kernel would actually run."""
    return [ch for ch in program.device_cohorts if ch.behaviours]


def pallas_refusal(program) -> Optional[str]:
    """Why `pallas=True` cannot run as asked on this program (the first
    cohort the drain kernel cannot tile), or None."""
    from .ops import mailbox_kernel as mk
    for ch in _dispatching(program):
        reason = mk.refusal(ch)
        if reason:
            return reason
    return None


def fused_refusal(program, opts: RuntimeOptions) -> Optional[str]:
    """Why `pallas_fused=True` cannot run as asked on this program (the
    first cohort the fused kernel cannot host — ops.fused_dispatch.
    refusal, with synchronous construction discovered via the verify
    pass's probe tracing, the same fact the engine's own probe finds),
    or None."""
    from . import verify
    from .ops import fused_dispatch as fd
    for ch in _dispatching(program):
        sync_init = any(verify.behaviour_effects(
            b, ch.atype, msg_words=opts.msg_words,
            default_max_sends=opts.max_sends).sync_spawns
            for b in ch.behaviours)
        reason = fd.refusal(ch, opts, sync_init)
        if reason:
            return reason
    return None


def check_requested(program, opts: RuntimeOptions) -> None:
    """Runtime.start()'s gate: an EXPLICITLY requested kernel that cannot
    run as asked raises here, naming the cohort and the reason — it
    never gives way to the XLA path without a word. ("auto" values are
    resolved before this runs and only ever pick variants with no
    refusal, see `variants`.)"""
    asked = []
    if opts.delivery == "pallas_mega":
        from .ops import megakernel     # always Mosaic's words on a TPU
        asked.append(('delivery="pallas_mega"',
                      megakernel.refusal(program, opts)))
    if opts.pallas is True:
        asked.append(("pallas=True", pallas_refusal(program)))
    if opts.pallas_fused is True:
        asked.append(("pallas_fused=True", fused_refusal(program, opts)))
    for what, reason in asked:
        if reason:
            raise ValueError(f"{what} cannot be honoured — {reason}")


def variants(program, opts: RuntimeOptions) -> List[Tuple[str, Dict]]:
    """Ordered (name, overrides) candidates for the opts' "auto" fields.
    The first entry is the baseline (plan / kernels off); `decide`
    breaks ties toward earlier entries, so noise can never flip a dead
    heat away from the safe default. A kernel is a candidate only where
    it would run on EVERY dispatching cohort (no refusal) — a variant
    that half-applies is the baseline wearing a costume. The window
    megakernel is never a candidate: it does not lower on TPU
    (ops/megakernel.py) and on CPU only runs interpreted."""
    busy = bool(_dispatching(program))
    deliveries = (["plan", "cosort"] if opts.delivery == "auto"
                  else [opts.delivery])
    pallas_vals = ([False, True]
                   if (opts.pallas == "auto" and busy
                       and pallas_refusal(program) is None)
                   else [False if opts.pallas == "auto" else opts.pallas])
    fused_vals = ([False, True]
                  if (opts.pallas_fused == "auto" and busy
                      and fused_refusal(program, opts) is None)
                  else [False if opts.pallas_fused == "auto"
                        else opts.pallas_fused])
    out: List[Tuple[str, Dict]] = []
    for f in fused_vals:
        for p in pallas_vals:
            for d in deliveries:
                name = d + ("+pallas" if p else "") + ("+fused" if f else "")
                out.append((name, {"delivery": d, "pallas": p,
                                   "pallas_fused": f}))
    return out


def decide(table: Dict[str, Optional[float]],
           order: Optional[List[str]] = None) -> Optional[str]:
    """The winning variant: minimum tick_ms, exact ties broken toward
    the earlier entry in `order` (insertion order by default — the
    baseline). Entries with None (variant failed to build/run) never
    win. Deterministic given the table — the property the tests pin."""
    order = list(table.keys()) if order is None else order
    best = None
    for name in order:
        t = table.get(name)
        if t is None:
            continue
        if best is None or t < table[best]:
            best = name
    return best


# ---------------------------------------------------------------------------
# the decision-table key


def tuning_key(program, opts: RuntimeOptions) -> Dict[str, Any]:
    """Everything the decision legitimately depends on — backend,
    compiler version, cohort layout, geometry — and nothing it doesn't
    (actor field VALUES don't change op shapes). Same key ⇒ the cached
    winner transfers."""
    import jax
    dev = jax.devices()[0]
    cohorts = [
        {"type": ch.atype.__name__, "capacity": int(ch.capacity),
         "batch": int(ch.batch), "max_sends": int(ch.max_sends),
         "msg_words": int(ch.msg_words),
         "behaviours": len(ch.behaviours),
         "host": bool(ch.host), "blobs": bool(ch.uses_blobs)}
        for ch in program.cohorts]
    geometry = {f: getattr(opts, f) for f in (
        "mailbox_cap", "msg_words", "batch", "max_sends", "spill_cap",
        "inject_slots", "mesh_shards", "route_bucket", "mute_slots",
        "dispatch_gating", "blob_slots", "blob_words")}
    return {
        # v3: delivery="pallas_mega" LEFT the variant space (it does
        # not lower on TPU, ops/megakernel.py) — a v2 record naming it
        # the winner must recalibrate, not be refused at start().
        "v": 3,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "jax": jax.__version__,
        "auto": sorted(auto_fields(opts)),
        "fixed": {f: getattr(opts, f) for f in VARIANT_FIELDS
                  if getattr(opts, f) != "auto"},
        "geometry": geometry,
        "cohorts": cohorts,
    }


def cache_path(cache_dir: str, key: Dict[str, Any]) -> str:
    blob = json.dumps(key, sort_keys=True).encode()
    return os.path.join(cache_dir,
                        hashlib.sha256(blob).hexdigest()[:24] + ".json")


def load_cached(cache_dir: Optional[str],
                key: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The cached record for `key`, or None on miss/corruption (a
    corrupt file recalibrates — and is then overwritten — rather than
    erroring a start)."""
    if cache_dir is None:
        return None
    path = cache_path(cache_dir, key)
    try:
        with open(path) as f:
            rec = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(rec, dict) or rec.get("key") != key \
            or not isinstance(rec.get("chosen"), dict):
        return None
    return rec


def store_cached(cache_dir: Optional[str], key: Dict[str, Any],
                 record: Dict[str, Any]) -> Optional[str]:
    """Best-effort persist (atomic rename; an unwritable cache dir never
    fails the start). Returns the path written, or None."""
    if cache_dir is None:
        return None
    path = cache_path(cache_dir, key)
    try:
        os.makedirs(cache_dir, exist_ok=True)
        tmp = path + f".tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(record, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        return None
    return path


# ---------------------------------------------------------------------------
# the synthetic calibration workload


def make_workload(program, opts: RuntimeOptions, state):
    """A throwaway busy state on the program's REAL cohort shapes.

    Built from the fresh post-start() state (all-zero mailboxes) by
    sharding-preserving array ops:

    - every device-cohort actor is alive with a FULL mailbox of its
      cohort's first behaviour (zero args) — the dispatch path runs its
      full planar cost while those drain (`ceil(cap/batch)` ticks), and
      the outbox keeps delivery's sort at full static width every tick;
    - the receiver spill is parked full, aimed at one victim actor
      (the first device cohort's row 0) — each tick the victim drains
      `batch` and delivery re-accepts `batch` spill entries, so REAL
      accepted messages flow through the sort/rebuild/pressure paths
      for ~spill_cap/batch sustained ticks, far past any window length
      the tuner uses.

    Values are garbage by design; the state is never installed — "auto"
    may change speed only, never semantics.
    """
    import jax.numpy as jnp

    cap = opts.mailbox_cap
    p = program.shards
    nl = program.n_local
    victim = None
    mask_local = np.zeros((nl,), bool)
    for ch in program.device_cohorts:
        mask_local[ch.local_start:ch.local_stop] = True
        if victim is None and ch.behaviours:
            victim = ch
    if not mask_local.any():
        return None, 0
    mask = jnp.asarray(np.tile(mask_local, p))

    new_buf = dict(state.buf)
    for ch in program.device_cohorts:
        gid0 = ch.behaviours[0].global_id if ch.behaviours else -7
        new_buf[ch.atype.__name__] = \
            state.buf[ch.atype.__name__].at[:, 0, :].set(jnp.int32(gid0))

    kw = dict(
        buf=new_buf,
        alive=state.alive | mask,
        tail=jnp.where(mask, jnp.int32(cap), state.tail),
    )
    sustain = max(1, cap // max(1, opts.batch))
    if victim is not None:
        vgid = victim.behaviours[0].global_id
        kw.update(
            dspill_tgt=state.dspill_tgt * 0 + jnp.int32(victim.local_start),
            dspill_sender=state.dspill_sender * 0 - 1,
            dspill_words=state.dspill_words.at[0, :].set(jnp.int32(vgid)),
            dspill_count=state.dspill_count * 0 + jnp.int32(opts.spill_cap),
        )
        sustain = max(sustain, opts.spill_cap // max(1, victim.batch))
    return dataclasses.replace(state, **kw), sustain


# ---------------------------------------------------------------------------
# calibration + resolution


def _window_ticks(opts: RuntimeOptions, sustain: int) -> int:
    if opts.tuning_ticks > 0:
        return opts.tuning_ticks
    return max(2, min(16, sustain))


def calibrate(program, opts: RuntimeOptions, mesh, state,
              names_overrides: List[Tuple[str, Dict]],
              ) -> Tuple[Dict[str, Optional[float]], Dict[str, Any]]:
    """Time every candidate on the synthetic workload. Returns
    ({name: tick_ms or None}, detail) — a variant that fails to
    build/run records None and its error, and says so ONCE on stderr
    with its name, instead of failing the start or losing in silence
    (e.g. a Mosaic lowering refused on a new backend)."""
    import jax
    import jax.numpy as jnp
    from .runtime import engine

    template, sustain = make_workload(program, opts, state)
    detail: Dict[str, Any] = {"errors": {}}
    table: Dict[str, Optional[float]] = {}
    if template is None:          # host-only program: nothing to measure
        for name, _ov in names_overrides:
            table[name] = None
        detail["skipped"] = "no device cohorts"
        return table, detail

    k = _window_ticks(opts, sustain)
    repeats = opts.tuning_repeats
    w1 = 1 + opts.msg_words + opts.trace_lanes
    slots = opts.inject_slots
    empty_inject = (jnp.full((slots,), -1, jnp.int32),
                    jnp.zeros((w1, slots), jnp.int32))
    limit = jnp.int32(k)
    detail.update(ticks_per_window=k, repeats=repeats,
                  sustain_ticks=int(sustain))

    for name, overrides in names_overrides:
        vopts = dataclasses.replace(opts, **overrides)
        try:
            fn = engine.jit_forced_window(program, vopts, mesh)
            t0 = time.perf_counter()
            out = fn(jax.tree.map(jnp.copy, template), *empty_inject,
                     limit)
            jax.block_until_ready(out)
            compile_s = time.perf_counter() - t0
            times = []
            for _ in range(repeats):
                st_in = jax.tree.map(jnp.copy, template)
                jax.block_until_ready(st_in)
                t0 = time.perf_counter()
                out = fn(st_in, *empty_inject, limit)
                jax.block_until_ready(out)
                times.append(time.perf_counter() - t0)
            table[name] = 1e3 * statistics.median(times) / k
            detail.setdefault("compile_s", {})[name] = round(compile_s, 3)
        except Exception as e:            # noqa: BLE001 — variant, not start
            table[name] = None
            detail["errors"][name] = f"{type(e).__name__}: {e}"[:500]
            print(f"ponyc_tpu tuning: variant {name!r} failed to "
                  f"build/run and is out of the race: "
                  f"{detail['errors'][name]}", file=sys.stderr)
    return table, detail


# ---------------------------------------------------------------------------
# adaptive quiesce-window resolution (runtime/controller.py)
#
# quiesce_interval="auto" is resolved through the SAME on-disk cache
# machinery as the formulation autos, but with its own record (keyed by
# the layout key + a field marker + the clamp bounds): the stored value
# is not a measured tick_ms winner, it is the window the adaptive
# controller CONVERGED to on a previous run of this layout — the run
# loop re-adapts from there instead of from a cold default, and a
# steady workload's second run starts at its steady state.


def quiesce_key(program, opts: RuntimeOptions) -> Dict[str, Any]:
    key = tuning_key(program, opts)
    key["field"] = "quiesce_interval"
    key["bounds"] = [int(opts.quiesce_interval_min),
                     int(opts.quiesce_interval_max)]
    # The formulation autos' own resolution state is irrelevant to the
    # window record (and would needlessly split the cache by it).
    key.pop("auto", None)
    key.pop("fixed", None)
    return key


# Cold-start initial window when the cache has no converged value: the
# pre-adaptive fixed default, clamped into the configured bounds.
DEFAULT_QUIESCE_INTERVAL = 64


def resolve_quiesce_interval(program, opts: RuntimeOptions,
                             ) -> Tuple[int, Dict[str, Any]]:
    """Concrete initial window for quiesce_interval="auto": the cached
    converged value for this layout, else the clamped default. Returns
    (initial, record) — the record rides Runtime.tuning_record into the
    bench JSON."""
    lo, hi = opts.quiesce_interval_min, opts.quiesce_interval_max
    clamp = lambda v: min(hi, max(lo, int(v)))         # noqa: E731
    record: Dict[str, Any] = {"bounds": [lo, hi]}
    cdir = tuning_cache_dir(opts)
    key = quiesce_key(program, opts)
    cached = load_cached(cdir, key)
    if cached is not None and isinstance(
            cached["chosen"].get("quiesce_interval"), int):
        v = clamp(cached["chosen"]["quiesce_interval"])
        record.update(source="cache", initial=v,
                      cache_path=cache_path(cdir, key))
        return v, record
    v = clamp(DEFAULT_QUIESCE_INTERVAL)
    record.update(source="default", initial=v)
    return v, record


def store_quiesce_interval(program, opts: RuntimeOptions,
                           window: int) -> Optional[str]:
    """Persist a converged adaptive window for this layout (called by
    the run loop when the controller reaches steady state; best-effort
    like every cache write)."""
    cdir = tuning_cache_dir(opts)
    if cdir is None:
        return None
    key = quiesce_key(program, opts)
    return store_cached(cdir, key, {
        "key": key, "chosen": {"quiesce_interval": int(window)},
        "winner": f"window={int(window)}",
        "written_unix": time.time()})


def resolve(program, opts: RuntimeOptions, mesh, state,
            ) -> Tuple[RuntimeOptions, Dict[str, Any]]:
    """Turn "auto" option values into concrete ones: cache hit →
    cached winner; miss → calibrate, decide, persist. Returns
    (concrete opts, decision record). The record rides into bench.py's
    JSON so every bench doubles as the A/B campaign's lab notebook."""
    autos = auto_fields(opts)
    if not autos:
        return opts, {"source": "none", "chosen": {}, "table": {}}

    cands = variants(program, opts)
    baseline = cands[0]
    record: Dict[str, Any] = {
        "auto": autos,
        "variants": [n for n, _ in cands],
        "table": {},
        "detail": {},
    }

    if len(cands) == 1:
        # Nothing eligible beyond the baseline (e.g. pallas_fused="auto"
        # on an all-ineligible program): decide without measuring.
        name, overrides = baseline
        record.update(source="default", chosen=overrides, winner=name)
        return dataclasses.replace(opts, **overrides), record

    key = tuning_key(program, opts)
    cdir = tuning_cache_dir(opts)
    record["cache_dir"] = cdir
    cached = load_cached(cdir, key)
    if cached is not None:
        record.update(source="cache", chosen=cached["chosen"],
                      winner=cached.get("winner"),
                      table=cached.get("table", {}),
                      cache_path=cache_path(cdir, key))
        return dataclasses.replace(opts, **cached["chosen"]), record

    table, detail = calibrate(program, opts, mesh, state, cands)
    winner = decide(table, order=[n for n, _ in cands])
    if winner is None:
        winner = baseline[0]
        print("ponyc_tpu tuning: no variant produced a timing; "
              f"running the baseline {winner!r} unmeasured",
              file=sys.stderr)
    overrides = dict(cands)[winner]
    record.update(source="calibrated", chosen=overrides, winner=winner,
                  table={n: (None if t is None else round(t, 4))
                         for n, t in table.items()},
                  detail=detail)
    stored = store_cached(cdir, key, {
        "key": key, "chosen": overrides, "winner": winner,
        "table": record["table"], "detail": detail,
        "written_unix": time.time()})
    if stored:
        record["cache_path"] = stored
    return dataclasses.replace(opts, **overrides), record
