"""The two caches a Runtime keeps under the checkout (git-ignored
`.cache/ponyc_tpu/`), and nothing that times anything.

- **The compile cache** (`enable_compile_cache`): jax's persistent
  compilation cache, wired for Runtime / chip_smoke.py.
- **The window length's memory** (`resolve_quiesce_interval`,
  `store_quiesce_interval`): `quiesce_interval="auto"` starts the
  adaptive controller (runtime/controller.py) from the window a
  previous run of the same layout converged to, kept as one small JSON
  record per (platform, jax version, cohort layout, geometry, bounds).

The formulation (`delivery`, `pallas`, `pallas_fused`) is not chosen
here or anywhere at start-up: it is an explicit option that
`engine.check_kernels` honours or refuses.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from typing import Any, Dict, Optional, Tuple

from .config import RuntimeOptions
from .platforms import compile_cache_forced, compile_cache_off


# ---------------------------------------------------------------------------
# cache locations

# Both caches default to ONE fixed place under the checkout (git-ignored
# `.cache/`): never `~`, a temp name, a pid or a time. The directory is
# part of jax's cache key handling (a directory that moves never hits),
# and two checkouts on one machine must not inherit each other's
# converged window.
CACHE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".cache", "ponyc_tpu")


def tuning_cache_dir(opts: RuntimeOptions) -> Optional[str]:
    """The window record's directory: opts.tuning_cache is
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

    On an accelerator, where ``JAX_COMPILATION_CACHE_DIR`` is set the
    cache can be placed from outside: jax reads the variable itself and
    this function sets no directory at all. Otherwise the directory is
    the fixed CACHE_ROOT/xla. Idempotent; call before the first compile
    that should be cached (Runtime.start() does).

    CPU guard: on the CPU backend the cache is switched OFF
    (platforms.compile_cache_off), also where the machine exports a
    directory and jax has by then switched it on by itself, unless
    PONY_TPU_COMPILE_CACHE_FORCE=1 (the re-test hook). Re-tested on
    jaxlib 0.9.0 (PR 21): single-device worlds reload soundly (fuzz,
    ring, gc, run-loop and differential suites pass cold and warm — the
    jaxlib 0.4.37 state corruption is gone), but a RELOADED meshed
    executable deadlocks its own collectives — on a warm cache
    tests/test_mesh_pressure.py::test_programmatic_backpressure_on_mesh
    dies in rendezvous.cc ("Expected 4 threads to join the rendezvous,
    but only 2 of them arrived"; rc 134 with only the variable exported,
    PR 31) — and every reload logs a 2 KB cpu_aot_loader
    feature-mismatch line. The start-up this cache attacks is the
    accelerator's anyway."""
    if setting == "off":
        return None
    import jax
    if jax.default_backend() == "cpu" and not compile_cache_forced():
        compile_cache_off()
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
# the window record


def quiesce_key(program, opts: RuntimeOptions) -> Dict[str, Any]:
    """Everything the converged window legitimately depends on —
    backend, compiler version, cohort layout, geometry, the clamp
    bounds — and nothing it doesn't (actor field VALUES don't change op
    shapes). Same key ⇒ the stored window transfers."""
    import jax
    dev = jax.devices()[0]
    cohorts = [
        {"type": ch.atype.__name__, "capacity": int(ch.capacity),
         "batch": int(ch.batch), "max_sends": int(ch.max_sends),
         "msg_words": int(ch.msg_words),
         **({"mailbox_cap": int(ch.mailbox_cap)}
            if ch.mailbox_cap != opts.mailbox_cap else {}),
         "behaviours": len(ch.behaviours),
         "host": bool(ch.host), "blobs": bool(ch.uses_blobs)}
        for ch in program.cohorts]
    geometry = {f: getattr(opts, f) for f in (
        "mailbox_cap", "msg_words", "batch", "max_sends", "spill_cap",
        "inject_slots", "mesh_shards", "route_bucket", "mute_slots",
        "blob_slots", "blob_words")}
    return {
        "v": 4,
        "field": "quiesce_interval",
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "jax": jax.__version__,
        "geometry": geometry,
        "cohorts": cohorts,
        "bounds": [int(opts.quiesce_interval_min),
                   int(opts.quiesce_interval_max)],
    }


def cache_path(cache_dir: str, key: Dict[str, Any]) -> str:
    blob = json.dumps(key, sort_keys=True).encode()
    return os.path.join(cache_dir,
                        hashlib.sha256(blob).hexdigest()[:24] + ".json")


def load_cached(cache_dir: Optional[str],
                key: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The cached record for `key`, or None on miss/corruption (a
    corrupt file means the default window — and is overwritten by the
    next converged one — rather than erroring a start)."""
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


# Cold-start initial window when the cache has no converged value: the
# pre-adaptive fixed default, clamped into the configured bounds.
DEFAULT_QUIESCE_INTERVAL = 64


def resolve_quiesce_interval(program, opts: RuntimeOptions,
                             ) -> Tuple[int, Dict[str, Any]]:
    """Concrete initial window for quiesce_interval="auto": the cached
    converged value for this layout, else the clamped default. Returns
    (initial, record) — the record is Runtime.tuning_record's
    "quiesce_interval" entry."""
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
    the run loop when the controller reaches steady state;
    best-effort)."""
    cdir = tuning_cache_dir(opts)
    if cdir is None:
        return None
    key = quiesce_key(program, opts)
    return store_cached(cdir, key, {
        "key": key, "chosen": {"quiesce_interval": int(window)},
        "written_unix": time.time()})
