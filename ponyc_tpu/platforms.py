"""Pin a process to the CPU backend, for tests and dry runs.

A program runs on whatever backend ``JAX_PLATFORMS`` / JAX itself
resolves, and fails if that backend fails to initialise: nothing in
this package probes for an accelerator in a child process or switches
to the CPU after failing to find one (a CPU number must never be read
as a chip number — PERF.md). This module is the one deliberate
exception: callers that WANT the CPU say so, in-process, before the
first device touch.
"""

from __future__ import annotations

import os
import re


def compile_cache_forced() -> bool:
    """PONY_TPU_COMPILE_CACHE_FORCE=1: the hook that re-tests jax's
    persistent compile cache on the CPU backend (ROADMAP C8)."""
    return os.environ.get("PONY_TPU_COMPILE_CACHE_FORCE", "0") == "1"


def compile_cache_off() -> None:
    """Switch jax's persistent compile cache off in this process,
    whatever the machine exports: jax reads JAX_COMPILATION_CACHE_DIR
    by itself at import and a set directory IS the cache switched on.
    On the CPU backend a reloaded meshed executable deadlocks its own
    collectives and aborts the process (tuning.enable_compile_cache has
    the evidence), so the CPU substrate neither reads nor writes one."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    if not (jax.config.jax_compilation_cache_dir
            or jax.config.jax_enable_compilation_cache):
        return                                   # already off
    jax.config.update("jax_compilation_cache_dir", None)
    jax.config.update("jax_enable_compilation_cache", False)
    # jax decides "is the cache used" once, at the first compile; forget
    # a decision taken before this call.
    compilation_cache.reset_cache()


def force_cpu(n_devices: int | None = None) -> None:
    """Pin this process to the CPU backend, optionally as `n_devices`
    virtual devices (a stand-in mesh for the sharded engine), with the
    persistent compile cache off (`compile_cache_off`) unless the
    re-test hook forces it.

    For tests (tests/conftest.py: 8 virtual devices) and
    ``__graft_entry__.dryrun_multichip``. Call before the first
    ``jax.devices()`` / trace: the
    ``xla_force_host_platform_device_count`` flag is read when the CPU
    client is created, and the platform cannot change once a backend
    is up."""
    if n_devices is not None:
        flag = f"--xla_force_host_platform_device_count={n_devices}"
        flags = os.environ.get("XLA_FLAGS", "")
        # Replace any pre-existing value (a stale =1 from the environment
        # would silently win and shrink every virtual mesh).
        flags, n_subs = re.subn(
            r"--xla_force_host_platform_device_count=\d+", flag, flags)
        if not n_subs:
            flags = (flags + " " + flag).strip()
        os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    if not compile_cache_forced():
        compile_cache_off()
