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


def force_cpu(n_devices: int | None = None) -> None:
    """Pin this process to the CPU backend, optionally as `n_devices`
    virtual devices (a stand-in mesh for the sharded engine).

    For tests (tests/conftest.py: 8 virtual devices) and
    ``__graft_entry__.dryrun_multichip``; bench.py's ``--platform cpu``
    uses it too. Call before the first ``jax.devices()`` / trace: the
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
