"""Devices for the benchmark's self-tests (`python -m pytest
benchmarks/tests`): a four-chip cell needs four devices, and the CPU
backend makes as many as XLA is told to before JAX is first imported.
This file is loaded before `tests/conftest.py` and any test module."""

import os

_FLAG = "--xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" {_FLAG}=4").strip()
