"""Test env: force an 8-device virtual CPU mesh before any JAX use.

≙ the reference's fake-stdlib/PassTest fixture strategy (test/libponyc/
util.h:32-82): tests run against a controllable substrate rather than the
real target. Multi-chip sharding tests use these 8 virtual devices; the
real TPU is exercised by chip_smoke.py (and bench.py), never by this
suite. The pinning (env var + config knob + the virtual-device XLA
flag, all before the first device touch) lives in ponyc_tpu.platforms.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ponyc_tpu.platforms import force_cpu  # noqa: E402

force_cpu(8)


def pytest_configure(config):
    # Tier-1 runs with `-m 'not slow'` (ROADMAP); register the marker
    # so opting a heavyweight test out of the budget is warning-free.
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 budgeted run")
