"""The host's part of the slow run-loop windows' excess: see
`slow_window_device_pct.py`."""

from benchmarks.layer_metrics.slow_window_device_pct import split


def read(ctx):
    parts = split(ctx)
    return None if parts is None else parts[1]
