"""window_host_us where the end-to-end metric is the hop."""

from benchmarks.layer_metrics.window_host_us import read  # noqa: F401
