"""wait_return_us where the end-to-end metric is the hop."""

from benchmarks.layer_metrics.wait_return_us import read  # noqa: F401
