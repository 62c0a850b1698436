"""launch_lead_us where the end-to-end metric is the hop."""

from benchmarks.layer_metrics.launch_lead_us import read  # noqa: F401
