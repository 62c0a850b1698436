"""host_idle_pct where the end-to-end metric is the hop."""

from benchmarks.layer_metrics.host_idle_pct import read  # noqa: F401
