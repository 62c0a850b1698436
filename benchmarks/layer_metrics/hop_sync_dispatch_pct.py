"""sync_dispatch_pct where the end-to-end metric is the hop."""

from benchmarks.layer_metrics.sync_dispatch_pct import read  # noqa: F401
