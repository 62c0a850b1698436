"""unnamed_pct where the end-to-end metric is the hop."""

from benchmarks.layer_metrics import unnamed_pct


def read(ctx):
    return unnamed_pct.read(ctx)
