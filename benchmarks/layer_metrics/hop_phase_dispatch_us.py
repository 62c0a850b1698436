"""phase_dispatch_ms where the end-to-end metric is the hop, in us."""

from benchmarks.layer_metrics import phase_dispatch_ms


def read(ctx):
    return phase_dispatch_ms.read(ctx, scale=1e6)
