"""phase_delivery_ms where the end-to-end metric is the hop, in us."""

from benchmarks.layer_metrics import phase_delivery_ms


def read(ctx):
    return phase_delivery_ms.read(ctx, scale=1e6)
