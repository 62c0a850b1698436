"""Producers muted a tick: the window's growth of the program's
`n_mutes` (transitions into muted) / its ticks."""

from benchmarks.layer_metrics.spill_carried_per_tick import per_tick


def read(ctx):
    return per_tick(ctx, "n_mutes")
