"""Device time a traced tick under `pony/delivery/plan`: the key
compare every tick, and on a miss the sort and the searchsorted that
rebuild the cached permutation."""

from benchmarks import phase_trace


def read(ctx):
    return phase_trace.per_tick(ctx, "delivery/plan", scale=1e3)
