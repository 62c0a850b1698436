"""Device time a traced tick under `pony/dispatch/heap/reserve`: the
engine's share of the pool — the free list's compaction and the
reservation windows handed to the allocating dispatches.
It lies inside `heap_update_ms`. A program without the scope reports
nothing."""

from benchmarks import phase_trace


def read(ctx):
    ms = phase_trace.per_tick(ctx, "dispatch/heap/reserve", scale=1e3)
    return ms or None
