"""Device time a traced tick under `pony/dispatch/heap/set`: a payload's
writes, every `blob_set` (its sort of the lanes, its scatter, its handle
checks).
It lies inside `heap_update_ms`. A program without the scope reports
nothing."""

from benchmarks import phase_trace


def read(ctx):
    ms = phase_trace.per_tick(ctx, "dispatch/heap/set", scale=1e3)
    return ms or None
