"""Device time a traced tick under `pony/dispatch/heap/free`:
`blob_free` — the handle's check, the slot's flag and length cleared.
It lies inside `heap_update_ms`. A program without the scope reports
nothing."""

from benchmarks import phase_trace


def read(ctx):
    ms = phase_trace.per_tick(ctx, "dispatch/heap/free", scale=1e3)
    return ms or None
