"""Device time a traced tick under `pony/dispatch/heap/alloc`:
`blob_alloc` — the slot's generation, flag and length, and the zeroing
of its words.
It lies inside `heap_update_ms`. A program without the scope reports
nothing."""

from benchmarks import phase_trace


def read(ctx):
    ms = phase_trace.per_tick(ctx, "dispatch/heap/alloc", scale=1e3)
    return ms or None
