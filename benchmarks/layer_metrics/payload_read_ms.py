"""Device time a traced tick under `pony/dispatch/heap/get`: a payload's
reads, `blob_length` and every `blob_get`, with their handle checks.
It lies inside `heap_update_ms`. A program without the scope reports
nothing."""

from benchmarks import phase_trace


def read(ctx):
    ms = phase_trace.per_tick(ctx, "dispatch/heap/get", scale=1e3)
    return ms or None
