"""Device time a traced tick under `pony/spawn` and below it: the
free-row compaction (`/spawn/free`), the reservation windows and the
next tick's row pressure (`/spawn/reserve`), the claims (`/spawn/claim`).
A program without these scopes, or a world that spawns nothing, reports
nothing."""

from benchmarks import phase_trace


def read(ctx):
    ms = phase_trace.per_tick(ctx, "spawn", scale=1e3)
    return ms or None
