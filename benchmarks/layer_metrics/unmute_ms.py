"""Device time a traced tick under `pony/unmute` (the spill's
per-target counts and the release pass over the muted) and `pony/mute`
(this tick's mutes merged into the senders' tables)."""

from benchmarks import phase_trace


def read(ctx):
    return phase_trace.per_tick(ctx, "unmute", "mute", scale=1e3)
