"""Device time a traced tick under `pony/drain` and `pony/dispatch`:
the batch pulled from every mailbox, and the behaviours run on it."""

from benchmarks import phase_trace


def read(ctx, scale=1e3):
    return phase_trace.per_tick(ctx, "drain", "dispatch", scale=scale)
