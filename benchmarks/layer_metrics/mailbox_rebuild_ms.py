"""Device time a traced tick under `pony/delivery/rebuild`: the
slot-plane gather that rewrites every mailbox slot, whatever is live."""

from benchmarks import phase_trace


def read(ctx):
    return phase_trace.per_tick(ctx, "delivery/rebuild", scale=1e3)
