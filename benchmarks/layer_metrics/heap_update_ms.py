"""Device time a traced tick under `pony/dispatch/heap`: the blob
pool's handle checks, gathers and scatters inside the behaviours'
dispatch (and any copy of the pool XLA makes for them). It lies inside
`phase_dispatch_ms`. A program without the scope reports nothing."""

from benchmarks import phase_trace


def read(ctx):
    ms = phase_trace.per_tick(ctx, "dispatch/heap", scale=1e3)
    return ms or None
