"""Device time a traced tick under the scope `pony/route` and below it
(`/route/sort`, `/bucket`, `/exchange`, `/spill`, and the route's own
concatenations), by `benchmarks/phase_trace.py`: a mean over the device
planes. None from a program that names no scope."""

from benchmarks import phase_trace


def read(ctx):
    return phase_trace.per_tick(ctx, "route", scale=1e3)
