"""Device time a traced tick under the scope `pony/delivery` and below
it (plan, permute, rebuild, pressure and delivery's own bookkeeping),
by `benchmarks/phase_trace.py`."""

from benchmarks import phase_trace


def read(ctx, scale=1e3):
    return phase_trace.per_tick(ctx, "delivery", scale=scale)
