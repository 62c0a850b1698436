"""Device operations a traced tick: leaf events on the device's `XLA
Ops` line (containers such as `while` and `conditional` excluded) /
traced ticks. The idle tick is a chain of small operations; this counts
them."""

from benchmarks import phase_trace


def read(ctx):
    reduced = phase_trace.of_run(ctx)
    if not reduced or not reduced["ticks"] or not reduced["devices"]:
        return None
    return reduced["leaf_ops"] / reduced["ticks"]
