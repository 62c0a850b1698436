"""Time a traced tick that the collective operations of the trace (the
route's all-to-alls, the vote's all-reduces, an all-gather where one
runs) are in flight, by `benchmarks/mesh_trace.py`: a mean over the
device planes. None where the trace holds no collective (one chip)."""

from benchmarks import mesh_trace


def read(ctx):
    m = mesh_trace.of_run(ctx)
    if not m or not m["collective_s"] or not ctx["trace"]["ticks"]:
        return None
    return 1e3 * m["collective_s"] / ctx["trace"]["ticks"]
