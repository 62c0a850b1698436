"""The share of the collectives' time (`collective_ms`) during which
nothing else runs on that device: what overlap would win back at most.
By `benchmarks/mesh_trace.py`."""

from benchmarks import mesh_trace


def read(ctx):
    m = mesh_trace.of_run(ctx)
    if not m or not m["collective_s"]:
        return None
    return 100.0 * m["exposed_s"] / m["collective_s"]
