"""How unevenly the mesh's devices are loaded: the busiest device's busy
time in the traced span / the mean over the devices - 1. By
`benchmarks/mesh_trace.py`; None on one device."""

from benchmarks import mesh_trace


def read(ctx):
    m = mesh_trace.of_run(ctx)
    if not m or m["devices"] < 2 or not sum(m["busy_s"]):
        return None
    mean = sum(m["busy_s"]) / m["devices"]
    return 100.0 * (max(m["busy_s"]) / mean - 1.0)
