"""Share of the device-busy time that lies under no `pony/` scope: the
guard that the names cover the tick. None where the program has no
scopes at all (there is then nothing to guard)."""

from benchmarks import phase_trace


def read(ctx):
    reduced = phase_trace.of_run(ctx)
    if not reduced or not reduced["scoped"] or not reduced["busy_s"]:
        return None
    unscoped = reduced["phases"].get(phase_trace.UNSCOPED, {"s": 0.0})
    return 100.0 * unscoped["s"] / reduced["busy_s"]
