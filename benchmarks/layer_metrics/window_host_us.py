"""What a window costs the host: self time of `pony:dispatching` and of
`pony:host-work` with its children / windows retired in the traced
part. Hidden today behind a tick of a second."""

from benchmarks import phase_trace


def read(ctx):
    reduced = phase_trace.of_run(ctx)
    if not reduced or not reduced["windows"]:
        return None
    return 1e6 * reduced["window_host_s"] / reduced["windows"]
