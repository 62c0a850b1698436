"""Share of the traced span in which the device was idle while the run
loop was doing something other than waiting for it: idle under any
`pony:` span but `pony:wait`. The rest of `device_idle_pct` is the
device's own gaps between operations, or lies outside `run()`."""

from benchmarks import phase_trace

WAIT = phase_trace.SPAN_PREFIX + "wait"


def read(ctx):
    reduced = phase_trace.of_run(ctx)
    if not reduced or not reduced["spans"] or not reduced["devices"]:
        return None
    imposed = sum(s for name, s in reduced["idle_s"].items()
                  if name.startswith(phase_trace.SPAN_PREFIX)
                  and name != WAIT)
    return 100.0 * imposed / reduced["span_s"]
