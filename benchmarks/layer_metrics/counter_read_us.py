"""What one `Runtime.counter()` costs: seconds under the `pony:counter`
spans of the traced part / their number. A segment ends in one such
read of one device word, with the device idle under it: the
`between-segments` idle of the breakdown, by name. None on a program
without the span."""

from benchmarks import phase_trace

COUNTER = phase_trace.SPAN_PREFIX + "counter"


def read(ctx):
    reduced = phase_trace.of_run(ctx)
    rec = reduced["spans"].get(COUNTER) if reduced else None
    if not rec or not rec["n"]:
        return None
    return 1e6 * rec["s"] / rec["n"]
