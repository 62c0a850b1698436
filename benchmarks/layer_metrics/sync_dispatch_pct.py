"""Share of the run loop's window dispatches in the timed window that
were sync-point dispatches (the device had nothing in flight) and not
pipelined behind a running window."""


def read(ctx):
    w = ctx["window"]
    total = w["sync_dispatches"] + w["pipelined_dispatches"]
    return 100.0 * w["sync_dispatches"] / total if total else None
