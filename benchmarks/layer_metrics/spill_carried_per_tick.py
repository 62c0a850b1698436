"""Entries a tick that went round through the spill: the window's
growth of the program's `n_rejected` / its ticks. A spilled item that
is rejected again counts again, as in the counter. None where the
mode does not read the protocol's counters."""


def per_tick(ctx, counter: str):
    w = ctx["window"]
    if "protocol" not in w or not w["ticks"]:
        return None
    return w["protocol"][counter] / w["ticks"]


def read(ctx):
    return per_tick(ctx, "n_rejected")
