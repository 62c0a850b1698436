"""Transactions completed a tick: the window's growth of the tellers'
`completed` (read at the segments' ends) / its ticks. None where the
mode does not count transactions."""


def read(ctx):
    w = ctx["window"]
    if "transactions" not in w or not w["ticks"]:
        return None
    return w["transactions"] / w["ticks"]
