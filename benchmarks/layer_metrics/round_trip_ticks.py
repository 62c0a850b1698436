"""Ticks a transaction takes from its credit to its teller's reply, by
Little's law: the transactions the world has out (`in_flight` a teller,
renewed one for one) / those completed a tick. 4 if nothing waited: a
transaction is four hops."""

from benchmarks.layer_metrics import tx_per_tick


def read(ctx):
    rate = tx_per_tick.read(ctx)
    return ctx["window"]["in_flight"] / rate if rate else None
