"""The price of one delivered word: device time a traced tick under
`pony/delivery` / (messages a tick x words a record), in nanoseconds.
Both factors are the world's `tick_shape()`; None without a trace."""

from benchmarks.layer_metrics import phase_delivery_ms


def read(ctx):
    shape = ctx["tick_shape"]
    words = shape["messages"] * shape["record_words"]
    tick_ns = phase_delivery_ms.read(ctx, scale=1e9)
    return None if tick_ns is None or not words else tick_ns / words
