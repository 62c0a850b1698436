"""The price of one payload word: device time a traced tick under
`pony/dispatch/heap/get` and `/set` / the payload words a tick writes
and reads (`payload_bytes.words_moved_per_tick`), in nanoseconds. None
without both scopes."""

from benchmarks import payload_bytes
from benchmarks.layer_metrics import payload_read_ms, payload_write_ms


def read(ctx):
    read_ms, write_ms = payload_read_ms.read(ctx), payload_write_ms.read(ctx)
    if read_ms is None or write_ms is None:
        return None
    words = payload_bytes.words_moved_per_tick(ctx["cfg"], ctx["traffic"])
    return 1e6 * (read_ms + write_ms) / words
