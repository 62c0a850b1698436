"""Pool slots claimed in the window / its ticks: the run loop's own
books (`run_loop_stats()["pool"]`, kept by `modes/throughput_payload.py`
as the window's `pool`). A program whose run loop keeps no such books
reports nothing."""


def read(ctx):
    pool, ticks = ctx["window"].get("pool"), ctx["window"]["ticks"]
    return None if not pool or not ticks else pool["allocs"] / ticks
