"""Pool slots in use at the window's end / the pool's slots, in percent:
the run loop's own books (`run_loop_stats()["pool"]`, kept by
`modes/throughput_payload.py` as the window's `pool`). A program whose
run loop keeps no such books reports nothing."""


def read(ctx):
    pool = ctx["window"].get("pool")
    return None if not pool else 100.0 * pool["blobs_in_use"] / pool["slots"]
