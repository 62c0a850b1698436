"""How near the run came to a refused spawn: the least free rows, net
of the next tick's reservations, that any tick left
(`run_loop_stats()["free_rows_low"]`, always on) / the world's rows.
The policy's, not a lever: the run loop collects just before it would
go below 0. None on a program without the counter."""


def read(ctx):
    w = ctx["window"]
    if w.get("free_rows_low") is None or not w.get("rows"):
        return None
    return 100.0 * w["free_rows_low"] / w["rows"]
