"""Share of the window's wall clock that the run loop itself counts as
host-imposed device idle (`run_loop_stats()["host_gap_us_total"]`: the
time from one window's retire to the next sync-point dispatch, inside
one run() call; a pipelined dispatch counts 0)."""


def read(ctx):
    w = ctx["window"]
    return 100.0 * w["host_gap_us"] / 1e6 / w["wall_s"]
