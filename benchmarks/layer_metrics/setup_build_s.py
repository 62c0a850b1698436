"""Seconds of set-up the program itself spent building the world: the
self seconds of the API phases `start`, `spawn`, `set-fields`,
`bulk-send` and `blob-store` (`run_loop_stats()["phase_s"]`, always on).
Every world calls these during set-up and never after it, so the sum at
the end of the run is set-up's. With `setup_cold_launch_s` it is the
program's part of `setup_s` before the first warm tick. None on a
program that does not name these calls."""

BUILD = ("start", "spawn", "set-fields", "bulk-send", "blob-store")


def recorder():
    """The newest runtime's flight recorder, reached as
    `slow_window_device_pct` reaches it; None without one."""
    try:
        from ponyc_tpu import flight
        return flight.latest()
    except (ImportError, AttributeError):
        return None


def stats():
    """That runtime's `run_loop_stats()`."""
    found = recorder()
    return None if found is None else found.rt.run_loop_stats()


def read(ctx):
    s = stats()
    if s is None or any(p not in s["phase_s"] for p in BUILD):
        return None
    return sum(s["phase_s"][p] for p in BUILD)
