"""Seconds the run loop waited for its first launch of the window:
JAX's trace and lowering of the tick, and the backend's compile or its
reload from the persistent cache (`run_loop_stats()["cold_dispatch_s"]`:
the `pony:dispatching` spans that carry `cold=1`). Read beside
`compile_s`, which is the backend's share net of reloads and knows
nothing of tracing. None on a program that does not mark a cold launch."""

from benchmarks.layer_metrics.setup_build_s import stats


def read(ctx):
    s = stats()
    return None if s is None else s.get("cold_dispatch_s")
