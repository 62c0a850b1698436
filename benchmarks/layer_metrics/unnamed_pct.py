"""Share of the device-busy time that the program's own symbol table
(`Runtime.window_symbols()`, `benchmarks/symbol_trace.py`) cannot name:
events whose row the ladder left on `none` and events no row matches.
What `unscoped_pct` becomes when the compiled text does the naming and
not the profiler's op_name of a fusion's root. None without a trace or
on a program that makes no table."""

from benchmarks import symbol_trace


def read(ctx):
    reduced = symbol_trace.of_run(ctx)
    if not reduced or not reduced["busy_s"]:
        return None
    unnamed = reduced["scopes"].get(symbol_trace.UNNAMED, {"s": 0.0})
    return 100.0 * unnamed["s"] / reduced["busy_s"]
