"""Device time a traced tick of the gathers and scatters of at least
1 Mi indices whose TABLE the compiler left in plain memory (no `S(1)`
on the buffer they read or write by index: `table_s1` false in
`Runtime.window_symbols()`; `symbol_trace.mark`): what the tick pays to
plain memory for its indexed reads and writes. The same gather is 2-3x
cheaper with its table marked, a marked output does not make up for a
plain table, and an edit anywhere in the window re-deals the marks
(PERF.md 7, C11): a move here with no edit to the gather itself is
such a re-deal. None without a trace or a table."""

from benchmarks import symbol_trace


def read(ctx):
    return symbol_trace.per_tick(ctx, symbol_trace.plain, scale=1e3)
