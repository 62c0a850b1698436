"""Device time a traced tick under `pony/drain` and `pony/dispatch/**`
by the program's symbol table: the scan over the batch slots with its
drain fusions in it, which the profiler leaves without a name (their
root is a `dynamic-update-slice`) and `phase_dispatch_ms` therefore
reads without. None without a trace or a table."""

from benchmarks import symbol_trace


def read(ctx):
    return symbol_trace.per_tick(
        ctx, lambda r: symbol_trace.under(r, "drain", "dispatch"), scale=1e3)
