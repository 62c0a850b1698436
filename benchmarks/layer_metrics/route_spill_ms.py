"""Device time a traced tick under the scope `pony/route/spill` and
below it (`/route/spill/lookup`: the sorted entries' targets looked up
in the mesh-wide hot word; `/route/spill/mute`: the senders muted for a
hot receiver or a full link; the overflow's compaction and the
conditionals' own operations directly under `/route/spill`), by
`benchmarks/phase_trace.py`: a mean over the device planes. None from a
program that names no scope, or whose tick ran nothing there."""

from benchmarks import phase_trace


def read(ctx):
    return phase_trace.per_tick(ctx, "route/spill", scale=1e3) or None
