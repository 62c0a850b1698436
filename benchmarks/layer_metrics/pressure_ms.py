"""Device time a traced tick under `pony/delivery/pressure` and below
it: the guard that decides whether anything was rejected or anyone is
overloaded, and, where it says yes, the branch's two halves
(`/spill`: the rejected entries compacted into the next spill; `/mute`:
who is muted and by whom). A program without the two child scopes
reports the same total."""

from benchmarks import phase_trace


def read(ctx):
    return phase_trace.per_tick(ctx, "delivery/pressure", scale=1e3)
