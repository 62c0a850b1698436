"""Throughput mode for a world that lives in backpressure ON A MESH:
`modes/throughput_pressure.py`'s segments, K, median, references and
accounting (imported, not copied), plus what `modes/throughput_mesh.py`
holds a sharded world to — with one difference: here a mute is the
protocol at work, on whatever shard, and not an error.

Added to `throughput_pressure`'s checks, all integers, compared exactly:

  route counters   after the warm-up's ticks (the reference ticks and
                   the first segment of K) the program's `n_routed`,
                   `n_routed_remote`, `n_route_pressure` and
                   `n_remote_mutes` are what the mesh reference
                   (`reference_fanin_mesh.Ticks.route_counters`) says
                   those ticks shipped, shipped across shards, looked up
                   and muted behind another shard's aggregator
  spill bounded    after every segment every shard's receiver spill
                   holds at most its proven bound (the world's
                   `spill_bound`: B items a producer wired to the
                   shard's aggregators), and the ROUTE spill is empty on
                   every shard (a bucket holds a whole outbox here: an
                   overflow of a link is an error). Eight words a
                   segment, read between two segments
  spread           every non-empty leaf of the state lives on `shards`
                   distinct devices, or is replicated

The window's record gains `route` (the counters' movement over the
timed window and the geometry the per-layer readers need) beside
`throughput_pressure`'s `protocol`; `spill_entries` is the FULLEST
shard's, against one shard's `spill_cap`.

A world for this mode offers what `throughput_pressure` asks and
`shards`, `route_reference(ticks)`, `spill_by_shard()`, `spill_bound`.
"""

from __future__ import annotations

import numpy as np

from benchmarks.modes import throughput, throughput_mesh, throughput_pressure
from benchmarks.modes.throughput import MASK32
from benchmarks.modes.throughput_pressure import _protocol_counts

# counter -> its key in the window's `route` record
ROUTE_COUNTERS = {"n_routed": "routed", "n_routed_remote": "remote",
                  "n_route_pressure": "lookups", "n_unpacked": "unpacked",
                  "n_remote_mutes": "remote_mutes"}


def _route_counts(rt) -> dict:
    return {c: rt.counter(c) & MASK32 for c in ROUTE_COUNTERS}


def _spills_off(world) -> int:
    """1 if a shard's receiver spill is over its bound or anything sits
    in a route spill right now."""
    return int(bool((world.spill_by_shard() > world.spill_bound).any())
               or throughput_mesh._spilled(world.rt) > 0)


def warm_up(world, traffic: dict, seconds: float) -> dict:
    rt = world.rt
    plan = throughput_pressure.warm_up(world, traffic, seconds)
    ticks = rt.steps_run
    want = {c: n & MASK32 for c, n in world.route_reference(ticks).items()}
    got = _route_counts(rt)
    plan["route_ok"] = all(got[c] == want[c] for c in want)
    plan["route_after_warm_up"] = {"ticks": ticks, "counters": got,
                                   "reference": want}
    plan["segments_off"] = _spills_off(world)
    plan["unspread"] = throughput_mesh.unspread_leaves(rt, world.shards)
    return plan


def _run(world, plan: dict, until) -> dict:
    """`throughput`'s segments, with both spills read after each."""
    rt = world.rt

    def stop(elapsed, segments):
        plan["segments_off"] += _spills_off(world)
        return until(elapsed, segments)
    before = _route_counts(rt)
    out = throughput._run_segments(world, plan, stop)
    after = _route_counts(rt)
    out["route"] = {**throughput_mesh._geometry(rt), "ticks": out["ticks"],
                    **{key: (after[c] - before[c]) & MASK32
                       for c, key in ROUTE_COUNTERS.items()}}
    return out


def window(world, plan: dict, seconds: float) -> dict:
    """The timed window, and beside it what the protocol did in it."""
    rt = world.rt
    before = _protocol_counts(rt)
    win = _run(world, plan, lambda t, _n: t >= seconds)
    after = _protocol_counts(rt)
    win["protocol"] = {c: (after[c] - before[c]) & MASK32 for c in after}
    win["spill_entries"] = int(world.spill_by_shard().max())
    win["spill_cap"] = int(rt.opts.spill_cap)
    win["muted_producers"] = int(world.observed()["muted"].sum())
    win["producers"] = int(world.p)
    return win


def traced(world, plan: dict, units: int) -> dict:
    return _run(world, plan, lambda _t, n: n >= units)


def finish(world, plan: dict, win: dict, extra: dict | None) -> dict:
    rt = world.rt
    out = throughput_pressure.finish(world, plan, win, extra)
    unspread = plan["unspread"] + throughput_mesh.unspread_leaves(
        rt, world.shards)
    out["checks"].update({
        "route_counters_are_the_references": plan["route_ok"],
        "spill_under_its_bound_no_route_spill": plan["segments_off"] == 0,
        "state_spread_over_the_mesh": not unspread,
    })
    out["failed"] += plan["segments_off"] + len(unspread) \
        + (not plan["route_ok"])
    out["notes"].update({
        "route": win["route"],
        "route_after_warm_up": plan["route_after_warm_up"],
        "spill_by_shard": world.spill_by_shard().tolist(),
        "spill_bound": np.asarray(world.spill_bound).tolist(),
        "unspread": unspread[:4]})
    return out
