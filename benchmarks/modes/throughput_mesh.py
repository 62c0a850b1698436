"""Throughput mode on a mesh: `modes/throughput.py`'s segments, K, median
and `msgs_per_s`, plus what only a sharded world can get wrong.

The window is `throughput`'s, call for call. Added, all integers and
all compared exactly:

  route counters   after the warm-up's ticks (the reference ticks and
                   the first segment of K) the program's `n_routed` and
                   `n_routed_remote` are what `reference_mesh.
                   remote_sends` says the world sent and sent across
                   shards in those ticks
  no route spill   after every segment `rspill_count` is 0 on every
                   shard, and nobody was ever muted (a full link mutes
                   its senders): the offered load fits the bucket, so an
                   overflow is an error here. The read is four words a
                   segment, between two segments
  spread           every non-empty leaf of the state lives on `shards`
                   distinct devices, or is replicated: none sits on one

The window's record gains `route`: the counters' movement over the
timed window and the geometry the per-layer readers need.

A world for this mode offers what `throughput` asks and `shards`,
`route_reference(ticks)`.
"""

from __future__ import annotations

import numpy as np

from benchmarks.modes import throughput
from benchmarks.modes.throughput import MASK32

ROUTE_COUNTERS = ("n_routed", "n_routed_remote")


def _route_counters(rt) -> tuple:
    return tuple(rt.counter(c) & MASK32 for c in ROUTE_COUNTERS)


def _spilled(rt) -> int:
    """Entries parked in the route spill right now, over all shards."""
    return int(np.asarray(rt.state.rspill_count).sum())


def unspread_leaves(rt, shards: int) -> list:
    """Paths of the non-empty state leaves that are neither on `shards`
    distinct devices nor replicated (chip_smoke.spread_check's test)."""
    import jax
    bad = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(rt.state)[0]:
        if leaf.size == 0 or leaf.is_fully_replicated:
            continue
        if len({s.device for s in leaf.addressable_shards}) != shards:
            bad.append(jax.tree_util.keystr(path))
    return bad


def _geometry(rt) -> dict:
    """The route's static sizes as the program lays them out."""
    from ponyc_tpu.runtime.state import layout_sizes
    e_out, bucket, entries = layout_sizes(rt.program, rt.opts)
    return {"shards": int(rt.program.shards), "bucket": int(bucket),
            "outbox": int(e_out), "delivery_entries": int(entries)}


def warm_up(world, traffic: dict, seconds: float) -> dict:
    rt = world.rt
    plan = throughput.warm_up(world, traffic, seconds)
    ticks = rt.steps_run
    sent, remote = world.route_reference(ticks)
    # one shard routes nothing, and counts nothing
    want = (int(sent.sum()), int(remote.sum())) if world.shards > 1 \
        else (0, 0)
    routed = _route_counters(rt)
    plan["route_ok"] = routed == tuple(n & MASK32 for n in want)
    plan["route_after_warm_up"] = {"ticks": ticks, "counters": routed,
                                   "reference": want}
    plan["spilled_segments"] = int(_spilled(rt) > 0)
    plan["unspread"] = unspread_leaves(rt, world.shards)
    return plan


def _run(world, plan: dict, until) -> dict:
    """`throughput`'s segments, with the route spill read after each."""
    rt = world.rt

    def stop(elapsed, segments):
        plan["spilled_segments"] += _spilled(rt) > 0
        return until(elapsed, segments)
    before = _route_counters(rt)
    out = throughput._run_segments(world, plan, stop)
    after = _route_counters(rt)
    routed, remote = ((a - b) & MASK32 for a, b in zip(after, before))
    out["route"] = {**_geometry(rt), "routed": routed, "remote": remote,
                    "ticks": out["ticks"]}
    return out


def window(world, plan: dict, seconds: float) -> dict:
    return _run(world, plan, lambda t, _n: t >= seconds)


def traced(world, plan: dict, units: int) -> dict:
    return _run(world, plan, lambda _t, n: n >= units)


def finish(world, plan: dict, win: dict, extra: dict | None) -> dict:
    rt = world.rt
    out = throughput.finish(world, plan, win, extra)
    mutes = rt.counter("n_mutes")
    unspread = plan["unspread"] + unspread_leaves(rt, world.shards)
    out["checks"].update({
        "route_counters_are_the_references": plan["route_ok"],
        "no_route_spill_no_link_mute":
        plan["spilled_segments"] == 0 and mutes == 0,
        "state_spread_over_the_mesh": not unspread,
    })
    out["failed"] += plan["spilled_segments"] + mutes + len(unspread) \
        + (not plan["route_ok"])
    out["notes"].update({"route": win["route"],
                         "route_after_warm_up": plan["route_after_warm_up"],
                         "n_mutes": mutes, "unspread": unspread[:4]})
    return out
