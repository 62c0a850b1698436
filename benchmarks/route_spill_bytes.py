"""The least bytes the route's pressure part of one tick must move, from
shapes: who of a tick's senders must mute because of where its message
goes.

For every entry a shard routes, the question needs the entry's target
and its sender (4 B each) and the target's hot word, one byte of the
mesh-wide table (declares pressure; overloaded). For every sender the
answer mutes, the muting receiver's id is written into the sender's ref
table (4 B) and its muted flag is set (1 B). Nothing else is counted: no
gather of the table to every shard, no pass over slots of the sorted
list that hold no entry, no second table for the sender's exemption, no
scatter's sort — those are the formulation's, and `route_spill_roofline`
says how far above the least it sits. The rule is one chip's memory
bandwidth, against `route_spill_ms`, a mean over the chips.
"""

from __future__ import annotations

WORD = 4
FLAG = 1


def entry_bytes() -> int:
    """Target, sender, and the target's hot word."""
    return 2 * WORD + FLAG


def mute_bytes() -> int:
    """The ref written and the muted flag set."""
    return WORD + FLAG


def tick_bytes_a_shard(routed_a_tick: float, muted_a_tick: float,
                       shards: int) -> float:
    return (routed_a_tick * entry_bytes()
            + muted_a_tick * mute_bytes()) / shards


def tick_min_seconds(routed_a_tick: float, muted_a_tick: float, shards: int,
                     peak: dict) -> float:
    return (tick_bytes_a_shard(routed_a_tick, muted_a_tick, shards)
            / peak["hbm_bytes_per_s"])
