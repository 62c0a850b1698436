"""The least bytes the heap's part of one tick must move, from shapes.

An update reads one table word and writes it back (4 B + 4 B), and
before it touches the word it must know that its handle still names
the slot: one generation word and one allocation flag of the slot
(4 B + 1 B). Nothing else is counted: no copy of the pool, no index
arithmetic, no second handle check for the write — those are the
formulation's, and `heap_roofline` says how far above the least it
sits. The rule is memory bandwidth: an xor is no FLOP.
"""

from __future__ import annotations

WORD = 4
FLAG = 1


def update_bytes() -> int:
    """One update: the word read and written, the handle's check."""
    return 2 * WORD + WORD + FLAG


def updates_per_tick(cfg: dict, traffic: dict) -> int:
    """Every streamer dispatches once a tick (worlds/gups.py)."""
    actors = int(cfg["actors"])
    return (actors - actors // 2) * int(traffic["updates_per_dispatch"])


def tick_bytes(cfg: dict, traffic: dict) -> float:
    return float(updates_per_tick(cfg, traffic) * update_bytes())


def tick_min_seconds(cfg: dict, traffic: dict, peak: dict) -> float:
    return tick_bytes(cfg, traffic) / peak["hbm_bytes_per_s"]
