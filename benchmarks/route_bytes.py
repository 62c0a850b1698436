"""The least bytes the mesh route of one tick must move, from shapes.

A routed entry is a record of target + sender + the message's words
(behaviour id + `msg_words` payload words), 4 B each. The route must
read every live outbox entry once and write it once into the exchange,
and the receiving side must write every entry it receives once, into
the delivery list. Nothing else is counted: no sort by destination, no
permutation, no padding of a bucket, no second copy on the way — those
are the formulation's, and `route_roofline` says how far above the least
it sits. The exchange's own wire time is not in it either: the rule is
one chip's memory bandwidth, against `route_ms`, a mean over the chips.
"""

from __future__ import annotations

WORD = 4


def entry_bytes(msg_words: int) -> int:
    """Target, sender, behaviour id and the payload words."""
    return WORD * (2 + 1 + int(msg_words))


def tick_bytes_a_shard(routed_a_tick: float, shards: int,
                       msg_words: int) -> float:
    """`routed_a_tick` entries leave the world's outboxes in a tick and
    as many arrive: a shard's share, read + written + written again."""
    return 3.0 * routed_a_tick / shards * entry_bytes(msg_words)


def tick_min_seconds(routed_a_tick: float, shards: int, msg_words: int,
                     peak: dict) -> float:
    return (tick_bytes_a_shard(routed_a_tick, shards, msg_words)
            / peak["hbm_bytes_per_s"])
