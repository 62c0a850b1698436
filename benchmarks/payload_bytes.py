"""The least bytes a tick's payloads must move, from shapes.

A dependency's payload is written once, by the task that fills it, and
read once, by the task that checks it: `2 * pairs` words each way. The
reader must know that its handle still names the slot before it touches
a word: one generation word and one allocation flag of the slot
(4 B + 1 B), as `heap_bytes.py` counts an update's. Nothing else is
counted: no zeroing of a fresh slot, no free list, no reservation, no
index, no second check for the write — those are the formulation's, and
`payload_roofline` says how far above the least it sits. The rule is
memory bandwidth: a compare and an add are no FLOPs.
"""

from __future__ import annotations

WORD = 4
FLAG = 1


def payload_words(traffic: dict) -> int:
    """Words of one dependency's payload."""
    return 2 * int(traffic["output_pairs"])


def payloads_per_tick(cfg: dict, traffic: dict) -> int:
    """Every point runs one timestep a tick (worlds/taskbench.py)."""
    return int(cfg["actors"]) * int(traffic["dependencies"])


def words_moved_per_tick(cfg: dict, traffic: dict) -> int:
    """Payload words written plus payload words read a tick."""
    return 2 * payload_words(traffic) * payloads_per_tick(cfg, traffic)


def payload_bytes(traffic: dict) -> int:
    """One dependency: its words written and read, its handle's check."""
    return 2 * payload_words(traffic) * WORD + WORD + FLAG


def tick_bytes(cfg: dict, traffic: dict) -> float:
    return float(payloads_per_tick(cfg, traffic) * payload_bytes(traffic))


def tick_min_seconds(cfg: dict, traffic: dict, peak: dict) -> float:
    return tick_bytes(cfg, traffic) / peak["hbm_bytes_per_s"]
