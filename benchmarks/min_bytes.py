"""The least bytes one tick must move, from shapes.

Each live message's record (header word + payload words) is written
once, where it is delivered, and read once, where it is dispatched;
each actor that dispatches in the tick reads its state fields once and
writes them once. Nothing else is counted: no plan, no sort, no outbox,
no mailbox table around the record — those are the formulation's
choices, and the roofline share says how far above the least they sit.
"""

from __future__ import annotations

WORD = 4


def tick_min_bytes(shape: dict) -> float:
    """`shape` is a world's `tick_shape()`: messages, dispatching_actors,
    record_words, state_words."""
    records = shape["messages"] * 2 * shape["record_words"] * WORD
    states = shape["dispatching_actors"] * 2 * shape["state_words"] * WORD
    return float(records + states)


def tick_min_seconds(shape: dict, peak: dict) -> float:
    return tick_min_bytes(shape) / peak["hbm_bytes_per_s"]
