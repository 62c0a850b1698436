"""The least bytes one collector pass must move, from shapes.

Once a pass: every row's `alive` and `pinned` flags (1 B each) and its
occupancy (`head` and `tail`, 4 B each) are read to find the roots, the
occupied mailbox slots are read for the Ref arguments they carry (the
header word and the Ref word of every queued message, 4 B each), and
`alive` is written back by the sweep (1 B a row). Once a HOP: every Ref
column is read (4 B a row a field) and the mark is read and written
(1 B + 1 B a row). Nothing else is counted: no sort of the edges, no
prefix sum, no second look at a row — those are the formulation's, and
`gc_mark_roofline` says how far above the least it sits. The rule is
memory bandwidth: a trace is no FLOP.
"""

from __future__ import annotations

WORD = 4
FLAG = 1


def pass_bytes(rows: int, ref_fields: int, queued: float,
               hops: float) -> float:
    once = rows * (2 * FLAG + 2 * WORD + FLAG) + queued * 2 * WORD
    a_hop = rows * (ref_fields * WORD + 2 * FLAG)
    return float(once + hops * a_hop)


def pass_min_seconds(rows: int, ref_fields: int, queued: float,
                     hops: float, peak: dict) -> float:
    return pass_bytes(rows, ref_fields, queued, hops) \
        / peak["hbm_bytes_per_s"]
