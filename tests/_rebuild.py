"""The tests' own count of what one mailbox-rebuild block reads
(delivery.rebuild_tables), kept apart from the code it checks."""

BLOCK = 8       # delivery.REBUILD_BLOCK, written out: an oracle's own


def block_indices(rows: int, deep: int, ranks: int) -> int:
    """Indices one rebuild block's gathers read over a cohort of `rows`
    local rows, `deep` of which have a message in the block and the
    fullest of which holds `ranks` of the block's 8 (the cohort's
    fullest acceptance of the tick, less the ranks of the blocks before,
    at most 8). Full width while the deep rows outnumber M = ceil(rows /
    8): the ranks some row holds, each over every row. Else compacted:
    8 ranks for M rows, however few of them are held (the way back to
    the table's lanes is a scatter and reads none)."""
    assert 1 <= ranks <= BLOCK
    m = -(-rows // BLOCK)
    return ranks * rows if deep > m else BLOCK * m


def tick_indices(rows: int, acc) -> int:
    """`block_indices` over the blocks one tick's rebuild runs for a
    cohort of `rows` rows that accept `acc` messages each (any
    sequence): ceil(max / 8) blocks, none for a tick that brings it
    nothing."""
    acc = [int(a) for a in acc]
    most = max(acc, default=0)
    return sum(
        block_indices(rows, sum(a > k for a in acc), min(BLOCK, most - k))
        for k in range(0, most, BLOCK))
