"""The tests' own count of what one mailbox-rebuild block reads
(delivery.rebuild_tables), kept apart from the code it checks."""

BLOCK = 8       # delivery.REBUILD_BLOCK, written out: an oracle's own


def block_indices(rows: int, deep: int) -> int:
    """Indices one rebuild block's gathers read over a cohort of `rows`
    local rows, `deep` of which have a message in the block: full width
    (8 ranks a row) while they outnumber M = ceil(rows / 8), else
    compacted (8 ranks for M rows; the way back to the table's lanes is
    a scatter and reads none)."""
    m = -(-rows // BLOCK)
    return BLOCK * (rows if deep > m else m)
