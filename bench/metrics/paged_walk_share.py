"""Share of the block-table reach that the paged decode passes walk: the
rows' live blocks (``pos // block_size + 1`` each) over rows times the
table's blocks, summed over the decode passes of the window.  Read from
the program's counters ``paged.walk_blocks`` / ``paged.reach_blocks``;
nothing to read where the program has no such counters or no decode pass
ran."""

LAYER = "kernels"
MOVES = "out_tok_s"


def read(run):
    c = run.counters
    reach = c.get("paged.reach_blocks", 0.0)
    if reach <= 0:
        return None
    return 100.0 * c["paged.walk_blocks"] / reach
