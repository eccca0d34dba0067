"""Mean share of the paged KV pool in use after each step
(``blockpool.blocks_in_use / blockpool.num_blocks``).  Nothing to read
where the model keeps no paged KV."""

LAYER = "memory"
MOVES = "out_tok_s"


def read(run):
    xs = [s.blocks_in_use / s.num_blocks for s in run.steps if s.num_blocks]
    return 100.0 * sum(xs) / len(xs) if xs else None
