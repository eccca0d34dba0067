"""Backend compiles inside the measured window (a persistent-cache hit
counts too).  Each one stalls the whole batch for its compile or load,
which the window's token count pays.  Read from ``CompileStats``."""

LAYER = "entry point"
MOVES = "out_tok_s"


def read(run):
    return float(run.compiles)
