"""Mean number of running requests after each step of the window
(``len(engine.running)``): how full the scheduler keeps the batch."""

LAYER = "scheduler and verifier"
MOVES = "out_tok_s"


def read(run):
    if not run.steps:
        return None
    return sum(s.running for s in run.steps) / len(run.steps)
