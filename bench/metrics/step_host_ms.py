"""Host time per engine step during which the device cannot be running
that step's work: ``engine.step`` less ``engine.wait`` (the host blocked
on a launch's outputs), over the steps of the window.  Read from the
program's span counters ``span.<name>.s`` / ``.n``; nothing to read where
the program has no such spans."""

LAYER = "scheduler and verifier"
MOVES = "out_tok_s"


def read(run):
    c = run.counters
    steps = c.get("span.engine.step.n", 0.0)
    if steps <= 0:
        return None
    host = c["span.engine.step.s"] - c.get("span.engine.wait.s", 0.0)
    return 1e3 * host / steps
