"""The determinism cost paid on the host, per engine step: landing
verdicts (``engine.verdicts``: splices, state restores, re-anchors),
building verify groups (``engine.verify_prep``) and landing their
results (``engine.verify_post``).  Read from the program's span counters;
nothing to read where no verify group was built in the window."""

LAYER = "scheduler and verifier"
MOVES = "out_tok_s"


def read(run):
    c = run.counters
    steps = c.get("span.engine.step.n", 0.0)
    if steps <= 0 or c.get("span.engine.verify_prep.n", 0.0) <= 0:
        return None
    host = sum(c.get(f"span.engine.{name}.s", 0.0)
               for name in ("verdicts", "verify_prep", "verify_post"))
    return 1e3 * host / steps
