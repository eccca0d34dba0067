"""Tokens recomputed after a rollback, as a share of the tokens delivered
in the window (``tokens.recomputed`` over the loop's delivered count).
Nothing to read where no request is deterministic."""

LAYER = "scheduler and verifier"
MOVES = "out_tok_s"


def read(run):
    if run.counters.get("verify.windows_submitted", 0) <= 0 or not run.delivered:
        return None
    return 100.0 * run.counters.get("tokens.recomputed", 0.0) / run.delivered
