"""Useful model FLOPs of the traced steps over the traced window, as a
share of the chip's bf16 peak.  Useful: every delivered token at its
context, and the prompt positions prefilled for it
(``bench/flops/<kind>.py``); recomputed tokens do not count."""

LAYER = "device step"
MOVES = "out_tok_s"


def read(run):
    if run.trace is None or not run.traced or not run.peaks:
        return None
    f = run.flops
    useful = 0
    for s in run.traced:
        useful += f.token_flops(run.model, s.delivered_ctx)
        for p in s.prefilled_lens:
            useful += f.prefill_flops(run.model, p)
    return 100.0 * useful / run.trace.window_s / run.peaks["bf16_flops"]
