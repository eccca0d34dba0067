"""Paged decode-attention kernel: its least time over its time in the
trace.  The least time of each traced decode pass is the larger of its
FLOPs over the bf16 peak and its bytes over the HBM bandwidth, counted
from the table blocks of each decode row (``bench/flops/paged_attention.py``);
the kernel's time is the sum of its events, found by name."""

from bench.flops import paged_attention as pa

LAYER = "kernels"
MOVES = "out_tok_s"
#: substring of the kernel's name in the device trace
NAME = "paged_attention"


def read(run):
    if run.trace is None or not run.traced or not run.peaks:
        return None
    t_kernel = run.trace.kernel_seconds(NAME)
    if t_kernel <= 0:
        return None
    bs = run.model["engine"]["block_size"]
    least = 0.0
    for s in run.traced:
        f, b = pa.pass_flops_bytes(run.model, s.decode_ctx, bs)
        least += max(f / run.peaks["bf16_flops"],
                     b / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t_kernel
