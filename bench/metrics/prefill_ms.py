"""Mean time of one exclusive prefill, from building its arguments to the
host holding its first token (``engine.prefill``, the wait on the device
included).  Read from the program's span counters; nothing to read where
no prefill ran in the window."""

LAYER = "device step"
MOVES = "ttft_p50_ms"


def read(run):
    c = run.counters
    n = c.get("span.engine.prefill.n", 0.0)
    if n <= 0:
        return None
    return 1e3 * c["span.engine.prefill.s"] / n
