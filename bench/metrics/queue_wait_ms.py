"""Mean wait of a request from ``Engine.submit`` to its first admission,
over the requests admitted in the window (the program's
``latency.queue_wait`` histogram, on the program's clock).  A mean, not a
tail: a window admits about 20 requests."""

LAYER = "scheduler and verifier"
MOVES = "ttft_p50_ms"


def read(run):
    c = run.counters
    n = c.get("latency.queue_wait.count", 0.0)
    if n <= 0:
        return None
    return 1e3 * c["latency.queue_wait.sum"] / n
