"""Unified observability layer: spans, tracing, metrics and audit.

One bundle, one wiring point (``Engine(trace=..., audit=...)``):

* :mod:`repro.obs.metrics` — the typed metrics registry every subsystem
  registers into; always on (pure host bookkeeping), ``snapshot()`` is the
  single source of truth ``mem_stats()`` and the benchmarks read.
* :mod:`repro.obs.trace` — host spans (``Observability.span``) on the obs
  clock, the wall clock the JAX profiler stamps host events with: each
  span is a profiler ``TraceAnnotation``, two always-on registry counters
  (``span.<name>.s`` / ``.n``) and, with the tracer on, a slice of the
  Chrome/Perfetto export (``serve.py --trace-out``).  The tracer is off by
  default (:class:`NullTracer`).
* :mod:`repro.obs.audit` — the per-committed-token determinism audit log
  (``serve.py --audit-out``).  Off by default (:class:`NullAudit`).

Everything here is observer-effect-free by construction: recorders are
host-side, device programs are identical with recording on or off, and
``tests/test_obs.py`` proves committed streams bitwise-identical across
the on/off matrix, the profiler included.
"""

from __future__ import annotations

from repro.obs.audit import AuditLog, NullAudit, TokenProvenance
from repro.obs.metrics import (
    Counter,
    Gauge,
    GaugeFn,
    Histogram,
    MetricsRegistry,
)
from repro.obs.trace import (
    NullTracer,
    Span,
    Tracer,
    now_ns,
    validate_chrome_trace,
)


class Observability:
    """The engine's observability bundle.

    ``metrics`` is always a live registry (snapshotting is free until
    called); ``tracer`` and ``audit`` are real recorders only when asked
    for — their Null twins cost one attribute check per call site.
    """

    def __init__(self, *, trace: bool = False, audit: bool = False,
                 registry: MetricsRegistry | None = None) -> None:
        self.metrics = registry if registry is not None else MetricsRegistry()
        self.tracer = Tracer() if trace else NullTracer()
        self.audit = AuditLog() if audit else NullAudit()
        self._open: list = []  # spans entered and not yet left, outermost first
        self._span_counters: dict = {}

    def span(self, name: str) -> Span:
        """A host span for a ``with`` block (:mod:`repro.obs.trace`)."""
        counters = self._span_counters.get(name)
        if counters is None:
            m = self.metrics
            counters = self._span_counters[name] = (
                m.counter(f"span.{name}.s", unit="s",
                          help=f"seconds inside {name} (obs clock)"),
                m.counter(f"span.{name}.n", unit="spans",
                          help=f"times {name} was entered"),
            )
        return Span(self, name, counters)


__all__ = [
    "AuditLog",
    "Counter",
    "Gauge",
    "GaugeFn",
    "Histogram",
    "MetricsRegistry",
    "NullAudit",
    "NullTracer",
    "Observability",
    "Span",
    "TokenProvenance",
    "Tracer",
    "now_ns",
    "validate_chrome_trace",
]
