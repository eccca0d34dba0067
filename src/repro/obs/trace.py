"""Host spans on the profiler's clock, and request tracing exported as
Chrome/Perfetto trace-event JSON.

**The obs clock.** :func:`now_ns` is wall time in nanoseconds since the
epoch (``time.time_ns``): the clock the JAX profiler stamps its host
events with.  An ``.xplane.pb`` stores them relative to the session's
``profile_start_time`` (a stat of its ``Task Environment`` plane), so
``profile_start_time + event.start_ns`` is an obs-clock stamp
(``tests/test_obs.py`` checks this against a CPU profile).

**Spans.** ``with obs.span("engine.plan"):`` (:class:`Span`, opened
through ``Observability.span``) does three things:

* opens ``jax.profiler.TraceAnnotation(name)``, so the span lands in any
  profiler trace on the same clock as the device's ops;
* adds its seconds and 1 to the registry counters ``span.<name>.s`` and
  ``span.<name>.n`` (always on: two clock reads and two adds);
* when the :class:`Tracer` is on, records a slice on the ``host`` row
  with the same start and end.

Spans nest as the ``with`` blocks do.  A span's self time is its
duration minus what its child spans cover (:attr:`Span.self_s`).

**Tracer export** (``serve.py --trace-out``): ``"ph": "X"`` slices of the
host spans (``ts``/``dur`` in whole microseconds of the obs clock),
``"ph": "i"`` protocol instants (submit, admit, verify_submit, commit,
rollback, preempt, restore, retire) on the ``protocol`` row,
``"ph": "b"``/``"e"`` one async track per request from submit to retire,
and ``"ph": "M"`` metadata naming the process and rows.  Stream-clock
values the scheduler acts on (a verdict's ``ready_at``) ride as instant
arguments.  :func:`validate_chrome_trace` is the schema gate every
export passes.

Recording is host-side only: device programs are the same with spans,
the tracer or the profiler on or off, so committed streams are bitwise
identical (``tests/test_obs.py``).  With tracing off the engine holds a
:class:`NullTracer`.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Tuple

import jax

#: rows of one process; per-request async tracks carry their own ids
TID_HOST = 0
TID_PROTOCOL = 1
_THREAD_NAMES = {TID_HOST: "host", TID_PROTOCOL: "protocol"}


def now_ns() -> int:
    """The obs clock: the JAX profiler's host clock, wall ns since the
    epoch."""
    return time.time_ns()


def _us(t_ns: int) -> int:
    """Whole microseconds, floored: a child's slice stays inside its
    parent's and adjacent slices never overlap."""
    return t_ns // 1000


class Span:
    """One timed host phase; see the module docstring.  ``start_ns`` /
    ``end_ns`` are obs-clock stamps, ``child_ns`` the time covered by the
    spans opened directly inside it."""

    __slots__ = ("name", "start_ns", "end_ns", "child_ns", "_obs", "_ann",
                 "_counters")

    def __init__(self, obs, name: str, counters) -> None:
        self.name = name
        self._obs = obs
        self._counters = counters
        self.start_ns = self.end_ns = self.child_ns = 0

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    @property
    def self_s(self) -> float:
        return (self.end_ns - self.start_ns - self.child_ns) / 1e9

    def __enter__(self) -> "Span":
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._obs._open.append(self)
        self.start_ns = now_ns()
        return self

    def __exit__(self, *exc) -> None:
        # the wall clock may step back: a span never lasts less than 0
        self.end_ns = end = max(now_ns(), self.start_ns)
        self._ann.__exit__(*exc)
        open_ = self._obs._open
        open_.pop()
        dur = end - self.start_ns
        if open_:
            open_[-1].child_ns += dur
        secs, count = self._counters
        secs.inc(dur / 1e9)
        count.inc()
        tr = self._obs.tracer
        if tr.enabled:
            tr.slice(self.name, self.start_ns, end,
                     self_us=_us(dur - self.child_ns))


class NullTracer:
    """No-op recorder: one attribute read per call site."""

    enabled = False

    def slice(self, name: str, start_ns: int, end_ns: int,
              **args: Any) -> None:
        pass

    def instant(self, name: str, **args: Any) -> None:
        pass

    def request_begin(self, rid: int, t_ns: int) -> None:
        pass

    def request_end(self, rid: int, t_ns: int) -> None:
        pass

    def to_chrome_trace(
        self, pid: int = 0, process_name: str = "llm42-engine"
    ) -> Dict[str, Any]:
        return {"traceEvents": [], "displayTimeUnit": "ms"}


class Tracer(NullTracer):
    enabled = True

    def __init__(self) -> None:
        #: host slices: (name, start_ns, end_ns, args)
        self.slices: List[Tuple[str, int, int, Dict[str, Any]]] = []
        #: instants: (name, t_ns, args)
        self._instants: List[Tuple[str, int, Dict[str, Any]]] = []
        #: async request events: (ph, rid, t_ns)
        self._asyncs: List[Tuple[str, int, int]] = []

    def slice(self, name: str, start_ns: int, end_ns: int,
              **args: Any) -> None:
        self.slices.append((name, start_ns, end_ns, args))

    def instant(self, name: str, **args: Any) -> None:
        self._instants.append((name, now_ns(), args))

    def request_begin(self, rid: int, t_ns: int) -> None:
        self._asyncs.append(("b", rid, t_ns))

    def request_end(self, rid: int, t_ns: int) -> None:
        self._asyncs.append(("e", rid, t_ns))

    def to_chrome_trace(
        self, pid: int = 0, process_name: str = "llm42-engine"
    ) -> Dict[str, Any]:
        """Chrome trace-event JSON (``traceEvents`` array form).

        ``pid``/``process_name`` namespace this tracer's rows: the cluster
        front end exports each replica under its own pid
        (``Cluster.chrome_trace`` merges them; the validator keys rows on
        (pid, tid), so a merged trace validates).
        """
        events: List[Dict[str, Any]] = [
            {"ph": "M", "pid": pid, "tid": 0, "name": "process_name",
             "args": {"name": process_name}},
        ]
        for tid, tname in _THREAD_NAMES.items():
            events.append({"ph": "M", "pid": pid, "tid": tid,
                           "name": "thread_name", "args": {"name": tname}})
        # (start, -end) order: a parent precedes the children it starts with
        for name, start, end, args in sorted(
            self.slices, key=lambda s: (_us(s[1]), -_us(s[2]))
        ):
            ts = _us(start)
            events.append({
                "ph": "X", "pid": pid, "tid": TID_HOST, "name": name,
                "cat": "host", "ts": ts, "dur": _us(end) - ts, "args": args,
            })
        for name, t, args in sorted(self._instants, key=lambda i: i[1]):
            events.append({
                "ph": "i", "pid": pid, "tid": TID_PROTOCOL, "name": name,
                "cat": "protocol", "s": "t", "ts": _us(t), "args": args,
            })
        for ph, rid, t in sorted(self._asyncs, key=lambda a: (a[2], a[0])):
            events.append({
                "ph": ph, "pid": pid, "tid": TID_PROTOCOL,
                "name": f"request {rid}", "cat": "request", "id": str(rid),
                "ts": _us(t),
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}


def validate_chrome_trace(trace: Any) -> List[str]:
    """Schema-check an exported trace; returns problems (empty = valid).

    Enforces what Perfetto's importer needs: the ``traceEvents`` container,
    required fields per phase, non-negative µs clocks, per-(pid, tid)
    monotone ``X`` starts, matched async begin/end per id, and proper
    nesting — two slices on one row either disjoint or contained, never
    partially overlapping."""
    errs: List[str] = []
    if not isinstance(trace, dict) or not isinstance(
        trace.get("traceEvents"), list
    ):
        return ["top level must be an object with a 'traceEvents' list"]
    by_row: Dict[Tuple[int, int], List[Tuple[float, float, str]]] = {}
    async_depth: Dict[str, int] = {}
    for i, ev in enumerate(trace["traceEvents"]):
        where = f"event[{i}]"
        if not isinstance(ev, dict):
            errs.append(f"{where}: not an object")
            continue
        ph = ev.get("ph")
        if ph not in ("X", "i", "b", "e", "M", "C"):
            errs.append(f"{where}: unknown phase {ph!r}")
            continue
        for field in ("pid", "name"):
            if field not in ev:
                errs.append(f"{where} (ph={ph}): missing {field!r}")
        if ph == "M":
            continue
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)) or ts < 0:
            errs.append(f"{where} (ph={ph}): ts must be a non-negative "
                        f"number of microseconds, got {ts!r}")
            continue
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                errs.append(f"{where}: X event needs dur >= 0, got {dur!r}")
                continue
            if "tid" not in ev:
                errs.append(f"{where}: X event missing tid")
                continue
            by_row.setdefault((ev["pid"], ev["tid"]), []).append(
                (float(ts), float(ts) + float(dur), ev.get("name", "?"))
            )
        elif ph == "i":
            if ev.get("s", "t") not in ("t", "p", "g"):
                errs.append(f"{where}: instant scope must be t/p/g")
        elif ph in ("b", "e"):
            if "id" not in ev or "cat" not in ev:
                errs.append(f"{where}: async {ph} event needs id and cat")
                continue
            d = async_depth.get(str(ev["id"]), 0) + (1 if ph == "b" else -1)
            async_depth[str(ev["id"])] = d
            if d < 0:
                errs.append(f"{where}: async end before begin for "
                            f"id {ev['id']!r}")
    for aid, d in async_depth.items():
        if d > 0:
            errs.append(f"async id {aid!r}: {d} begin(s) without end")
    eps = 1e-6  # sub-nanosecond slack for float error in ts + dur sums
    for (pid, tid), rows in by_row.items():
        last_start = -1.0
        for start, _, name in rows:
            if start < last_start - eps:
                errs.append(
                    f"row (pid={pid}, tid={tid}): X events not sorted by ts "
                    f"at slice {name!r}"
                )
                break
            last_start = start
        stack: List[Tuple[float, float, str]] = []
        for start, end, name in rows:
            while stack and start >= stack[-1][1] - eps:
                stack.pop()
            if stack and end > stack[-1][1] + eps:
                errs.append(
                    f"row (pid={pid}, tid={tid}): slice {name!r} "
                    f"[{start}, {end}) partially overlaps enclosing "
                    f"{stack[-1][2]!r} [{stack[-1][0]}, {stack[-1][1]})"
                )
                break
            stack.append((start, end, name))
    return errs
