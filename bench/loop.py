"""The host loop: drives the engine through its public path only.

``Engine.submit`` and ``Engine.step``, nothing private.  After each step
the loop reads ``len(req.committed)`` of every request in flight on the
host clock: a deterministic request's token is delivered when it is
committed, any other request's when it is emitted (it commits on
emission), and tokens recomputed after a rollback are never delivered.
Every step, submit and collect is wrapped in a profiler annotation of its
own, so a traced run can put each idle gap of the device down to what
the host was doing.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Iterable, List, Optional

import jax

from bench.traffic import RequestSpec

STEP, SUBMIT, COLLECT = "bench.step", "bench.submit", "bench.collect"


@dataclasses.dataclass
class Served:
    spec: RequestSpec
    req: object  # repro.serving.request.Request
    submit_t: float
    times: List[float] = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.times) >= self.spec.max_new_tokens


@dataclasses.dataclass
class StepSample:
    running: int  # len(engine.running) after the step
    blocks_in_use: Optional[float]  # None where KV is not paged
    num_blocks: Optional[float]
    decode_ctx: List[int]  # context length of each row able to decode
    delivered: int  # tokens delivered by this step
    prefilled_lens: List[int]  # prompts whose first token came in this step
    delivered_ctx: List[int]  # position of each delivered token


def make_request(spec: RequestSpec, rid: int):
    from repro.serving.request import Request, SamplingParams

    return Request(
        rid=rid, prompt=list(spec.prompt),
        sampling=SamplingParams(
            temperature=0.0, top_k=0, seed=rid,
            max_new_tokens=spec.max_new_tokens,
            is_deterministic=spec.deterministic,
        ),
    )


def _gauge(engine, name: str) -> Optional[float]:
    series = engine.obs.metrics.get(name)
    return None if series is None else float(series.value)


def _decode_ctx(engine) -> List[int]:
    from repro.serving.request import State

    return [
        r.prompt_len + len(r.committed) + len(r.speculation)
        for r in engine.running
        if r.state is not State.PREFILLING and not r.done_decoding()
    ]


class ClosedLoop:
    """``clients`` clients, each with one request in flight at a time."""

    def __init__(self, engine, requests: Iterable[RequestSpec], clients: int,
                 rid_base: int = 0):
        self.engine = engine
        self.source = iter(requests)
        self.clients = clients
        self.rid_base = rid_base
        self.served: List[Served] = []
        self.inflight: List[Served] = []
        self.steps: List[StepSample] = []
        self.paged = bool(_gauge(engine, "blockpool.paged"))

    def _submit(self, now: float) -> None:
        spec = next(self.source)
        req = make_request(spec, self.rid_base + spec.index)
        with jax.profiler.TraceAnnotation(SUBMIT):
            self.engine.submit(req)
        s = Served(spec, req, now)
        self.served.append(s)
        self.inflight.append(s)

    def run(self, seconds: float, ramp: float = 0.0,
            on_open: Optional[Callable[[], None]] = None,
            on_step: Optional[Callable[[int, float], None]] = None) -> float:
        """Send the first request of every client, run ``ramp`` seconds of
        host clock so that the window opens on requests in every stage,
        then the window of ``seconds``; returns the window's start.  Only
        the window's steps are recorded; ``on_open`` is called as the
        window opens, ``on_step`` before each of its steps."""
        eng = self.engine
        t = time.perf_counter()
        for _ in range(self.clients):
            self._submit(t)
        t_start = None
        t_end = t + ramp + seconds
        i = 0
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            if t_start is None and now >= t_end - seconds:
                if on_open is not None:
                    on_open()
                t_start = time.perf_counter()
                t_end = t_start + seconds
                now = t_start
            if t_start is not None and on_step is not None:
                on_step(i, now)
            ctx = _decode_ctx(eng)
            with jax.profiler.StepTraceAnnotation(STEP, step_num=i):
                eng.step()
            t1 = time.perf_counter()
            if t_start is not None and t1 > t_end:
                # the step ran past the window's close: what it delivered
                # falls outside the window
                break
            with jax.profiler.TraceAnnotation(COLLECT):
                delivered, prefilled, dctx = self._collect(t1)
            if t_start is not None:
                self.steps.append(StepSample(
                    len(eng.running),
                    _gauge(eng, "blockpool.blocks_in_use") if self.paged
                    else None,
                    _gauge(eng, "blockpool.num_blocks") if self.paged
                    else None,
                    ctx, delivered, prefilled, dctx,
                ))
                i += 1
        return t_start

    def _collect(self, t: float):
        delivered = 0
        prefilled: List[int] = []
        dctx: List[int] = []
        current, self.inflight = self.inflight, []
        still = []
        for s in current:
            new = len(s.req.committed) - len(s.times)
            if new > 0:
                if not s.times:
                    prefilled.append(len(s.spec.prompt))
                base = len(s.spec.prompt) + len(s.times)
                dctx.extend(range(base, base + new))
                s.times.extend([t] * new)
                delivered += new
            if s.done:
                self._submit(t)  # the client's next request
            else:
                still.append(s)
        self.inflight = still + self.inflight
        return delivered, prefilled, dctx


def drain(engine, specs: List[RequestSpec], rid_base: int,
          starts: Optional[List[int]] = None) -> List[object]:
    """Submit ``specs`` (``specs[i]`` before step ``starts[i]``, default
    all at once) and step until the engine is empty."""
    starts = starts or [0] * len(specs)
    reqs = [make_request(s, rid_base + s.index) for s in specs]
    pending = sorted(zip(starts, range(len(reqs))))
    step = 0
    while True:
        while pending and pending[0][0] <= step:
            engine.submit(reqs[pending.pop(0)[1]])
        busy = engine.step()
        step += 1
        if not busy and not pending:
            return reqs
