"""Observability-layer tests.

Four contracts:

* **Schema** — engine-exported Chrome/Perfetto traces pass
  :func:`repro.obs.validate_chrome_trace` (and the validator itself
  rejects each class of malformed trace).
* **One clock** — the engine's host spans land in a JAX profiler trace,
  nested as called, at the times the tracer recorded for them.
* **Observer-effect freedom** — committed streams are bitwise identical
  with spans, tracer, audit and profiler on vs off, across scheduler
  policies and speculation depths (hypothesis-driven).
* **Audit coverage** — every committed token of an audited run has
  exactly one provenance record (schedule + window + margin for
  verify-committed tokens); rollback victims have none.

Plus unit tests for the metrics registry, the ``mem_stats`` compat shim,
and the ``persist.py --check`` tolerance comparator.
"""

import glob
import json
import os
import shutil
import tempfile

import jax
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_smoke_config
from repro.core.determinism import Mode, ReductionPolicy
from repro.models import init_params
from repro.obs import (
    AuditLog,
    MetricsRegistry,
    Observability,
    TokenProvenance,
    now_ns,
    validate_chrome_trace,
)
from repro.obs.trace import TID_HOST, TID_PROTOCOL
from repro.serving.engine import Engine
from repro.serving.request import Request, SamplingParams
from repro.serving.scheduler import (
    AdaptivePolicy,
    OverlapPolicy,
    PauseDecodePolicy,
)

#: aggressive drift so rollbacks actually happen at toy scale
DRIFTY = ReductionPolicy(
    thresholds=((2, 16), (4, 8), (16, 4)), combine_dtype="bfloat16"
)

SCHEDULERS = {
    "pause": PauseDecodePolicy,
    "overlap": OverlapPolicy,
    "adaptive": AdaptivePolicy,
}


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config("llama3-8b")
    return cfg, init_params(cfg, jax.random.key(0))


def _reqs(cfg, n=4, max_new=14):
    return [
        Request(
            rid=i, prompt=[(5 * i + j) % cfg.vocab_size for j in range(9)],
            sampling=SamplingParams(
                max_new_tokens=max_new, is_deterministic=(i % 2 == 0),
                seed=70 + i,
            ),
        )
        for i in range(n)
    ]


#: steps [start, stop) of a profiled run that the JAX profiler records
PROFILED_STEPS = (3, 6)


def _run(cfg, params, *, scheduler="overlap", spec_depth=1, trace=False,
         audit=False, n=4, max_new=14, profile=False):
    """One engine run to drain.  ``profile=True`` records the steps
    ``PROFILED_STEPS`` with the JAX profiler and keeps, on the engine,
    the obs-clock bounds of the recorded span (``profiled_ns``) and the
    trace's host events (``profiled_events``, see ``_host_events``)."""
    eng = Engine(cfg, params, mode=Mode.LLM42, policy=DRIFTY, window=5,
                 group=2, max_batch=8, capacity=256,
                 scheduler=SCHEDULERS[scheduler](), spec_depth=spec_depth,
                 trace=trace, audit=audit)
    for r in _reqs(cfg, n, max_new):
        eng.submit(r)
    if not profile:
        return eng, eng.run()
    logdir = tempfile.mkdtemp(prefix="obs-profile-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    start, stop = PROFILED_STEPS
    busy, i, bounds = True, 0, []
    while busy:
        if i == start:
            jax.profiler.start_trace(logdir, profiler_options=opts)
            bounds.append(now_ns())
        busy = eng.step()
        i += 1
        if i == stop or (not busy and len(bounds) == 1):
            bounds.append(now_ns())
            jax.profiler.stop_trace()
    assert not (eng.running or eng.queue or eng.preempted)
    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    eng.profiled_ns = tuple(bounds)
    eng.profiled_events = _host_events(path)
    shutil.rmtree(logdir, ignore_errors=True)
    return eng, list(eng.finished)


def _host_events(path):
    """``(name, start_ns, end_ns)`` of every ``engine.*`` host event of an
    ``.xplane.pb``, on the obs clock: the file stores host events relative
    to the session's ``profile_start_time``."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    (t0,) = [dict(p.stats)["profile_start_time"] for p in planes
             if p.name == "Task Environment"]
    return [
        (e.name, int(t0 + e.start_ns), int(t0 + e.start_ns + e.duration_ns))
        for p in planes if p.name.startswith("/host:")
        for line in p.lines for e in line.events
        if e.name.startswith("engine.")
    ]


#: run cache — hypothesis revisits configurations, engine runs are the
#: expensive part, and every run is deterministic by construction
_RUNS = {}


def _cached_run(cfg, params, scheduler, spec_depth, obs_on):
    """``obs_on``: tracer, audit and a few profiled steps all on."""
    key = (scheduler, spec_depth, obs_on)
    if key not in _RUNS:
        _RUNS[key] = _run(cfg, params, scheduler=scheduler,
                          spec_depth=spec_depth, trace=obs_on, audit=obs_on,
                          profile=obs_on)
    return _RUNS[key]


# ----------------------------------------------------------------------
# metrics registry
# ----------------------------------------------------------------------


def test_counter_gauge_histogram_snapshot():
    reg = MetricsRegistry()
    c = reg.counter("a.count", unit="1", help="things")
    c.inc()
    c.inc(3)
    g = reg.gauge("a.level")
    g.set(2.5)
    g.set_max(1.0)  # lower: no-op
    reg.gauge_fn("a.pull", lambda: 7)
    h = reg.histogram("a.lat", unit="s")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["a.count"] == 4 and isinstance(snap["a.count"], int)
    assert snap["a.level"] == 2.5
    assert snap["a.pull"] == 7
    assert snap["a.lat.count"] == 4
    assert snap["a.lat.sum"] == 10
    assert snap["a.lat.min"] == 1 and snap["a.lat.max"] == 4
    assert snap["a.lat.p50"] == 3  # nearest-rank
    assert snap["a.lat.p99"] == 4


def test_registry_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    c1 = reg.counter("x")
    c2 = reg.counter("x")
    assert c1 is c2
    with pytest.raises(AssertionError):
        reg.gauge("x")
    with pytest.raises(AssertionError):
        c1.inc(-1)
    # gauge_fn re-registration replaces the callback (engine re-binds the
    # runtime under bind_cost_model)
    g = reg.gauge_fn("y", lambda: 1)
    reg.gauge_fn("y", lambda: 2)
    assert g.value == 2
    assert "y" in reg and reg.get("zzz") is None


def test_histogram_empty_and_describe():
    reg = MetricsRegistry()
    reg.histogram("h", unit="s", help="empty")
    snap = reg.snapshot()
    assert snap["h.count"] == 0 and snap["h.p99"] == 0
    cat = reg.describe()
    assert cat == [{"name": "h", "kind": "histogram", "unit": "s",
                    "help": "empty"}]


def test_registry_dump(tmp_path):
    reg = MetricsRegistry()
    reg.counter("n").inc(2)
    p = tmp_path / "m.json"
    reg.dump(str(p))
    d = json.loads(p.read_text())
    assert d["snapshot"] == {"n": 2}
    assert d["catalog"][0]["name"] == "n"


# ----------------------------------------------------------------------
# trace validator (negative cases — no engine needed)
# ----------------------------------------------------------------------


def _ev(**kw):
    base = {"ph": "X", "pid": 0, "tid": 0, "name": "p", "ts": 0, "dur": 1}
    base.update(kw)
    return base


def test_validator_rejects_malformed_traces():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"traceEvents": [_ev(ph="Q")]}) != []
    assert validate_chrome_trace({"traceEvents": [_ev(ts=-1)]}) != []
    assert validate_chrome_trace({"traceEvents": [_ev(dur=None)]}) != []
    # unmatched async begin
    assert validate_chrome_trace({"traceEvents": [
        {"ph": "b", "pid": 0, "tid": 2, "name": "r", "cat": "request",
         "id": "0", "ts": 0},
    ]}) != []
    # async end before begin
    assert validate_chrome_trace({"traceEvents": [
        {"ph": "e", "pid": 0, "tid": 2, "name": "r", "cat": "request",
         "id": "0", "ts": 0},
    ]}) != []
    # partial overlap on one row
    assert validate_chrome_trace({"traceEvents": [
        _ev(ts=0, dur=10), _ev(ts=5, dur=10),
    ]}) != []
    # out-of-order starts
    assert validate_chrome_trace({"traceEvents": [
        _ev(ts=10), _ev(ts=0),
    ]}) != []


def test_validator_accepts_nested_and_adjacent():
    assert validate_chrome_trace({"traceEvents": [
        _ev(ts=0, dur=10, name="parent"),
        _ev(ts=0, dur=4, name="child1"),
        _ev(ts=4, dur=6, name="child2"),
        _ev(ts=10, dur=5, name="next"),
    ]}) == []


def test_span_nesting_self_time_and_counters():
    obs = Observability()
    with obs.span("outer") as outer:
        with obs.span("inner") as a:
            pass
        with obs.span("inner") as b:
            pass
    assert outer.start_ns <= a.start_ns <= a.end_ns <= b.start_ns
    assert b.end_ns <= outer.end_ns
    assert outer.child_ns == (a.end_ns - a.start_ns) + (b.end_ns - b.start_ns)
    assert outer.self_s == pytest.approx(
        outer.seconds - a.seconds - b.seconds)
    assert a.self_s == a.seconds  # no children
    snap = obs.metrics.snapshot()
    assert snap["span.outer.n"] == 1 and snap["span.inner.n"] == 2
    assert snap["span.inner.s"] == pytest.approx(a.seconds + b.seconds)
    assert snap["span.outer.s"] == pytest.approx(outer.seconds)
    assert not obs._open  # every span left
    # the tracer is off: nothing recorded beyond the counters
    assert obs.tracer.to_chrome_trace()["traceEvents"] == []


def test_tracer_logical_layout_and_groups():
    """Spans become nested slices of the host row, on the obs clock;
    instants and request tracks carry obs-clock stamps too."""
    obs = Observability(trace=True)
    tr = obs.tracer
    t0 = now_ns()
    tr.request_begin(7, t0)
    with obs.span("engine.step"):
        with obs.span("engine.plan"):
            pass
        with obs.span("engine.launch"):
            tr.instant("commit", rid=7, ready_at=2.5)
    tr.request_end(7, now_ns())
    trace = tr.to_chrome_trace()
    assert validate_chrome_trace(trace) == []
    evs = trace["traceEvents"]
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(xs) == {"engine.step", "engine.plan", "engine.launch"}
    assert all(e["tid"] == TID_HOST and e["cat"] == "host"
               for e in xs.values())
    step, plan, launch = xs["engine.step"], xs["engine.plan"], \
        xs["engine.launch"]
    assert step["ts"] <= plan["ts"] <= plan["ts"] + plan["dur"] <= launch["ts"]
    assert launch["ts"] + launch["dur"] <= step["ts"] + step["dur"]
    assert step["args"]["self_us"] <= step["dur"]
    (commit,) = [e for e in evs if e["ph"] == "i"]
    assert commit["tid"] == TID_PROTOCOL and commit["args"]["ready_at"] == 2.5
    assert launch["ts"] <= commit["ts"] <= launch["ts"] + launch["dur"]
    begin, end = [e for e in evs if e["ph"] in "be"]
    assert begin["ts"] == t0 // 1000 <= step["ts"]
    assert end["ts"] >= step["ts"] + step["dur"]


# ----------------------------------------------------------------------
# engine-exported traces (golden schema)
# ----------------------------------------------------------------------

#: host spans every traced LLM42 overlap run passes through
ENGINE_SPANS = {
    "engine.step", "engine.verdicts", "engine.retire", "engine.admit",
    "engine.prefill", "engine.memory", "engine.plan", "engine.prep",
    "engine.verify_prep", "engine.launch", "engine.wait", "engine.post",
    "engine.verify_post",
}


def test_engine_trace_schema_and_attribution(model):
    cfg, params = model
    eng, done = _cached_run(cfg, params, "overlap", 1, True)
    trace = eng.obs.tracer.to_chrome_trace()
    assert validate_chrome_trace(trace) == []
    evs = trace["traceEvents"]

    # rows are named via metadata: host spans and protocol instants
    names = {e["args"]["name"] for e in evs if e["ph"] == "M"
             and e["name"] == "thread_name"}
    assert names == {"host", "protocol"}

    # every host span is a slice of the host row; the step encloses the rest
    xs = [e for e in evs if e["ph"] == "X"]
    assert all(e["tid"] == TID_HOST for e in xs)
    assert ENGINE_SPANS <= {e["name"] for e in xs}
    steps = [e for e in xs if e["name"] == "engine.step"]
    assert len(steps) == eng.obs.metrics.snapshot()["span.engine.step.n"]
    for e in xs:
        assert any(s["ts"] <= e["ts"] and
                   e["ts"] + e["dur"] <= s["ts"] + s["dur"] for s in steps)

    # per-request lifecycle: one async begin + one end per request
    begins = [e for e in evs if e["ph"] == "b"]
    ends = [e for e in evs if e["ph"] == "e"]
    assert len(begins) == len(done) and len(ends) == len(done)
    assert {e["id"] for e in begins} == {str(r.rid) for r in done}

    # protocol instants cover the lifecycle events this run had
    instants = {e["name"] for e in evs if e["ph"] == "i"}
    assert {"submit", "admit", "verify_submit", "retire"} <= instants
    assert instants & {"commit", "rollback"}
    assert all(e["tid"] == TID_PROTOCOL for e in evs if e["ph"] == "i")


def test_engine_trace_costed_clock(model):
    """Under the costed stream clock the trace stays on the obs clock: the
    stream clock reaches it only as the verdict deadline a
    ``verify_submit`` instant carries."""
    cfg, params = model
    from repro.configs import get_config

    t0 = now_ns()
    eng = Engine(cfg, params, mode=Mode.LLM42, policy=DRIFTY, window=5,
                 group=2, max_batch=8, capacity=256,
                 scheduler=OverlapPolicy(), trace=True,
                 verify_latency_ms=5.0, cost_cfg=get_config("llama3-8b"))
    for r in _reqs(cfg):
        eng.submit(r)
    eng.run()
    t1 = now_ns()
    trace = eng.obs.tracer.to_chrome_trace()
    assert validate_chrome_trace(trace) == []
    stamped = [e for e in trace["traceEvents"] if e["ph"] != "M"]
    assert all(t0 // 1000 <= e["ts"] <= t1 // 1000 for e in stamped)
    subs = [e for e in stamped if e["name"] == "verify_submit"]
    assert subs and all(
        0 < e["args"]["ready_at"] <= eng.runtime.makespan + 5e-3
        for e in subs)


def test_engine_spans_on_the_profilers_clock(model):
    """Every ``engine.*`` span of the profiled steps is a host event of the
    profiler's trace, nested as called, and the tracer's slice of it
    starts and ends within 1 ms of the event: one clock."""
    cfg, params = model
    eng, _ = _cached_run(cfg, params, "overlap", 1, True)
    lo, hi = eng.profiled_ns
    spans = [(n, s, e) for n, s, e, _ in eng.obs.tracer.slices
             if n.startswith("engine.") and lo <= s and e <= hi]
    events = eng.profiled_events
    assert {"engine.step", "engine.launch", "engine.wait"} <= {
        n for n, _, _ in spans}
    assert len(events) == len(spans)

    def parent(item, items):
        outer = [o for o in items if o is not item and o[1] <= item[1]
                 and item[2] <= o[2]]
        return min(outer, key=lambda o: o[2] - o[1])[0] if outer else None

    for span in spans:
        ev = min((e for e in events if e[0] == span[0]),
                 key=lambda e: abs(e[1] - span[1]))
        assert abs(ev[1] - span[1]) < 1e6 and abs(ev[2] - span[2]) < 1e6
        assert parent(ev, events) == parent(span, spans), span[0]


# ----------------------------------------------------------------------
# observer-effect freedom (the tentpole invariant)
# ----------------------------------------------------------------------


@settings(max_examples=6, deadline=None)
@given(
    scheduler=st.sampled_from(sorted(SCHEDULERS)),
    spec_depth=st.sampled_from([1, 4]),
)
def test_observability_is_observer_effect_free(model, scheduler, spec_depth):
    """Tracer, audit and profiler on vs off (spans are always on):
    committed streams bitwise identical for EVERY request (deterministic
    and fast-path alike — the engine launches identical device programs
    either way)."""
    cfg, params = model
    _, done_on = _cached_run(cfg, params, scheduler, spec_depth, True)
    _, done_off = _cached_run(cfg, params, scheduler, spec_depth, False)
    on = {r.rid: list(r.committed) for r in done_on}
    off = {r.rid: list(r.committed) for r in done_off}
    assert on == off


def test_policies_agree_with_observability_on(model):
    """The scheduler-interchangeability invariant holds for the
    deterministic subset while traced+audited."""
    cfg, params = model
    ref = None
    for scheduler in sorted(SCHEDULERS):
        _, done = _cached_run(cfg, params, scheduler, 1, True)
        streams = {r.rid: list(r.committed) for r in done
                   if r.sampling.is_deterministic}
        if ref is None:
            ref = streams
        assert streams == ref, f"{scheduler} moved a deterministic stream"


def _cluster_run(cfg, params, obs_on):
    from repro.cluster import Cluster, run_online

    def make_engine(idx):
        return Engine(cfg, params, mode=Mode.LLM42, policy=DRIFTY, window=5,
                      group=2, max_batch=2, capacity=128,
                      trace=obs_on, audit=obs_on)

    cluster = Cluster(make_engine, 2)
    shared = [(7 * j + 3) % cfg.vocab_size for j in range(32)]
    reqs = [
        Request(
            rid=i, prompt=shared + [(5 * i + j) % cfg.vocab_size
                                    for j in range(3)],
            sampling=SamplingParams(
                max_new_tokens=10, is_deterministic=(i % 2 == 0),
                seed=70 + i,
            ),
        )
        for i in range(5)
    ]
    res = run_online(cluster, cfg, [(r, 0.0) for r in reqs])
    return cluster, res


def test_cluster_router_path_is_observer_effect_free(model):
    """The routed multi-replica path keeps the tentpole invariant: with
    per-replica tracing + auditing on vs off, the router makes the same
    assignments and every replica commits bitwise-identical streams."""
    cfg, params = model
    cl_on, res_on = _cluster_run(cfg, params, True)
    cl_off, res_off = _cluster_run(cfg, params, False)
    assert res_on.assignment == res_off.assignment
    on = {r.rid: list(r.committed) for r in cl_on.finished}
    off = {r.rid: list(r.committed) for r in cl_off.finished}
    assert on == off


def test_cluster_merged_trace_validates_per_pid(model):
    """The merged fleet trace keys rows on (pid, tid): each replica's
    spans nest within its own process namespace and the whole trace
    passes the schema validator."""
    cfg, params = model
    cluster, _ = _cluster_run(cfg, params, True)
    trace = cluster.chrome_trace()
    assert validate_chrome_trace(trace) == []
    pids = {e["pid"] for e in trace["traceEvents"]}
    assert pids == {0, 1}
    # per-pid process_name metadata is present for both replicas
    meta = {
        (e["pid"], e["args"]["name"]) for e in trace["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    assert meta == {(0, "llm42-replica-0"), (1, "llm42-replica-1")}


# ----------------------------------------------------------------------
# determinism audit log
# ----------------------------------------------------------------------


@pytest.mark.parametrize("scheduler,spec_depth",
                         [("pause", 1), ("overlap", 1), ("overlap", 4),
                          ("adaptive", 1)])
def test_audit_covers_committed_stream_exactly(model, scheduler, spec_depth):
    cfg, params = model
    eng, done = _cached_run(cfg, params, scheduler, spec_depth, True)
    audit = eng.obs.audit
    assert audit.coverage_errors(done) == []
    total = sum(len(r.committed) for r in done)
    assert len(audit.records) == total


def test_audit_verify_records_carry_provenance(model):
    cfg, params = model
    eng, done = _cached_run(cfg, params, "overlap", 1, True)
    recs = eng.obs.audit.records
    vrecs = [r for r in recs if r.origin == "verify"]
    assert vrecs, "no verify-committed tokens in an LLM42 run with det reqs"
    for r in vrecs:
        assert r.window >= 0 and r.occurrence >= 0
        assert r.n_match >= 0
        assert r.schedule.startswith("(")  # str(tuple(schedule))
        assert r.margin is not None and r.margin >= 0.0
    # within one window, the accepted candidates precede the verifier token
    assert any(not r.accepted for r in vrecs), "every window ends in a " \
        "verifier-token record (accepted=False)"
    # det requests under LLM42 never commit from the fast path
    det = {r.rid for r in done if r.sampling.is_deterministic}
    assert all(r.origin != "decode" for r in recs if r.rid in det)
    # the committing schedule for verify commits is the verify-grade one
    from repro.core.determinism import VERIFY_SCHEDULE
    assert all(r.schedule == str(tuple(VERIFY_SCHEDULE)) for r in vrecs)


def test_audit_rollback_semantics(model):
    cfg, params = model
    eng, done = _cached_run(cfg, params, "overlap", 1, True)
    recs = eng.obs.audit.records
    # DRIFTY forces flips: some splice rolled back, and its record says so
    assert any(r.rollback for r in recs), "DRIFTY run had no rollback"
    total_rollbacks = sum(r.num_rollbacks for r in done)
    assert total_rollbacks > 0
    # rollback victims were never committed => coverage is exact (checked
    # above) AND indices are dense per request
    for r in done:
        idxs = [rec.index for rec in eng.obs.audit.for_request(r.rid)]
        assert idxs == list(range(len(r.committed)))


def test_audit_coverage_errors_detects_problems():
    audit = AuditLog()
    req = type("R", (), {"rid": 1, "committed": [5, 6]})()
    audit.record(TokenProvenance(rid=1, index=0, token=5, origin="prefill",
                                 schedule="s"))
    errs = audit.coverage_errors([req])  # index 1 uncovered
    assert any("index 1" in e for e in errs)
    audit.record(TokenProvenance(rid=1, index=1, token=99, origin="decode",
                                 schedule="s"))
    errs = audit.coverage_errors([req])  # wrong token
    assert any("99" in e for e in errs)
    audit.record(TokenProvenance(rid=2, index=0, token=1, origin="decode",
                                 schedule="s"))
    errs = audit.coverage_errors([req])  # unknown rid
    assert any("unknown rid 2" in e for e in errs)


# ----------------------------------------------------------------------
# engine metrics + mem_stats shim
# ----------------------------------------------------------------------


def test_engine_metrics_snapshot(model):
    cfg, params = model
    eng, done = _cached_run(cfg, params, "overlap", 1, True)
    snap = eng.obs.metrics.snapshot()
    assert snap["engine.requests_finished"] == len(done)
    assert snap["tokens.committed"] == sum(len(r.committed) for r in done)
    assert snap["verify.rollbacks"] == sum(r.num_rollbacks for r in done)
    assert snap["tokens.recomputed"] == sum(
        r.num_recomputed_tokens for r in done
    )
    assert snap["verify.rollback_depth.count"] == snap["verify.rollbacks"]
    assert snap["latency.ttft.count"] == len(done)
    assert snap["latency.e2e.count"] == len(done)
    assert snap["latency.queue_wait.count"] == len(done)
    assert 0 <= snap["latency.queue_wait.sum"] <= snap["latency.e2e.sum"]
    # host spans: one engine.step per iteration, the rest inside it
    assert snap["span.engine.step.n"] == snap["engine.iterations"]
    for name in ENGINE_SPANS - {"engine.step"}:
        assert 0 < snap[f"span.{name}.s"] <= snap["span.engine.step.s"]
    assert snap["engine.running"] == 0  # drained
    assert snap["engine.peak_running"] >= 1
    assert snap["blockpool.peak_blocks_in_use"] >= 1
    assert snap["verify.acceptance_ema.count"] == sum(
        1 for r in done if r.sampling.is_deterministic
    )
    # the catalog describes every snapshot series (histograms expand)
    catalog = {c["name"] for c in eng.obs.metrics.describe()}
    for key in snap:
        base = key.rsplit(".", 1)[0] if key.split(".")[-1] in (
            "count", "sum", "min", "max", "mean", "p50", "p90", "p99"
        ) else key
        assert base in catalog or key in catalog


def test_mem_stats_is_a_snapshot_shim(model):
    cfg, params = model
    eng, _ = _cached_run(cfg, params, "overlap", 1, True)
    ms = eng.mem_stats()
    snap = eng.obs.metrics.snapshot()
    assert ms["block_size"] == snap["blockpool.block_size"]
    assert ms["num_blocks"] == snap["blockpool.num_blocks"]
    assert ms["peak_blocks_in_use"] == snap["blockpool.peak_blocks_in_use"]
    assert ms["num_preemptions"] == snap["mem.preemptions"]
    assert ms["num_restores"] == snap["mem.restores"]
    assert ms["peak_running"] == snap["engine.peak_running"]
    assert ms["paged"] == bool(snap["blockpool.paged"])
    if eng.prefix_cache is not None:
        assert ms["prefix_hits"] == snap["prefixcache.hits"]
        assert ms["prefix_hit_tokens"] == snap["prefixcache.hit_tokens"]


def test_disabled_observability_is_null(model):
    cfg, params = model
    eng, _ = _cached_run(cfg, params, "overlap", 1, False)
    assert not eng.obs.tracer.enabled and not eng.obs.audit.enabled
    assert eng.obs.tracer.to_chrome_trace()["traceEvents"] == []
    # metrics stay live even with trace/audit off (mem_stats shim needs it)
    assert eng.obs.metrics.snapshot()["engine.requests_finished"] >= 1


# ----------------------------------------------------------------------
# persist.py tolerance comparator
# ----------------------------------------------------------------------


def test_persist_tolerance_classes():
    import importlib.util
    import pathlib

    spec = importlib.util.spec_from_file_location(
        "persist", pathlib.Path(__file__).parents[1] / "benchmarks"
        / "persist.py"
    )
    persist = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(persist)

    assert persist.tolerance("fig_x_tput", "us_per_call") == ("rel", 2.0)
    assert persist.tolerance("fig_x_ratio", "derived") == ("abs", 0.15)
    assert persist.tolerance("fig_x_ttft_p50_ms", "derived") == ("rel", 0.5)
    kind, _ = persist.tolerance("fig_x_verify_passes", "derived")
    assert kind == "relabs"

    committed = {
        "a_tput": {"name": "a_tput", "us_per_call": "", "derived": 100.0},
        "a_ratio": {"name": "a_ratio", "us_per_call": "", "derived": 1.0},
        "a_passes": {"name": "a_passes", "us_per_call": "", "derived": 4},
        "gone": {"name": "gone", "us_per_call": "", "derived": 1},
    }
    fresh = {
        "a_tput": {"name": "a_tput", "us_per_call": "", "derived": 120.0},
        "a_ratio": {"name": "a_ratio", "us_per_call": "", "derived": 1.5},
        "a_passes": {"name": "a_passes", "us_per_call": "", "derived": 5},
        "new": {"name": "new", "us_per_call": "", "derived": 1},
    }
    table = persist.compare_rows(committed, fresh, "t")
    verdict = {(m, c): ok for _, m, c, _, _, _, ok in table}
    assert verdict[("a_tput", "derived")] is True  # 20% < rel 0.5
    assert verdict[("a_ratio", "derived")] is False  # 0.5 > abs 0.15
    assert verdict[("a_passes", "derived")] is True  # +/-2 slack
    assert verdict[("gone", "-")] is False  # missing from fresh
    assert verdict[("new", "-")] is False  # missing from committed
