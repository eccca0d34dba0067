"""One run of one cell: set-up, the measured window, the check, the result.

Set-up (``setup_s``, from the start of the process to the window): the
weights are drawn on the device from the seed, the engine is built through
``serve.make_engine`` from the configuration file, and a warm-up on the
public path sends, on a seed the window never uses, every prompt length
the mix can send, every decode batch of 1 .. ``max_batch`` rows and every
count of verify groups beside them, and builds the wipe of every count of
blocks a finishing request frees, so that the window compiles nothing.
The loop then runs ``ramp_s`` seconds before the window opens.

The window: the closed loop of ``bench/loop.py`` for ``--seconds`` of host
clock, the profiler off (``--trace 0``: the end-to-end metrics) or tracing
a few seconds in its middle (``--trace 1``: the per-layer metrics).

After it: the checks of ``bench/check.py``, the device's peak memory read
before the reference runs, and one JSON line on stdout.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import shutil
import statistics
import sys
import tempfile
import time
from typing import Dict, List, Optional

from bench import check, loop, spec, tracing, traffic
from bench import weights as weights_mod

#: a traced run profiles this share of the window, starting a third in
TRACE_FRACTION, TRACE_MAX_S = 0.25, 8.0
#: the correctness sample: the longest finished request, then more until
#: this many served tokens (or requests), and this many deterministic
#: requests served again alone
SAMPLE_TOKENS, SAMPLE_REQUESTS, DET_RESERVE = 384, 6, 2
WARMUP_SEED_STREAM = 7  # warm-up traffic is drawn from (seed, 7): never measured


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Run:
    """What a per-layer reader may read."""

    model: Dict  # the configuration file
    steps: List[loop.StepSample]  # every step of the window
    traced: List[loop.StepSample]  # the steps inside the traced span
    counters: Dict[str, float]  # engine counters, change over the window
    compiles: int  # backend compiles inside the window
    trace: Optional[tracing.Summary]
    peaks: Dict
    flops: object  # bench/flops/<kind>.py
    delivered: int


def model_config(conf: Dict):
    """The program's config of ``conf["arch"]`` with every field the file
    states: the file, not the program's default, is what runs."""
    from repro import configs

    return dataclasses.replace(configs.get_config(conf["arch"]),
                               **conf["program"])


def engine_factory(conf: Dict, cfg, params):
    """A function that builds the configuration's engine, every argument
    taken from the file."""
    from repro.core.determinism import ReductionPolicy
    from repro.launch import serve

    e = conf["engine"]
    argv = [
        "--mode", e["mode"], "--window", str(e["window"]),
        "--group", str(e["group"]), "--max-batch", str(e["max_batch"]),
        "--scheduler", e["scheduler"], "--spec-depth", str(e["spec_depth"]),
        "--prefill-chunk", str(e["prefill_chunk"]),
        "--block-size", str(e["block_size"]),
        "--prefix-cache", e["prefix_cache"], "--tp", str(e["tp"]),
    ]
    if e["num_blocks"] is not None:
        argv += ["--num-blocks", str(e["num_blocks"])]
    args = serve.build_parser().parse_args(argv)
    pol = e["policy"]
    policy = ReductionPolicy(
        thresholds=tuple(tuple(t) for t in pol["thresholds"]),
        default_splits=pol["default_splits"],
        combine_dtype=pol["combine_dtype"],
    )

    def make():
        return serve.make_engine(args, cfg, params, cfg, policy=policy,
                                 capacity=e["capacity"])

    return make


def warmup_batches(conf: Dict, mix: Dict):
    """Batches of ``(RequestSpec list, start steps)`` that reach every shape
    the window can: each prompt length the mix sends; a decode pass at each
    batch of 1 .. max_batch rows, with a verify group beside it where the
    mix is deterministic; and the verify passes of two groups beside
    1 .. max_batch - 1 decoding rows, which need deterministic rows whose
    windows fall due together while others decode or hold.  The staggered
    waves for two groups beside fewer than ``group`` decoding rows came
    from a search over waves on the smoke model; the scheduler, not the
    weights, decides them.  ``window_compiles`` shows any shape missed."""
    e = conf["engine"]
    W, G, B = e["window"], e["group"], e["max_batch"]
    lens = sorted(set(traffic.quantile_lengths(mix["prompt"],
                                               mix["stratum"]).tolist()))
    p0 = lens[0]
    det = mix["deterministic_share"] > 0
    # outputs: one verify window; past it; two windows
    one, past, two, long_ = W + 1, W + 2, 2 * W + 1, 2 * W + 2
    batches = [[(0, p, 2, False) for p in lens[i:i + B]]
               for i in range(0, len(lens), B)]
    for b in range(1, B + 1):
        # a decode pass at b rows; with deterministic rows, then a verify
        # group beside b decoding rows
        d = min(b, G) if det else 0
        batches.append([(0, p0, one, True)] * d
                       + [(0, p0, past if det else 3, False)] * (b - d))
    if det and B > G:
        for n in range(0, B - G):  # two groups beside G + n decoding rows
            batches.append([(0, p0, one, True)] * (G + 1)
                           + [(0, p0, past, False)] * n)
        for k in (1,):  # two groups beside fewer than G decoding rows
            batches += [
                [(0, p0, one, True)] * 3 + [(0, p0, long_, False)] * 3
                + [(k + 3, p0, two, True)] * 2,
                [(0, p0, one, True)] * G + [(k, p0, two, True)] * G,
                [(0, p0, one, True)] + [(k, p0, two, True)] * G,
            ]
    tok = traffic.seeded_rng(0, WARMUP_SEED_STREAM)
    out, i = [], 0
    for batch in batches:
        specs, starts = [], []
        for start, p, n, d in batch:
            specs.append(traffic.RequestSpec(
                i, tok.integers(0, conf["vocab_size"], p).tolist(), n, d))
            starts.append(start)
            i += 1
        out.append((specs, starts))
    return out


def warm_block_frees(engine, mix: Dict) -> None:
    """The block pool wipes the blocks a request frees with eager array
    ops whose shapes follow the count of blocks freed, so each new count
    compiles a handful of small programs.  A finishing request frees its
    output's blocks (its prompt's stay in the prefix cache): build the
    wipe for every count up to the longest output, on the pool as it is,
    and drop the result."""
    import jax

    from repro.serving import blockpool

    pool = engine.pool
    if not pool.paged:
        return
    most = -(-(mix["output"]["max"] + engine.window) // pool.block_size) + 1
    for n in range(1, most + 1):
        jax.block_until_ready(
            blockpool.wipe_blocks(pool.data, pool.layout, list(range(n))))


def quartiles_ms(xs: List[float]) -> str:
    if len(xs) < 2:
        return "n/a"
    q = statistics.quantiles(xs, n=4)
    return f"{q[0] * 1e3:.1f}/{q[1] * 1e3:.1f}/{q[2] * 1e3:.1f}"


def percentile(xs: List[float], p: float) -> float:
    """Nearest-rank percentile (the value at rank ceil(p/100 * n))."""
    s = sorted(xs)
    return s[max(0, math.ceil(p / 100.0 * len(s)) - 1)]


def end_to_end(lp: loop.ClosedLoop, t0: float, seconds: float,
               setup_s: float, log) -> Dict[str, Dict]:
    """Tokens delivered in the window over its length; the median time to
    first token of the requests whose first token came in the window; the
    95th percentile of every gap between two consecutive tokens of a
    request, both delivered in the window."""
    t1 = t0 + seconds
    delivered = sum(s.delivered for s in lp.steps)
    ttft = [s.times[0] - s.submit_t for s in lp.served
            if s.times and t0 <= s.times[0] <= t1]
    gaps = []
    for s in lp.served:
        ts = [t for t in s.times if t0 <= t <= t1]
        gaps.extend(b - a for a, b in zip(ts, ts[1:]))
    sent = sum(s.submit_t >= t0 for s in lp.served)
    done = sum(s.done and s.times[-1] >= t0 for s in lp.served)
    log(f"window: {seconds} s, {len(lp.steps)} steps, {sent} requests "
        f"sent, {done} finished, {delivered} tokens delivered")
    log(f"ttft: {len(ttft)} first tokens in the window, quartiles (ms) "
        f"{quartiles_ms(ttft)}")
    log(f"itl: {len(gaps)} gaps, quartiles (ms) {quartiles_ms(gaps)}")
    out = {
        "out_tok_s": {"value": delivered / seconds, "unit": "tokens/s"},
        "ttft_p50_ms": {"value": (statistics.median(ttft) * 1e3
                                  if ttft else float("nan")), "unit": "ms"},
        "itl_p95_ms": {"value": (percentile(gaps, 95) * 1e3
                                 if gaps else float("nan")), "unit": "ms"},
        "setup_s": {"value": setup_s, "unit": "s"},
    }
    return out


def _counters(engine) -> Dict[str, float]:
    snap = engine.obs.metrics.snapshot()
    return {k: float(v) for k, v in snap.items()
            if isinstance(v, (int, float))}


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, require_chip: bool = True,
             control: bool = False, warm: bool = True, fault: str = "",
             log=None) -> Dict:
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    from repro.launch import compile_cache

    cache_dir = compile_cache.enable()
    stats = compile_cache.stats()
    import jax

    # keep every program, however quick to build: the engine's small eager
    # ops take a new shape per count of blocks freed, and a later run must
    # find them in the cache instead of compiling them inside its window
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    devs = jax.devices()
    dev = devs[0]
    if require_chip and (dev.platform != "tpu" or len(devs) < cell.chips):
        raise NoChip(f"the cell needs {cell.chips} TPU chip(s); JAX found "
                     f"{len(devs)} {dev.platform} device(s)")
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
        f"compile cache {cache_dir}")
    conf, mix = cell.config, cell.traffic
    if fault == "verifier_bypassed":
        # every request on the fast path, deterministic or not: what a
        # server that dropped the verifier would deliver
        conf = dict(conf, engine=dict(conf["engine"], mode="nondet"))
    elif fault:
        raise ValueError(f"unknown fault {fault!r}")
    cfg = model_config(conf)
    params = weights_mod.make(cfg, seed, conf["weights"])
    jax.block_until_ready(params)
    t_w = time.perf_counter()
    make_engine = engine_factory(conf, cfg, params)
    engine = make_engine()
    snap = stats.snapshot()
    for i, (specs, starts) in enumerate(warmup_batches(conf, mix)
                                        if warm else []):
        loop.drain(engine, specs, -(i + 1) * 100000, starts)
    if warm:
        warm_block_frees(engine, mix)
    mem = dev.memory_stats() or {}
    log(f"set-up: weights in {t_w - t_start:.1f} s since start; warm-up "
        f"{time.perf_counter() - t_w:.1f} s, {stats.since(snap)}; device "
        f"memory peak {mem.get('peak_bytes_in_use', 0) / 1e9:.3f} GB of "
        f"{mem.get('bytes_limit', 0) / 1e9:.3f} GB")

    # ---- the window
    gen = traffic.make(mix, seed, conf["vocab_size"])
    lp = loop.ClosedLoop(engine, gen, int(mix["clients"]))
    base: Dict = {}
    compiled: List[str] = []

    def on_open() -> None:
        base["counters"], base["compiles"] = _counters(engine), stats.snapshot()
        jax.monitoring.register_event_duration_secs_listener(
            lambda ev, d, **kw: compiled.append(kw.get("fun_name", "?"))
            if ev == "/jax/core/compile/backend_compile_duration" else None)
    span = {"dir": None, "steps": [None, None]}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # Python call tracing would double a step
    t_trace = min(TRACE_MAX_S, TRACE_FRACTION * seconds)

    def on_step(i: int, now: float) -> None:
        span.setdefault("open", now)
        first = span["steps"][0]
        if first is None and i >= 1 and now >= span["open"] + seconds / 3:
            span["dir"] = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(span["dir"], profiler_options=opts)
            span["steps"][0], span["t"] = i, now
        elif (first is not None and span["steps"][1] is None
              and now >= span["t"] + t_trace):
            jax.profiler.stop_trace()
            span["steps"][1] = i

    ramp = float(mix["ramp_s"])
    t0 = lp.run(seconds, ramp, on_open, on_step if trace else None)
    setup_s = t0 - t_start  # the ramp of the loop is set-up too
    if trace and span["steps"][0] is not None and span["steps"][1] is None:
        jax.profiler.stop_trace()
        span["steps"][1] = len(lp.steps)
    compiles = stats.count - base["compiles"][0]
    counters = {k: v - base["counters"].get(k, 0.0)
                for k, v in _counters(engine).items()}
    log(f"window compiles: {stats.since(base['compiles'])}: {compiled}")
    log("window counters: " + ", ".join(
        f"{k}={counters[k]:g}" for k in (
            "tokens.committed", "tokens.recomputed", "verify.rollbacks",
            "verify.windows_submitted", "mem.preemptions") if k in counters))
    e2e = end_to_end(lp, t0, seconds, setup_s, log)

    # ---- the checks; the window's engine goes first
    lim = conf["check"]
    numbers: Dict[str, Dict] = {}
    del engine
    lp.engine = None
    gc.collect()
    if mix["deterministic_share"] > 0:
        det = check.det_sample(lp.served, seed, DET_RESERVE)
        numbers["det_streams_differ"] = (
            {"value": check.reserve(make_engine, det, rid_base=10 ** 9),
             "limit": 0} if det else check.empty(0))
    gc.collect()
    mem = dev.memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    items = check.sample(lp.served, seed, SAMPLE_TOKENS, SAMPLE_REQUESTS)
    if items:
        ref = spec.reference(conf["reference"])
        t_ref = time.perf_counter()
        g = check.logit_gaps(ref, params, conf, items, mix["prompt"]["max"],
                             mix["output"]["max"], control=control)
        log(f"reference: {len(items)} requests, {g['tokens']} served tokens "
            f"in {time.perf_counter() - t_ref:.1f} s; program gap "
            f"{g['gap']!r}" + (f", control gap {g['control_gap']!r}"
                               if control else ""))
        value = g["control_gap"] if control else g["gap"]
        numbers["logit_gap"] = {"value": value, "limit": lim["logit_gap"]}
    else:
        numbers["logit_gap"] = check.empty(lim["logit_gap"])
    correct = check.verdict(numbers)

    # ---- per-layer metrics (traced runs)
    metrics = e2e
    breakdown = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if trace:
        summary = None
        if span["dir"]:
            path = tracing.find_xplane(span["dir"])
            if path:
                summary = tracing.reduce(tracing.load_planes(path),
                                         (loop.STEP, loop.SUBMIT,
                                          loop.COLLECT))
            shutil.rmtree(span["dir"], ignore_errors=True)
        a, b = span["steps"]
        run = Run(conf, lp.steps,
                  lp.steps[a:b] if a is not None else [], counters, compiles,
                  summary, spec.peaks(dev.device_kind)
                  if dev.platform == "tpu" else {},
                  spec.flops(conf["flops"]),
                  sum(s.delivered for s in lp.steps))
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            breakdown = {
                "device_ops": [[n, s] for n, s in summary.device_ops],
                "idle_gaps": [[n, s] for n, s in summary.idle_gaps],
            }
            log("trace: top device ops " + "; ".join(
                f"{n} {t:.4f} s" for n, t in sorted(
                    summary.op_seconds.items(), key=lambda kv: -kv[1])[:25]))
            log(f"trace: window {summary.window_s:.3f} s, busy "
                f"{summary.busy_s:.3f} s; idle by host: " + ", ".join(
                    f"{k} {v:.3f} s" for k, v in summary.idle_by_host.items()))
    else:
        metrics = {m["name"]: e2e[m["name"]] for m in cell.end_to_end}

    for line in check.describe(numbers):
        log(line)
    result = {
        "correct": correct,
        "attempted": sum(s.submit_t <= t0 + seconds
                         and (not s.done or s.times[-1] >= t0)
                         for s in lp.served),
        "failed": 0,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = numbers
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="read the float8 reference's gap in the program's "
                         "place (the control of the check; never in a "
                         "benchmark run)")
    ap.add_argument("--fault", default="", choices=("", "verifier_bypassed"),
                    help="plant a fault in the served path, to read what "
                         "the check gives it (never in a benchmark run)")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_start, control=bool(args.control),
                          fault=args.fault)
    except NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0
