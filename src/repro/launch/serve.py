"""End-to-end serving driver.

Runs the LLM-42 engine on a synthetic or ShareGPT-like workload with a mix
of deterministic and non-deterministic requests on whatever device JAX
finds, and reports the device, wall time, compiles, rollback and
recomputation statistics.  ``--arch`` builds the published config at full
width (random weights from ``--seed``); ``--smoke`` builds the reduced
same-family config that runs on a CPU.  Lines marked "cost-model estimate
(not measured)" replay the run's event log through the analytical TPU cost
model (``serving.costmodel``); they are not timings.

  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve --smoke \
      --arch tinyllama-1.1b --requests 16 --det-ratio 0.25 --mode llm42
  PYTHONPATH=src python -m repro.launch.serve --arch phi3-mini-3.8b  # on a TPU
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro import configs as config_registry
from repro.core.determinism import FAST_PATH_POLICY, Mode
from repro.launch import compile_cache
from repro.models import init_params
from repro.models.multimodal import audio_frames, vision_embeds
from repro.serving import costmodel
from repro.serving.engine import Engine
from repro.serving.request import Request, SamplingParams
from repro.serving.scheduler import (
    AdaptivePolicy,
    OverlapPolicy,
    PauseDecodePolicy,
)
from repro.training.data import SHAREGPT, sample_workload


def build_requests(cfg, n, det_ratio, max_out, seed=0, workload="synthetic",
                   in_len=32):
    rng = np.random.default_rng(seed)
    if workload == "sharegpt":
        lens = sample_workload(SHAREGPT, n, seed, max_in=256, max_out=max_out)
    else:
        lens = [(in_len, max_out)] * n
    reqs = []
    for i, (il, ol) in enumerate(lens):
        prompt = rng.integers(0, cfg.vocab_size, il).tolist()
        det = rng.random() < det_ratio
        r = Request(
            rid=i, prompt=prompt,
            sampling=SamplingParams(
                max_new_tokens=min(ol, max_out), is_deterministic=det,
                seed=1000 + i,
            ),
        )
        if cfg.family == "encdec":
            r.enc_embeds = audio_frames(
                jax.random.PRNGKey(i), 1, cfg.encoder_seq_len, cfg.d_model
            )
        if cfg.num_prefix_embeds:
            r.prefix_embeds = vision_embeds(
                jax.random.PRNGKey(i), 1, cfg.d_model,
                num_tiles=0 if cfg.num_prefix_embeds < 576 else 4,
            )[:, : cfg.num_prefix_embeds]
        reqs.append(r)
    return reqs


def run_cluster(args, full_cfg, make_engine, reqs) -> None:
    """Multi-replica path: N engines behind the deterministic router,
    driven on per-replica costed dual-clock runtimes (repro.cluster)."""
    from repro.cluster import Cluster, run_online
    from repro.obs import validate_chrome_trace

    cluster = Cluster(make_engine, args.replicas)
    arrivals = [
        (i / args.qps) if args.qps > 0 else 0.0 for i in range(len(reqs))
    ]
    t0 = time.time()
    res = run_online(
        cluster, full_cfg, list(zip(reqs, arrivals)),
        invariant_mode=(args.mode == "batch_invariant"),
    )
    wall = time.time() - t0
    done = cluster.finished
    print(f"cluster: {args.replicas} replicas, tp={args.tp}, "
          f"finished {len(done)} requests, {res.out_tokens} tokens "
          f"in {wall:.1f}s wall")
    print(f"cost-model estimate (not measured): fleet time "
          f"{res.total_time * 1e3:.1f} ms -> {res.throughput:.0f} tok/s "
          f"aggregate (goodput @ TTFT<=1s: {res.goodput(1.0):.0f} tok/s)")
    rt = cluster.router
    print(f"router: {rt.assignments} assignments, "
          f"affinity hit rate {100 * rt.affinity_hit_rate:.0f}%, "
          f"{rt.diverted} diverted by load guard, "
          f"{rt.transfers} block transfers "
          f"({rt.transferred_tokens} KV tokens moved)")
    occ = ", ".join(
        f"r{r.idx}={res.metrics[f'cluster.replica.{r.idx}.occupancy']:.2f}"
        for r in cluster.replicas
    )
    print(f"final occupancy: {occ}")
    if args.trace_out:
        trace = cluster.chrome_trace()
        errors = validate_chrome_trace(trace)
        assert not errors, f"trace failed schema validation: {errors[:5]}"
        with open(args.trace_out, "w") as f:
            json.dump(trace, f)
        print(f"trace: {len(trace['traceEvents'])} events across "
              f"{args.replicas} pids -> {args.trace_out}")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-runnable);"
                         " without it the published config runs at full"
                         " width")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="prompt tokens per request (synthetic workload)")
    ap.add_argument("--det-ratio", type=float, default=0.25)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--mode", default="llm42",
                    choices=["llm42", "nondet", "batch_invariant"])
    ap.add_argument("--window", type=int, default=8)
    ap.add_argument("--group", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--workload", default="synthetic",
                    choices=["synthetic", "sharegpt"])
    ap.add_argument("--scheduler", default="default",
                    choices=["default", "overlap", "pause", "adaptive"],
                    help="verify/decode policy (default: overlap for llm42;"
                         " adaptive demotes high-flip requests to pause-style"
                         " verification and promotes them back)")
    ap.add_argument("--verify-latency-ms", type=float, default=None,
                    help="continuous verdict latency: run the engine on the"
                         " costed dual-stream clock (serving.streams), with"
                         " verdicts landing this many ms after the verify"
                         " stream completes the pass (default: the legacy"
                         " 1-iteration logical shim)")
    ap.add_argument("--spec-depth", type=int, default=1,
                    help="verify windows a deterministic request may have in"
                         " flight at once (multi-window speculation pipeline;"
                         " 1 = the paper's protocol).  Deeper pipelines hide"
                         " verdict latency; rollbacks cascade through later"
                         " windows, and on ssm/hybrid archs the double-"
                         " buffered state pool checkpoints recurrent state"
                         " per window")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="tokens per prefill chunk, co-scheduled with decode"
                         " under the overlap policy (0 = legacy exclusive"
                         " whole-prompt prefill at admission)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged-KV block size in tokens (serving.blockpool):"
                         " full-attention KV is allocated block-by-block as"
                         " sequences grow instead of one dense max_seq_len"
                         " ring per slot")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="KV block-pool size (HBM budget knob); default ="
                         " dense parity (max_batch x capacity/block_size)."
                         " Undersized pools trigger the preemption lane:"
                         " LRU victims are evicted and later restored by"
                         " deterministic recompute of their committed"
                         " stream")
    ap.add_argument("--prefix-cache", default="on", choices=["on", "off"],
                    help="commit-aware radix prefix cache: admissions map"
                         " their longest committed-prefix match to shared"
                         " read-only KV blocks and prefill only the tail")
    ap.add_argument("--tp", type=int, default=1,
                    help="logical tensor-parallel width for the FAST path"
                         " (reduction schedule modeling a TP=N mesh; must"
                         " divide the canonical pinned width).  The commit"
                         " path always replays under the canonical mesh"
                         " schedule, so committed streams are identical at"
                         " any --tp — that invariance is what the analysis"
                         " gate proves")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the deterministic cluster"
                         " router (repro.cluster): radix-prefix-affinity"
                         " routing with index tie-breaks, cross-replica KV"
                         " block transfer on diverted prefix hits, aggregate"
                         " goodput off the shared cost model")
    ap.add_argument("--qps", type=float, default=0.0,
                    help="replica-mode arrival rate (requests/s of simulated"
                         " time, evenly spaced; 0 = all arrive at t=0)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="export a Chrome/Perfetto trace-event JSON of the"
                         " run (per-request lifecycle spans + main/verify"
                         " stream pass slices; load in ui.perfetto.dev or"
                         " chrome://tracing)")
    ap.add_argument("--metrics-interval", type=int, default=0, metavar="N",
                    help="print a metrics-snapshot line every N engine"
                         " iterations (0 = only the final summary)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="dump the final metrics-registry snapshot as JSON")
    ap.add_argument("--audit-out", default=None, metavar="PATH",
                    help="write the per-committed-token determinism audit"
                         " log as JSONL (one provenance record per token:"
                         " committing schedule, verify window, n_match,"
                         " top-1/top-2 logit margin)")
    return ap


def make_engine(args, cfg, params, cost_cfg, **overrides) -> Engine:
    """The engine ``main`` serves with, from parsed ``args``; keyword
    overrides replace single Engine arguments (e.g. ``max_batch``)."""
    kw = dict(
        mode=Mode(args.mode), policy=FAST_PATH_POLICY,
        window=args.window, group=args.group, max_batch=args.max_batch,
        capacity=min(cfg.max_seq_len, 512),
        scheduler={
            "default": None,
            "overlap": OverlapPolicy(),
            "pause": PauseDecodePolicy(),
            "adaptive": AdaptivePolicy(),
        }[args.scheduler],
        spec_depth=args.spec_depth,
        verify_latency_ms=args.verify_latency_ms,
        cost_cfg=cost_cfg,  # deadlines priced at the full model's scale
        prefill_chunk=args.prefill_chunk,
        block_size=args.block_size,
        num_blocks=args.num_blocks,
        prefix_cache=(args.prefix_cache == "on"),
        trace=args.trace_out is not None,
        audit=args.audit_out is not None,
        tp=args.tp,
    )
    kw.update(overrides)
    return Engine(cfg, params, **kw)


def device_line() -> str:
    devs = jax.devices()
    return (f"device: platform={devs[0].platform} "
            f"kind={devs[0].device_kind} count={len(devs)}")


def main(argv=None):
    """Serve one workload; returns ``(engine, finished_requests)``."""
    args = build_parser().parse_args(argv)
    cache_dir = compile_cache.enable()
    stats = compile_cache.stats()
    print(device_line())
    print(f"compile cache: {cache_dir}")

    full_cfg = config_registry.get_config(args.arch)
    cfg = (config_registry.get_smoke_config(args.arch) if args.smoke
           else full_cfg)
    print(f"arch={cfg.name} mode={args.mode} n={args.requests} "
          f"det_ratio={args.det_ratio}")
    params = init_params(cfg, jax.random.key(args.seed))
    leaves = jax.tree_util.tree_leaves(params)
    print(f"params: {sum(x.size for x in leaves) / 1e9:.3f}B parameters, "
          f"{sum(x.nbytes for x in leaves) / 1e9:.3f} GB ({cfg.dtype})")

    def engine_for(idx: int = 0) -> Engine:
        return make_engine(args, cfg, params, full_cfg)

    reqs = build_requests(cfg, args.requests, args.det_ratio, args.max_new,
                          args.seed, args.workload, in_len=args.prompt_len)

    if args.replicas > 1:
        run_cluster(args, full_cfg, engine_for, reqs)
        return None, None

    engine = engine_for()
    for r in reqs:
        engine.submit(r)
    snap = stats.snapshot()
    t0 = time.perf_counter()
    if args.metrics_interval > 0:
        done = None
        for it in range(1, 100001):
            if not engine.step():
                done = engine.finished
                break
            if it % args.metrics_interval == 0:
                snap = engine.obs.metrics.snapshot()
                print(f"[iter {it}] committed={snap['tokens.committed']} "
                      f"running={snap['engine.running']} "
                      f"queued={snap['engine.queued']} "
                      f"rollbacks={snap['verify.rollbacks']} "
                      f"verify_inflight={snap['verify.inflight']}")
        assert done is not None, "engine did not drain"
    else:
        done = engine.run()
    jax.block_until_ready(engine.pool.data)
    wall = time.perf_counter() - t0

    out_tokens = sum(r.num_output for r in done)
    rollbacks = sum(r.num_rollbacks for r in done)
    recomputed = sum(r.num_recomputed_tokens for r in done)
    cascaded = sum(r.num_cascaded_windows for r in done)
    sim = costmodel.simulate(
        full_cfg, engine.events,
        invariant_mode=(args.mode == "batch_invariant"),
    )
    print(f"finished {len(done)} requests, {out_tokens} tokens "
          f"in {wall:.3f} s wall on {jax.devices()[0].platform}, "
          f"compile included: {stats.since(snap)}")
    print(f"rollbacks={rollbacks} recomputed_tokens={recomputed} "
          f"({100.0 * recomputed / max(out_tokens, 1):.2f}%)")
    print(f"speculation pipeline: depth limit {args.spec_depth}, "
          f"peak in-flight {engine.statepool.peak_depth}, "
          f"cascade-invalidated windows {cascaded}")
    ms = engine.mem_stats()
    if ms["paged"]:
        print(f"paged KV: {ms['num_blocks']} blocks x {ms['block_size']} tok, "
              f"peak in use {ms['peak_blocks_in_use']}, "
              f"peak concurrency {ms['peak_running']}")
        if engine.prefix_cache is not None:
            hits, misses = ms["prefix_hits"], ms["prefix_misses"]
            rate = hits / max(hits + misses, 1)
            print(f"prefix cache: hit rate {100 * rate:.0f}% "
                  f"({ms['prefix_hit_tokens']} tokens served from cache), "
                  f"{ms['prefix_size_blocks']} blocks resident, "
                  f"{ms['prefix_evictions']} evicted")
        print(f"preemption lane: {ms['num_preemptions']} preemptions, "
              f"{ms['num_restores']} restores "
              f"({ms['restored_tokens']} tokens recomputed bitwise)")
    prefill_ms = (sim.get("prefill_s", 0) + sim.get("prefill_chunk_s", 0)) * 1e3
    # a costed engine clock is authoritative (it saw verdict-gated waits
    # that emit no events); the log replay is the fallback for the
    # logical shim
    total_s = (
        engine.runtime.makespan
        if args.verify_latency_ms is not None else sim["total_s"]
    )
    print(f"cost-model estimate (not measured): {total_s * 1e3:.1f} ms "
          f"-> {out_tokens / total_s:.0f} tok/s "
          f"(decode {sim.get('decode_s', 0) * 1e3:.1f} ms, "
          f"verify {sim.get('verify_s', 0) * 1e3:.1f} ms, "
          f"prefill {prefill_ms:.1f} ms; "
          f"verify-stream occupancy "
          f"{100.0 * sim.get('verify_occupancy', 0):.0f}%)")
    if args.verify_latency_ms is not None:
        rt = engine.runtime
        print(f"cost-model stream clocks (not measured): "
              f"main {rt.main.now * 1e3:.1f} ms, "
              f"verify backlog {rt.verify_backlog * 1e3:.2f} ms, "
              f"makespan {rt.makespan * 1e3:.1f} ms")

    if args.trace_out:
        from repro.obs import validate_chrome_trace

        trace = engine.obs.tracer.to_chrome_trace()
        errors = validate_chrome_trace(trace)
        assert not errors, f"trace failed schema validation: {errors[:5]}"
        with open(args.trace_out, "w") as f:
            json.dump(trace, f)
        print(f"trace: {len(trace['traceEvents'])} events -> {args.trace_out}"
              f" (load in ui.perfetto.dev)")
    if args.metrics_out:
        engine.obs.metrics.dump(args.metrics_out)
        print(f"metrics: {len(engine.obs.metrics.snapshot())} series "
              f"-> {args.metrics_out}")
    if args.audit_out:
        audit = engine.obs.audit
        errors = audit.coverage_errors(done)
        assert not errors, f"audit coverage check failed: {errors[:5]}"
        audit.to_jsonl(args.audit_out)
        print(f"audit: {len(audit.records)} provenance records "
              f"({len(done)} requests, every committed token covered) "
              f"-> {args.audit_out}")
    mem = jax.devices()[0].memory_stats()
    if mem:
        print(f"device memory: peak {mem.get('peak_bytes_in_use', 0) / 1e9:.3f} GB"
              f" of {mem.get('bytes_limit', 0) / 1e9:.3f} GB")
    return engine, done


if __name__ == "__main__":
    main()
