"""The LLM-42 serving engine: continuous batching + selective determinism.

Three modes (paper §5 baselines):

  * ``Mode.NONDET``           — SGLang-Non-Deterministic: fast path only;
                                schedules vary with dynamic batch size.
  * ``Mode.BATCH_INVARIANT``  — SGLang-Deterministic: one universal schedule
                                for every op, all traffic pays for it.
  * ``Mode.LLM42``            — the paper: fast path for everyone +
                                decode-verify-rollback for requests with
                                ``is_deterministic=True``.

Per-iteration verify/decode arbitration is delegated to the scheduler
subsystem (``serving.scheduler``): ``PauseDecodePolicy`` reproduces the
paper prototype's behaviour (verification pauses decoding, §5.2 limitation
(1)); ``OverlapPolicy`` — the default for ``Mode.LLM42`` — co-schedules the
verify group alongside the same iteration's decode batch, with a
per-request in-flight verify FIFO (``core.pipeline``) so a request keeps
speculating past submitted windows and pipelines up to ``spec_depth``
windows deep; verdicts splice strictly in submission order, rollbacks
cascade through later windows, and the double-buffered state pool
(``serving.statepool``) checkpoints recurrent state at each window
submission so ssm/hybrid archs pipeline just as deep (they used to be
hard-capped at one window).  Prefill stays per-request (deterministic by
construction, never co-batched) but is chunk-resumable: with
``prefill_chunk > 0`` a prompt advances ``C`` tokens per iteration as the
scheduler's third lane instead of one exclusive pass at admission, so a
long prompt no longer stalls the decode batch (§5.2 limitation (2));
decode batches are formed from all decodable requests each iteration
(continuous batching).

Memory is paged (``serving.blockpool``): full-attention KV lives in a
global pool of fixed-size ref-counted blocks addressed through per-request
block tables, a radix prefix cache (``serving.prefixcache``) maps the
longest *committed*-prefix match of an arriving prompt onto shared
read-only blocks (only the tail is prefilled), and on pool exhaustion the
memory policy (``scheduler.BlockMemoryPolicy``) preempts an LRU victim —
its blocks are evicted while its committed stream, slot and statepool
replay anchor survive, and the later restore replays only committed
tokens through the chunked-prefill lane, which is bitwise-identical by
construction.  Admission is free-block accounting, not dense per-slot
reservation.

Every device step goes through a jitted function cached per *shape class*
(batch size, prompt bucket, window) — recompilation per shape is exactly
the shape→schedule coupling (O2) the paper builds on.

Time is kept by the dual-clock execution-stream runtime
(``serving.streams``): decode/prefill passes charge the main stream,
deferred verification launches on the verify stream, and verdict deadlines
are continuous (``verify_latency_ms``; the default clock is the logical
1-tick-per-iteration mode).  An event log still records (kind, shape
metadata, wall time) per step; the benchmark harness replays it through
the TPU cost model (``serving.costmodel``) to derive paper-comparable
throughput numbers.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, List, Optional, Set

import jax
import jax.numpy as jnp

from repro.core import dvr, pipeline
from repro.core.determinism import (
    FAST_PATH_POLICY,
    INVARIANT_SCHEDULE,
    Mode,
    ReductionPolicy,
    Schedule,
    VERIFY_SCHEDULE,
)
from repro.core.verifier import make_verify_body, make_verify_fn
from repro.models.base import ModelConfig
from repro.models.layers import PagedView
from repro.models.transformer import build_cross_cache, forward
from repro.obs import Observability, TokenProvenance, now_ns
from repro.serving import costmodel, kv_cache, prefixcache, statepool, streams
from repro.serving import blockpool
from repro.serving import scheduler as sched
from repro.serving.request import Request, State
from repro.serving.sampler import sample_batch, sample_token, top2_margin


def _spanned(name: str) -> Callable:
    """Run the decorated method inside the host span ``name``
    (``Observability.span``)."""

    def wrap(method: Callable) -> Callable:
        @functools.wraps(method)
        def run(self, *args, **kwargs):
            with self.obs.span(name):
                return method(self, *args, **kwargs)

        return run

    return wrap


def _bucket(n: int) -> int:
    """Next power-of-two bucket (>= 8) for prompt padding."""
    b = 8
    while b < n:
        b *= 2
    return b


class Engine:
    def __init__(
        self,
        cfg: ModelConfig,
        params: Dict,
        *,
        mode: Mode = Mode.LLM42,
        policy: ReductionPolicy = FAST_PATH_POLICY,
        window: int = 8,  # verification window W (verifies W-1 candidates)
        group: int = 4,  # requests verified together (grouped verification)
        max_batch: int = 8,
        capacity: Optional[int] = None,
        scheduler: Optional[sched.SchedulePolicy] = None,
        spec_depth: int = 1,  # verify windows in flight per request
        verify_latency_ms: Optional[float] = None,  # continuous verdict latency
        cost_cfg: Optional[ModelConfig] = None,  # config the stream clocks cost at
        hw: costmodel.Hardware = costmodel.V5E,
        prefill_chunk: int = 0,  # tokens per prefill chunk; 0 = exclusive
        block_size: int = blockpool.DEFAULT_BLOCK_SIZE,  # KV tokens per block
        num_blocks: Optional[int] = None,  # pool size; None = dense parity
        prefix_cache: bool = True,  # share committed-prefix KV blocks
        mem_policy: Optional[sched.BlockMemoryPolicy] = None,
        paged_attention: bool = True,  # in-place paged forward + fused step
        trace: bool = False,  # host-span and request tracing (repro.obs.trace)
        audit: bool = False,  # per-token determinism audit (repro.obs.audit)
        tp: int = 1,  # fast-path tensor-parallel width (logical model axis)
    ):
        self.cfg = cfg
        self.params = params
        self.mode = mode
        self.policy = policy
        # Logical TP width of the FAST PATH: decode/prefill schedules carry
        # tp_shards=tp un-pinned (the mesh-order combine a width-tp ring
        # all-reduce would produce).  The commit path never reads this —
        # make_verify_fn closes over CANONICAL_MESH_SCHEDULE, whose pinned
        # balanced tree every power-of-two width dividing
        # CANONICAL_TP_SHARDS realizes bitwise — which is exactly the
        # TP-invariance theorem the analysis prover checks.
        from repro.core.determinism import CANONICAL_TP_SHARDS
        assert tp >= 1 and CANONICAL_TP_SHARDS % tp == 0, (
            f"tp={tp} must divide the canonical shard count "
            f"{CANONICAL_TP_SHARDS} for the commit tree to be realizable"
        )
        self.tp = int(tp)
        self.window = window
        self.group = group
        self.max_batch = max_batch
        self.capacity = capacity or cfg.max_seq_len
        self.pool = kv_cache.CachePool(
            cfg, max_batch, self.capacity,
            block_size=block_size, num_blocks=num_blocks,
        )
        self.axes = self.pool.axes
        # commit-aware prefix sharing needs (a) paged full-attention KV to
        # share, (b) a token-addressable position 0 (prefix embeds and
        # encdec cross caches are per-request side inputs the radix key
        # cannot see), and (c) no recurrent state (an O(1) state at the
        # match point cannot be reconstructed from shared KV alone)
        shareable = (
            self.pool.paged
            and not statepool.has_recurrent_state(cfg)
            and not cfg.num_prefix_embeds
            and cfg.family != "encdec"
        )
        self.prefix_cache: Optional[prefixcache.PrefixCache] = (
            prefixcache.PrefixCache(self.pool.block_size)
            if (prefix_cache and shareable) else None
        )
        self.mem_policy = mem_policy or sched.BlockMemoryPolicy()
        # recurrent/hybrid archs advance SSM/RWKV state irreversibly on the
        # fast path; the double-buffered state pool (serving.statepool)
        # carries the verify replay anchor + per-window rollback checkpoints
        # so speculation can run `spec_depth` windows deep anyway.  For
        # attention archs the pool is host-side depth/extent telemetry only.
        self.has_recurrent_state = statepool.has_recurrent_state(cfg)
        assert spec_depth >= 1, "at least one verify window must be allowed"
        self.spec_depth = int(spec_depth)
        self.statepool = statepool.StatePool(cfg, max_batch, self.spec_depth)

        self.scheduler = scheduler if scheduler is not None else sched.default_policy(mode)
        assert verify_latency_ms is None or verify_latency_ms >= 0.0
        self.verify_latency_ms = verify_latency_ms
        self.hw = hw
        # dual-clock execution-stream runtime (serving.streams).  Default is
        # the logical clock (1 tick per iteration, verdicts 1 tick after
        # launch).  Passing verify_latency_ms — or calling
        # bind_cost_model(), which run_online() does — switches to the
        # costed clock: continuous main/verify stream times from the cost
        # model, verify passes queueing on their own stream, verdicts
        # landing latency_ms after the pass completes.
        self.runtime = streams.DualClockRuntime(latency=1.0)
        if verify_latency_ms is not None:
            self.bind_cost_model(cost_cfg or cfg, hw)
        assert prefill_chunk >= 0, "prefill_chunk must be >= 0 (0 = exclusive)"
        self.prefill_chunk = int(prefill_chunk)
        # chunked prefill covers every family: attention archs share the
        # embeds-based chunk pass; recurrent/hybrid archs run a
        # state-collecting variant that checkpoints the state at each
        # chunk's last REAL position, so final-chunk padding never leaks
        # into the recurrent state and the chunk schedule is
        # size-invariant (the per-chunk prefill checkpoint from ROADMAP)
        self.chunked_prefill = self.prefill_chunk > 0

        self.queue: List[Request] = []
        self.running: List[Request] = []
        self.preempted: List[Request] = []  # blocks evicted, restore pending
        self.finished: List[Request] = []
        self.events: List[Dict[str, Any]] = []
        self._fns: Dict[Any, Callable] = {}
        # paged in-place forward: decode/verify (and the chunked prefill)
        # read and repair full-attention KV *through* the block tables
        # instead of round-tripping a per-row gathered view, which is what
        # lets one fused launch cover the whole mixed batch.  Requires a
        # paged pool (full-attention leaves); archs without one (rwkv,
        # sliding-only) keep the legacy lanes.  Committed streams are
        # bitwise identical either way.
        self.paged_attention = bool(paged_attention)
        self._paged_fwd = self.pool.paged and self.paged_attention
        self._verify_fn = make_verify_fn(
            cfg, group, window, self.pool.layout, paged=self._paged_fwd
        )
        self._now = 0  # logical iteration counter
        # memory-subsystem telemetry
        self.num_preemptions = 0
        self.num_restores = 0
        self.restored_tokens = 0
        self.peak_running = 0
        # unified observability (repro.obs): metrics registry (always on),
        # tracer and audit log (Null twins unless asked for).  Host-side
        # bookkeeping over values the engine computes anyway — committed
        # streams are bitwise identical with recording on or off.
        self.obs = Observability(trace=trace, audit=audit)
        self._register_metrics()

    # ------------------------------------------------------------------
    # stream clocks
    # ------------------------------------------------------------------

    def bind_cost_model(
        self,
        cost_cfg: ModelConfig,
        hw: Optional[costmodel.Hardware] = None,
        *,
        invariant: bool = False,
    ) -> None:
        """Switch the runtime to a costed clock: stream times come from the
        TPU cost model evaluated at ``cost_cfg``'s scale (benchmarks cost
        the full model while scheduling the reduced one).  Must happen
        before the first step — rebinding mid-run would tear the clock.

        Verdict latency under a costed clock is ``verify_latency_ms``
        (default 0: a verdict is visible as soon as the verify-stream pass
        completes).
        """
        assert getattr(self, "_now", 0) == 0, "bind the clock before stepping"
        hw = hw or self.hw
        self.hw = hw

        def cost_fn(ev: Dict[str, Any]) -> float:
            if invariant:
                ev = dict(ev, invariant=True)
            if self.tp > 1 and "tp" not in ev:
                ev = dict(ev, tp=self.tp)
            return costmodel.step_time(cost_cfg, ev, hw)

        self.runtime = streams.DualClockRuntime(
            cost_fn,
            latency=(self.verify_latency_ms or 0.0) / 1e3,
            contention=hw.stream_contention,
        )

    # ------------------------------------------------------------------
    # observability (repro.obs)
    # ------------------------------------------------------------------

    def _register_metrics(self) -> None:
        """Register every engine series with the metrics registry.  Pull
        gauges close over ``self`` attribute lookups — never over the
        objects themselves (``bind_cost_model`` replaces ``self.runtime``
        wholesale)."""
        m = self.obs.metrics
        self._c_iters = m.counter(
            "engine.iterations", unit="iterations",
            help="scheduler iterations stepped")
        self._c_submitted = m.counter(
            "engine.requests_submitted", unit="requests",
            help="requests submitted to the engine")
        self._c_finished = m.counter(
            "engine.requests_finished", unit="requests",
            help="requests retired")
        self._c_fused = m.counter(
            "engine.fused_steps", unit="launches",
            help="iterations whose whole device side ran as one fused "
                 "mixed-batch launch")
        m.gauge_fn("engine.running", lambda: len(self.running),
                   unit="requests", help="requests in the running set")
        m.gauge_fn("engine.queued", lambda: len(self.queue),
                   unit="requests", help="requests awaiting admission")
        m.gauge_fn("engine.preempted", lambda: len(self.preempted),
                   unit="requests", help="requests evicted, restore pending")
        m.gauge_fn("engine.peak_running", lambda: self.peak_running,
                   unit="requests", help="peak concurrent running requests")
        m.gauge_fn("engine.tp", lambda: self.tp,
                   unit="shards", help="fast-path tensor-parallel width")
        self._c_committed = m.counter(
            "tokens.committed", unit="tokens",
            help="tokens committed across all requests (prefill T0 + "
                 "direct decode commits + verify splices)")
        self._c_recomputed = m.counter(
            "tokens.recomputed", unit="tokens",
            help="speculated tokens rejected by verification (rollback "
                 "recompute cost)")
        self._c_windows = m.counter(
            "verify.windows_submitted", unit="windows",
            help="verify windows moved into in-flight FIFOs (deferred path)")
        self._c_passes = m.counter(
            "verify.passes", unit="verdicts",
            help="verify verdicts applied (sync passes + pipelined splices)")
        self._c_rollbacks = m.counter(
            "verify.rollbacks", unit="rollbacks",
            help="verdicts that rejected at least one speculated token")
        self._c_cascaded = m.counter(
            "verify.cascaded_windows", unit="windows",
            help="in-flight windows discarded by cascade invalidation")
        self._h_rollback_depth = m.histogram(
            "verify.rollback_depth", unit="tokens",
            help="tokens rejected per rolling-back verdict (in-window + "
                 "cascaded + fresh tail)")
        self._h_acceptance = m.histogram(
            "verify.acceptance_ema", unit="fraction",
            help="per-request acceptance EMA at retirement (det requests)")
        m.gauge_fn("verify.inflight",
                   lambda: sum(len(r.pipeline) for r in self.running),
                   unit="windows", help="verify windows currently in flight")
        # memory subsystem: block pool, preemption lane, prefix cache
        m.gauge_fn("mem.preemptions", lambda: self.num_preemptions,
                   unit="preemptions", help="requests evicted under pressure")
        m.gauge_fn("mem.restores", lambda: self.num_restores,
                   unit="restores", help="preempted requests re-admitted")
        m.gauge_fn("mem.restored_tokens", lambda: self.restored_tokens,
                   unit="tokens", help="positions replayed by restores")
        m.gauge_fn("blockpool.block_size", lambda: self.pool.block_size,
                   unit="tokens", help="KV positions per block")
        m.gauge_fn("blockpool.num_blocks",
                   lambda: self.pool.alloc_blocks.num_blocks,
                   unit="blocks", help="total pool blocks")
        m.gauge_fn("blockpool.blocks_in_use",
                   lambda: self.pool.alloc_blocks.in_use(),
                   unit="blocks", help="blocks currently referenced")
        m.gauge_fn("blockpool.peak_blocks_in_use",
                   lambda: self.pool.alloc_blocks.peak_in_use,
                   unit="blocks", help="peak referenced blocks")
        m.gauge_fn("blockpool.free_blocks",
                   lambda: self.pool.alloc_blocks.num_free(),
                   unit="blocks", help="immediately free blocks")
        m.gauge_fn("blockpool.allocs",
                   lambda: getattr(self.pool.alloc_blocks, "num_allocs", 0),
                   unit="blocks", help="block allocations served")
        m.gauge_fn("blockpool.frees",
                   lambda: getattr(self.pool.alloc_blocks, "num_frees", 0),
                   unit="blocks", help="blocks returned to the free list")
        m.gauge_fn("blockpool.paged", lambda: int(self.pool.paged),
                   help="1 when full-attention KV is paged")
        self._c_walk = m.counter(
            "paged.walk_blocks", unit="blocks",
            help="live table blocks (pos // block_size + 1) of the rows of "
                 "paged single-token decode passes: what the fast-path "
                 "kernel walks")
        self._c_reach = m.counter(
            "paged.reach_blocks", unit="blocks",
            help="table reach (rows x blocks per table) of the same passes")
        if self.prefix_cache is not None:
            def _pc(key: str):
                return lambda: self.prefix_cache.stats()[key]
            m.gauge_fn("prefixcache.hits", _pc("prefix_hits"),
                       unit="lookups", help="admissions matching >= 1 block")
            m.gauge_fn("prefixcache.misses", _pc("prefix_misses"),
                       unit="lookups", help="admissions matching 0 blocks")
            m.gauge_fn("prefixcache.hit_tokens", _pc("prefix_hit_tokens"),
                       unit="tokens", help="prompt tokens served from cache")
            m.gauge_fn("prefixcache.insertions", _pc("prefix_insertions"),
                       unit="blocks", help="blocks registered with the radix")
            m.gauge_fn("prefixcache.evictions", _pc("prefix_evictions"),
                       unit="blocks", help="cached blocks reclaimed LRU")
            m.gauge_fn("prefixcache.size_blocks", _pc("prefix_size_blocks"),
                       unit="blocks", help="blocks resident in the cache")
            m.gauge_fn(
                "prefixcache.hit_rate",
                lambda: (lambda s: s["prefix_hits"]
                         / max(s["prefix_hits"] + s["prefix_misses"], 1))(
                    self.prefix_cache.stats()),
                unit="fraction", help="lookup hit rate")
        if hasattr(self.scheduler, "num_demotions"):
            m.gauge_fn("scheduler.demotions",
                       lambda: self.scheduler.num_demotions,
                       unit="transitions",
                       help="adaptive demotions to sync verification")
            m.gauge_fn("scheduler.promotions",
                       lambda: self.scheduler.num_promotions,
                       unit="transitions",
                       help="adaptive promotions back to overlap")
        self._h_ttft = m.histogram(
            "latency.ttft", unit="s",
            help="submit to first committed token (obs clock)")
        self._h_tpot = m.histogram(
            "latency.tpot", unit="s",
            help="mean inter-token time past T0 (obs clock)")
        self._h_e2e = m.histogram(
            "latency.e2e", unit="s",
            help="submit to retirement (obs clock)")
        self._h_queue = m.histogram(
            "latency.queue_wait", unit="s",
            help="submit to first admission (obs clock)")

    def _mark_first_token(self, req: Request) -> None:
        if req.first_token_ns < 0:
            req.first_token_ns = now_ns()

    def _note_t0(self, req: Request, margin: Optional[float] = None) -> None:
        """Metrics + audit for the T0 token a prefill pass just committed
        (sampled under the fixed verify-grade schedule in every mode —
        deterministic by construction, window -1)."""
        self._c_committed.inc()
        self._mark_first_token(req)
        au = self.obs.audit
        if au.enabled:
            schedule = (
                INVARIANT_SCHEDULE if self.mode == Mode.BATCH_INVARIANT
                else VERIFY_SCHEDULE
            )
            au.record(TokenProvenance(
                rid=req.rid, index=len(req.committed) - 1,
                token=req.committed[-1], origin="prefill",
                schedule=schedule, margin=margin,
            ))

    def _note_splice(self, req: Request, outcome: pipeline.SpliceOutcome,
                     ) -> None:
        """Metrics + trace + audit for one pipelined front splice."""
        fl = outcome.record
        self._c_passes.inc()
        self._c_committed.inc(outcome.committed_count)
        if outcome.rejected:
            self._c_rollbacks.inc()
            self._c_recomputed.inc(outcome.rejected)
            self._h_rollback_depth.observe(outcome.rejected)
        if outcome.cascaded:
            self._c_cascaded.inc(len(outcome.cascaded))
        tr = self.obs.tracer
        if tr.enabled:
            tr.instant(
                "rollback" if outcome.rolled_back else "commit",
                rid=req.rid, window=fl.seq,
                n_match=fl.n_match, committed=outcome.committed_count,
                rejected=outcome.rejected, cascaded=len(outcome.cascaded),
            )
        if req.committed:
            self._mark_first_token(req)
        au = self.obs.audit
        if not au.enabled:
            return
        n = min(fl.n_match, len(fl.cands))
        for j in range(outcome.committed_count):
            idx = outcome.committed_base + j
            au.record(TokenProvenance(
                rid=req.rid, index=idx, token=req.committed[idx],
                origin="verify", schedule=VERIFY_SCHEDULE,
                window=fl.seq, occurrence=fl.ring_idx,
                n_match=fl.n_match, accepted=j < n,
                rollback=outcome.rolled_back,
                cascaded=len(outcome.cascaded), shifted=fl.shifted,
                margin=(fl.margins[j]
                        if fl.margins and j < len(fl.margins) else None),
            ))

    # ------------------------------------------------------------------
    # jitted step builders (cached per shape class)
    # ------------------------------------------------------------------

    def _pview(self) -> Optional[PagedView]:
        """Static paged-addressing descriptor threaded into ``forward``
        when the in-place paged path is on (None = legacy gathered
        views)."""
        if not self._paged_fwd:
            return None
        lay = self.pool.layout
        return PagedView(lay.block_size, lay.null_bid, lay.scratch_bid)

    def _decode_body(self, B: int, schedule: Schedule) -> Callable:
        """UNJITTED one-token decode body for a fixed batch size.

        Under the paged path the pool's full-attention leaves are passed
        whole and the forward reads/writes them through the block tables
        (``models.layers.attention_paged``) — the per-iteration
        gather/scatter copy of every row's KV never materializes.  The
        legacy path keeps the gathered per-row views.  Separate from
        ``_decode_fn`` so the fused mixed-batch step can compose it with
        the prefill-chunk and verify bodies under one jit."""
        cfg, lay = self.cfg, self.pool.layout
        paged, pview = self._paged_fwd, self._pview()

        @jax.named_scope("decode")
        def decode_step(params, pool, slots, tables, tokens, pos, seeds,
                        temps, out_pos, top_ks):
            if paged:
                cache = kv_cache.gather_mixed(pool, lay, slots)
            else:
                cache = kv_cache.gather(pool, lay, slots, tables)
            logits, new_cache, _ = forward(
                params, cfg, tokens[:, None],
                cache=cache, start_pos=pos, schedule=schedule,
                tables=tables if paged else None, paged=pview,
            )
            nxt = sample_batch(logits[:, 0], seeds, out_pos, temps, top_ks)
            # top-1/top-2 margin per row: audit provenance for directly
            # committed fast-path tokens.  Computed unconditionally so the
            # device program is identical with auditing on or off; host
            # float conversion is gated instead.
            margins = top2_margin(logits[:, 0])
            if paged:
                pool2 = kv_cache.scatter_mixed(pool, lay, slots, new_cache)
            else:
                pool2 = kv_cache.scatter(pool, lay, slots, tables, new_cache)
            return pool2, nxt, margins

        return decode_step

    def _decode_fn(self, B: int, schedule: Schedule) -> Callable:
        key = ("decode", B, schedule)
        if key not in self._fns:
            self._fns[key] = jax.jit(self._decode_body(B, schedule))
        return self._fns[key]

    # det: commit-path
    def _prefill_fn(self, P: int) -> Callable:
        key = ("prefill", P)
        if key not in self._fns:
            cfg, lay = self.cfg, self.pool.layout
            n_prefix = cfg.num_prefix_embeds
            rec = self.has_recurrent_state
            schedule = (
                INVARIANT_SCHEDULE if self.mode == Mode.BATCH_INVARIANT
                else VERIFY_SCHEDULE
            )

            @jax.jit
            @jax.named_scope("prefill")
            def prefill_step(params, pool, slot, table, tokens, plen, seed,
                             temp, top_k, prefix_embeds):
                slots = slot[None]
                cache = kv_cache.gather(pool, lay, slots, table[None])
                if n_prefix:
                    tok_embeds = jnp.take(params["embed"], tokens, axis=0)
                    embeds = jnp.concatenate([prefix_embeds, tok_embeds], axis=1)
                    logits, new_cache, per_pos = forward(
                        params, cfg, inputs_embeds=embeds,
                        cache=cache, start_pos=jnp.zeros(1, jnp.int32),
                        schedule=schedule, collect_states=rec,
                    )
                    last = plen + n_prefix - 1
                else:
                    logits, new_cache, per_pos = forward(
                        params, cfg, tokens,
                        cache=cache, start_pos=jnp.zeros(1, jnp.int32),
                        schedule=schedule, collect_states=rec,
                    )
                    last = plen - 1
                tok = sample_token(logits[0, last], seed, jnp.int32(0), temp,
                                   top_k)
                marg = top2_margin(logits[0, last])  # T0 audit provenance
                if rec:  # bucket-pad positions must not advance O(1) state
                    new_cache = statepool.merge_rows(
                        new_cache, statepool.select_index(per_pos, last[None]),
                    )
                pool2 = kv_cache.scatter(pool, lay, slots, table[None], new_cache)
                return pool2, tok, marg

            self._fns[key] = prefill_step
        return self._fns[key]

    def _prefill_chunk_body(self, C: int) -> Callable:
        """UNJITTED fixed-shape C-token prefill-chunk body.

        Recurrent/hybrid archs run a state-collecting variant: the chunk's
        recurrent state is checkpointed at ``last`` (the chunk's final REAL
        position), so final-chunk pad embeds never advance the O(1) state —
        which is what makes a recurrent chunk schedule size-invariant and
        lets ssm/hybrid prompts join the co-scheduled prefill lane.  The
        chunk runs the fixed verify-grade schedule in every mode, and the
        paged in-place variant reads/writes KV through the block table, so
        both variants are deterministic by construction and bitwise
        equal."""
        cfg, lay = self.cfg, self.pool.layout
        rec = self.has_recurrent_state
        paged, pview = self._paged_fwd, self._pview()
        schedule = (
            INVARIANT_SCHEDULE if self.mode == Mode.BATCH_INVARIANT
            else VERIFY_SCHEDULE
        )

        @jax.named_scope("prefill_chunk")
        def prefill_chunk_step(params, pool, slot, table, embeds, start,
                               last):
            slots = slot[None]
            tables = table[None]
            if paged:
                cache = kv_cache.gather_mixed(pool, lay, slots)
            else:
                cache = kv_cache.gather(pool, lay, slots, tables)
            logits, new_cache, per_pos = forward(
                params, cfg, inputs_embeds=embeds, cache=cache,
                start_pos=start[None], schedule=schedule,
                collect_states=rec,
                tables=tables if paged else None, paged=pview,
            )
            if rec:  # state after the last real position, pads dropped
                new_cache = statepool.merge_rows(
                    new_cache,
                    statepool.select_index(per_pos, last[None]),
                )
            if paged:
                pool2 = kv_cache.scatter_mixed(pool, lay, slots, new_cache)
            else:
                pool2 = kv_cache.scatter(pool, lay, slots, tables, new_cache)
            return pool2, logits

        return prefill_chunk_step

    # det: commit-path
    def _prefill_chunk_fn(self, C: int) -> Callable:
        """Fixed-shape C-token prefill chunk, usable by every arch
        (generalizes the old sliding-window-only chunk path).  Takes input
        embeddings so token prompts, prefix embeds (multimodal) and encdec
        decoder prompts all share one shape class per chunk size.  The
        semantics live in ``_prefill_chunk_body``; this is just the cached
        standalone jit of it."""
        rec = self.has_recurrent_state
        key = ("prefill_chunk_rec" if rec else "prefill_chunk", C)
        if key not in self._fns:
            self._fns[key] = jax.jit(self._prefill_chunk_body(C))
        return self._fns[key]

    def _cross_fn(self, Se: int) -> Callable:
        key = ("cross", Se)
        if key not in self._fns:
            cfg = self.cfg

            @jax.jit
            def build(params, enc_embeds):
                return build_cross_cache(params, cfg, enc_embeds)

            self._fns[key] = build
        return self._fns[key]

    def _fused_fn(
        self, C: Optional[int], B: int, schedule: Schedule, n_groups: int
    ) -> Callable:
        """ONE jitted launch for the iteration's whole mixed batch: the
        current prefill chunk (``C`` tokens, or None), the decode batch
        (``B`` rows, or 0) and ``n_groups`` due verify groups run as
        sequential sub-passes threading a single pool (+ state-pool anchor
        on recurrent archs).  The weights stream once per iteration instead
        of once per sub-pass, and the per-launch fixed overhead is paid
        once; the sub-passes keep their exact standalone bodies (and their
        exact per-shape schedules), so fusing moves no committed token.
        Cached per shape class — (chunk, batch, schedule, group count) —
        like every other jitted step."""
        key = ("fused", C, B, schedule, n_groups)
        if key not in self._fns:
            pbody = self._prefill_chunk_body(C) if C is not None else None
            dbody = self._decode_body(B, schedule) if B else None
            vbody = (
                make_verify_body(
                    self.cfg, self.group, self.window, self.pool.layout,
                    paged=self._paged_fwd,
                )
                if n_groups else None
            )
            rec = self.has_recurrent_state

            def fused_step(params, pool, anchor, pargs, dargs, vargs_list):
                logits_p = nxt = dmarg = None
                if pbody is not None:
                    pool, logits_p = pbody(params, pool, *pargs)
                if dbody is not None:
                    pool, nxt, dmarg = dbody(params, pool, *dargs)
                vouts = []
                for vargs in vargs_list:
                    if rec:
                        (pool, anchor, commit_rows, n_match, commit_tok,
                         _v, marg) = vbody(params, pool, anchor, *vargs)
                        vouts.append((commit_rows, n_match, commit_tok, marg))
                    else:
                        pool, n_match, commit_tok, _v, marg = vbody(
                            params, pool, *vargs
                        )
                        vouts.append((None, n_match, commit_tok, marg))
                return pool, anchor, logits_p, nxt, dmarg, vouts

            self._fns[key] = jax.jit(fused_step)
        return self._fns[key]

    # ------------------------------------------------------------------
    # scheduling
    # ------------------------------------------------------------------

    def submit(self, req: Request) -> None:
        self._check_capacity(req)
        req.state = State.QUEUED
        req.submit_ns = now_ns()
        self.queue.append(req)
        self._c_submitted.inc()
        tr = self.obs.tracer
        if tr.enabled:
            tr.request_begin(req.rid, req.submit_ns)
            tr.instant("submit", rid=req.rid,
                       prompt_len=req.prompt_len,
                       deterministic=req.sampling.is_deterministic)

    def _worst_need(self, req: Request) -> int:
        """Worst-case KV positions this request can ever occupy.

        A deterministic request reserves ``spec_depth x (W-1) + 1`` verify
        rows past its output budget: up to ``spec_depth`` windows of W-1
        candidates can be in flight at once, and the deepest window's
        replay writes one verifier token past its last candidate.  Peak
        usage is the MAX of the prefill and decode phases, not their sum —
        decode/verify writes start at L and overwrite the prefill pad
        tail."""
        cfg = self.cfg
        prefix = cfg.num_prefix_embeds or 0
        L = prefix + req.prompt_len
        if self._use_chunked(req):
            C = self._chunk_size()
            extent = -(-L // C) * C  # the last chunk pads to the chunk shape
        else:
            extent = prefix + _bucket(req.prompt_len)
        spec = (
            self.spec_depth * (self.window - 1) + 1
            if self.mode == Mode.LLM42 and req.sampling.is_deterministic
            else 0
        )
        return max(extent, L + req.sampling.max_new_tokens + spec)

    def _check_capacity(self, req: Request) -> None:
        """Admission capacity guard, derived from block-pool accounting:
        reject a request whose worst-case footprint (prefill extent vs
        prompt + output budget + the ``spec_depth x (W-1) + 1`` verify-row
        reservation) exceeds the per-request block-table reach
        (``capacity``) or the whole pool's block supply — instead of
        silently overflowing.  Transient pressure is NOT rejected here:
        a request that could *ever* fit queues, and the preemption lane
        arbitrates the pool at run time."""
        cfg = self.cfg
        has_full_attn = cfg.attn_kind != "sliding" and any(
            cfg.layer_kind(i) == "attn" for i in range(cfg.num_layers)
        )
        if not has_full_attn:
            return  # sliding ring buffers wrap; recurrent state is O(1)
        need = self._worst_need(req)
        total_blocks = self.pool.alloc_blocks.num_blocks
        need_blocks = self._blocks_for(need)
        if need > self.capacity or need_blocks > total_blocks:
            prefix = cfg.num_prefix_embeds or 0
            raise ValueError(
                f"request {req.rid} cannot fit the KV pool: "
                f"max(prefill extent, prompt {prefix + req.prompt_len} + "
                f"max_new_tokens {req.sampling.max_new_tokens} + verify "
                f"rows [depth {self.spec_depth} x (W-1) + 1]) = {need} "
                f"positions = {need_blocks} blocks > per-request capacity "
                f"{self.capacity} or pool {total_blocks} blocks of "
                f"{self.pool.block_size}"
            )

    def _chunk_size(self) -> int:
        """Effective prefill chunk (ring-buffer contract caps it at the
        sliding window so a pass never overwrites in-window keys)."""
        C = self.prefill_chunk
        if self.cfg.attn_kind == "sliding":
            C = min(C, self.cfg.window)
        return max(1, C)

    def _use_chunked(self, req: Request) -> bool:
        """Chunked lane only when the prompt actually spans > 1 chunk: a
        prompt that fits one chunk runs the legacy exclusive pass — same
        single-iteration stall, but padded to its (smaller) power-of-two
        bucket instead of the full chunk width."""
        if not self.chunked_prefill:
            return False
        prefix = self.cfg.num_prefix_embeds or 0
        return prefix + req.prompt_len > self._chunk_size()

    # ------------------------------------------------------------------
    # block-pool accounting, admission, preemption
    # ------------------------------------------------------------------

    def _blocks_for(self, positions: int) -> int:
        """Blocks covering ``positions`` KV slots."""
        return -(-max(positions, 0) // self.pool.block_size)

    def _alloc_block(self) -> Optional[int]:
        """One free block, reclaiming LRU zero-ref prefix-cache blocks on
        demand.  None = pool genuinely exhausted (preemption's cue)."""
        alloc = self.pool.alloc_blocks
        bid = alloc.alloc()
        while bid is None and self.prefix_cache is not None:
            evicted = self.prefix_cache.evict_lru(alloc)
            if evicted is None:
                break
            with self.obs.span("blockpool.wipe"):
                self.pool.free_blocks([evicted])
            bid = alloc.alloc()
        return bid

    def _grow_blocks(self, req: Request, target_blocks: int) -> bool:
        """Append private blocks until the table reaches ``target_blocks``;
        False (with partial growth kept) when the pool is dry."""
        while len(req.blocks) < target_blocks:
            bid = self._alloc_block()
            if bid is None:
                return False
            req.blocks.append(bid)
        return True

    def _ensure_blocks(self, req: Request, end_pos: int) -> bool:
        """Guarantee the table covers KV positions [0, end_pos), preempting
        LRU victims (scheduler.BlockMemoryPolicy) when the pool is dry.
        False = unsatisfiable right now (no victim left): the request
        stalls this iteration and retries next."""
        if not self.pool.paged:
            return True
        target = self._blocks_for(min(end_pos, self.capacity))
        while not self._grow_blocks(req, target):
            cands = [
                r for r in self.running
                if r is not req and r.state is not State.PREFILLING
            ]
            victim = self.mem_policy.pick_victim(cands, self._now)
            if victim is None:
                return False
            self.preempt(victim)
        return True

    def _release_blocks(self, req: Request, *, insert: bool) -> None:
        """Drop the request's block references.  ``insert=True`` first
        registers the committed-stream prefix with the radix cache (the
        blocks then stay resident-but-evictable instead of freeing)."""
        alloc = self.pool.alloc_blocks
        if insert and self.prefix_cache is not None:
            stream = self._cacheable_stream(req)
            n = len(stream) // self.pool.block_size
            if n:
                self.prefix_cache.insert(
                    stream, req.blocks[:n], self._now, alloc
                )
        freed = []
        for bid in req.blocks:
            if alloc.decref(bid) == 0 and bid not in alloc.cached:
                freed.append(bid)
        if freed:
            with self.obs.span("blockpool.wipe"):
                self.pool.free_blocks(freed)
        req.blocks = []
        req.blocks_shared = 0

    def _cacheable_stream(self, req: Request) -> List[int]:
        """The committed token stream whose KV is deterministic AND
        resident: the prompt always (prefill runs the fixed schedule in
        every mode), plus committed output for deterministic traffic
        (verify-grade by the DVR protocol) and BATCH_INVARIANT mode —
        minus the last committed token, whose KV is written by the *next*
        decode and may not exist yet.  Never fast-path non-deterministic
        output."""
        det_out = (
            self.mode == Mode.BATCH_INVARIANT
            or (self.mode == Mode.LLM42 and req.sampling.is_deterministic)
        )
        if det_out and req.committed:
            return list(req.prompt) + list(req.committed[:-1])
        return list(req.prompt)

    def _insert_prompt_blocks(self, req: Request) -> None:
        """Register the freshly prefilled prompt's whole blocks with the
        prefix cache, so concurrent arrivals with the same system prompt
        share them immediately."""
        if self.prefix_cache is None:
            return
        n = req.prompt_len // self.pool.block_size
        if n:
            self.prefix_cache.insert(
                req.prompt, req.blocks[:n], self._now,
                self.pool.alloc_blocks,
            )

    def _admit(self) -> None:
        # restore lane first: preempted requests re-enter with priority
        # (their committed work is sunk cost), gated by the memory
        # policy's anti-thrash hysteresis
        while self.preempted and len(self.running) < self.max_batch:
            req = self.preempted[0]
            avail = (
                self.pool.alloc_blocks.available()
                if self.pool.paged else 10 ** 9
            )
            need = self._blocks_for(self._worst_need(req))
            if not self.mem_policy.may_restore(req, avail, need, self._now):
                break
            self.preempted.pop(0)
            self._restore(req)
        while self.queue and self.pool.num_free() > 0 and (
            len(self.running) < self.max_batch
        ):
            if not self._try_admit(self.queue[0]):
                break  # FIFO admission: the head waits for memory
            self.queue.pop(0)

    def _try_admit(self, req: Request) -> bool:
        """Admit one queued request if the block pool can cover its prompt
        (free-block accounting, not dense per-slot reservation): map the
        longest committed-prefix match to shared cache blocks, allocate
        private blocks for the tail, and start prefill on the tail only."""
        cfg = self.cfg
        prefix = cfg.num_prefix_embeds or 0
        L = prefix + req.prompt_len
        alloc = self.pool.alloc_blocks
        matched: List[int] = []
        if self.prefix_cache is not None:
            matched = self.prefix_cache.match(req.prompt, self._now)
            # the boundary block is never shared: at least the prompt's
            # last position must run (T0's logits), and it writes KV —
            # copy-on-write by recompute
            matched = matched[: (req.prompt_len - 1) // self.pool.block_size]
        if self.pool.paged:
            need = self._blocks_for(L) - len(matched)
            if not self.mem_policy.may_admit(alloc.available(), need):
                return False
        for bid in matched:
            alloc.incref(bid)
        req.blocks = list(matched)
        req.blocks_shared = len(matched)
        req.cached_prefix_tokens = len(matched) * self.pool.block_size
        if self.pool.paged and not self._grow_blocks(req, self._blocks_for(L)):
            # raced the watermark (fragmentation vs evictable estimate):
            # roll back and keep the request queued
            self._release_blocks(req, insert=False)
            return False
        if self.prefix_cache is not None:
            self.prefix_cache.note_lookup(len(matched))
        req.slot = self.pool.alloc()
        self._h_queue.observe((now_ns() - req.submit_ns) / 1e9)
        cached = req.cached_prefix_tokens
        if cached:
            self.events.append({
                "kind": "cache_hit", "rid": req.rid, "tokens": cached,
                "iter": self._now,
            })
        tr = self.obs.tracer
        if tr.enabled:
            tr.instant("admit", rid=req.rid,
                       cached_tokens=cached, slot=req.slot)
        if self._use_chunked(req) or cached > 0:
            # third lane: prefill advances chunk-by-chunk via scheduler
            # plans instead of one exclusive pass at admission; a cache
            # hit enters the same lane with the cursor past the match
            self._prepare_prefill(req)
            req.prefill_pos = cached
            req.state = State.PREFILLING
            if not self._use_chunked(req):
                # cache-hit tail under the exclusive-prefill engine: run
                # the tail synchronously (legacy admission semantics)
                with self.obs.span("engine.prefill"):
                    self._prefill_tail_sync(req)
        else:
            with self.obs.span("engine.prefill"):
                self._prefill(req)
            req.state = State.RUNNING
        self.running.append(req)
        return True

    def _flush_pipeline(self, req: Request) -> None:
        """Force-apply every in-flight verdict in submission order.  The
        discrete-event engine computes verdicts eagerly at launch — only
        their *visibility* is deferred — so an early flush commits exactly
        the tokens that would have committed anyway (in-order splices,
        cascades included).  Device state work is skipped: the caller is
        about to evict the slot's KV and the restore replay rebuilds
        recurrent state from the committed stream."""
        for outcome in pipeline.apply_ready(req, self.window, float("inf")):
            self._note_splice(req, outcome)
            self.statepool.note_splice(req.slot, len(outcome.cascaded))
        self.statepool.note_preempt(req.slot)

    @_spanned("engine.preempt")
    def preempt(self, req: Request) -> bool:
        """Evict a running request's KV blocks (the memory policy's lane,
        and a test hook for adversarial eviction schedules).  The request
        keeps its slot, its committed stream and its statepool replay
        anchor; fresh speculation is dropped (uncommitted by definition)
        and in-flight verdicts are flushed first — so the committed stream
        is untouched, which is what makes the later restore-by-recompute
        bitwise-identical."""
        if req not in self.running or req.state is State.PREFILLING:
            return False
        self._flush_pipeline(req)
        if req.finished():
            # the flushed verdicts completed the request: retire instead
            self._finish(req)
            return True
        dropped = len(req.candidates)
        req.candidates = []
        req.num_preempted_tokens += dropped
        req.num_preemptions += 1
        req.preempt_iter = self._now
        # committed-prefix blocks go to the radix cache (evictable, so the
        # pool reclaims them LRU — and an early restore may re-match them);
        # the speculative tail frees outright
        self._release_blocks(req, insert=True)
        req.state = State.PREEMPTED
        self.running.remove(req)
        self.preempted.append(req)
        self.num_preemptions += 1
        self.events.append({
            "kind": "preempt", "rid": req.rid, "iter": self._now,
            "dropped_tokens": dropped, "committed": len(req.committed),
        })
        tr = self.obs.tracer
        if tr.enabled:
            tr.instant("preempt", rid=req.rid,
                       dropped_tokens=dropped, committed=len(req.committed))
        return True

    @_spanned("engine.restore")
    def _restore(self, req: Request) -> None:
        """Re-admit a preempted request by deterministic recompute: replay
        its committed stream (prompt + committed[:-1] — the last committed
        token's KV is written by the resuming decode, exactly as in the
        un-preempted flow) through the chunked-prefill lane.  The replay
        runs the fixed verify-grade schedule, and every committed
        position's KV was verify-grade before eviction, so the rebuilt
        cache — and, on recurrent archs, the rebuilt state and replay
        anchor — is bitwise-identical by construction.  Blocks still
        resident in the prefix cache are re-matched instead of
        recomputed."""
        stream = list(req.prompt) + list(req.committed[:-1])
        # the replay starts from position 0 on a pristine state row: the
        # slot survived preemption, but its live recurrent state (and any
        # sliding ring content) is post-speculation — NOT the state a
        # fresh prefill would start from
        self.pool.reset_slot(req.slot)
        alloc = self.pool.alloc_blocks
        matched: List[int] = []
        if self.prefix_cache is not None:
            # a replay samples nothing, so a full-stream match needs no
            # recompute at all — the boundary rule only bounds matches to
            # whole blocks, which the radix walk does by construction
            matched = self.prefix_cache.match(stream, self._now)
        for bid in matched:
            alloc.incref(bid)
        req.blocks = list(matched)
        req.blocks_shared = len(matched)
        self._prepare_prefill(req, stream=stream)
        req.prefill_pos = len(matched) * self.pool.block_size
        req.replaying = True
        ok = self._grow_blocks(
            req, self._blocks_for((self.cfg.num_prefix_embeds or 0)
                                  + len(stream))
        )
        assert ok, "restore gate admitted a replay the pool cannot hold"
        self.num_restores += 1
        self.restored_tokens += max(req.prefill_total - req.prefill_pos, 0)
        self.events.append({
            "kind": "restore", "rid": req.rid, "iter": self._now,
            "replay_tokens": max(req.prefill_total - req.prefill_pos, 0),
            "rematched_blocks": len(matched),
        })
        tr = self.obs.tracer
        if tr.enabled:
            tr.instant(
                "restore", rid=req.rid,
                replay_tokens=max(req.prefill_total - req.prefill_pos, 0),
                rematched_blocks=len(matched),
            )
        self.running.append(req)
        if req.prefill_pos >= req.prefill_total:
            # everything survived in the cache: nothing to recompute
            self._finish_prefill(req, sample=False)
        else:
            # a restore always re-enters via PREFILLING: the replay rides
            # the third lane when chunking is on, else runs synchronously
            req.state = State.PREFILLING
            if not self.chunked_prefill:
                self._prefill_tail_sync(req)

    def _prefill_tail_sync(self, req: Request) -> None:
        """Exclusive (synchronous) prefill of the remaining
        ``[prefill_pos, prefill_total)`` span — the cache-hit tail or a
        restore replay under a non-chunked engine.  One fixed-shape pass
        sized to the tail's power-of-two bucket (capped at the sliding
        window's ring contract), looped to completion; emits one legacy
        ``prefill`` event."""
        start = req.prefill_pos
        replay = req.replaying
        C = _bucket(max(req.prefill_total - start, 1))
        if self.cfg.attn_kind == "sliding":
            C = min(C, self.cfg.window)
        wall = 0.0
        while req.prefill_pos < req.prefill_total:
            wall += self._prefill_advance(req, C)["wall"]
        ev = {
            "kind": "prefill", "tokens": req.prefill_total - start,
            "padded": -(-(req.prefill_total - start) // C) * C,
            "wall": wall, "iter": self._now, "cached": start,
            "replay": replay,
        }
        self.runtime.charge(ev)
        self.events.append(ev)

    def mem_stats(self) -> Dict[str, Any]:
        """Legacy memory-telemetry view — now a thin compat shim over the
        metrics registry's ``snapshot()`` (the single source of truth).
        New consumers should read ``engine.obs.metrics.snapshot()``
        directly; the namespaced keys carry the same values."""
        snap = self.obs.metrics.snapshot()
        out: Dict[str, Any] = {
            "block_size": snap["blockpool.block_size"],
            "num_blocks": snap["blockpool.num_blocks"],
            "blocks_in_use": snap["blockpool.blocks_in_use"],
            "peak_blocks_in_use": snap["blockpool.peak_blocks_in_use"],
            "free_blocks": snap["blockpool.free_blocks"],
            "num_preemptions": snap["mem.preemptions"],
            "num_restores": snap["mem.restores"],
            "restored_tokens": snap["mem.restored_tokens"],
            "peak_running": snap["engine.peak_running"],
            "paged": bool(snap["blockpool.paged"]),
        }
        if self.prefix_cache is not None:
            out.update({
                "prefix_hits": snap["prefixcache.hits"],
                "prefix_misses": snap["prefixcache.misses"],
                "prefix_hit_tokens": snap["prefixcache.hit_tokens"],
                "prefix_insertions": snap["prefixcache.insertions"],
                "prefix_evictions": snap["prefixcache.evictions"],
                "prefix_size_blocks": snap["prefixcache.size_blocks"],
            })
        return out

    def _build_cross(self, req: Request) -> None:
        assert req.enc_embeds is not None, "encdec request needs enc_embeds"
        cross = self._cross_fn(req.enc_embeds.shape[1])(self.params, req.enc_embeds)
        slot = jnp.array([req.slot])
        cross_axes = {"k": 1, "v": 1, "mask": 0}
        self.pool.data["cross"] = kv_cache.scatter_slots(
            self.pool.data["cross"], cross_axes, slot, cross
        )

    def _prepare_prefill(
        self, req: Request, stream: Optional[List[int]] = None
    ) -> None:
        """Host-side setup for chunk-resumable prefill: side inputs (cross
        cache, prefix embeds) and the chunk cursor.  Chunks embed their own
        token slice on demand (``_chunk_embeds``), so residency stays
        O(chunk), not O(prompt).  ``stream`` overrides the fed tokens — a
        restore replay feeds prompt + committed[:-1] instead of the
        prompt."""
        cfg = self.cfg
        req._prefix_len = cfg.num_prefix_embeds
        if cfg.family == "encdec":
            self._build_cross(req)
        if cfg.num_prefix_embeds:
            prefix = req.prefix_embeds
            if prefix is None:
                prefix = jnp.zeros(
                    (1, cfg.num_prefix_embeds, cfg.d_model), jnp.dtype(cfg.dtype)
                )
            req._prefix_src = prefix
        req.prefill_stream = (
            list(stream) if stream is not None else list(req.prompt)
        )
        req.prefill_total = (cfg.num_prefix_embeds or 0) + len(req.prefill_stream)
        req.prefill_pos = 0

    def _chunk_embeds(self, req: Request, s: int, C: int) -> jax.Array:
        """Input embeddings for prefill positions [s, s+C): prefix embeds
        where the chunk overlaps the prefix region, token embeddings for
        the fed-stream slice.  At most C real positions materialize."""
        prefix = getattr(req, "_prefix_len", 0) or 0
        stream = req.prefill_stream
        parts = []
        if s < prefix:
            parts.append(req._prefix_src[:, s : min(prefix, s + C)])
        lo = max(s - prefix, 0)
        hi = min(s + C - prefix, len(stream))
        if hi > lo:
            toks = jnp.array([stream[lo:hi]], jnp.int32)
            parts.append(jnp.take(self.params["embed"], toks, axis=0))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)

    def _pad_embed(self) -> jax.Array:
        """(1, 1, D) embedding of token 0 — the legacy pad content."""
        if not hasattr(self, "_pad_row"):
            self._pad_row = jnp.take(
                self.params["embed"], jnp.array([[0]], jnp.int32), axis=0
            )
        return self._pad_row

    def _finish_prefill(
        self, req: Request, *, sample: bool, logits=None, last_rel: int = 0
    ) -> None:
        """Prefill-completion bookkeeping, shared by the chunk lane and the
        zero-recompute restore path.  ``sample=True`` commits T0 from the
        final chunk's logits; a restore replay skips it (T0 — and
        everything after — is already committed)."""
        if sample:
            tok = sample_token(
                logits[0, last_rel], jnp.int32(req.sampling.seed),
                jnp.int32(0), jnp.float32(req.sampling.temperature),
                jnp.int32(req.sampling.top_k),
            )
            req.committed.append(int(tok))  # T0: deterministic by construction
            self._note_t0(req, (
                float(top2_margin(logits[0, last_rel]))
                if self.obs.audit.enabled else None
            ))
        # commit point == post-stream state: the verify replay anchor (on a
        # replay, the state after committed[:-1] — exactly what the next
        # anchored window starts from)
        self.statepool.set_commit_point(self.pool.data, req.slot)
        if req.prefill_time < 0:
            req.prefill_time = self._now
        req.state = State.RUNNING
        req._prefix_src = None
        if req.replaying:
            req.replaying = False
            req.restore_iter = self._now
        else:
            self._insert_prompt_blocks(req)

    def _prefill_chunk_prep(self, req: Request, C: int):
        """Device arguments for the request's next C-token prefill chunk.
        Pad positions embed token 0 (exactly the legacy padded passes);
        their writes land past the allocated block table and are absorbed
        by the pool's scratch block.  Returns ``(args, s, real)`` — the
        chunk cursor and real-token count feed ``_prefill_chunk_post``."""
        s = req.prefill_pos
        emb = self._chunk_embeds(req, s, C)
        real = emb.shape[1]
        if real < C:
            pad = jnp.broadcast_to(self._pad_embed(), (1, C - real, emb.shape[2]))
            emb = jnp.concatenate([emb, pad], axis=1)
        table = self.pool.table_array([req.blocks])[0]
        args = (
            jnp.int32(req.slot), table, emb, jnp.int32(s),
            jnp.int32(max(real - 1, 0)),
        )
        return args, s, real

    def _prefill_chunk_post(
        self, req: Request, C: int, s: int, real: int, logits, wall: float
    ) -> Dict[str, Any]:
        """Host bookkeeping after a chunk pass: advance the cursor; the
        final chunk samples T0 (unless this is a restore replay) and flips
        the request to RUNNING."""
        req.last_sched = self._now
        req.prefill_pos = s + real
        total = req.prefill_total
        done = req.prefill_pos >= total
        replay = req.replaying
        if done:
            self._finish_prefill(
                req, sample=not replay, logits=logits,
                last_rel=total - 1 - s,
            )
        return {
            "kind": "prefill_chunk", "tokens": real, "padded": C, "start": s,
            "wall": wall, "iter": self._now, "rid": req.rid, "done": done,
            "replay": replay,
        }

    def _prefill_advance(self, req: Request, C: int,
                         prepared=None) -> Dict[str, Any]:
        """Advance one fixed-shape C-token prefill chunk as a standalone
        launch (the fused step composes the same prep/body/post instead).
        ``prepared`` is ``_prefill_chunk_prep``'s result where the caller
        built it already."""
        args, s, real = prepared or self._prefill_chunk_prep(req, C)
        t0 = time.perf_counter()
        with self.obs.span("engine.launch"):
            self.pool.data, logits = self._prefill_chunk_fn(C)(
                self.params, self.pool.data, *args
            )
        if s + real >= req.prefill_total and not req.replaying:
            self._wait(logits)  # post samples T0 from them
        wall = time.perf_counter() - t0
        return self._prefill_chunk_post(req, C, s, real, logits, wall)

    def _wait(self, *outs) -> None:
        """Block until a launch's ``outs`` are ready, where the host's
        first read of them would block anyway (``engine.wait``)."""
        with self.obs.span("engine.wait"):
            jax.block_until_ready(outs)

    # det: commit-path
    def _prefill(self, req: Request) -> None:
        cfg = self.cfg
        P = _bucket(req.prompt_len)
        if cfg.attn_kind == "sliding" and P > cfg.window:
            # ring-buffer contract: feed the prompt in window-sized chunks
            self._prepare_prefill(req)
            self._prefill_sliding(req)
            return
        req._prefix_len = cfg.num_prefix_embeds
        if cfg.family == "encdec":
            self._build_cross(req)
        tokens = jnp.array(
            [req.prompt + [0] * (P - req.prompt_len)], jnp.int32
        )
        prefix = req.prefix_embeds
        if cfg.num_prefix_embeds and prefix is None:
            prefix = jnp.zeros(
                (1, cfg.num_prefix_embeds, cfg.d_model), jnp.dtype(cfg.dtype)
            )
        table = self.pool.table_array([req.blocks])[0]
        t0 = time.perf_counter()
        with self.obs.span("engine.launch"):
            self.pool.data, tok, marg = self._prefill_fn(P)(
                self.params, self.pool.data, jnp.int32(req.slot), table,
                tokens, jnp.int32(req.prompt_len),
                jnp.int32(req.sampling.seed),
                jnp.float32(req.sampling.temperature),
                jnp.int32(req.sampling.top_k), prefix,
            )
        self._wait(tok, marg)
        wall = time.perf_counter() - t0
        req.last_sched = self._now
        # commit point == post-prompt state: first verify replay anchor
        self.statepool.set_commit_point(self.pool.data, req.slot)
        req.committed.append(int(tok))  # T0: deterministic by construction
        self._note_t0(req, float(marg) if self.obs.audit.enabled else None)
        req.prefill_time = self._now
        self._insert_prompt_blocks(req)
        ev = {
            "kind": "prefill", "tokens": req.prompt_len + (cfg.num_prefix_embeds or 0),
            "padded": P + (cfg.num_prefix_embeds or 0), "wall": wall, "iter": self._now,
        }
        self.runtime.charge(ev)
        self.events.append(ev)

    def _prefill_sliding(self, req: Request) -> None:
        """Exclusive chunked prefill for sliding-window archs (<= window per
        pass — the ring-buffer contract).  Runs the same chunk machinery as
        the co-scheduled lane, synchronously, and emits one legacy
        ``prefill`` event.  Per-request fixed chunking => still
        deterministic by construction."""
        W = self.cfg.window
        wall = 0.0
        while req.prefill_pos < req.prefill_total:
            wall += self._prefill_advance(req, W)["wall"]
        ev = {
            "kind": "prefill", "tokens": req.prompt_len,
            "padded": ((req.prompt_len + W - 1) // W) * W, "wall": wall,
            "iter": self._now,
        }
        self.runtime.charge(ev)
        self.events.append(ev)

    def _view(self, stalled: Optional[Set[int]] = None) -> sched.SchedulerView:
        """Snapshot handed to the schedule policy each iteration.
        ``stalled`` rids (block-pool pressure with no victim left) are
        hidden from the policy — they retry next iteration."""
        stalled = stalled or set()
        visible = tuple(r for r in self.running if r.rid not in stalled)
        return sched.SchedulerView(
            running=visible,
            mode=self.mode,
            window=self.window,
            group=self.group,
            # the double-buffered state pool makes speculation past
            # submitted windows safe on EVERY arch: verification never
            # writes the live recurrent state at launch, and rollbacks
            # restore from the window's ring checkpoint
            speculate_past_inflight=True,
            now=self._now,
            prefilling=tuple(
                r for r in visible if r.state is State.PREFILLING
            ),
            now_time=self.runtime.now,
            verify_inflight=sum(len(r.pipeline) for r in self.running),
            verify_backlog=self.runtime.verify_backlog,
            acceptance={r.rid: r.accept_ema for r in self.running},
            spec_depth=self.spec_depth,
            free_blocks=self.pool.num_free_blocks(),
            num_preempted=len(self.preempted),
        )

    # ------------------------------------------------------------------
    # steps
    # ------------------------------------------------------------------

    def _decode_schedule(self, B: int) -> Schedule:
        if self.mode == Mode.BATCH_INVARIANT:
            return INVARIANT_SCHEDULE
        sched_ = self.policy.schedule_for(B)
        if self.tp > 1:
            # fast path on a width-tp mesh: the TP partial-sum tree follows
            # the mesh (un-pinned) — mesh geometry perturbs decode exactly
            # like batch geometry does, and DVR catches both the same way
            sched_ = sched_._replace(tp_shards=self.tp, tp_pinned=False)
        return sched_

    def _decode_prep(self, batch: List[Request]):
        """Device arguments for one decode pass over ``batch``.  Safe to
        run before OR after this iteration's verify pre-launch: submitting
        a window only moves the candidates' head into the in-flight FIFO's
        tail, so ``committed + speculation`` — everything read here — is
        unchanged by it."""
        slots = jnp.array([r.slot for r in batch], jnp.int32)
        tables = self.pool.table_array([r.blocks for r in batch])
        last_tok, pos, out_pos, seeds, temps, top_ks = [], [], [], [], [], []
        for r in batch:
            # speculation order: committed, in-flight window, fresh candidates
            seq = r.committed + r.speculation
            last_tok.append(seq[-1])
            prefix = getattr(r, "_prefix_len", 0)
            pos.append(r.prompt_len + prefix + len(seq) - 1)
            out_pos.append(len(seq))
            seeds.append(r.sampling.seed)
            temps.append(r.sampling.temperature)
            top_ks.append(r.sampling.top_k)
            r.last_sched = self._now
        if self._paged_fwd:
            bs, nblk = self.pool.block_size, tables.shape[1]
            self._c_walk.inc(sum(min(p // bs + 1, nblk) for p in pos))
            self._c_reach.inc(len(batch) * nblk)
        args = (
            slots, tables,
            jnp.array(last_tok, jnp.int32), jnp.array(pos, jnp.int32),
            jnp.array(seeds, jnp.int32), jnp.array(temps, jnp.float32),
            jnp.array(out_pos, jnp.int32), jnp.array(top_ks, jnp.int32),
        )
        return args, pos

    def _decode_post(
        self, batch: List[Request], schedule: Schedule, pos: List[int],
        nxt, wall: float, margins=None,
    ) -> Dict[str, Any]:
        """Land one decode pass's tokens: fresh candidates for det
        requests (plus window-state marking), committed tokens otherwise.
        Directly committed tokens get a decode-origin audit record carrying
        the fast-path schedule that produced them (``margins`` is the
        pass's per-row top-1/top-2 margin output; host conversion is gated
        on auditing)."""
        B = len(batch)
        nxt = [int(t) for t in nxt]
        au = self.obs.audit
        for i, (r, t) in enumerate(zip(batch, nxt)):
            if self.mode == Mode.LLM42 and r.sampling.is_deterministic:
                r.candidates.append(t)
                dvr.mark_window_state(r, self.window)
            else:
                r.committed.append(t)
                self._c_committed.inc()
                self._mark_first_token(r)
                if au.enabled:
                    au.record(TokenProvenance(
                        rid=r.rid, index=len(r.committed) - 1, token=t,
                        origin="decode", schedule=schedule,
                        margin=(float(margins[i])
                                if margins is not None else None),
                    ))
        return {
            "kind": "decode", "batch": B, "schedule": tuple(schedule),
            "ctx_sum": sum(pos) + B, "wall": wall, "iter": self._now,
            "rids": [r.rid for r in batch],
        }

    def _decode_step(self, batch: List[Request]) -> Dict[str, Any]:
        schedule = self._decode_schedule(len(batch))
        with self.obs.span("engine.prep"):
            args, pos = self._decode_prep(batch)
        return self._decode_launch(batch, schedule, args, pos)

    def _decode_launch(self, batch: List[Request], schedule: Schedule,
                       args, pos: List[int]) -> Dict[str, Any]:
        """One standalone decode pass on prepared ``args``: launch, wait
        for its tokens, land them."""
        t0 = time.perf_counter()
        with self.obs.span("engine.launch"):
            self.pool.data, nxt, margins = self._decode_fn(
                len(batch), schedule
            )(self.params, self.pool.data, *args)
        self._wait(nxt, margins)
        wall = time.perf_counter() - t0
        with self.obs.span("engine.post"):
            return self._decode_post(batch, schedule, pos, nxt, wall,
                                     margins)

    def _pad_verify_row(self, inputs, cands, cand_lens, starts, bases, slots,
                        seeds, temps, tks, ring_idxs, table_rows) -> None:
        """One padding row for a short verify group: writes go to the
        pool's scratch slot; the empty block table sends paged reads to
        the frozen null block and paged writes to the scratch block."""
        W = self.window
        inputs.append([0] * W)
        cands.append([-1] * (W - 1))
        cand_lens.append(0)
        starts.append(0)
        bases.append(0)
        slots.append(self.pool.scratch_slot)
        seeds.append(0)
        temps.append(0.0)
        tks.append(0)
        ring_idxs.append(0)
        table_rows.append([])

    @_spanned("engine.verify_prep")
    def _verify_prelaunch(self, rows: List[Request]):
        """Host protocol work for one deferred verify group, BEFORE the
        device pass: build each row's replay inputs, then move its window
        into the request's in-flight FIFO as a placeholder record
        (``n_match = -1`` keeps ``apply_ready`` from splicing it before the
        verdict payload lands in ``_verify_postlaunch``).  Submitting at
        prep time is what lets several chained groups of one iteration
        stack: group k+1's rows condition on the windows group k just
        pushed.  It is also safe ahead of the same iteration's decode
        bookkeeping — the submit only moves the candidates' head into the
        FIFO tail, leaving ``committed + speculation`` unchanged, and the
        fresh decode token lands behind the window just built."""
        G, W = self.group, self.window
        assert len({id(r) for r in rows}) == len(rows), (
            "a request may contribute one window per grouped pass — chained "
            "windows replay sequentially, never inside one batch"
        )
        n_pad = G - len(rows)
        inputs, cands, cand_lens, starts, bases, slots, seeds, temps, tks = (
            [], [], [], [], [], [], [], [], []
        )
        ring_idxs: List[int] = []
        fls: List[pipeline.InflightVerify] = []
        table_rows: List[List[int]] = []
        for r in rows:
            assert len(r.pipeline) < self.spec_depth, (
                "scheduler plan exceeds the configured spec_depth"
            )
            ring_idx = r.window_seq % self.spec_depth
            i, c, cl, sp, ob = dvr.build_verify_row(r, W)
            inputs.append(i)
            cands.append(c)
            cand_lens.append(cl)
            starts.append(sp)
            bases.append(ob)
            slots.append(r.slot)
            seeds.append(r.sampling.seed)
            temps.append(r.sampling.temperature)
            tks.append(r.sampling.top_k)
            table_rows.append(r.blocks)
            r.last_sched = self._now
            ring_idxs.append(ring_idx)
            fls.append(pipeline.submit_window(
                r, W, 0.0, float("inf"), ring_idx=ring_idx
            ))
        for _ in range(n_pad):
            self._pad_verify_row(inputs, cands, cand_lens, starts, bases,
                                 slots, seeds, temps, tks, ring_idxs,
                                 table_rows)
        args = (
            jnp.array(slots, jnp.int32),
            self.pool.table_array(table_rows),
            jnp.array(starts, jnp.int32),
            jnp.array(inputs, jnp.int32), jnp.array(cands, jnp.int32),
            jnp.array(cand_lens, jnp.int32), jnp.array(seeds, jnp.int32),
            jnp.array(temps, jnp.float32), jnp.array(bases, jnp.int32),
            jnp.array(tks, jnp.int32),
        )
        return args, fls, ring_idxs, slots, starts, n_pad

    def _verify_event(
        self, rows: List[Request], starts: List[int], n_pad: int,
        wall: float, n_decodable: int, deferred: bool,
    ) -> Dict[str, Any]:
        G, W = self.group, self.window
        return {
            "kind": "verify", "group": len(rows), "window": W,
            "pad_rows": n_pad,
            "ctx_sum": sum(starts) + W * G, "wall": wall, "iter": self._now,
            # requests that could decode this iteration — under the pause
            # policy these are the requests the verify pass stalls; under
            # overlap they ride in the composite event's decode batch
            "rids": [r.rid for r in rows], "n_decodable": n_decodable,
            # stream assignment for per-stream time accounting: a deferred
            # pass rides the verify stream; a sync pass blocks the main one
            "deferred": deferred,
        }

    @_spanned("engine.verify_post")
    def _verify_postlaunch(
        self, rows: List[Request], fls, ev: Dict[str, Any], ring_idxs,
        slots, starts, n_match, commit_tok, commit_rows, margins=None,
    ) -> None:
        """Land the host side of one deferred verify pass: stream-clock
        launch, state-pool checkpoints, verdict payloads into the
        placeholder FIFO records — and the post-submit state rule,
        re-applied here because it must be evaluated AFTER this iteration's
        decode bookkeeping (the fused step submits windows before the
        decode's candidate lands; the rule's ``done_decoding`` answer is
        only final once it has)."""
        W = self.window
        ready_at = self.runtime.launch_verify(ev, sync=False)
        submitted_at = self.runtime.now
        tr = self.obs.tracer
        self._c_windows.inc(len(rows))
        audit = self.obs.audit.enabled
        if commit_rows is not None:
            self.statepool.checkpoint(ring_idxs, slots, commit_rows)
        n_match = [int(n) for n in n_match]
        commit_tok = [int(t) for t in commit_tok]
        for i, r in enumerate(rows):
            fl = fls[i]
            fl.submitted_at, fl.ready_at = submitted_at, ready_at
            fl.n_match, fl.commit_tok = n_match[i], commit_tok[i]
            if audit and margins is not None:
                # window-position margins, parallel to cands + commit token
                # (front normalization pops both in lockstep)
                fl.margins = [float(x) for x in margins[i]]
            if tr.enabled:
                tr.instant("verify_submit", rid=r.rid,
                           window=fl.seq, cands=len(fl.cands),
                           ready_at=ready_at)
            self.statepool.note_submit(r.slot, starts[i] + W)
            if r.state is not State.FINISHED:
                r.state = (
                    State.AWAITING_VERIFY if r.done_decoding()
                    else State.RUNNING
                )

    def _pack_verify_groups(
        self, entries: List[Request]
    ) -> List[List[Request]]:
        """Split the plan's verify entries into grouped passes.  The k-th
        occurrence of a request is its k-th chained window this iteration
        and must replay after its predecessors, so occurrences layer:
        layer k's groups follow every layer < k, and each group holds up
        to ``group`` DISTINCT requests."""
        layers: List[List[Request]] = []
        seen: Dict[int, int] = {}
        for r in entries:
            k = seen.get(id(r), 0)
            seen[id(r)] = k + 1
            if k == len(layers):
                layers.append([])
            layers[k].append(r)
        groups: List[List[Request]] = []
        for layer in layers:
            for i in range(0, len(layer), self.group):
                groups.append(layer[i:i + self.group])
        return groups

    def _verify_step(
        self, group: List[Request], *, defer: bool = False,
        n_decodable: int = 0,
    ) -> Dict[str, Any]:
        """Run one grouped verification pass as a standalone launch.

        ``defer=False`` (pause policy / an AdaptivePolicy sync plan): the
        verdict is applied synchronously, exactly the seed behaviour; the
        pass blocks the main stream.  ``defer=True`` (overlap policy): the
        submitted candidates move into each request's in-flight FIFO
        (``core.pipeline``, up to ``spec_depth`` windows deep) and the
        pass is launched on the verify *stream* — its verdict becomes
        visible when the stream completes the pass plus the modeled extra
        latency (``verify_latency_ms``; one tick under the logical
        clock), and splices strictly in submission order.  The
        device pass still executes eagerly (host-sequential simulation of
        an async verify stream), so its KV repair is in place before any
        later cache read — in particular before the next chained window of
        the same request replays — but the *protocol* result arrives at
        the stream-clock deadline.  On recurrent archs the pass routes its
        state selections through the double-buffered state pool instead of
        touching the live state (``core.verifier`` docstring).
        """
        G, W = self.group, self.window
        rows = group[:G]
        span = self.obs.span
        if defer:
            with span("engine.prep"):
                args, fls, ring_idxs, slots, starts, n_pad = (
                    self._verify_prelaunch(rows)
                )
            return self._verify_launch(rows, fls, ring_idxs, slots, starts,
                                       n_pad, args, n_decodable)
        # ---- sync path: FIFOs are empty, the verdict applies on the spot
        assert len({id(r) for r in rows}) == len(rows), (
            "a request may contribute one window per grouped pass — chained "
            "windows replay sequentially, never inside one batch"
        )
        n_pad = G - len(rows)
        inputs, cands, cand_lens, starts, bases, slots, seeds, temps, tks = (
            [], [], [], [], [], [], [], [], []
        )
        ring_idxs: List[int] = []
        table_rows: List[List[int]] = []
        with span("engine.prep"):
            for r in rows:
                i, c, cl, sp, ob = dvr.build_verify_row(r, W)
                inputs.append(i)
                cands.append(c)
                cand_lens.append(cl)
                starts.append(sp)
                bases.append(ob)
                slots.append(r.slot)
                seeds.append(r.sampling.seed)
                temps.append(r.sampling.temperature)
                tks.append(r.sampling.top_k)
                table_rows.append(r.blocks)
                r.last_sched = self._now
                ring_idxs.append(0)  # sync: FIFO empty, ring 0 is free
            for _ in range(n_pad):
                self._pad_verify_row(inputs, cands, cand_lens, starts, bases,
                                     slots, seeds, temps, tks, ring_idxs,
                                     table_rows)
            t0 = time.perf_counter()
            args = (
                jnp.array(slots, jnp.int32),
                self.pool.table_array(table_rows),
                jnp.array(starts, jnp.int32),
                jnp.array(inputs, jnp.int32), jnp.array(cands, jnp.int32),
                jnp.array(cand_lens, jnp.int32), jnp.array(seeds, jnp.int32),
                jnp.array(temps, jnp.float32), jnp.array(bases, jnp.int32),
                jnp.array(tks, jnp.int32),
            )
        commit_rows, n_match, commit_tok, margins = self._run_verify(args)
        wall = time.perf_counter() - t0
        with span("engine.post"):
            return self._verify_sync_post(rows, ring_idxs, slots, starts,
                                          n_pad, n_match, commit_tok,
                                          commit_rows, margins, wall,
                                          n_decodable)

    def _verify_launch(self, rows: List[Request], fls, ring_idxs, slots,
                       starts, n_pad: int, args,
                       n_decodable: int) -> Dict[str, Any]:
        """One standalone deferred verify pass on prepared ``args``:
        launch, wait for its verdicts, land them in the FIFOs."""
        t0 = time.perf_counter()
        commit_rows, n_match, commit_tok, margins = self._run_verify(args)
        wall = time.perf_counter() - t0
        with self.obs.span("engine.post"):
            ev = self._verify_event(rows, starts, n_pad, wall, n_decodable,
                                    True)
            self._verify_postlaunch(rows, fls, ev, ring_idxs, slots, starts,
                                    n_match, commit_tok, commit_rows, margins)
        return ev

    def _run_verify(self, args):
        """Launch the standalone verify pass on ``args`` and wait for its
        verdicts: ``(commit_rows, n_match, commit_tok, margins)``, with
        ``commit_rows`` None on attention-only archs."""
        with self.obs.span("engine.launch"):
            if self.has_recurrent_state:
                (self.pool.data, self.statepool.anchor, commit_rows, n_match,
                 commit_tok, _v, margins) = self._verify_fn(
                    self.params, self.pool.data, self.statepool.anchor, *args
                )
            else:
                commit_rows = None
                self.pool.data, n_match, commit_tok, _v, margins = (
                    self._verify_fn(self.params, self.pool.data, *args)
                )
        self._wait(n_match, commit_tok, margins, commit_rows)
        return commit_rows, n_match, commit_tok, margins

    def _verify_sync_post(self, rows: List[Request], ring_idxs, slots,
                          starts, n_pad: int, n_match, commit_tok,
                          commit_rows, margins, wall: float,
                          n_decodable: int) -> Dict[str, Any]:
        """Apply a sync verify pass's verdicts on the spot."""
        W = self.window
        if commit_rows is not None:
            self.statepool.checkpoint(ring_idxs, slots, commit_rows)
        n_match = [int(n) for n in n_match]
        commit_tok = [int(t) for t in commit_tok]
        ev = self._verify_event(rows, starts, n_pad, wall, n_decodable,
                                False)
        self.runtime.launch_verify(ev, sync=True)
        tr, au = self.obs.tracer, self.obs.audit
        for i, (r, n, t) in enumerate(zip(rows, n_match, commit_tok)):
            cand_len = len(r.candidates)
            base = len(r.committed)
            nc, nrej = dvr.apply_verify_result(r, n, t, window=W)
            self._c_passes.inc()
            self._c_committed.inc(nc)
            if nrej:
                self._c_rollbacks.inc()
                self._c_recomputed.inc(nrej)
                self._h_rollback_depth.observe(nrej)
            if tr.enabled:
                tr.instant("rollback" if nrej else "commit", rid=r.rid,
                           window=r.num_verify_passes - 1, n_match=n,
                           committed=nc, rejected=nrej, cascaded=0)
            if r.committed:
                self._mark_first_token(r)
            if au.enabled:
                # sync windows never enter the in-flight FIFO, so the
                # audit window id is the request's verify-pass ordinal
                # (``window_seq`` stays untouched on this path)
                for j in range(nc):
                    idx = base + j
                    au.record(TokenProvenance(
                        rid=r.rid, index=idx, token=r.committed[idx],
                        origin="verify", schedule=VERIFY_SCHEDULE,
                        window=r.num_verify_passes - 1, occurrence=0,
                        n_match=n, accepted=j < min(n, cand_len),
                        rollback=nrej > 0,
                        margin=float(margins[i][j]),
                    ))
            if self.statepool.active:
                # live state + replay anchor <- the commit-index state
                # the pass just checkpointed (ring 0)
                self.pool.data = self.statepool.restore(
                    self.pool.data, r.slot, 0
                )
        return ev

    def _fused_step(self, plan: sched.Plan, view: sched.SchedulerView):
        """Run the iteration's entire device side — the current prefill
        chunk, the decode batch and EVERY due verify group — as ONE fused
        mixed-batch launch (``_fused_fn``) threading a single pool.  The
        paged in-place forward is what makes this possible: no sub-pass
        needs a privately gathered copy of the pool, so they chain on the
        shared leaves with no host round-trip between them.

        Host order: all preps first (prefill args, decode args, verify
        pre-launches in layer order), one device call, then prefill post,
        decode post (fresh candidates + window marking) and verify
        post-launches in layer order — each post-launch re-applies the
        post-submit state rule, so the request ends the iteration exactly
        where the legacy decode-then-submit order would put it.  Wall time
        splits equally across sub-passes; the lead sub-event (prefill,
        else decode, else the first verify group) carries the iteration's
        single weight stream + launch overhead in the cost model and every
        follower is marked ``fused``.  Returns ``(pev, dev, vev, vextra)``
        — the composite-event parts (``vextra`` = verify groups past the
        first)."""
        span = self.obs.span
        C = self._chunk_size()
        preq = plan.prefill
        with span("engine.prep"):
            pargs = ps = preal = None
            if preq is not None:
                pargs, ps, preal = self._prefill_chunk_prep(preq, C)
            batch = [r for r in plan.decode if not r.done_decoding()]
            B = len(batch)
            schedule = self._decode_schedule(B)
            dargs = dpos = None
            if batch:
                dargs, dpos = self._decode_prep(batch)
            groups = (
                self._pack_verify_groups(list(plan.verify))
                if plan.verify else []
            )
            vargs_list, vstates = [], []
            for rows in groups:
                args, fls, ring_idxs, slots, starts, n_pad = (
                    self._verify_prelaunch(rows)
                )
                vargs_list.append(args)
                vstates.append((rows, fls, ring_idxs, slots, starts, n_pad))
        n_subs = (preq is not None) + (1 if batch else 0) + len(groups)
        if n_subs == 0:
            return None, None, None, []
        n_decodable = len(sched.decodable(view))
        rec = self.has_recurrent_state
        if n_subs == 1:
            # single lane: dispatch to the standalone per-lane jit (same
            # bodies, no extra compile variants for degenerate shapes)
            if preq is not None:
                pev = self._prefill_advance(preq, C, (pargs, ps, preal))
                self.runtime.charge(pev)
                return pev, None, None, []
            if batch:
                dev = self._decode_launch(batch, schedule, dargs, dpos)
                self.runtime.charge(dev)
                return None, dev, None, []
            rows, fls, ring_idxs, slots, starts, n_pad = vstates[0]
            vev = self._verify_launch(
                rows, fls, ring_idxs, slots, starts, n_pad, vargs_list[0],
                n_decodable,
            )
            return None, None, vev, []

        t0 = time.perf_counter()
        anchor = self.statepool.anchor if rec else None
        with span("engine.launch"):
            pool, anchor, logits_p, nxt, dmarg, vouts = self._fused_fn(
                C if preq is not None else None, B, schedule, len(groups)
            )(
                self.params, self.pool.data, anchor,
                pargs if pargs is not None else (),
                dargs if dargs is not None else (),
                tuple(vargs_list),
            )
        self.pool.data = pool
        if rec:
            self.statepool.anchor = anchor
        # a fused launch holds a decode or a verify group, whose outputs
        # the host reads first thing in post
        self._wait(logits_p, nxt, dmarg, vouts)
        wall = time.perf_counter() - t0
        share = wall / n_subs
        self._c_fused.inc()

        pev = dev = vev = None
        vextra: List[Dict[str, Any]] = []
        lead = True
        with span("engine.post"):
            if preq is not None:
                pev = self._prefill_chunk_post(preq, C, ps, preal, logits_p,
                                               share)
                lead = False
                self.runtime.charge(pev)
            if batch:
                dev = self._decode_post(batch, schedule, dpos, nxt, share,
                                        dmarg)
                if not lead:
                    dev["fused"] = True
                lead = False
                self.runtime.charge(dev)
            for gi, (rows, fls, ring_idxs, slots, starts, n_pad) in enumerate(
                vstates
            ):
                commit_rows, n_match, commit_tok, vmarg = vouts[gi]
                ev = self._verify_event(rows, starts, n_pad, share,
                                        n_decodable, True)
                if not lead:
                    ev["fused"] = True
                lead = False
                self._verify_postlaunch(rows, fls, ev, ring_idxs, slots,
                                        starts, n_match, commit_tok,
                                        commit_rows, vmarg)
                if vev is None:
                    vev = ev
                else:
                    vextra.append(ev)
        return pev, dev, vev, vextra

    def _finish(self, req: Request) -> None:
        """Retire one request: committed-stream blocks go to the prefix
        cache (commit-aware insertion — ``_cacheable_stream``), the rest
        free, and the slot's dense rows are wiped for the next owner."""
        req.state = State.FINISHED
        req.finish_time = self._now
        self.running.remove(req)
        self._release_blocks(req, insert=True)
        self.pool.free(req.slot)
        self.statepool.note_release(req.slot)
        req.slot = -1
        self.finished.append(req)
        self._c_finished.inc()
        now = now_ns()
        if req.submit_ns >= 0:
            self._h_e2e.observe((now - req.submit_ns) / 1e9)
            if req.first_token_ns >= 0:
                self._h_ttft.observe(
                    (req.first_token_ns - req.submit_ns) / 1e9)
        if req.first_token_ns >= 0 and req.num_output > 1:
            self._h_tpot.observe(
                (now - req.first_token_ns) / 1e9 / (req.num_output - 1)
            )
        if self.mode == Mode.LLM42 and req.sampling.is_deterministic:
            self._h_acceptance.observe(req.accept_ema)
        tr = self.obs.tracer
        if tr.enabled:
            tr.instant("retire", rid=req.rid,
                       committed=req.num_output,
                       rollbacks=req.num_rollbacks,
                       verify_passes=req.num_verify_passes)
            tr.request_end(req.rid, now)

    def _retire(self) -> None:
        done = [r for r in self.running if r.finished() or (
            not r.sampling.is_deterministic and r.done_decoding()
        ) or (self.mode != Mode.LLM42 and r.done_decoding())]
        for r in done:
            # a det request must have no outstanding speculation at retirement
            if self.mode == Mode.LLM42 and r.sampling.is_deterministic and (
                r.candidates or r.pipeline
            ):
                continue
            self._finish(r)

    # ------------------------------------------------------------------
    # main loop
    # ------------------------------------------------------------------

    @_spanned("engine.step")
    def step(self) -> bool:
        """One scheduler iteration.  Returns False when fully drained.

        Order within an iteration: advance the stream clock, land due
        verdicts, retire, admit, plan, PREFILL chunk, DECODE, then VERIFY
        launch.  Verdicts land *before* retirement so a request whose final
        in-flight verdict is due this iteration retires this iteration —
        not one late (``finish_time`` off-by-one, drain one step longer).
        Decode-before-verify is a correctness requirement, not taste: the
        decode of a row being submitted this iteration re-feeds its last
        candidate, writing fast-path KV at the window's final position — a
        position the verify replay is about to repair and that no later
        replay will ever cover again.  Launching the verify afterwards lets
        its repair win; every later speculative write lands at positions >=
        the next window start, which the next replay rewrites.  The prefill
        chunk touches only its own (PREFILLING) slot, so it is
        order-independent.

        Under the paged in-place forward (``paged_attention=True`` on a
        paged pool) a deferring iteration runs its whole device side as
        ONE fused launch (``_fused_step``): same sub-pass bodies, same
        host bookkeeping order for every observable effect, one weight
        stream.  Archs without paged KV — and sync-verify plans — keep
        the legacy one-launch-per-role lanes below.

        Time accounting rides the dual-stream runtime: prefill and decode
        passes charge the main stream (serial — two launches on one
        stream), a deferred verify launches on the verify stream
        (``streams.DualClockRuntime``), and a sync verify (pause policy, or
        an ``AdaptivePolicy`` demotion) blocks the main stream.  An
        iteration that ran >= 2 passes still emits a single composite
        ``overlap`` event for log replay (``costmodel``).

        Each phase runs inside a host span (``engine.*``, ``repro.obs``):
        on the profiler's clock, and counted in the metrics registry."""
        span = self.obs.span
        self._now += 1
        self.runtime.begin_iteration()
        self._c_iters.inc()
        with span("engine.verdicts"):
            applied = self._apply_due_verdicts()
        with span("engine.retire"):
            self._retire()
        with span("engine.admit"):
            self._admit()
        if not self.running and not self.queue and not self.preempted:
            return False
        self.peak_running = max(self.peak_running, len(self.running))

        with span("engine.memory"):
            stalled = self._ensure_memory()
        with span("engine.plan"):
            view = self._view(stalled)
            plan = self.scheduler.plan(view)
        defer = self.scheduler.defers_verify and not plan.sync_verify
        pev = dev = vev = None
        vextra: List[Dict[str, Any]] = []
        if self._paged_fwd and defer:
            # fused mixed-batch step: prefill chunk + decode + every due
            # verify group under ONE launch (the tentpole path)
            pev, dev, vev, vextra = self._fused_step(plan, view)
        else:
            # legacy lanes: one launch per role, gathered KV views.  A
            # plan with chained-window repeats (multi-group expansion)
            # collapses to first occurrences — the legacy verify pass
            # launches one window per request per iteration.
            if plan.prefill is not None:
                pev = self._prefill_advance(plan.prefill, self._chunk_size())
                self.runtime.charge(pev)
            if plan.decode:
                batch = [r for r in plan.decode if not r.done_decoding()]
                if batch:
                    dev = self._decode_step(batch)
                    self.runtime.charge(dev)
            if plan.verify:
                rows, seen = [], set()
                for r in plan.verify:
                    if id(r) not in seen:
                        seen.add(id(r))
                        rows.append(r)
                vev = self._verify_step(
                    rows, defer=defer,
                    n_decodable=len(sched.decodable(view)),
                )
        self.runtime.end_iteration()

        subs = [("decode", dev), ("verify", vev), ("prefill", pev)]
        present = [(k, ev) for k, ev in subs if ev is not None]
        if present and (len(present) + len(vextra)) >= 2:
            comp = {
                "kind": "overlap", **dict(present),
                "wall": sum(ev["wall"] for _, ev in present)
                + sum(ev["wall"] for ev in vextra),
                "iter": self._now,
            }
            if vextra:
                # verify groups past the first (chained windows landing
                # the iteration they became due) ride along explicitly
                comp["verifies"] = vextra
            self.events.append(comp)
        elif present:
            self.events.append(present[0][1])
        if present or applied:
            return True
        return bool(self.running or self.queue or self.preempted)

    def _ensure_memory(self) -> Set[int]:
        """Pre-plan memory phase: grow every running request's block table
        to cover this iteration's worst-case writes (one decode token past
        the live sequence; a prefill chunk for the third lane), preempting
        LRU victims on exhaustion.  Returns the rids that could not be
        covered — they are hidden from the scheduler this iteration.
        Verify writes never exceed the decode bound for *real* content:
        window pad positions land past the table and are absorbed by the
        scratch block."""
        if not self.pool.paged:
            return set()
        stalled: Set[int] = set()
        for r in list(self.running):
            if r not in self.running:
                continue  # preempted by an earlier request's growth
            prefix = getattr(r, "_prefix_len", 0) or 0
            if r.state is State.PREFILLING:
                chunk = (
                    self._chunk_size() if self.chunked_prefill
                    else r.prefill_total - r.prefill_pos
                )
                end = min(r.prefill_pos + max(chunk, 1), r.prefill_total)
            else:
                seq = len(r.committed) + len(r.speculation)
                end = r.prompt_len + prefix + seq
            if not self._ensure_blocks(r, end):
                stalled.add(r.rid)
        return stalled

    def _apply_due_verdicts(self) -> bool:
        """Land in-flight verify results whose stream-clock deadline has
        been reached (``ready_at <= main-stream now``).  Groups launched at
        different times may land in the same iteration — and, with a
        per-launch latency schedule, in inverted launch order; splicing is
        per-request and strictly in submission order (``core.pipeline``
        applies only the FIFO front, however early later verdicts arrived),
        so landing order never moves a committed token.  A rollback splice
        — or one that leaves no surviving speculation — restores the slot's
        live recurrent state (and replay anchor) from the window's
        state-pool checkpoint."""
        applied = False
        now = self.runtime.now
        for r in self.running:
            for outcome in pipeline.apply_ready(r, self.window, now):
                applied = True
                self._note_splice(r, outcome)
                self.statepool.note_splice(r.slot, len(outcome.cascaded))
                if not self.statepool.active or (
                    r.finished() and not (r.pipeline or r.candidates)
                ):
                    # skip device work only when the request is about to
                    # retire with nothing left to verify — an EOS-finished
                    # request with a surviving tail still verifies it, and
                    # that replay needs the anchor advanced
                    continue
                if outcome.restore_state:
                    self.pool.data = self.statepool.restore(
                        self.pool.data, r.slot, outcome.record.ring_idx
                    )
                elif outcome.reanchor:
                    # FIFO drained but live state + speculation tail
                    # survive: only the replay anchor moves (the next
                    # window launches anchored, one token past the chained
                    # start state the last launch recorded)
                    self.statepool.reanchor(r.slot, outcome.record.ring_idx)
        return applied

    def run(self, max_iters: int = 100000) -> List[Request]:
        for _ in range(max_iters):
            if not self.step():
                break
        assert not (self.running or self.queue or self.preempted), (
            "engine did not drain"
        )
        return self.finished
