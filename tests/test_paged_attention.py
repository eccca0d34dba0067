"""Paged-attention kernel + fused mixed-batch engine step.

Two layers of contract:

* kernel — ``kernels.paged_attention`` must match the pure-jnp oracle
  (``kernels/ref.py``) bitwise in interpret mode on randomized block
  tables, including ``-1`` (null-block) entries, and must be bitwise
  repeatable across invocations; the ``# det: fastpath`` split variant
  must match the oracle at the same split/combine configuration.
* engine — with ``paged_attention=True`` the engine runs the in-place
  paged forward and ONE fused mixed-batch launch per iteration; committed
  streams of deterministic requests must be bitwise identical to the
  legacy gather/scatter path across block sizes, schedulers and
  speculation depths, and the fused composite events must carry the
  structure the cost model prices (lead pays the weight stream, followers
  are marked ``fused``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.determinism import Mode, ReductionPolicy
from repro.kernels import paged_attention as pk
from repro.kernels import ref
from repro.models import init_params
from repro.serving.engine import Engine
from repro.serving.request import Request, SamplingParams
from repro.serving.scheduler import (
    AdaptivePolicy,
    OverlapPolicy,
    PauseDecodePolicy,
)

DRIFTY = ReductionPolicy(
    thresholds=((2, 16), (4, 8), (16, 4)), combine_dtype="bfloat16"
)

_MODELS = {}


def _model(arch="llama3-8b"):
    if arch not in _MODELS:
        cfg = get_smoke_config(arch)
        _MODELS[arch] = (cfg, init_params(cfg, jax.random.key(0)))
    return _MODELS[arch]


# ----------------------------------------------------------------------
# kernel vs oracle
# ----------------------------------------------------------------------


def _rand_problem(seed, *, B=3, H=4, KV=2, D=8, NB=20, bs=4, nblk=5,
                  dtype=jnp.float32, Dp=None):
    """Random head-major pool + tables; the last two pool blocks are
    null/scratch.  ``Dp > D`` pads the stored head dim with junk lanes
    that every reader must ignore."""
    rng = np.random.default_rng(seed)
    null_bid, scratch_bid = NB - 2, NB - 1
    Dp = D if Dp is None else Dp
    q = jnp.asarray(rng.standard_normal((B, H, D)), dtype)
    k = jnp.asarray(rng.standard_normal((NB, KV, bs, Dp)), dtype)
    v = jnp.asarray(rng.standard_normal((NB, KV, bs, Dp)), dtype)
    # null block: positions -1 (always masked), zero K/V
    k = k.at[null_bid].set(0.0)
    v = v.at[null_bid].set(0.0)

    pos = np.full((NB, bs), -1, np.int32)
    tables = np.full((B, nblk), -1, np.int32)
    real = list(rng.permutation(null_bid))  # distinct real block ids
    q_pos = np.zeros((B,), np.int32)
    for b in range(B):
        n_alloc = int(rng.integers(1, nblk + 1))  # rest stay -1 (null reads)
        length = int(rng.integers((n_alloc - 1) * bs + 1, n_alloc * bs + 1))
        for j in range(n_alloc):
            bid = real.pop()
            tables[b, j] = bid
            fill = min(bs, length - j * bs)
            pos[bid, :fill] = np.arange(j * bs, j * bs + fill)
        q_pos[b] = length - 1
    return (q, k, v, jnp.asarray(pos), jnp.asarray(tables),
            jnp.asarray(q_pos), null_bid)


class TestPagedKernel:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_commit_kernel_matches_oracle_bitwise(self, seed):
        q, k, v, pos, tab, qp, null_bid = _rand_problem(seed)
        got = pk.paged_attention(q, k, v, pos, tab, qp, null_bid=null_bid)
        want = ref.paged_attention(q, k, v, pos, tab, qp, null_bid=null_bid)
        assert jnp.array_equal(got, want), f"seed={seed}"

    def test_null_block_reads_are_masked(self):
        """Rows whose tables are mostly -1 read the null block; those
        positions are -1 and must contribute exactly nothing."""
        q, k, v, pos, tab, qp, null_bid = _rand_problem(7, nblk=6)
        got = pk.paged_attention(q, k, v, pos, tab, qp, null_bid=null_bid)
        # poison the null block's K/V: masked reads must not see it
        k2 = k.at[null_bid].set(1e4)
        v2 = v.at[null_bid].set(1e4)
        got2 = pk.paged_attention(q, k2, v2, pos, tab, qp, null_bid=null_bid)
        assert jnp.array_equal(got, got2)
        assert bool(jnp.all(jnp.isfinite(got)))

    def test_commit_kernel_bitwise_repeatable(self):
        q, k, v, pos, tab, qp, null_bid = _rand_problem(3)
        a = pk.paged_attention(q, k, v, pos, tab, qp, null_bid=null_bid)
        b = pk.paged_attention(q, k, v, pos, tab, qp, null_bid=null_bid)
        assert jnp.array_equal(a, b)

    @pytest.mark.parametrize("splits,cd,tol", [
        # f32 combine: kernel and oracle run the same tree tightly; bf16
        # combine rounds at different points (scratch stays f32 on-chip),
        # so agreement is only to bf16 precision
        (2, "float32", 1e-5),
        (4, "bfloat16", 2e-2),
    ])
    def test_fastpath_matches_split_oracle(self, splits, cd, tol):
        q, k, v, pos, tab, qp, null_bid = _rand_problem(11, nblk=4)
        got = pk.paged_attention_fast(
            q, k, v, pos, tab, qp, kv_splits=splits, combine_dtype=cd,
            null_bid=null_bid,
        )
        want = ref.paged_attention(
            q, k, v, pos, tab, qp, kv_splits=splits, combine_dtype=cd,
            null_bid=null_bid,
        )
        assert jnp.allclose(got, want, atol=tol, rtol=tol)

    def test_fastpath_split_count_changes_result(self):
        """Sanity that the fast path really is schedule-dependent — the
        reason it carries ``# det: fastpath`` instead of a proof."""
        q, k, v, pos, tab, qp, null_bid = _rand_problem(5, nblk=4)
        a = pk.paged_attention_fast(
            q, k, v, pos, tab, qp, kv_splits=1, combine_dtype="bfloat16",
            null_bid=null_bid,
        )
        b = pk.paged_attention_fast(
            q, k, v, pos, tab, qp, kv_splits=4, combine_dtype="bfloat16",
            null_bid=null_bid,
        )
        assert not jnp.array_equal(a, b)


# ----------------------------------------------------------------------
# engine: paged/fused vs legacy gather — bitwise identity sweep
# ----------------------------------------------------------------------

SCHEDULERS = {
    "pause": PauseDecodePolicy,
    "overlap": OverlapPolicy,
    "adaptive": AdaptivePolicy,
}


def _reqs(cfg, det, max_new=12):
    out = []
    for i in range(4):
        tail = [(5 * i + j) % cfg.vocab_size for j in range(9)]
        out.append(Request(
            rid=i, prompt=tail,
            sampling=SamplingParams(
                max_new_tokens=max_new, is_deterministic=(i in det),
                seed=70 + i,
            ),
        ))
    return out


def _run(cfg, params, *, paged, scheduler="overlap", block_size=16,
         spec_depth=1):
    eng = Engine(
        cfg, params, mode=Mode.LLM42, policy=DRIFTY, window=5, group=2,
        max_batch=8, capacity=128, scheduler=SCHEDULERS[scheduler](),
        block_size=block_size, spec_depth=spec_depth, paged_attention=paged,
    )
    det = {0, 2}
    for r in _reqs(cfg, det):
        eng.submit(r)
    it = 0
    while eng.step():
        it += 1
        assert it < 5000, "engine did not drain"
    done = {r.rid: r for r in eng.finished}
    return {rid: done[rid].committed for rid in det}, eng


class TestFusedStepBitwiseIdentity:
    @pytest.mark.parametrize("scheduler", sorted(SCHEDULERS))
    @pytest.mark.parametrize("spec_depth", [1, 4])
    def test_scheduler_depth_sweep(self, scheduler, spec_depth):
        cfg, params = _model()
        base, _ = _run(cfg, params, paged=False, scheduler=scheduler,
                       spec_depth=spec_depth)
        got, eng = _run(cfg, params, paged=True, scheduler=scheduler,
                        spec_depth=spec_depth)
        assert got == base, (scheduler, spec_depth)
        assert eng._paged_fwd

    @pytest.mark.parametrize("block_size", [8, 64])
    def test_block_size_sweep(self, block_size):
        cfg, params = _model()
        base, _ = _run(cfg, params, paged=False, block_size=block_size)
        got, _ = _run(cfg, params, paged=True, block_size=block_size)
        assert got == base, block_size

    def test_lane_padded_pool_identity(self, monkeypatch):
        """The TPU pool layout (head dim padded to 128 lanes) commits the
        same streams as the unpadded CPU layout: pad lanes are written as
        zeros and never read."""
        from repro.serving import blockpool

        cfg, params = _model()
        base, _ = _run(cfg, params, paged=True)
        monkeypatch.setattr(blockpool, "default_lanes", lambda: 128)
        got, eng = _run(cfg, params, paged=True)
        k = next(
            leaf for leaf, d in zip(
                jax.tree_util.tree_leaves(eng.pool.data),
                jax.tree_util.tree_leaves(eng.pool.layout.axes),
            ) if d.head_dim
        )
        assert k.shape[-1] == 128 and cfg.hd < 128
        assert got == base

    def test_recurrent_arch_identity(self):
        """Hybrid (attn + mamba + MoE) engine: the fused step threads the
        state-pool anchor through the same launch."""
        cfg, params = _model("jamba-1.5-large-398b")
        base, _ = _run(cfg, params, paged=False)
        got, _ = _run(cfg, params, paged=True)
        assert got == base


class TestFusedStepStructure:
    def test_one_fused_launch_per_mixed_iteration(self):
        """Overlap iterations on the paged engine are ONE launch: exactly
        one sub-pass (the lead) pays the weight stream, every other
        sub-pass is marked ``fused``."""
        cfg, params = _model()
        _, eng = _run(cfg, params, paged=True, scheduler="overlap")
        ov = [e for e in eng.events if e.get("kind") == "overlap"]
        assert ov, "no overlapped iterations at all"
        saw_fused = False
        for e in ov:
            subs = [e[k] for k in ("prefill", "decode", "verify") if k in e]
            subs += list(e.get("verifies", ()))
            leads = [s for s in subs if not s.get("fused")]
            assert len(leads) == 1, e
            saw_fused |= len(subs) > 1
        assert saw_fused

    def test_legacy_engine_never_marks_fused(self):
        cfg, params = _model()
        _, eng = _run(cfg, params, paged=False, scheduler="overlap")
        from repro.serving.costmodel import flatten_events
        assert not any(e.get("fused") for e in flatten_events(eng.events))

    def test_multi_group_iteration_emits_verifies(self):
        """With spec_depth > 1 the scheduler may drain several due windows
        in one iteration; extra groups ride the composite event's
        ``verifies`` list and the cost model prices them."""
        cfg, params = _model()
        _, eng = _run(cfg, params, paged=True, scheduler="overlap",
                      spec_depth=4)
        from repro.serving import costmodel
        ov = [e for e in eng.events if e.get("kind") == "overlap"]
        assert ov
        multi = [e for e in ov if e.get("verifies")]
        for e in multi:
            for v in e["verifies"]:
                assert v["kind"] == "verify"
            # extra groups serialize on the verify stream: pricing the
            # composite must strictly exceed pricing it without them
            bare = {k: v for k, v in e.items() if k != "verifies"}
            t_with = costmodel.step_time(cfg, e)
            t_without = costmodel.step_time(cfg, bare)
            assert t_with > t_without
        # and the flattened view exposes them as leaf verify events
        flat = costmodel.flatten_events(eng.events)
        n_groups = sum(1 for e in flat if e.get("kind") == "verify")
        n_inline = sum(1 for e in eng.events if e.get("kind") == "verify")
        n_in_comp = sum(
            (1 if "verify" in e else 0) + len(e.get("verifies", ()))
            for e in ov
        )
        assert n_groups == n_inline + n_in_comp
