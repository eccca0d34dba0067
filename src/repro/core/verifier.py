"""Fixed-shape grouped verification pass (paper §4.2–4.3).

``make_verify_fn(cfg, G, W)`` builds a jitted function that replays
G requests × W tokens in ONE forward pass of a *fixed* (G, W) shape under
``CANONICAL_MESH_SCHEDULE`` (== ``VERIFY_SCHEDULE``).  Fixed shape + fixed
schedule ⇒ shape-consistent reductions (O2) ⇒ the replay is bitwise
reproducible across runs, making it the deterministic reference execution.

The schedule is pinned along BOTH axes that change reduction geometry:

* batch shape — the (G, W) window is padded to fixed shape, so no request's
  tokens ever see a batch-dependent split count;
* mesh shape — every GEMM reduces K through the canonical TP decomposition
  (``tp_shards`` f32 partials, balanced-tree f32 combine), which a width-d
  ``model`` mesh realizes bitwise for every power-of-two d | tp_shards
  (``distributed.sharding.tp_matmul``).  The fast path may run on whatever
  mesh the deployment chose; the verify replay's partial-sum tree never
  follows it, so a token committed on TP=1 is the token committed on
  TP=2/4.

Per row the pass:
  inputs  = [conditioning_token, cand_0, …, cand_{W-2}]    (padded to W)
  outputs = verifier tokens v_0 … v_{W-1}
  n_match = length of the leading run where v_j == cand_j (among the
            row's real candidates)
  commit  = cand_0 … cand_{n_match-1}  plus  v_{n_match}   (≥ 1 token ⇒
            guaranteed forward progress, paper §4.2)

The conditioning token is ``committed[-1]`` for an *anchored* window (empty
in-flight FIFO — the paper's case) or the previous in-flight window's last
candidate for a *chained* window (multi-window pipeline, ``core.pipeline``):
the replay context of window k+1 is the committed prefix plus windows
1..k's candidates verbatim, which is exactly the reference stream when
those windows fully match — and is discarded by cascade invalidation when
they don't.

State repair:
  * attention KV — the verifier's own cache writes overwrite the tainted
    fast-path entries for all W window positions (paper: "the verifier
    replaces the KV cache entries generated by the decode phase with its
    own").  Entries beyond the commit point hold replay values of rejected
    candidates; they are shadowed by the position mask until the resumed
    decode overwrites them, so truncation is pointer-free.  Windows of one
    request replay in submission order, so a chained window reads the
    verifier KV its predecessor just wrote.
  * recurrent state (mamba/rwkv) — cannot be truncated; the pass collects
    the state after EVERY window position and routes two selections through
    the double-buffered state pool (``serving.statepool``): the state at
    index ``n_match`` (the window's rollback/commit checkpoint, written to
    a ring buffer by the engine) and the state *before the last input*
    (the replay anchor the NEXT chained window starts from).  The live
    pool state is never written at launch time — that is what lets the
    fast path keep speculating past submitted windows on ssm/hybrid archs
    instead of capping depth at one (beyond-paper, DESIGN.md §4).

Padding rows (when fewer than G requests are ready) write to the pool's
scratch slot — and, under the paged KV layout (``serving.blockpool``),
carry an all-empty block table, so their view reads the frozen null block
and their writes are absorbed by the scratch block.  The fixed shape never
costs correctness.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.core.determinism import (
    CANONICAL_MESH_SCHEDULE, CANONICAL_TP_SHARDS, VERIFY_SCHEDULE,
)

# The commit path replays under the canonical mesh-reduction schedule: the
# TP partial-sum tree must be pinned, or a mesh change would silently change
# committed streams.  Guarded here (import time) rather than per-call.
assert VERIFY_SCHEDULE.tp_pinned, "verify schedule must pin the TP tree"
assert VERIFY_SCHEDULE.tp_shards == CANONICAL_TP_SHARDS
assert CANONICAL_MESH_SCHEDULE == VERIFY_SCHEDULE
from repro.models.base import ModelConfig
from repro.models.layers import PagedView
from repro.models.transformer import forward
from repro.serving import kv_cache, statepool
from repro.serving.blockpool import Layout
from repro.serving.sampler import sample_window, top2_margin


def _match_and_commit(v_tokens: jax.Array, cand: jax.Array,
                      cand_len: jax.Array, W: int):
    cmp = (v_tokens[:, : W - 1] == cand).astype(jnp.int32)
    valid = (jnp.arange(W - 1)[None, :] < cand_len[:, None]).astype(jnp.int32)
    n_match = jnp.sum(jnp.cumprod(cmp * valid, axis=1), axis=1)  # (G,)
    commit_tok = jnp.take_along_axis(v_tokens, n_match[:, None], axis=1)[:, 0]
    return n_match, commit_tok


def make_verify_body(
    cfg: ModelConfig, G: int, W: int, layout: Layout, paged: bool = False
):
    """Build the UNJITTED grouped-verification body for fixed (G, W).

    The body carries the whole verification semantics; ``make_verify_fn``
    below is just ``jax.jit`` around it.  Keeping the body separate lets
    the engine's fused mixed-batch step compose it with the decode and
    prefill-chunk bodies under ONE jit (one launch, one weight stream)
    without re-tracing the verification logic.

    ``paged=False`` gathers per-row contiguous KV views through the block
    tables (the legacy copy path).  ``paged=True`` passes the block pool
    through whole: the forward reads and repairs full-attention KV *in
    place* through ``tables`` (``models.layers.attention_paged``), so the
    per-iteration gather/scatter copy never materializes.  Both modes run
    the identical universal schedule over views whose content is
    position-for-position identical, so committed streams are bitwise
    equal between them.
    """
    collect = statepool.has_recurrent_state(cfg)
    pview = (
        PagedView(layout.block_size, layout.null_bid, layout.scratch_bid)
        if paged
        else None
    )

    def _fwd(params, pool, slots, tables, start_pos, inputs, collect_states):
        if paged:
            cache = kv_cache.gather_mixed(pool, layout, slots)
        else:
            cache = kv_cache.gather(pool, layout, slots, tables)
        logits, new_cache, per_pos = forward(
            params, cfg, inputs,
            cache=cache, start_pos=start_pos, schedule=VERIFY_SCHEDULE,
            collect_states=collect_states,
            tables=tables if paged else None, paged=pview,
        )
        return cache, logits, new_cache, per_pos

    def _writeback(pool, slots, tables, new_cache):
        if paged:
            return kv_cache.scatter_mixed(pool, layout, slots, new_cache)
        return kv_cache.scatter(pool, layout, slots, tables, new_cache)

    if not collect:

        @jax.named_scope("verify")
        def verify_step(
            params: Dict,
            pool: Any,
            slots: jax.Array,  # (G,) pool slots (scratch slot for pad rows)
            tables: jax.Array,  # (G, nblk) block tables (-1 rows for pads)
            start_pos: jax.Array,  # (G,) abs position of inputs[:, 0]
            inputs: jax.Array,  # (G, W) [conditioning, candidates..., pad]
            cand: jax.Array,  # (G, W-1) candidate tokens (pad = -1)
            cand_len: jax.Array,  # (G,) number of real candidates
            seeds: jax.Array,  # (G,)
            temps: jax.Array,  # (G,)
            out_base: jax.Array,  # (G,) output index of v_0
            top_ks: jax.Array,  # (G,)
        ):
            _, logits, new_cache, _ = _fwd(
                params, pool, slots, tables, start_pos, inputs, False
            )
            v_tokens = sample_window(logits, seeds, out_base, temps, top_ks)
            n_match, commit_tok = _match_and_commit(v_tokens, cand, cand_len, W)
            # (G, W) top-1/top-2 margin per window position: the audit
            # log's per-token provenance field.  Computed unconditionally
            # so the device program is identical with auditing on or off
            # (observer-effect freedom); host conversion is gated instead.
            margins = top2_margin(logits)
            pool = _writeback(pool, slots, tables, new_cache)
            return pool, n_match, commit_tok, v_tokens, margins

        return verify_step

    @jax.named_scope("verify")
    def verify_step(
        params: Dict,
        pool: Any,
        anchor: Any,  # state-pool replay-anchor tree
        slots: jax.Array,
        tables: jax.Array,
        start_pos: jax.Array,
        inputs: jax.Array,
        cand: jax.Array,
        cand_len: jax.Array,
        seeds: jax.Array,
        temps: jax.Array,
        out_base: jax.Array,
        top_ks: jax.Array,
    ):
        if paged:
            cache = kv_cache.gather_mixed(pool, layout, slots)
        else:
            cache = kv_cache.gather(pool, layout, slots, tables)
        live_rows = statepool.rows_from_cache(cache)
        # replay starts from the anchor: state after everything preceding
        # the window, minus its conditioning token (re-consumed first)
        cache = statepool.merge_rows(
            cache, statepool.gather_rows(anchor, slots)
        )
        logits, new_cache, per_pos = forward(
            params, cfg, inputs,
            cache=cache, start_pos=start_pos, schedule=VERIFY_SCHEDULE,
            collect_states=True,
            tables=tables if paged else None, paged=pview,
        )
        v_tokens = sample_window(logits, seeds, out_base, temps, top_ks)
        n_match, commit_tok = _match_and_commit(v_tokens, cand, cand_len, W)
        margins = top2_margin(logits)  # audit provenance, as above

        # per_pos[j] = state after consuming inputs[j]
        commit_rows = statepool.select_index(per_pos, n_match)
        resume_rows = statepool.select_index(
            per_pos, jnp.maximum(cand_len - 1, 0)
        )
        anchor = statepool.scatter_rows(anchor, slots, resume_rows)
        # KV repair reaches the live pool; recurrent state does NOT — the
        # fast path may be speculating past this window right now
        new_cache = statepool.merge_rows(new_cache, live_rows)
        pool = _writeback(pool, slots, tables, new_cache)
        return pool, anchor, commit_rows, n_match, commit_tok, v_tokens, margins

    return verify_step


# det: commit-path
def make_verify_fn(
    cfg: ModelConfig, G: int, W: int, layout: Layout, paged: bool = False
):
    """Build the jitted grouped-verification step for fixed (G, W).

    ``layout`` is the engine pool's paged-KV layout: rows address their
    full-attention KV through per-row block ``tables`` (G,
    blocks_per_table), everything else through ``slots``.  Attention-only
    archs return ``(pool, n_match, commit_tok, v_tokens, margins)`` — KV
    repair is positional, no state buffering needed.  Recurrent/hybrid
    archs take the state pool's replay-anchor tree as an extra argument
    and return
    ``(pool, anchor, commit_rows, n_match, commit_tok, v_tokens, margins)``:
    attention KV is gathered from (and repaired into) the live pool, the
    recurrent replay starts from the anchor, the anchor advances to the
    next chained window's start state, ``commit_rows`` carries each row's
    checkpoint for the state-pool ring — and the live recurrent state is
    scattered back UNCHANGED.

    ``paged=True`` reads/repairs full-attention KV in place through the
    block tables instead of round-tripping a gathered view (see
    :func:`make_verify_body`); committed streams are bitwise identical
    either way.
    """
    return jax.jit(make_verify_body(cfg, G, W, layout, paged))
