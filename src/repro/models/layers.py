"""Shared layers: RMSNorm, RoPE, GQA attention (cached + train), SwiGLU, MoE.

Every matrix multiply routes through ``repro.core.determinism.matmul`` with an
explicit ``Schedule``, so the reduction tree of the entire forward pass is a
function of the schedule — which the fast path derives from the dynamic batch
size (the paper's non-determinism mechanism) and the verifier pins.

Cached attention uses a uniform cache layout:
    {"k": (B, C, KV, HD), "v": (B, C, KV, HD), "pos": (B, C) int32}
where C is the cache capacity (max_seq_len for full attention, the window
size for sliding-window attention — a ring buffer).  ``pos`` records the
absolute position held in each slot (-1 = empty); masking is computed from
``pos`` so ring-buffer wraparound needs no special cases.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.determinism import Schedule, matmul, segment_reduce_sum
from repro.kernels import ops

F32 = jnp.float32


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float, schedule: Schedule) -> jax.Array:
    """RMSNorm with a schedule-dependent feature reduction (paper Fig. 4b)."""
    ss = segment_reduce_sum(x * x, axis=-1, schedule=schedule)
    var = ss / x.shape[-1]
    inv = jax.lax.rsqrt(var + eps)
    return (x.astype(F32) * inv[..., None]).astype(x.dtype) * scale


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Apply rotary embedding.  x: (..., T, H, D); positions: (..., T)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=F32) * (jnp.log(theta) / half))
    ang = positions[..., None].astype(F32) * freqs  # (..., T, half)
    cos = jnp.cos(ang)[..., None, :]  # (..., T, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(F32), x[..., half : 2 * half].astype(F32)
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    out = jnp.concatenate([out1, out2, x[..., 2 * half :].astype(F32)], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def _qkv(p: Dict, cfg, x: jax.Array, schedule: Schedule):
    """Project to q,k,v heads.  x: (B, T, D)."""
    B, T, _ = x.shape
    q = matmul(x, p["wq"], schedule)
    k = matmul(x, p["wk"], schedule)
    v = matmul(x, p["wv"], schedule)
    if cfg.use_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, T, cfg.num_heads, cfg.hd)
    k = k.reshape(B, T, cfg.num_kv_heads, cfg.hd)
    v = v.reshape(B, T, cfg.num_kv_heads, cfg.hd)
    return q, k, v


def _softmax_attend(
    q: jax.Array,  # (B, T, H, D) f32, pre-scaled
    k: jax.Array,  # (B, S, KV, D)
    v: jax.Array,  # (B, S, KV, D)
    mask: jax.Array,  # (B, T, S) bool or broadcastable
    schedule: Schedule,
    logit_softcap: float = 0.0,
) -> jax.Array:
    """GQA attention with schedule-dependent KV-split softmax combine.

    kv_splits == 1: single-pass softmax over the full key axis in f32 (the
    verifier's / batch-invariant schedule).  kv_splits == S: the key axis is
    chunked (FlashDecoding-style sequence parallelism); each chunk computes a
    local (max, exp-sum, weighted value) triple in f32, and chunk triples are
    combined *sequentially in combine_dtype* — a different reduction tree,
    hence potentially different low-order bits (paper §4.4 "Attention").
    """
    B, T, H, D = q.shape
    S = k.shape[1]
    KV = k.shape[2]
    G = H // KV  # query heads per kv head
    qg = q.reshape(B, T, KV, G, D).astype(F32)
    kf = k.astype(F32)
    vf = v.astype(F32)

    def scores_for(kc):  # kc: (B, Sc, KV, D) -> (B, T, KV, G, Sc)
        s = jnp.einsum("btkgd,bskd->btkgs", qg, kc, precision=jax.lax.Precision.HIGHEST)
        if logit_softcap > 0.0:
            s = jnp.tanh(s / logit_softcap) * logit_softcap
        return s

    splits = schedule.kv_splits
    if splits <= 1 or splits > S:
        s = scores_for(kf)
        s = jnp.where(mask[:, :, None, None, :], s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        m = jnp.maximum(m, -1e30)  # rows with no valid key
        e = jnp.exp(s - m)
        denom = jnp.sum(e, axis=-1)
        out = jnp.einsum("btkgs,bskd->btkgd", e, vf, precision=jax.lax.Precision.HIGHEST)
        out = out / jnp.maximum(denom, 1e-30)[..., None]
        return out.reshape(B, T, H, D)

    # chunked (split-KV) path
    cd = jnp.dtype(schedule.combine_dtype)
    base, rem = divmod(S, splits)
    sizes = [base + (1 if i < rem else 0) for i in range(splits)]
    m_acc = None  # (B,T,KV,G)
    d_acc = None
    o_acc = None  # (B,T,KV,G,D)
    start = 0
    for size in sizes:
        kc = jax.lax.slice_in_dim(kf, start, start + size, axis=1)
        vc = jax.lax.slice_in_dim(vf, start, start + size, axis=1)
        mc = jax.lax.slice_in_dim(mask, start, start + size, axis=2)
        s = scores_for(kc)
        s = jnp.where(mc[:, :, None, None, :], s, -jnp.inf)
        m_c = jnp.maximum(jnp.max(s, axis=-1), -1e30)
        e = jnp.exp(s - m_c[..., None])
        d_c = jnp.sum(e, axis=-1)
        o_c = jnp.einsum("btkgs,bskd->btkgd", e, vc, precision=jax.lax.Precision.HIGHEST)
        if m_acc is None:
            m_acc, d_acc, o_acc = m_c, d_c.astype(cd), o_c.astype(cd)
        else:
            m_new = jnp.maximum(m_acc, m_c)
            a1 = jnp.exp(m_acc - m_new)
            a2 = jnp.exp(m_c - m_new)
            d_acc = (a1 * d_acc.astype(F32) + a2 * d_c).astype(cd)
            o_acc = (
                a1[..., None] * o_acc.astype(F32) + a2[..., None] * o_c
            ).astype(cd)
            m_acc = m_new
        start += size
    out = o_acc.astype(F32) / jnp.maximum(d_acc.astype(F32), 1e-30)[..., None]
    return out.reshape(B, T, H, D)


#: above this many query rows, attention runs q-chunked (flash-style) so the
#: (B, T, S) score tensor is never materialized — essential for the 32k/4k
#: dry-run memory analysis and faithful to production TPU attention.
CHUNK_THRESHOLD = 2048
Q_CHUNK = 512


def _chunked_attend(
    q: jax.Array,  # (B, T, H, D) f32, pre-scaled + roped
    k: jax.Array,  # (B, S, KV, D)
    v: jax.Array,
    q_pos: jax.Array,  # (B, T) absolute positions
    k_pos: jax.Array,  # (B, S) absolute positions (-1 = invalid)
    schedule: Schedule,
    logit_softcap: float,
    window: int,
) -> jax.Array:
    """Query-chunked attention: lax.map over q chunks; per-chunk scores are
    (B, Q_CHUNK, S) — bounded VMEM/HBM footprint at any context length."""
    B, T, H, D = q.shape
    pad = (-T) % Q_CHUNK
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)), constant_values=-(10**9))
    n_chunks = q.shape[1] // Q_CHUNK
    qc = q.reshape(B, n_chunks, Q_CHUNK, H, D).transpose(1, 0, 2, 3, 4)
    pc = q_pos.reshape(B, n_chunks, Q_CHUNK).transpose(1, 0, 2)

    def one(args):
        q_i, p_i = args  # (B, Qc, H, D), (B, Qc)
        mask = (k_pos[:, None, :] >= 0) & (k_pos[:, None, :] <= p_i[:, :, None])
        if window > 0:
            mask = mask & (k_pos[:, None, :] > p_i[:, :, None] - window)
        return _softmax_attend(q_i, k, v, mask, schedule, logit_softcap)

    out = jax.lax.map(one, (qc, pc))  # (n_chunks, B, Qc, H, D)
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, -1, H, D)
    return out[:, :T]


def attention_train(
    p: Dict, cfg, x: jax.Array, schedule: Schedule, window: int = 0
) -> jax.Array:
    """Full-sequence causal attention (training / no cache).  x: (B, S, D)."""
    B, S, _ = x.shape
    q, k, v = _qkv(p, cfg, x, schedule)
    pos = jnp.arange(S)[None, :]
    q = rope(q, jnp.broadcast_to(pos, (B, S)), cfg.rope_theta)
    k = rope(k, jnp.broadcast_to(pos, (B, S)), cfg.rope_theta)
    q = q * (cfg.hd**-0.5)
    if S > CHUNK_THRESHOLD:
        pos_b = jnp.broadcast_to(pos, (B, S))
        out = _chunked_attend(
            q.astype(F32), k, v, pos_b, pos_b, schedule,
            cfg.logit_softcap, window,
        )
    else:
        qp = jnp.arange(S)[:, None]
        kp = jnp.arange(S)[None, :]
        mask = kp <= qp
        if window > 0:
            mask = mask & (kp > qp - window)
        mask = jnp.broadcast_to(mask[None], (B, S, S))
        out = _softmax_attend(q, k, v, mask, schedule, cfg.logit_softcap)
    return matmul(out.reshape(B, S, -1).astype(x.dtype), p["wo"], schedule)


def attention_cached(
    p: Dict,
    cfg,
    x: jax.Array,  # (B, W, D)
    cache: Dict,  # {"k","v": (B,C,KV,HD), "pos": (B,C)}
    start_pos: jax.Array,  # (B,) absolute position of x[:, 0]
    schedule: Schedule,
    window: int = 0,
) -> Tuple[jax.Array, Dict]:
    """Incremental attention: write W new tokens into the cache, attend.

    Works uniformly for prefill (W = prompt len), decode (W = 1) and
    verification (W = window).  The cache may be a ring buffer (C < max
    position): slots are addressed by ``abs_pos % C`` and masking uses the
    stored absolute ``pos`` so wraparound is handled naturally.
    """
    B, W, _ = x.shape
    C = cache["k"].shape[1]
    # Ring-buffer contract: a pass writing W positions must not overwrite
    # keys still inside any query's attention window:
    # capacity >= W + window - 1.  Callers chunk longer prefills
    # (Engine._prefill_sliding); full-attention caches have C >= max pos.
    need = W + (window - 1 if window > 0 else 0)
    assert need <= C, (
        f"pass of {W} tokens (+window {window}) exceeds cache capacity {C}; "
        f"chunk it")
    q, k_new, v_new = _qkv(p, cfg, x, schedule)
    abs_pos = start_pos[:, None] + jnp.arange(W)[None, :]  # (B, W)
    q = rope(q, abs_pos, cfg.rope_theta) * (cfg.hd**-0.5)
    k_new = rope(k_new, abs_pos, cfg.rope_theta)

    slots = abs_pos % C  # (B, W)
    b_idx = jnp.arange(B)[:, None]
    k_cache = cache["k"].at[b_idx, slots].set(k_new.astype(cache["k"].dtype))
    v_cache = cache["v"].at[b_idx, slots].set(v_new.astype(cache["v"].dtype))
    pos_cache = cache["pos"].at[b_idx, slots].set(abs_pos)

    if W > CHUNK_THRESHOLD:
        out = _chunked_attend(
            q.astype(F32), k_cache, v_cache, abs_pos, pos_cache, schedule,
            cfg.logit_softcap, window,
        )
    else:
        kp = pos_cache[:, None, :]  # (B, 1, C)
        qp = abs_pos[:, :, None]  # (B, W, 1)
        mask = (kp >= 0) & (kp <= qp)
        if window > 0:
            mask = mask & (kp > qp - window)
        out = _softmax_attend(q, k_cache, v_cache, mask, schedule, cfg.logit_softcap)
    out = matmul(out.reshape(B, W, -1).astype(x.dtype), p["wo"], schedule)
    return out, {"k": k_cache, "v": v_cache, "pos": pos_cache}


class PagedView(NamedTuple):
    """Static geometry of a paged KV pool, threaded into the forward pass."""

    block_size: int
    null_bid: int  # reads through -1 table entries land here (pos == -1)
    scratch_bid: int  # writes past the table land here (never read)


def attention_paged(
    p: Dict,
    cfg,
    x: jax.Array,  # (B, W, D)
    cache: Dict,  # {"k","v": (NB+2, KV, bs, HDp), "pos": (NB+2, bs)} pool-shaped
    tables: jax.Array,  # (B, nblk) int32 block ids, -1 = unallocated
    start_pos: jax.Array,  # (B,) absolute position of x[:, 0]
    schedule: Schedule,
    paged: PagedView,
) -> Tuple[jax.Array, Dict]:
    """Incremental attention reading/writing K/V *through the block table*.

    The pool leaves carry no batch axis and store each block head-major
    with the head dim padded to ``HDp`` lanes (``serving.blockpool``); each
    row's view is the concatenation of its table's blocks (``-1`` entries
    read the null block, whose positions are ``-1`` and therefore always
    masked).  Writes for the W new tokens go to
    ``tables[b, abs_pos // block_size]``; positions past the table (padded
    rows / padded window tails) are absorbed by the scratch block, which is
    never read.  Semantically — and bitwise — this equals gathering the
    view and running :func:`attention_cached` on it; the host-side gather
    copy is what disappears.
    """
    B, W, _ = x.shape
    bs = paged.block_size
    nblk = tables.shape[1]
    hd, hdp = cfg.hd, cache["k"].shape[-1]
    q, k_new, v_new = _qkv(p, cfg, x, schedule)
    abs_pos = start_pos[:, None] + jnp.arange(W)[None, :]  # (B, W)
    q = rope(q, abs_pos, cfg.rope_theta)
    k_new = rope(k_new, abs_pos, cfg.rope_theta)

    blk = abs_pos // bs  # (B, W)
    off = abs_pos % bs
    bid = jnp.take_along_axis(tables, jnp.clip(blk, 0, nblk - 1), axis=1)
    bid = jnp.where((bid < 0) | (blk >= nblk), paged.scratch_bid, bid)
    # (B, W, 1) x (KV,) x (B, W, 1) index -> one (block, head, slot) row each
    heads = jnp.arange(k_new.shape[2])
    at = (bid[..., None], heads, off[..., None])
    lanes = [(0, 0)] * 3 + [(0, hdp - hd)]
    k_cache = cache["k"].at[at].set(jnp.pad(k_new, lanes).astype(cache["k"].dtype))
    v_cache = cache["v"].at[at].set(jnp.pad(v_new, lanes).astype(cache["v"].dtype))
    pos_cache = cache["pos"].at[bid, off].set(abs_pos)

    if W == 1 and ops.on_tpu() and cfg.logit_softcap == 0:
        # single-token decode on TPU: the table-walking Pallas kernels
        # (commit single-pass vs `# det: fastpath` split variant, selected
        # by the schedule) DMA only the table's blocks from HBM — the
        # (B, nblk*bs, ...) view gather below never materializes.  The
        # dispatcher scales q by hd^-0.5 itself, so it gets the unscaled
        # roped q.
        out = ops.paged_attention(
            q[:, 0], k_cache, v_cache, pos_cache, tables, abs_pos[:, 0],
            schedule, null_bid=paged.null_bid,
        )
        out = matmul(out.reshape(B, W, -1).astype(x.dtype), p["wo"], schedule)
        return out, {"k": k_cache, "v": v_cache, "pos": pos_cache}

    q = q * (cfg.hd**-0.5)
    flat = jnp.where(tables < 0, paged.null_bid, tables)  # (B, nblk)

    def view(leaf):  # (B, nblk, KV, bs, HDp) -> (B, nblk*bs, KV, hd)
        v = jnp.swapaxes(leaf[flat][..., :hd], 2, 3)
        return v.reshape(B, nblk * bs, -1, hd)

    kp = pos_cache[flat].reshape(B, 1, nblk * bs)  # (B, 1, S)
    qp = abs_pos[:, :, None]  # (B, W, 1)
    mask = (kp >= 0) & (kp <= qp)
    out = _softmax_attend(q, view(k_cache), view(v_cache), mask, schedule,
                          cfg.logit_softcap)
    out = matmul(out.reshape(B, W, -1).astype(x.dtype), p["wo"], schedule)
    return out, {"k": k_cache, "v": v_cache, "pos": pos_cache}


def cross_attention(
    p: Dict,
    cfg,
    x: jax.Array,  # (B, W, D) decoder states
    enc_k: jax.Array,  # (B, Se, KV, HD) precomputed encoder keys
    enc_v: jax.Array,
    enc_mask: jax.Array,  # (B, Se) bool
    schedule: Schedule,
) -> jax.Array:
    B, W, _ = x.shape
    q = matmul(x, p["wq"], schedule).reshape(B, W, cfg.num_heads, cfg.hd)
    q = q * (cfg.hd**-0.5)
    mask = jnp.broadcast_to(enc_mask[:, None, :], (B, W, enc_k.shape[1]))
    out = _softmax_attend(q.astype(F32), enc_k, enc_v, mask, schedule)
    return matmul(out.reshape(B, W, -1).astype(x.dtype), p["wo"], schedule)


def encode_cross_kv(p: Dict, cfg, enc_out: jax.Array, schedule: Schedule):
    """Precompute cross-attention K/V from encoder output (per request)."""
    B, Se, _ = enc_out.shape
    k = matmul(enc_out, p["wk"], schedule).reshape(B, Se, cfg.num_kv_heads, cfg.hd)
    v = matmul(enc_out, p["wv"], schedule).reshape(B, Se, cfg.num_kv_heads, cfg.hd)
    return k, v


# ---------------------------------------------------------------------------
# feed-forward
# ---------------------------------------------------------------------------


def swiglu_ffn(p: Dict, x: jax.Array, schedule: Schedule) -> jax.Array:
    gate = matmul(x, p["wi_gate"], schedule)
    up = matmul(x, p["wi_up"], schedule)
    h = jax.nn.silu(gate.astype(F32)).astype(x.dtype) * up
    return matmul(h, p["wo"], schedule)


def moe_ffn(
    p: Dict, cfg, x: jax.Array, schedule: Schedule, capacity_factor: float = 1.25
) -> Tuple[jax.Array, Dict]:
    """Top-k MoE with sort-based dispatch and static expert capacity.

    Routing itself goes through a schedule-dependent matmul: the router's
    argmax can flip under different reduction trees, which is why MoE models
    are where the paper's O1 token flips are most likely (DESIGN.md §4).

    Returns (output, aux) where aux carries router load statistics.
    """
    orig_shape = x.shape
    d = orig_shape[-1]
    xt = x.reshape(-1, d)  # (T, d)
    T = xt.shape[0]
    E, K = cfg.num_experts, cfg.top_k

    logits = matmul(xt, p["router"], schedule).astype(F32)  # (T, E)
    gates, idx = jax.lax.top_k(logits, K)  # (T, K)
    gates = jax.nn.softmax(gates, axis=-1)

    if schedule.moe_no_drop:
        C = T  # worst case: every token routed to one expert — never drop
    else:
        C = max(int(T * K * capacity_factor / E + 0.999), 1)
        # pad capacity to a lane-friendly multiple when large
        if C > 8:
            C = (C + 7) // 8 * 8

    flat_e = idx.reshape(-1)  # (T*K,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    # position of each routed token within its expert bucket.  The bucket
    # starts are exact integer counts (#{assignments < e}, i.e. the 'left'
    # insertion index) computed by a fixed-structure reduction rather than
    # jnp.searchsorted, which lowers to a binary-search scan whose
    # ceil(log2(T*K)) trip count varies with the token count — a
    # batch-variant structure on the commit path
    starts = jnp.sum(
        (sorted_e[None, :] < jnp.arange(E)[:, None]).astype(jnp.int32), axis=1
    )
    pos_in_e = jnp.arange(T * K) - starts[sorted_e]
    keep = pos_in_e < C
    dest = jnp.where(keep, sorted_e * C + pos_in_e, E * C)  # overflow bucket

    token_idx = order // K  # which token each routed slot came from
    xin = xt[token_idx]  # (T*K, d)
    buckets = jnp.zeros((E * C + 1, d), xt.dtype).at[dest].set(
        jnp.where(keep[:, None], xin, 0)
    )
    buckets = buckets[: E * C].reshape(E, C, d)

    # expert computation — active FLOPs only: E * C * d * f per matmul
    gate_h = jnp.einsum(
        "ecd,edf->ecf", buckets.astype(F32), p["wi_gate"].astype(F32),
        precision=jax.lax.Precision.HIGHEST,
    )
    up_h = jnp.einsum(
        "ecd,edf->ecf", buckets.astype(F32), p["wi_up"].astype(F32),
        precision=jax.lax.Precision.HIGHEST,
    )
    h = jax.nn.silu(gate_h) * up_h
    yb = jnp.einsum(
        "ecf,efd->ecd", h, p["wo"].astype(F32),
        precision=jax.lax.Precision.HIGHEST,
    ).astype(xt.dtype)

    # gather back: routed slot -> (token, k)
    yb_flat = jnp.concatenate([yb.reshape(E * C, d), jnp.zeros((1, d), xt.dtype)], 0)
    y_routed = yb_flat[dest]  # (T*K, d); dropped slots read the zero row
    inv = jnp.argsort(order, stable=True)
    y_per_k = y_routed[inv].reshape(T, K, d)
    y = jnp.sum(y_per_k.astype(F32) * gates[..., None], axis=1).astype(xt.dtype)

    load = jnp.bincount(flat_e, length=E) / (T * K)
    importance = jnp.mean(jax.nn.softmax(logits, -1), axis=0)
    aux = {
        "router_load": load,
        "aux_loss": E * jnp.sum(load * importance),
        "dropped_frac": 1.0 - jnp.mean(keep.astype(F32)),
    }
    return y.reshape(orig_shape), aux
