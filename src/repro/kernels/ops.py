"""Jit'd dispatch wrappers for the Pallas kernels.

Handles shape padding (block-multiple M/N, split-multiple K/S) and backend
selection: ``impl="pallas"`` runs the Pallas kernel (interpret=True on CPU,
compiled on TPU), ``impl="jnp"`` runs the pure-jnp reference semantics from
``repro.core.determinism`` (bit-identical contract, fast on CPU).  The
serving engine uses the jnp path on CPU; the Pallas path is the TPU-target
implementation validated against the same oracle.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.core.determinism import Schedule
from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _pallas_decode_attn
from repro.kernels.gemm_batch_invariant import gemm_batch_invariant as _pallas_bi
from repro.kernels.gemm_splitk import gemm_splitk as _pallas_splitk
from repro.kernels.paged_attention import (
    paged_attention as _pallas_paged_attn,
    paged_attention_fast as _pallas_paged_attn_fast,
)
from repro.kernels.rmsnorm import rmsnorm as _pallas_rmsnorm


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def matmul(
    x: jax.Array,
    w: jax.Array,
    schedule: Schedule,
    *,
    impl: str = "auto",
) -> jax.Array:
    """Schedule-aware GEMM.  x: (..., K), w: (K, N).

    ``schedule.tp_shards > 1`` decomposes K into the mesh chunks of the
    canonical TP reduction *above* the local split schedule: each chunk runs
    the local kernel on f32 inputs (one device's shard arithmetic), then the
    partials combine by the pinned balanced tree (commit path) or
    sequentially in combine_dtype (un-pinned fast path) — same semantics as
    the jnp reference in ``repro.core.determinism``.
    """
    if impl == "auto":
        impl = "pallas" if on_tpu() else "jnp"
    if impl == "jnp":
        from repro.core.determinism import matmul as jnp_matmul

        return jnp_matmul(x, w, schedule)

    K = x.shape[-1]
    if schedule.tp_shards > 1 and schedule.tp_shards <= K:
        from repro.core.determinism import _split_sizes, tree_combine

        local = schedule._replace(tp_shards=1, tp_pinned=False)
        parts = []
        start = 0
        for size in _split_sizes(K, schedule.tp_shards):
            xc = jax.lax.slice_in_dim(x, start, start + size, axis=x.ndim - 1)
            wc = jax.lax.slice_in_dim(w, start, start + size, axis=0)
            parts.append(
                matmul(
                    xc.astype(jnp.float32), wc.astype(jnp.float32),
                    local, impl=impl,
                )
            )
            start += size
        if schedule.tp_pinned:
            acc = tree_combine(parts)
        else:
            cd = jnp.dtype(schedule.combine_dtype)
            acc = None
            for p in parts:
                pc = p.astype(cd)
                acc = pc if acc is None else (acc + pc)
        return acc.astype(x.dtype)

    lead = x.shape[:-1]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    bm = 128 if M >= 128 else max(8, M)
    xp = _pad_to(x2, 0, bm)
    wp = _pad_to(w, 1, 128) if w.shape[1] % 128 else w
    splits = schedule.splits if K % max(schedule.splits, 1) == 0 else 1
    out = _pallas_splitk(
        xp, wp, splits=max(splits, 1), combine_dtype=schedule.combine_dtype,
        bm=bm, bn=min(128, wp.shape[1]), interpret=not on_tpu(),
    )
    return out[: M, : w.shape[1]].reshape(*lead, w.shape[1])


def matmul_batch_invariant(x: jax.Array, w: jax.Array, *, impl: str = "auto") -> jax.Array:
    if impl == "auto":
        impl = "pallas" if on_tpu() else "jnp"
    if impl == "jnp":
        return ref.gemm_batch_invariant(x, w)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    out = _pallas_bi(x2, w, interpret=not on_tpu())  # pads internally
    return out.reshape(*lead, w.shape[1])


def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lengths: jax.Array,
    schedule: Schedule,
    *,
    impl: str = "auto",
) -> jax.Array:
    if impl == "auto":
        impl = "pallas" if on_tpu() else "jnp"
    S = k.shape[1]
    splits = schedule.kv_splits if S % max(schedule.kv_splits, 1) == 0 else 1
    if impl == "jnp":
        return ref.decode_attention(
            q, k, v, lengths, max(splits, 1), schedule.combine_dtype
        )
    return _pallas_decode_attn(
        q, k, v, lengths, kv_splits=max(splits, 1),
        combine_dtype=schedule.combine_dtype, interpret=not on_tpu(),
    )


def paged_attention(
    q: jax.Array,         # (B, H, D)
    k_pool: jax.Array,    # (NB, KV, bs, Dp)
    v_pool: jax.Array,    # (NB, KV, bs, Dp)
    pos_pool: jax.Array,  # (NB, bs)
    tables: jax.Array,    # (B, nblk)
    q_pos: jax.Array,     # (B,)
    schedule: Schedule,
    *,
    null_bid: int | None = None,
    impl: str = "auto",
) -> jax.Array:
    """Paged decode/verify attention reading K/V through the block table.

    ``schedule.kv_splits == 1`` selects the commit-path kernel (fixed-shape
    single-pass softmax, lint-clean); any other split count selects the
    ``# det: fastpath`` flash-decode variant.  Splits that do not divide the
    table reach fall back to 1, mirroring ``decode_attention``.
    """
    if impl == "auto":
        impl = "pallas" if on_tpu() else "jnp"
    nblk = tables.shape[1]
    splits = schedule.kv_splits if nblk % max(schedule.kv_splits, 1) == 0 else 1
    splits = max(splits, 1)
    if impl == "jnp":
        return ref.paged_attention(
            q, k_pool, v_pool, pos_pool, tables, q_pos,
            null_bid=null_bid, kv_splits=splits,
            combine_dtype=schedule.combine_dtype,
        )
    if splits == 1:
        return _pallas_paged_attn(
            q, k_pool, v_pool, pos_pool, tables, q_pos,
            null_bid=null_bid, interpret=not on_tpu(),
        )
    return _pallas_paged_attn_fast(
        q, k_pool, v_pool, pos_pool, tables, q_pos,
        kv_splits=splits, combine_dtype=schedule.combine_dtype,
        null_bid=null_bid, interpret=not on_tpu(),
    )


def rmsnorm(
    x: jax.Array,
    scale: jax.Array,
    residual: jax.Array | None = None,
    *,
    eps: float = 1e-5,
    impl: str = "auto",
) -> jax.Array:
    if impl == "auto":
        impl = "pallas" if on_tpu() else "jnp"
    if impl == "jnp":
        return ref.rmsnorm(x, scale, eps, residual)
    lead = x.shape[:-1]
    D = x.shape[-1]
    x2 = x.reshape(-1, D)
    M = x2.shape[0]
    bm = 128 if M >= 128 else max(1, M)
    xp = _pad_to(x2, 0, bm)
    rp = _pad_to(residual.reshape(-1, D), 0, bm) if residual is not None else None
    out = _pallas_rmsnorm(xp, scale, rp, eps=eps, bm=bm, interpret=not on_tpu())
    return out[:M].reshape(*lead, D)
