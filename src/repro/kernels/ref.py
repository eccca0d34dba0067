"""Pure-jnp oracles for every Pallas kernel in this package.

These define the *numerical contract* each kernel must satisfy bitwise
(or to tight tolerance) in interpret mode.  The split-K / split-KV refs are
the same reduction-tree semantics as ``repro.core.determinism`` — the model
zoo's jnp fallback path — so kernel == ref == model numerics.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.determinism import Schedule, matmul as _sched_matmul

F32 = jnp.float32


def gemm_splitk(x: jax.Array, w: jax.Array, splits: int,
                combine_dtype: str = "float32") -> jax.Array:
    """Split-K GEMM oracle: per-chunk f32 reduction, sequential combine in
    combine_dtype.  x: (M, K), w: (K, N)."""
    return _sched_matmul(x, w, Schedule(splits=splits, combine_dtype=combine_dtype))


def gemm_batch_invariant(x: jax.Array, w: jax.Array) -> jax.Array:
    """Universal-schedule GEMM oracle: one f32 reduction pass, no splits."""
    return _sched_matmul(x, w, Schedule(splits=1))


def decode_attention(
    q: jax.Array,        # (B, H, D)
    k: jax.Array,        # (B, S, KV, D)
    v: jax.Array,        # (B, S, KV, D)
    lengths: jax.Array,  # (B,) number of valid cache positions
    kv_splits: int,
    combine_dtype: str = "float32",
) -> jax.Array:
    """Flash-decode oracle: chunked softmax with LSE combine in combine_dtype."""
    B, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = (q.reshape(B, KV, G, D) * (D**-0.5)).astype(F32)
    kf, vf = k.astype(F32), v.astype(F32)
    pos = jnp.arange(S)[None, :]  # (1, S)
    valid = pos < lengths[:, None]  # (B, S)

    cd = jnp.dtype(combine_dtype)
    base, rem = divmod(S, kv_splits)
    sizes = [base + (1 if i < rem else 0) for i in range(kv_splits)]
    m_acc = d_acc = o_acc = None
    start = 0
    for size in sizes:
        kc = jax.lax.slice_in_dim(kf, start, start + size, axis=1)
        vc = jax.lax.slice_in_dim(vf, start, start + size, axis=1)
        mc = jax.lax.slice_in_dim(valid, start, start + size, axis=1)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, kc,
                       precision=jax.lax.Precision.HIGHEST)
        s = jnp.where(mc[:, None, None, :], s, -jnp.inf)
        m_c = jnp.maximum(jnp.max(s, axis=-1), -1e30)
        e = jnp.exp(s - m_c[..., None])
        d_c = jnp.sum(e, axis=-1)
        o_c = jnp.einsum("bkgs,bskd->bkgd", e, vc,
                         precision=jax.lax.Precision.HIGHEST)
        if m_acc is None:
            m_acc, d_acc, o_acc = m_c, d_c.astype(cd), o_c.astype(cd)
        else:
            m_new = jnp.maximum(m_acc, m_c)
            a1, a2 = jnp.exp(m_acc - m_new), jnp.exp(m_c - m_new)
            d_acc = (a1 * d_acc.astype(F32) + a2 * d_c).astype(cd)
            o_acc = (a1[..., None] * o_acc.astype(F32) + a2[..., None] * o_c).astype(cd)
            m_acc = m_new
        start += size
    out = o_acc.astype(F32) / jnp.maximum(d_acc.astype(F32), 1e-30)[..., None]
    return out.reshape(B, H, D).astype(q.dtype)


def paged_attention(
    q: jax.Array,         # (B, H, D)
    k_pool: jax.Array,    # (NB, KV, bs, Dp) global block pool, head-major
    v_pool: jax.Array,    # (NB, KV, bs, Dp), Dp >= D (pad lanes ignored)
    pos_pool: jax.Array,  # (NB, bs) int32 absolute positions, -1 = empty
    tables: jax.Array,    # (B, nblk) int32 block ids, -1 = unallocated
    q_pos: jax.Array,     # (B,) int32 absolute query position
    *,
    null_bid: int | None = None,
    kv_splits: int = 1,
    combine_dtype: str = "float32",
) -> jax.Array:
    """Paged-attention oracle: gather the per-row view through the block
    table (``-1`` entries read the null block, masked via ``pos == -1``),
    then run the same softmax semantics as ``decode_attention`` over it.
    ``kv_splits=1`` is the commit-path universal schedule: a single-pass
    f32 softmax whose reduction extent is the fixed table reach.

    Each (row, KV head) is evaluated on its own with 2-D ``(G, D) x (S, D)``
    products — the shape of one kernel grid step — mapped over rows and
    heads, so the oracle and the kernel's interpret mode share one
    arithmetic (a batched einsum may reduce in another order on CPU)."""
    B, H, D = q.shape
    NB, KV, bs = k_pool.shape[:3]
    nblk = tables.shape[1]
    nb = (NB - 2) if null_bid is None else null_bid
    tab = jnp.where(tables < 0, nb, tables)

    def view(pool):  # (B, nblk, KV, bs, Dp) -> (B, KV, nblk*bs, D)
        v = pool[tab][..., :D].transpose(0, 2, 1, 3, 4)
        return v.reshape(B, KV, nblk * bs, D).astype(F32)

    kf, vf = view(k_pool), view(v_pool)
    pos = pos_pool[tab].reshape(B, nblk * bs)
    valid = (pos >= 0) & (pos <= q_pos[:, None])  # (B, S)

    G = H // KV
    qg = q.reshape(B, KV, G, D).astype(F32) * (D**-0.5)  # scaled in f32
    cd = jnp.dtype(combine_dtype)
    S = nblk * bs
    base, rem = divmod(S, kv_splits)
    sizes = [base + (1 if i < rem else 0) for i in range(kv_splits)]

    def head(qh, kh, vh, ok):  # (G, D), (S, D), (S, D), (S,) -> (G, D)
        m_acc = d_acc = o_acc = None
        start = 0
        for size in sizes:
            kc = jax.lax.slice_in_dim(kh, start, start + size, axis=0)
            vc = jax.lax.slice_in_dim(vh, start, start + size, axis=0)
            mc = jax.lax.slice_in_dim(ok, start, start + size, axis=0)
            s = jax.lax.dot_general(
                qh, kc, (((1,), (1,)), ((), ())), preferred_element_type=F32,
                precision=jax.lax.Precision.HIGHEST,
            )
            s = jnp.where(mc[None, :], s, -jnp.inf)
            m_c = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), -1e30)
            e = jnp.exp(s - m_c)
            d_c = jnp.sum(e, axis=-1, keepdims=True)
            o_c = jnp.dot(e, vc, preferred_element_type=F32,
                          precision=jax.lax.Precision.HIGHEST)
            if m_acc is None:
                m_acc, d_acc, o_acc = m_c, d_c.astype(cd), o_c.astype(cd)
            else:
                m_new = jnp.maximum(m_acc, m_c)
                a1, a2 = jnp.exp(m_acc - m_new), jnp.exp(m_c - m_new)
                d_acc = (a1 * d_acc.astype(F32) + a2 * d_c).astype(cd)
                o_acc = (a1 * o_acc.astype(F32) + a2 * o_c).astype(cd)
                m_acc = m_new
            start += size
        return o_acc.astype(F32) / jnp.maximum(d_acc.astype(F32), 1e-30)

    def row(args):
        qb, kb, vb, ok = args
        return jax.lax.map(lambda a: head(*a, ok), (qb, kb, vb))

    out = jax.lax.map(row, (qg, kf, vf, valid))  # (B, KV, G, D)
    return out.reshape(B, H, D).astype(F32)


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-5,
            residual: jax.Array | None = None) -> jax.Array:
    """Fused (residual-add +) RMSNorm oracle; f32 single-pass reduction."""
    if residual is not None:
        x = (x.astype(F32) + residual.astype(F32)).astype(x.dtype)
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * scale.astype(F32)
    return out.astype(x.dtype)
