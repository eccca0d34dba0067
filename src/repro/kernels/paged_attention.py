"""Paged decode/verify attention: K/V read through the block table.

vLLM-paged-attention-shaped: K/V live in a global pool of fixed-size blocks
``(num_blocks + 2, KV, block_size, Dp)`` with no batch axis, stored
head-major so that one (block, head) pair is a contiguous
``(block_size, Dp)`` tile; ``Dp`` is the head dim padded to whole lanes
(``serving.blockpool``).  Each row owns a table of block indices (``-1`` =
unallocated, mapped to the pool's *null block* whose positions are ``-1``
and therefore always masked).

The pools stay in HBM (``memory_space=ANY``).  The tables and query
positions arrive by scalar prefetch (SMEM), and each grid step DMAs only
its row's table blocks, for its KV head, into a VMEM view.  The positions
of that view are gathered outside the kernel (``B x nblk x bs`` int32).

Two variants, per the determinism contract:

* ``paged_attention`` — the commit-path kernel.  Grid ``(B, KV)`` carries no
  reduction axes at all (both axes index the output tile); the view is the
  whole table reach, assembled in walk order with the literal
  ``block_size`` as chunk, so the reduction tree over keys is a single
  fixed-shape f32 softmax — exactly the universal schedule
  ``kernels/ref.py`` defines.  It must stay clean under
  ``repro.analysis.kernel_lint``.
* ``paged_attention_fast`` — the licensed fast path: kv-split flash-decode
  over the table (grid ``(B, KV, kv_splits)``), merging per-split partials
  through f32 VMEM scratch.  Split count follows the workload, so its
  schedule is nondeterministic by design and the function is exempted with
  ``# det: fastpath`` (the taint pass proves it unreachable from the commit
  side).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _fetch_view(tab_ref, kp_hbm, vp_hbm, kbuf, vbuf, sem, *, row, head, lo,
                n_blocks, blocks_per_row, block_size):
    """DMA table blocks ``[lo, lo + n_blocks)`` of ``row`` for KV ``head``
    from the HBM pools into the VMEM views ``kbuf``/``vbuf``
    (``(n_blocks * block_size, Dp)``), in table order."""

    def copies(j):
        bid = tab_ref[row * blocks_per_row + lo + j]
        dst = pl.ds(j * block_size, block_size)
        return (
            pltpu.make_async_copy(kp_hbm.at[bid, head], kbuf.at[dst], sem.at[0]),
            pltpu.make_async_copy(vp_hbm.at[bid, head], vbuf.at[dst], sem.at[1]),
        )

    def start(j, carry):
        for cp in copies(j):
            cp.start()
        return carry

    def wait(j, carry):
        for cp in copies(j):
            cp.wait()
        return carry

    jax.lax.fori_loop(0, n_blocks, start, 0)
    jax.lax.fori_loop(0, n_blocks, wait, 0)


def _pool_args(q, k_pool, tables, q_pos, pos_pool, null_bid):
    """Shared wrapper prep: GQA-grouped q, the flat sentinel-mapped table
    (scalar prefetch), and the gathered ``(B, 1, S)`` position view."""
    B, H, D = q.shape
    NB, KVH, bs, _ = k_pool.shape
    nblk = tables.shape[1]
    qg = q.reshape(B, KVH, H // KVH, D)
    sentinel = (NB - 2) if null_bid is None else null_bid
    tab = jnp.where(tables < 0, sentinel, tables).astype(jnp.int32)
    pv = pos_pool[tab].reshape(B, 1, nblk * bs)
    return qg, tab.reshape(-1), q_pos.astype(jnp.int32), pv


def _paged_kernel(
    tab_ref, qpos_ref, q_ref, kp_hbm, vp_hbm, pv_ref, o_ref, kbuf, vbuf, sem,
    *, blocks_per_row, block_size, scale
):
    # q_ref (G, D); pools (NB, KV, bs, Dp) in HBM; pv_ref (1, S); views (S, Dp)
    b = pl.program_id(0)
    _fetch_view(
        tab_ref, kp_hbm, vp_hbm, kbuf, vbuf, sem, row=b, head=pl.program_id(1),
        lo=0, n_blocks=blocks_per_row, blocks_per_row=blocks_per_row,
        block_size=block_size,
    )
    q = q_ref[...].astype(F32) * scale  # (G, D)
    d = q.shape[-1]
    kv = kbuf[:, :d].astype(F32)
    vv = vbuf[:, :d].astype(F32)
    s = jax.lax.dot_general(
        q, kv, (((1,), (1,)), ((), ())), preferred_element_type=F32,
        precision=HIGHEST,
    )  # (G, S)
    pv = pv_ref[...]
    valid = (pv >= 0) & (pv <= qpos_ref[b])
    s = jnp.where(valid, s, -jnp.inf)
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), -1e30)
    e = jnp.exp(s - m)
    denom = jnp.sum(e, axis=-1, keepdims=True)
    o = jnp.dot(e, vv, preferred_element_type=F32, precision=HIGHEST)
    o_ref[...] = (o / jnp.maximum(denom, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("null_bid", "interpret"))
def paged_attention(
    q: jax.Array,  # (B, H, D)
    k_pool: jax.Array,  # (NB, KV, bs, Dp)
    v_pool: jax.Array,  # (NB, KV, bs, Dp)
    pos_pool: jax.Array,  # (NB, bs) int32, -1 = empty
    tables: jax.Array,  # (B, nblk) int32 block ids, -1 = unallocated
    q_pos: jax.Array,  # (B,) int32 absolute query position
    *,
    null_bid: int | None = None,
    interpret: bool = True,
) -> jax.Array:
    """Commit-path paged attention: one fixed-shape f32 softmax per row."""
    qg, tab, qp, pv = _pool_args(q, k_pool, tables, q_pos, pos_pool, null_bid)
    B, KV, G, D = qg.shape
    Dp = k_pool.shape[3]
    bs = k_pool.shape[2]
    nblk = tables.shape[1]
    S = pv.shape[2]
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel,
            blocks_per_row=nblk,
            block_size=bs,
            scale=D ** -0.5,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, KV),
            in_specs=[
                pl.BlockSpec((None, None, G, D), lambda b, h, t, p: (b, h, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((None, 1, S), lambda b, h, t, p: (b, 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (None, None, G, D), lambda b, h, t, p: (b, h, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((S, Dp), k_pool.dtype),
                pltpu.VMEM((S, Dp), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), F32),
        interpret=interpret,
    )(tab, qp, qg, k_pool, v_pool, pv)
    return out.reshape(B, KV * G, D)


# det: fastpath
def _paged_fast_kernel(
    tab_ref, qpos_ref, q_ref, kp_hbm, vp_hbm, pv_ref, o_ref, kbuf, vbuf, sem,
    m_ref, d_ref, acc_ref, *, kv_splits, blocks_per_split, blocks_per_row,
    block_size, scale, combine_dtype
):
    b = pl.program_id(0)
    s_idx = pl.program_id(2)
    _fetch_view(
        tab_ref, kp_hbm, vp_hbm, kbuf, vbuf, sem, row=b, head=pl.program_id(1),
        lo=s_idx * blocks_per_split, n_blocks=blocks_per_split,
        blocks_per_row=blocks_per_row, block_size=block_size,
    )
    q = q_ref[...].astype(F32) * scale  # (G, D)
    d = q.shape[-1]
    kv = kbuf[:, :d].astype(F32)
    vv = vbuf[:, :d].astype(F32)
    s = jax.lax.dot_general(
        q, kv, (((1,), (1,)), ((), ())), preferred_element_type=F32,
        precision=HIGHEST,
    )
    pv = pv_ref[...]  # (1, S / kv_splits): this split's positions
    valid = (pv >= 0) & (pv <= qpos_ref[b])
    s = jnp.where(valid, s, -jnp.inf)
    m_c = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), -1e30)  # (G, 1)
    e = jnp.exp(s - m_c).astype(combine_dtype)
    d_c = jnp.sum(e, axis=-1, keepdims=True)  # (G, 1)
    o_c = jnp.dot(e.astype(F32), vv, preferred_element_type=F32,
                  precision=HIGHEST)  # (G, D)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = m_c
        d_ref[...] = d_c.astype(F32)
        acc_ref[...] = o_c

    @pl.when(s_idx > 0)
    def _merge():
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, m_c)
        a_prev = jnp.exp(m_prev - m_new)
        a_c = jnp.exp(m_c - m_new)
        m_ref[...] = m_new
        d_ref[...] = d_ref[...] * a_prev + d_c.astype(F32) * a_c
        acc_ref[...] = acc_ref[...] * a_prev + o_c * a_c

    @pl.when(s_idx == kv_splits - 1)
    def _emit():
        denom = jnp.maximum(d_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


# det: fastpath
@functools.partial(
    jax.jit, static_argnames=("kv_splits", "combine_dtype", "null_bid", "interpret")
)
def paged_attention_fast(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    pos_pool: jax.Array,
    tables: jax.Array,
    q_pos: jax.Array,
    *,
    kv_splits: int = 1,
    combine_dtype: str = "float32",
    null_bid: int | None = None,
    interpret: bool = True,
) -> jax.Array:
    """Fast-path paged attention: kv-split flash-decode over the table."""
    nblk = tables.shape[1]
    if nblk % kv_splits != 0:
        raise ValueError(f"kv_splits={kv_splits} must divide table reach {nblk}")
    qg, tab, qp, pv = _pool_args(q, k_pool, tables, q_pos, pos_pool, null_bid)
    B, KV, G, D = qg.shape
    _, _, bs, Dp = k_pool.shape
    per = nblk // kv_splits
    pv = pv.reshape(B * kv_splits, 1, per * bs)  # one row per (row, split)
    out = pl.pallas_call(
        functools.partial(
            _paged_fast_kernel,
            kv_splits=kv_splits,
            blocks_per_split=per,
            blocks_per_row=nblk,
            block_size=bs,
            scale=D ** -0.5,
            combine_dtype=jnp.dtype(combine_dtype),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, KV, kv_splits),
            in_specs=[
                pl.BlockSpec(
                    (None, None, G, D), lambda b, h, s, t, p: (b, h, 0, 0)
                ),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(
                    (None, 1, per * bs),
                    lambda b, h, s, t, p: (b * kv_splits + s, 0, 0),
                ),
            ],
            out_specs=pl.BlockSpec(
                (None, None, G, D), lambda b, h, s, t, p: (b, h, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((per * bs, Dp), k_pool.dtype),
                pltpu.VMEM((per * bs, Dp), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((G, 1), F32),
                pltpu.VMEM((G, 1), F32),
                pltpu.VMEM((G, D), F32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), F32),
        interpret=interpret,
    )(tab, qp, qg, k_pool, v_pool, pv)
    return out.reshape(B, KV * G, D)
