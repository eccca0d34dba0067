"""Fixed-size KV block pool: allocator + block-indexed device cache layout.

The dense cache manager bound one ``max_seq_len``-long KV ring to every
slot, so concurrency was capped by the *worst-case* footprint of a request
(ROADMAP: "heavy traffic from millions of users" wants memory-bounded
admission, not slot-bounded).  This module replaces that layout for
full-attention KV leaves with a **paged** one:

* the position axis of every full-attention leaf (``k``/``v``/``pos``,
  capacity-long) is re-cut into fixed-size **blocks**: a leaf shaped
  ``(..., B_slots, capacity, ...)`` becomes ``(..., num_blocks + 2,
  block_size, ...)`` — one global pool of blocks shared by all requests;
* a request owns an ordered **block table** (``Request.blocks``): block
  ``j`` holds its KV for absolute positions ``[j*bs, (j+1)*bs)``;
* :func:`gather` assembles, per batch row, a contiguous
  ``(B, view_capacity, ...)`` view by indexing blocks — the forward pass
  (and its ``pos``-mask) is completely unchanged; :func:`scatter` writes
  the view back through the table.

Blocks are **ref-counted** so the prefix cache (``serving.prefixcache``)
can map one committed-prefix block into many requests' tables read-only;
refcounts dropping to zero return a block to the free list (or leave it
resident-but-evictable when the prefix cache registered it).

Two sentinel block ids make fixed-shape views safe without per-row length
plumbing:

* ``null`` — a frozen all-empty block (``pos == -1`` everywhere, never
  written): table entries past a request's allocated extent *gather* from
  it, so the view tail is guaranteed masked out;
* ``scratch`` — a trash block that *absorbs* every write the scatter
  would otherwise direct at an unallocated table entry (the view tail
  pass-through, and verify-pass pad writes past the ensured extent).
  Scratch content is never gathered, so the junk is quarantined.

Recurrent O(1) state (mamba/rwkv), sliding-window rings (bounded at
``window + RING_SLACK``) and encdec cross caches keep the dense per-slot
layout — paging buys nothing for constant-size state; :func:`build_layout`
classifies every cache leaf once, by shape, into ``slot`` vs ``paged``.

K/V leaves are stored *head-major* inside a block, ``(num_blocks + 2,
KV, block_size, head_dim_padded)``: one (block, head) pair is then a
contiguous ``(block_size, head_dim)`` tile, which is what the paged
attention kernel DMAs from HBM.  The head dim is padded up to a multiple
of ``default_lanes()`` (128 on TPU, whose HBM layout pads it to whole lane tiles
anyway, so the padding costs no memory there; 1 elsewhere).  Padding
lanes are written as zeros and sliced away by every reader.

Freed blocks are wiped (``pos`` leaves back to -1) before they can be
reallocated: a stale absolute position *smaller* than a new owner's query
position would otherwise mask garbage keys into attention.  (Stale
positions *ahead* of the query are harmless — the same shadowing argument
the verifier's pointer-free rollback already relies on.)
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Set

import jax
import jax.numpy as jnp

from repro.models.base import ModelConfig
from repro.models.transformer import cache_spec

#: shape sentinels for leaf classification (never collide with real dims)
_SENT_B = 1_000_003
_SENT_C = 1_000_033

#: default KV block size (tokens per block)
DEFAULT_BLOCK_SIZE = 16


# ---------------------------------------------------------------------------
# layout: classify cache leaves, size the paged storage
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LeafDesc:
    """Per-leaf addressing descriptor.  Deliberately NOT a pytree
    container, so an axes tree of these zips leaf-for-leaf with the cache
    tree under ``tree_map``."""

    kind: str  # "slot" (dense per-slot) | "paged" (block-cut)
    axis: int  # batch axis (paged: capacity axis is axis + 1)
    head_dim: int = 0  # paged K/V leaves: logical head dim (0 = pos leaf)
    head_dim_padded: int = 0  # ... and its stored, lane-padded width


@dataclasses.dataclass(frozen=True)
class Layout:
    """Static description of the paged cache layout (closed over by jits).

    ``axes`` mirrors the cache pytree with a :class:`LeafDesc` per leaf:
    ``slot`` for dense per-slot leaves, ``paged`` for block-cut leaves.
    """

    axes: Any
    block_size: int
    num_blocks: int  # real allocatable blocks (excludes null + scratch)
    blocks_per_table: int  # table width: ceil(capacity / block_size)
    has_paged: bool

    @property
    def null_bid(self) -> int:
        return self.num_blocks

    @property
    def scratch_bid(self) -> int:
        return self.num_blocks + 1

    @property
    def view_capacity(self) -> int:
        return self.blocks_per_table * self.block_size


def default_lanes() -> int:
    """Lane width the stored K/V head dim is padded to on this backend."""
    return 128 if jax.default_backend() == "tpu" else 1


def build_layout(
    cfg: ModelConfig, capacity: int, block_size: int, num_blocks: int
) -> Layout:
    """Classify every cache leaf by shape (sentinel batch/capacity dims)."""
    assert block_size >= 1
    lanes = default_lanes()
    spec = cache_spec(cfg, _SENT_B, _SENT_C)

    def classify(s: jax.ShapeDtypeStruct) -> LeafDesc:
        b = [i for i, d in enumerate(s.shape) if d == _SENT_B]
        assert len(b) == 1, f"ambiguous batch axis in {s.shape}"
        c = [i for i, d in enumerate(s.shape) if d == _SENT_C]
        if not c:
            return LeafDesc("slot", b[0])
        assert c == [b[0] + 1], f"capacity axis must follow batch in {s.shape}"
        tail = s.shape[b[0] + 2:]
        if not tail:
            return LeafDesc("paged", b[0])
        assert len(tail) == 2, f"paged K/V leaf must end in (KV, hd): {s.shape}"
        hd = tail[1]
        return LeafDesc("paged", b[0], hd, -(-hd // lanes) * lanes)

    axes = jax.tree_util.tree_map(classify, spec)
    has_paged = any(
        d.kind == "paged" for d in jax.tree_util.tree_leaves(axes)
    )
    bpt = -(-capacity // block_size)
    return Layout(
        axes=axes, block_size=block_size, num_blocks=num_blocks,
        blocks_per_table=bpt, has_paged=has_paged,
    )


def init_cache(cfg: ModelConfig, lay: Layout, num_slots: int) -> Any:
    """Device storage: slot leaves carry ``num_slots + 1`` rows (+ scratch
    slot, as before); paged leaves carry ``num_blocks + 2`` blocks of
    ``block_size`` (+ null + scratch blocks)."""
    spec = cache_spec(cfg, _SENT_B, _SENT_C)

    def make(s: jax.ShapeDtypeStruct, desc: LeafDesc) -> jax.Array:
        if desc.kind == "slot":
            shape = tuple(
                num_slots + 1 if d == _SENT_B else d for d in s.shape
            )
        elif desc.head_dim:
            ax = desc.axis
            shape = s.shape[:ax] + (
                lay.num_blocks + 2, s.shape[ax + 2], lay.block_size,
                desc.head_dim_padded,
            )
        else:
            ax = desc.axis
            shape = s.shape[:ax] + (lay.num_blocks + 2, lay.block_size)
        if s.dtype == jnp.int32:
            return jnp.full(shape, -1, s.dtype)  # pos slots start empty
        return jnp.zeros(shape, s.dtype)

    return jax.tree_util.tree_map(make, spec, lay.axes)


# ---------------------------------------------------------------------------
# device gather / scatter through block tables
# ---------------------------------------------------------------------------


def _to_blocks(u: jax.Array, desc: LeafDesc) -> jax.Array:
    """Token-major ``(..., N, bs, KV, hd)`` -> stored ``(..., N, KV, bs, hdp)``."""
    if not desc.head_dim:
        return u
    u = jnp.swapaxes(u, desc.axis + 1, desc.axis + 2)
    pad = desc.head_dim_padded - desc.head_dim
    if pad:
        u = jnp.pad(u, [(0, 0)] * (u.ndim - 1) + [(0, pad)])
    return u


def _from_blocks(x: jax.Array, desc: LeafDesc) -> jax.Array:
    """Inverse of :func:`_to_blocks`: drop the pad lanes, token-major again."""
    if not desc.head_dim:
        return x
    return jnp.swapaxes(x[..., : desc.head_dim], desc.axis + 1, desc.axis + 2)


def gather(pool: Any, lay: Layout, slots: jax.Array, tables: jax.Array) -> Any:
    """Per-row cache views: slot leaves index by ``slots`` (B,), paged
    leaves assemble ``(B, view_capacity, ...)`` from ``tables``
    (B, blocks_per_table) int32; ``-1`` table entries read the null block
    (always masked)."""
    B, nblk = tables.shape
    flat = jnp.where(tables < 0, lay.null_bid, tables).reshape(-1)

    def g(leaf, desc):
        ax = desc.axis
        if desc.kind == "slot":
            return jnp.take(leaf, slots, axis=ax)
        out = _from_blocks(jnp.take(leaf, flat, axis=ax), desc)
        return out.reshape(out.shape[:ax] + (B, nblk * lay.block_size)
                           + out.shape[ax + 2:])

    return jax.tree_util.tree_map(g, pool, lay.axes)


def scatter(
    pool: Any, lay: Layout, slots: jax.Array, tables: jax.Array, update: Any
) -> Any:
    """Write per-row views back: ``-1`` table entries dump into the scratch
    block (absorbing view-tail pass-through and pad writes); duplicate real
    entries (prefix-shared blocks in one batch) carry bitwise-identical
    content, so write order is immaterial."""
    B, nblk = tables.shape
    flat = jnp.where(tables < 0, lay.scratch_bid, tables).reshape(-1)

    def s(leaf, desc, u):
        ax = desc.axis
        if desc.kind == "slot":
            idx = (slice(None),) * ax + (slots,)
            return leaf.at[idx].set(u.astype(leaf.dtype))
        u2 = u.reshape(
            u.shape[:ax] + (B * nblk, lay.block_size) + u.shape[ax + 2:]
        )
        idx = (slice(None),) * ax + (flat,)
        return leaf.at[idx].set(_to_blocks(u2, desc).astype(leaf.dtype))

    return jax.tree_util.tree_map(s, pool, lay.axes, update)


def gather_mixed(pool: Any, lay: Layout, slots: jax.Array) -> Any:
    """Row-pack *slot* leaves only; *paged* leaves pass through whole.

    The paged-attention forward reads K/V in place through the block table,
    so — unlike :func:`gather` — no per-row contiguous view is ever copied
    out for full-attention leaves.  Dense leaves (recurrent state, sliding
    rings, cross caches) still need row packing by ``slots``.
    """

    def g(leaf, desc):
        if desc.kind == "paged":
            return leaf
        return jnp.take(leaf, slots, axis=desc.axis)

    return jax.tree_util.tree_map(g, pool, lay.axes)


def scatter_mixed(pool: Any, lay: Layout, slots: jax.Array, update: Any) -> Any:
    """Inverse of :func:`gather_mixed`: slot leaves write back per-row by
    ``slots``; paged leaves were updated in place by the forward (the update
    *is* the new pool) and replace the old leaf wholesale."""

    def sm(leaf, desc, u):
        if desc.kind == "paged":
            return u.astype(leaf.dtype)
        idx = (slice(None),) * desc.axis + (slots,)
        return leaf.at[idx].set(u.astype(leaf.dtype))

    return jax.tree_util.tree_map(sm, pool, lay.axes, update)


def wipe_blocks(pool: Any, lay: Layout, bids: List[int]) -> Any:
    """Reset freed blocks' position bookkeeping (``pos`` -> -1) so stale
    absolute positions never mask into a future owner's attention."""
    if not bids:
        return pool
    idx = jnp.array(bids, jnp.int32)

    def wipe(leaf, desc):
        if desc.kind != "paged" or leaf.dtype != jnp.int32:
            return leaf
        at = (slice(None),) * desc.axis + (idx,)
        return leaf.at[at].set(-1)

    return jax.tree_util.tree_map(wipe, pool, lay.axes)


def copy_blocks(
    src_pool: Any, dst_pool: Any, lay: Layout,
    src_bids: List[int], dst_bids: List[int],
) -> Any:
    """Copy paged-leaf block rows ``src_bids`` (of ``src_pool``) into
    ``dst_bids`` (of ``dst_pool``); returns the updated destination tree.

    The cluster front end's cross-replica prefix transfer
    (``cluster.replica.transfer_prefix``): both pools must share one
    :class:`Layout`.  Slot leaves (recurrent state, rings) never move —
    prefix sharing is defined only for paged full-attention KV.
    """
    assert len(src_bids) == len(dst_bids)
    if not src_bids:
        return dst_pool
    si = jnp.array(src_bids, jnp.int32)
    di = jnp.array(dst_bids, jnp.int32)

    def cp(dst_leaf, src_leaf, desc):
        if desc.kind != "paged":
            return dst_leaf
        rows = jnp.take(src_leaf, si, axis=desc.axis)
        at = (slice(None),) * desc.axis + (di,)
        return dst_leaf.at[at].set(rows.astype(dst_leaf.dtype))

    return jax.tree_util.tree_map(cp, dst_pool, src_pool, lay.axes)


def wipe_slot(pool: Any, lay: Layout, slot: int) -> Any:
    """Reset a released slot's dense leaves (sliding rings, recurrent
    state): int32 leaves to -1, the rest to zero — the old dense-pool
    ``free`` semantics, now scoped to slot-kind leaves only."""

    def wipe(leaf, desc):
        if desc.kind != "slot":
            return leaf
        idx = (slice(None),) * desc.axis + (slot,)
        if leaf.dtype == jnp.int32:
            return leaf.at[idx].set(-1)
        return leaf.at[idx].set(jnp.zeros_like(leaf[idx]))

    return jax.tree_util.tree_map(wipe, pool, lay.axes)


# ---------------------------------------------------------------------------
# host-side allocator
# ---------------------------------------------------------------------------


class BlockAllocator:
    """Ref-counted free-list allocator over ``num_blocks`` block ids.

    ``cached`` marks blocks registered with the prefix cache: their
    refcount reaching zero leaves them *resident* (evictable by the cache's
    LRU policy) instead of free.  The allocator never touches the device —
    the cache pool wipes freed blocks before reuse.
    """

    def __init__(self, num_blocks: int):
        assert num_blocks >= 1
        self.num_blocks = num_blocks
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self.refs: List[int] = [0] * num_blocks
        self.cached: Set[int] = set()
        self.peak_in_use = 0
        # allocation-churn telemetry (obs.metrics gauges)
        self.num_allocs = 0
        self.num_frees = 0

    def num_free(self) -> int:
        return len(self._free)

    def in_use(self) -> int:
        return self.num_blocks - len(self._free)

    def num_evictable(self) -> int:
        """Cached blocks no live request references — reclaimable."""
        return sum(1 for b in self.cached if self.refs[b] == 0)

    def available(self) -> int:
        """Free now plus reclaimable-by-eviction."""
        return self.num_free() + self.num_evictable()

    def alloc(self) -> Optional[int]:
        if not self._free:
            return None
        bid = self._free.pop()
        assert self.refs[bid] == 0 and bid not in self.cached
        self.refs[bid] = 1
        self.num_allocs += 1
        self.peak_in_use = max(self.peak_in_use, self.in_use())
        return bid

    def incref(self, bid: int) -> None:
        self.refs[bid] += 1

    def decref(self, bid: int) -> int:
        assert self.refs[bid] > 0, f"double free of block {bid}"
        self.refs[bid] -= 1
        return self.refs[bid]

    def release(self, bid: int) -> None:
        """Return a zero-ref, uncached block to the free list."""
        assert self.refs[bid] == 0 and bid not in self.cached
        self.num_frees += 1
        self._free.append(bid)
