"""Logical-axis → PartitionSpec rules (MaxText-style, minimal).

``param_specs`` (models/base.py) annotates every tensor dim with a logical
axis name; this module maps those names onto mesh axes per execution mode:

  * TRAIN — FSDP: weight ``embed`` dims sharded over the data axes
    (ZeRO-3-style, all-gathered per layer by GSPMD), tensor-parallel
    ``heads/ffn/vocab`` over ``model``, MoE ``experts`` expert-parallel
    over the data axes.
  * SERVE — weights replicated over data (decode batches shard over data),
    tensor-parallel over ``model``; MoE experts expert-parallel over
    ``model`` (all-to-all dispatch inside a chip group).

Divisibility fallback: if a dim is not divisible by the mesh-axes product
(e.g. kv_heads=8 over model=16), axes are dropped right-to-left until it
divides — every (arch × shape × mesh) combination must lower, so the rules
degrade to replication rather than erroring (DESIGN.md §5).  A mesh axis is
never used twice in one PartitionSpec (GSPMD requirement); first dim wins.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.determinism import (
    Schedule, VERIFY_SCHEDULE, _split_sizes, matmul as sched_matmul, tree_combine,
)
from repro.models.base import ModelConfig, param_specs
from repro.models.transformer import cache_spec


Axes = Tuple[str, ...]


def tp_matmul(
    x: jax.Array,
    w: jax.Array,
    mesh: Mesh,
    *,
    axis: str = "model",
    schedule: Schedule = VERIFY_SCHEDULE,
) -> jax.Array:
    """Row-parallel commit-path GEMM under the canonical mesh-reduction schedule.

    The physical realization of ``core.determinism.matmul`` with a pinned
    schedule: ``w``'s K dim is sharded over the ``axis`` mesh axis (width d),
    each device reduces its ``tp_shards/d`` canonical K chunks to f32
    partials and sums them through its *local subtree* of the balanced tree,
    then a recursive-doubling butterfly (``ppermute`` XOR pairs, one add per
    level) completes the top log2(d) levels **in the same association** —
    ``((p0+p1)+(p2+p3))`` regardless of d.  IEEE addition is commutative
    bitwise, so each device adding (mine + received) lands on the identical
    sum.  Hence the result is bitwise equal to the single-device
    ``matmul(x, w, schedule)`` for every power-of-two d dividing
    ``schedule.tp_shards`` — a token committed on TP=1 is the token
    committed on TP=2/4.

    Falls back to the logical single-device path when the mesh axis is
    absent/1-wide, when d does not divide ``tp_shards``, or when K is not
    divisible by ``tp_shards`` (chunk boundaries would straddle shards).
    """
    K = x.shape[-1]
    d = _axis_sizes(mesh).get(axis, 1)
    tp = schedule.tp_shards
    if (
        d <= 1 or tp <= 1 or tp > K
        or tp % d != 0 or K % tp != 0 or (d & (d - 1)) != 0
    ):
        return sched_matmul(x, w, schedule)

    chunk = K // tp
    per_dev = tp // d
    local = schedule._replace(tp_shards=1, tp_pinned=False)
    out_dtype = x.dtype

    def body(xb: jax.Array, wb: jax.Array) -> jax.Array:
        # xb: (..., K/d) local activation slice; wb: (K/d, N) weight shard.
        parts = []
        for c in range(per_dev):
            xc = jax.lax.slice_in_dim(
                xb, c * chunk, (c + 1) * chunk, axis=xb.ndim - 1
            )
            wc = jax.lax.slice_in_dim(wb, c * chunk, (c + 1) * chunk, axis=0)
            parts.append(
                sched_matmul(
                    xc.astype(jnp.float32), wc.astype(jnp.float32), local
                )
            )
        acc = tree_combine(parts)  # this device's local subtree, f32
        if schedule.tp_pinned:
            dist = 1
            while dist < d:  # top log2(d) tree levels, canonical association
                perm = [(i, i ^ dist) for i in range(d)]
                acc = acc + jax.lax.ppermute(acc, axis, perm=perm)
                dist *= 2
        else:
            # un-pinned: mesh-order ring reduce in combine_dtype — the
            # fast-path hazard; result depends on d.
            cd = jnp.dtype(schedule.combine_dtype)
            acc = jax.lax.psum(acc.astype(cd), axis)
        return acc.astype(out_dtype)

    x_spec = P(*([None] * (x.ndim - 1) + [axis]))
    w_spec = P(axis, None)
    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(x_spec, w_spec), out_specs=P(),
        check_vma=False,
    )
    return fn(x, w)


def _axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(mesh.shape)  # works for Mesh and AbstractMesh


def _data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def rules_train(mesh: Mesh, *, fsdp: bool = True) -> Dict[str, Any]:
    """fsdp=False replicates weights over the data axes (pure DP x TP) —
    trades memory for the per-layer all-gather traffic (§Perf lever)."""
    d = _data_axes(mesh)
    return {
        "embed": d if fsdp else None,
        "heads": "model", "kv": "model", "ffn": "model", "vocab": "model",
        "experts": d, "inner": "model", "state": None, "layers": None,
    }


def rules_serve(mesh: Mesh, *, moe_ep: str = "model") -> Dict[str, Any]:
    """moe_ep: which mesh axis carries the MoE expert dim at serving time.
    "model" (baseline): experts sharded 16-way, each expert's weights
    unsharded -> 1/16 of total expert params per device (129 GB for
    kimi-k2 — over HBM).  "data": 2-D expert sharding — experts over data,
    per-expert ffn over model -> 1/256 per device (§Perf P3 lever; the
    batch's token->expert dispatch becomes an all-to-all over data)."""
    return {
        "embed": None,
        "heads": "model", "kv": "model", "ffn": "model", "vocab": "model",
        "experts": moe_ep, "inner": "model", "state": None, "layers": None,
    }


def _normalize(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


def spec_for(shape: Sequence[int], axes: Axes, rules: Dict[str, Any],
             mesh: Mesh) -> P:
    """PartitionSpec for one tensor, with divisibility + reuse fallback."""
    sizes = _axis_sizes(mesh)
    used: set = set()
    parts = []
    for dim, ax in zip(shape, axes):
        proposal = [a for a in _normalize(rules.get(ax)) if a not in used]
        # drop axes right-to-left until the dim divides
        while proposal:
            prod = int(np.prod([sizes[a] for a in proposal]))
            if dim % prod == 0:
                break
            proposal = proposal[:-1]
        if proposal:
            used.update(proposal)
            parts.append(tuple(proposal) if len(proposal) > 1 else proposal[0])
        else:
            parts.append(None)
    return P(*parts)


def param_pspecs(cfg: ModelConfig, mesh: Mesh, rules: Dict[str, Any]) -> Any:
    return jax.tree_util.tree_map(
        lambda s: spec_for(s.shape, s.axes, rules, mesh), param_specs(cfg)
    )


def param_shardings(cfg: ModelConfig, mesh: Mesh, rules: Dict[str, Any]) -> Any:
    return jax.tree_util.tree_map(
        lambda p: NamedSharding(mesh, p), param_pspecs(cfg, mesh, rules)
    )


def batch_pspec(mesh: Mesh) -> P:
    return P(_data_axes(mesh))


_SENTINEL_B, _SENTINEL_C = 1717, 1719


def cache_pspec_tree(
    cfg: ModelConfig, mesh: Mesh, batch: int, capacity: int,
    *, kv_policy: str = "feature_first",
) -> Any:
    """PartitionSpecs for the serving cache pytree.

    Batch dims go over the data axes.  The model-axis placement of KV
    leaves is the §Perf lever:

    * ``feature_first`` (the paper-faithful baseline we dry-ran): shard the
      first model-divisible non-batch dim — kv_heads when divisible, else
      head_dim.  head_dim sharding forces GSPMD resharding (involuntary
      full rematerialization) around the attention einsum.
    * ``seq_first``: shard the cache *sequence* dim over model (flash-
      decoding sequence parallelism): the attention contraction batches
      over the sharded axis, partial softmax stats combine with small
      collectives, no replication.  Found in hillclimb #1.

    Recurrent-state leaves shard their d_inner / head dim over model.
    Batch/seq axes are located via sentinel-sized template shapes.
    """
    sizes = _axis_sizes(mesh)
    model = sizes.get("model", 1)
    d = _data_axes(mesh)
    dprod = int(np.prod([sizes[a] for a in d]))

    template = cache_spec(cfg, _SENTINEL_B, _SENTINEL_C)
    real = cache_spec(cfg, batch, capacity)

    def leaf_spec(t: jax.ShapeDtypeStruct, r: jax.ShapeDtypeStruct) -> P:
        tshape, rshape = t.shape, r.shape
        parts: list = [None] * len(rshape)
        seq_axis = None
        for i, (td, rd) in enumerate(zip(tshape, rshape)):
            if td == _SENTINEL_B:  # batch axis
                if rd % dprod == 0:
                    parts[i] = tuple(d) if len(d) > 1 else d[0]
                elif len(d) > 1 and rd % sizes[d[-1]] == 0:
                    parts[i] = d[-1]
            elif td == _SENTINEL_C:
                seq_axis = i

        def try_seq() -> bool:
            if seq_axis is not None and rshape[seq_axis] % model == 0 \
                    and parts[seq_axis] is None:
                parts[seq_axis] = "model"
                return True
            return False

        def try_feature() -> bool:
            cand = [
                i for i, (td, rd) in enumerate(zip(tshape, rshape))
                if td not in (_SENTINEL_B, _SENTINEL_C) and parts[i] is None
                and rd % model == 0 and rd >= model and i >= 1
            ]
            if cand:
                parts[cand[0]] = "model"
                return True
            return False

        if kv_policy == "seq_first" and seq_axis is not None:
            try_seq() or try_feature()
        else:
            try_feature() or try_seq()
        return P(*parts)

    return jax.tree_util.tree_map(leaf_spec, template, real)
