"""The benchmark's own weights: drawn on the device from the seed.

Shapes and init kinds (zeros, ones, normal) come from the program's
parameter specs; the scales are the benchmark's, written in each
configuration file, so a change of the program's initializer does not
move the yardstick.  A normal leaf whose own shape (without the stacked
``layers`` axis) has two or more axes is a matrix and gets
``shape[-2] ** -0.5``, the fan-in of ``x @ w``; any other normal leaf gets
the file's ``vector_scale``.  The whole tree is one jitted call that
returns every leaf in the served dtype.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp


def leaf_scale(shape, axes, rule: Dict) -> float:
    own = [s for s, a in zip(shape, axes) if a != "layers"]
    if len(own) >= 2:
        return float(shape[-2]) ** -0.5
    return float(rule["vector_scale"])


def make(cfg, seed: int, rule: Dict) -> Dict[str, Any]:
    """Weights of ``cfg`` from ``seed`` under ``rule`` (one jitted call)."""
    from repro.models.base import param_specs

    specs = param_specs(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(specs)
    dtype = jnp.dtype(cfg.dtype)

    def draw(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for spec, k in zip(leaves, keys):
            if spec.init == "zeros":
                out.append(jnp.zeros(spec.shape, dtype))
            elif spec.init == "ones":
                out.append(jnp.ones(spec.shape, dtype))
            else:
                scale = leaf_scale(spec.shape, spec.axes, rule)
                out.append((jax.random.normal(k, spec.shape, jnp.float32)
                            * scale).astype(dtype))
        return out

    s = int(seed) % (1 << 64)
    key = jax.random.fold_in(jax.random.key(s & 0xFFFFFFFF), s >> 32)
    return jax.tree_util.tree_unflatten(treedef, jax.jit(draw)(key))
