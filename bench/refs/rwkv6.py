"""Plain reference of RWKV-6 "Finch" as served: f32, jax.numpy only.

Each layer: RMSNorm, then time mixing: token shift, r/k/v/g projections of
the shifted mixes, the data-dependent decay ``w = exp(-exp(w_decay +
tanh(x_w A) B))`` (the Finch LoRA), the WKV recurrence

    out_t = r_t (S_{t-1} + u * k_t^T v_t),   S_t = w_t * S_{t-1} + k_t^T v_t

per head, a per-head normalization of ``out``, a SiLU gate and the output
projection; a residual add; RMSNorm, then channel mixing: token shift,
``sigmoid(x_r W_r) * (relu(x_k W_k)^2 W_v)``; a residual add.  A final
RMSNorm and an untied output head.  The state starts at zero.

Departures from the Finch paper, which the served model shares (so the
comparison holds the program to the same equations):

* the token-shift mixes of r/k/v/g/w are static vectors ``mu``, not the
  paper's data-dependent ``ddlerp``;
* the WKV output is normalized per head by its RMS (epsilon 1e-6) and
  scaled by ``ln_x_scale``, not by GroupNorm with a bias;
* layers are pre-normed with RMSNorm, not LayerNorm, and there is no
  LayerNorm after the embedding.

Nothing here imports the program.  It reads the weight tree the benchmark
drew by name (``blocks/0/{norm0,norm1,rwkv/...}``) and runs layer by
layer, the recurrence as a scan over positions, in f32 at
``Precision.HIGHEST``.  ``quant="fp8"`` takes every matrix product through
float8 e4m3, as in ``refs/dense.py``.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from bench.refs.dense import HIGHEST, mm, rms_norm

F32 = jnp.float32
WKV_EPS = 1e-6


def _shift(x: jax.Array) -> jax.Array:
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], axis=0)


def layer(lp: Dict, x: jax.Array, m: Dict, quant: Optional[str]) -> jax.Array:
    T, D = x.shape
    hd = m["head_size"]
    H = D // hd
    p = {k: v.astype(F32) for k, v in lp["rwkv"].items()}

    h = rms_norm(x, lp["norm0"], m["rms_norm_eps"])
    hs = _shift(h)
    mix = lambda mu: h + (hs - h) * mu  # noqa: E731
    r = mm(mix(p["mu_r"]), p["wr"], quant).reshape(T, H, hd)
    k = mm(mix(p["mu_k"]), p["wk"], quant).reshape(T, H, hd)
    v = mm(mix(p["mu_v"]), p["wv"], quant).reshape(T, H, hd)
    g = mm(mix(p["mu_g"]), p["wg"], quant)
    dd = p["w_decay"] + mm(jnp.tanh(mm(mix(p["mu_w"]), p["w_lora_a"], quant)),
                           p["w_lora_b"], quant)
    w = jnp.exp(-jnp.exp(dd)).reshape(T, H, hd)
    u = p["u_bonus"]  # (H, hd)

    def step(s, t):
        r_t, k_t, v_t, w_t = t
        kv = k_t[:, :, None] * v_t[:, None, :]  # (H, hd_k, hd_v)
        out = jnp.einsum("hk,hkv->hv", r_t, s + u[:, :, None] * kv,
                         precision=HIGHEST)
        return w_t[:, :, None] * s + kv, out

    _, out = jax.lax.scan(step, jnp.zeros((H, hd, hd), F32), (r, k, v, w))
    out = out * jax.lax.rsqrt(jnp.mean(out * out, -1, keepdims=True) + WKV_EPS)
    out = out.reshape(T, D) * p["ln_x_scale"] * jax.nn.silu(g)
    x = x + mm(out, p["wo"], quant)

    h = rms_norm(x, lp["norm1"], m["rms_norm_eps"])
    hs = _shift(h)
    mix = lambda mu: h + (hs - h) * mu  # noqa: E731
    kk = jnp.square(jax.nn.relu(mm(mix(p["cm_mu_k"]), p["cm_wk"], quant)))
    rr = jax.nn.sigmoid(mm(mix(p["cm_mu_r"]), p["cm_wr"], quant))
    return x + rr * mm(kk, p["cm_wv"], quant)


@partial(jax.jit, static_argnames=("m", "quant"))
def _layer_at(blocks, i, x, m, quant):
    lp = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), blocks)
    return layer(lp, x, dict(m), quant)


@partial(jax.jit, static_argnames=("m", "quant"))
def _head(params, x, m, quant):
    x = rms_norm(x, params["final_norm"], dict(m)["rms_norm_eps"])
    return mm(x, params["unembed"], quant)


def logits(params: Dict, model: Dict, tokens: jax.Array,
           quant: Optional[str] = None) -> jax.Array:
    """(T, vocab) f32 logits of ``tokens`` (T,)."""
    m = tuple(sorted((k, v) for k, v in model.items()
                     if isinstance(v, (int, float, str))))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
        blocks = params["blocks"]["0"]
        for i in range(model["num_hidden_layers"]):
            x = _layer_at(blocks, jnp.int32(i), x, m, quant)
        return _head({k: params[k] for k in ("final_norm", "unembed")}, x, m,
                     quant)
