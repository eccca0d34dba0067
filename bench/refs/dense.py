"""Plain reference of the dense decoder (phi3-mini): f32, jax.numpy only.

Pre-norm decoder layers as the Phi-3 report describes them (a Llama-style
block): RMSNorm, rotary embeddings on the two halves of each head
(``rotate_half``), causal softmax attention with grouped KV heads, a
residual add, RMSNorm, a SwiGLU feed-forward, a residual add; a final
RMSNorm and an untied output head.  Every product runs in f32 at
``Precision.HIGHEST`` on the benchmark's bf16 weights, upcast.

Departures from the published model: Phi-3-mini fuses its q/k/v and its
gate/up projections into one matrix each; split matrices compute the same
products.  The published 4k model declares a 2047-token sliding window;
the cells serve at most 1536 positions, where it never masks a key, so the
reference (like the program) attends to all earlier positions.

Nothing here imports the program.  It reads the weight tree the benchmark
drew, by name: ``embed``, ``final_norm``, ``unembed`` and the stacked
``blocks/0/{norm0,norm1,attn/{wq,wk,wv,wo},ffn/{wi_gate,wi_up,wo}}``.
It runs layer by layer (one compiled layer, a dynamic layer index), so at
full width it holds one layer's f32 activations at a time.

``quant="fp8"`` is the control: every matrix product takes its operands
through float8 e4m3, scaled per row of the activations and per column of
the weights, the precision step below the configuration's bf16.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def quantize(a: jax.Array, axis: int) -> jax.Array:
    """``a`` rounded through float8 e4m3 with an absmax scale on ``axis``."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def mm(x: jax.Array, w: jax.Array, quant: Optional[str]) -> jax.Array:
    w = w.astype(F32)
    if quant == "fp8":
        x, w = quantize(x, -1), quantize(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * inv * g.astype(F32)


def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x: (T, H, D); rotate the first and second halves of each head."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos[:, None].astype(F32) * freqs  # (T, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(lp: Dict, x: jax.Array, m: Dict, quant: Optional[str]) -> jax.Array:
    T = x.shape[0]
    H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    pos = jnp.arange(T)
    a = lp["attn"]
    h = rms_norm(x, lp["norm0"], m["rms_norm_eps"])
    q = rope(mm(h, a["wq"], quant).reshape(T, H, hd), pos, m["rope_theta"])
    k = rope(mm(h, a["wk"], quant).reshape(T, KV, hd), pos, m["rope_theta"])
    v = mm(h, a["wv"], quant).reshape(T, KV, hd)
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST) * hd ** -0.5
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hts,shd->thd", p, v, precision=HIGHEST).reshape(T, H * hd)
    x = x + mm(o, a["wo"], quant)
    f = lp["ffn"]
    h = rms_norm(x, lp["norm1"], m["rms_norm_eps"])
    g = jax.nn.silu(mm(h, f["wi_gate"], quant)) * mm(h, f["wi_up"], quant)
    return x + mm(g, f["wo"], quant)


@partial(jax.jit, static_argnames=("m", "quant"))
def _layer_at(blocks, i, x, m, quant):
    lp = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), blocks)
    return layer(lp, x, dict(m), quant)


@partial(jax.jit, static_argnames=("m", "quant"))
def _head(params, x, m, quant):
    x = rms_norm(x, params["final_norm"], dict(m)["rms_norm_eps"])
    w = params["embed"].T if "unembed" not in params else params["unembed"]
    return mm(x, w, quant)


def logits(params: Dict, model: Dict, tokens: jax.Array,
           quant: Optional[str] = None) -> jax.Array:
    """(T, vocab) f32 logits of ``tokens`` (T,), each position predicting
    the next token."""
    m = tuple(sorted((k, v) for k, v in model.items()
                     if isinstance(v, (int, float, str))))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
        blocks = params["blocks"]["0"]
        for i in range(model["num_hidden_layers"]):
            x = _layer_at(blocks, jnp.int32(i), x, m, quant)
        return _head({k: params[k] for k in ("embed", "final_norm", "unembed")
                      if k in params}, x, m, quant)
