"""FLOPs of the dense decoder (refs/dense.py), counted from its shapes.

A multiply-add is two operations.  Per token, per layer: the q, k, v and
output projections and the three SwiGLU matrices, and attention over
``ctx`` keys (``q.k`` and ``p.v``, ``2 * heads * head_dim`` each per key);
once per token, the output head.  Norms, rotary embeddings and the
softmax are left out: they are a fraction of a percent at these widths.
"""

from __future__ import annotations

from typing import Dict, Iterable


def matmul_flops(m: Dict) -> int:
    """FLOPs of the weight products of one token."""
    d, hd = m["hidden_size"], m["head_dim"]
    h, kv, ff = m["num_attention_heads"], m["num_key_value_heads"], m["intermediate_size"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    return 2 * (m["num_hidden_layers"] * per_layer + d * m["vocab_size"])


def attention_flops(m: Dict, ctx: int) -> int:
    """FLOPs of one token's attention over ``ctx`` keys, all layers."""
    return 4 * m["num_hidden_layers"] * m["num_attention_heads"] * m["head_dim"] * ctx


def token_flops(m: Dict, ctxs: Iterable[int]) -> int:
    """FLOPs of producing one token at each context length in ``ctxs``."""
    ctxs = list(ctxs)
    return len(ctxs) * matmul_flops(m) + sum(attention_flops(m, c) for c in ctxs)


def prefill_flops(m: Dict, prompt_len: int) -> int:
    """FLOPs of the prompt positions whose output is not a delivered token:
    positions 0 .. P-2 attend 1 .. P-1 keys (position P-1 produces the
    first output token, counted by ``token_flops`` at context P)."""
    n = prompt_len - 1
    return n * matmul_flops(m) + attention_flops(m, n * (n + 1) // 2)
