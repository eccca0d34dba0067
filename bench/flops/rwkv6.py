"""FLOPs of RWKV-6 Finch as served (refs/rwkv6.py), counted from its shapes.

A multiply-add is two operations.  Per token, per layer: the r, k, v, g
and output projections, the decay LoRA (``d x rank`` and ``rank x d``),
and the channel mix (``d x ffn``, ``ffn x d``, ``d x d``); the WKV
recurrence, per head, reads the state with ``r`` and adds the rank-one
``k^T v`` into it (``2 * head_size^2`` each); once per token, the output
head.  The work of a token does not depend on its context.
"""

from __future__ import annotations

from typing import Dict, Iterable


def matmul_flops(m: Dict) -> int:
    d, ff, r = m["hidden_size"], m["intermediate_size"], m["decay_lora_rank"]
    per_layer = 6 * d * d + 2 * d * ff + 2 * d * r
    return 2 * (m["num_hidden_layers"] * per_layer + d * m["vocab_size"])


def state_flops(m: Dict) -> int:
    d, hd = m["hidden_size"], m["head_size"]
    return m["num_hidden_layers"] * (d // hd) * 4 * hd * hd


def token_flops(m: Dict, ctxs: Iterable[int]) -> int:
    return len(list(ctxs)) * (matmul_flops(m) + state_flops(m))


def prefill_flops(m: Dict, prompt_len: int) -> int:
    return (prompt_len - 1) * (matmul_flops(m) + state_flops(m))
