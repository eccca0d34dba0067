"""Least work of the paged decode-attention kernel for one decode pass.

For each decode row the kernel needs the K and V of the blocks in its
table, at the model's head dim and dtype, once per layer, plus its query
and output; and ``4 * heads * head_dim`` FLOPs per key.  Bytes are counted
from the table blocks (``ceil(ctx / block_size)`` of them), not from the
table's whole reach or the lane padding of the stored head dim: what a
kernel that reads only what the row holds would move.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple


def row_flops_bytes(m: Dict, ctx: int, block_size: int,
                    itemsize: int = 2) -> Tuple[int, int]:
    L, H, KV, hd = (m["num_hidden_layers"], m["num_attention_heads"],
                    m["num_key_value_heads"], m["head_dim"])
    keys = -(-ctx // block_size) * block_size
    flops = 4 * H * hd * ctx
    kv_bytes = 2 * KV * hd * keys * itemsize
    qo_bytes = H * hd * (itemsize + 4)  # bf16 query in, f32 output out
    return L * flops, L * (kv_bytes + qo_bytes)


def pass_flops_bytes(m: Dict, ctxs: Iterable[int], block_size: int,
                     itemsize: int = 2) -> Tuple[int, int]:
    f = b = 0
    for c in ctxs:
        df, db = row_flops_bytes(m, c, block_size, itemsize)
        f, b = f + df, b + db
    return f, b
