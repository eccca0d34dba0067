"""FLOP and byte functions against counts made by hand at a small shape,
and the peak table."""

from __future__ import annotations

import pytest

DENSE = {"num_hidden_layers": 2, "hidden_size": 8, "intermediate_size": 16,
         "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
         "vocab_size": 10}
RWKV = {"num_hidden_layers": 2, "hidden_size": 8, "head_size": 4,
        "intermediate_size": 16, "vocab_size": 10, "decay_lora_rank": 2}


def test_dense_token_flops_by_hand():
    from bench.flops import dense

    # per layer: q 8*8, k 8*4, v 8*4, o 8*8, gate/up/down 3*8*16 = 576
    # 2 layers 1152 + head 8*10 = 1232 multiply-adds = 2464 FLOPs
    assert dense.matmul_flops(DENSE) == 2464
    # attention over 5 keys: 2 layers * 2 heads * 4 dims * 5 keys * 4
    assert dense.attention_flops(DENSE, 5) == 320
    assert dense.token_flops(DENSE, [5, 1]) == 2 * 2464 + 320 + 64
    # prompt of 3: positions 0, 1 attend 1 and 2 keys
    assert dense.prefill_flops(DENSE, 3) == 2 * 2464 + 64 * 3


def test_rwkv_token_flops_by_hand():
    from bench.flops import rwkv6

    # per layer: 6 d^2 = 384, 2 d ff = 256, 2 d r = 32 -> 672; 2 layers 1344
    # + head 80 = 1424 multiply-adds = 2848 FLOPs
    assert rwkv6.matmul_flops(RWKV) == 2848
    # state: 2 layers * 2 heads * 4 * 16
    assert rwkv6.state_flops(RWKV) == 256
    assert rwkv6.token_flops(RWKV, [7, 900]) == 2 * (2848 + 256)
    assert rwkv6.prefill_flops(RWKV, 4) == 3 * (2848 + 256)


def test_paged_attention_flops_bytes_by_hand():
    from bench.flops import paged_attention as pa

    # ctx 5 in blocks of 4: 2 blocks = 8 keys read; per layer
    # K+V: 2 * 1 head * 4 dims * 8 keys * 2 B = 128; q bf16 + out f32:
    # 2 heads * 4 dims * 6 B = 48; flops 4 * 2 * 4 * 5 = 160
    f, b = pa.row_flops_bytes(DENSE, 5, 4)
    assert (f, b) == (2 * 160, 2 * 176)
    assert pa.pass_flops_bytes(DENSE, [5, 5], 4) == (4 * 160, 4 * 176)


def test_peak_table():
    from bench import spec

    v5e = spec.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")
