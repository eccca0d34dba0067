"""Compile rehearsal: the serving path's Pallas kernels, compiled for a
described (not attached) TPU v5e at phi3-mini-3.8b widths.

Interpret mode cannot see what Mosaic refuses on the chip: block shapes off
the (8, 128) tiling, DMA slices that are not tile-aligned, or more VMEM
than a kernel may use.  These tests run the TPU compiler itself on a
``v5e:2x2`` topology description, one chip's worth, so a kernel that would
not compile on the chip fails here.  Nothing runs and nothing is timed.

The topology is described inside a module fixture (never at import), and
the persistent compilation cache is off around these compiles: a program
compiled for a described chip cannot be read back without one.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import paged_attention as pk

# phi3-mini-3.8b attention: 32 heads, 32 KV heads, head_dim 96 (stored
# padded to 128 lanes), a pool of a few thousand blocks, 2048-token reach
H = KV = 32
D, DP = 96, 128
NUM_BLOCKS = 4096
REACH = 2048


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            jax.config.update("jax_enable_compilation_cache", was_on)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _shapes(sharding, B, bs):
    nblk = REACH // bs

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    pool = (NUM_BLOCKS + 2, KV, bs, DP)
    return (
        sds((B, H, D), jnp.bfloat16),
        sds(pool, jnp.bfloat16),
        sds(pool, jnp.bfloat16),
        sds((NUM_BLOCKS + 2, bs), jnp.int32),
        sds((B, nblk), jnp.int32),
        sds((B,), jnp.int32),
    )


@pytest.mark.parametrize("B", [1, 8])
@pytest.mark.parametrize("bs", [16, 64])
def test_commit_kernel_compiles_for_v5e(one_chip, B, bs):
    compiled = pk.paged_attention.lower(
        *_shapes(one_chip, B, bs), null_bid=NUM_BLOCKS, interpret=False
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("B", [1, 8])
@pytest.mark.parametrize("bs", [16, 64])
def test_fastpath_kernel_compiles_for_v5e(one_chip, B, bs):
    # the split counts the default fast-path policy picks at these batches
    splits = 8 if B < 4 else 4
    compiled = pk.paged_attention_fast.lower(
        *_shapes(one_chip, B, bs), kv_splits=splits, combine_dtype="bfloat16",
        null_bid=NUM_BLOCKS, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_step_programs_carry_pass_scopes_and_kernel_names(one_chip,
                                                          monkeypatch):
    """The engine's fused step and decode step, lowered for a described
    v5e, name their programs (``fused_step``, ``decode_step``), carry each
    sub-pass's scope (``prefill_chunk``, ``decode``, ``verify``) and name
    both paged kernels, so a device trace can split time by pass and find
    each kernel after a refactor.  Smoke widths: nothing is compiled."""
    from repro.configs import get_smoke_config
    from repro.core.determinism import INVARIANT_SCHEDULE, Mode
    from repro.kernels import ops
    from repro.models import init_params
    from repro.serving.engine import Engine

    cfg = get_smoke_config("llama3-8b")
    params = init_params(cfg, jax.random.key(0))
    eng = Engine(cfg, params, mode=Mode.LLM42, window=5, group=2,
                 max_batch=8, capacity=128, prefill_chunk=8)
    # the table-walking kernels are the TPU branch of the paged forward
    monkeypatch.setattr(ops, "on_tpu", lambda: True)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def like(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

    i32, f32 = jnp.int32, jnp.float32
    nblk = eng.pool.layout.blocks_per_table
    B, G, W, C = 2, eng.group, eng.window, eng._chunk_size()
    pargs = (sds((), i32), sds((nblk,), i32),
             sds((1, C, cfg.d_model), jnp.dtype(cfg.dtype)), sds((), i32),
             sds((), i32))
    dargs = (sds((B,), i32), sds((B, nblk), i32), sds((B,), i32),
             sds((B,), i32), sds((B,), i32), sds((B,), f32),
             sds((B,), i32), sds((B,), i32))
    vargs = (sds((G,), i32), sds((G, nblk), i32), sds((G,), i32),
             sds((G, W), i32), sds((G, W - 1), i32), sds((G,), i32),
             sds((G,), i32), sds((G,), f32), sds((G,), i32), sds((G,), i32))
    fast = eng._decode_schedule(B)
    assert fast.kv_splits > 1  # the fast path's split kernel
    fused = eng._fused_fn(C, B, fast, 1).__wrapped__
    text = jax.jit(fused).lower(
        like(params), like(eng.pool.data), None, pargs, dargs, (vargs,)
    ).as_text(debug_info=True)
    assert "@jit_fused_step" in text
    for scope in ("prefill_chunk", "decode", "verify"):
        assert f"jit(fused_step)/{scope}/" in text, scope
    assert '"paged_attention_fast/pallas_call"' in text

    decode = eng._decode_body(B, INVARIANT_SCHEDULE)  # one split: commit kernel
    text = jax.jit(decode).lower(
        like(params), like(eng.pool.data), *dargs
    ).as_text(debug_info=True)
    assert "@jit_decode_step" in text and "jit(decode_step)/decode/" in text
    assert '"paged_attention/pallas_call"' in text
