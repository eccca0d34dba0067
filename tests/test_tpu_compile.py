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
