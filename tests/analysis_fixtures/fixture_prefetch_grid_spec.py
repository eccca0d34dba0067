"""SEEDED VIOLATIONS (do not fix): a scalar-prefetch kernel whose grid and
specs live in ``grid_spec=``.

The lint must read ``PrefetchScalarGridSpec`` like plain ``pallas_call``
keywords.  It must flag:
  * kernel_lint/grid-reduction-extent  (the table walk is a grid axis whose
    extent is the runtime table length)
  * kernel_lint/accum-dtype  (``acc_ref`` is a bf16 accumulator)
and must NOT flag the bf16 ``kbuf`` scratch: the kernel only fills it by
DMA, so it is a staging copy of the pool, not an accumulator.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BF16 = jnp.bfloat16


def _stage(tab_ref, k_hbm, buf, sem, *, row, j):
    cp = pltpu.make_async_copy(k_hbm.at[tab_ref[row]], buf.at[j], sem.at[0])
    cp.start()
    cp.wait()


def _kernel(tab_ref, q_ref, k_hbm, o_ref, kbuf, acc_ref, sem):
    j = pl.program_id(1)
    _stage(tab_ref, k_hbm, kbuf, sem, row=pl.program_id(0), j=j)
    s = jnp.dot(q_ref[...], kbuf[j].T, preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] + s.astype(BF16)  # VIOLATION: bf16 fold
    o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def walk(q, k_pool, tables):
    B, D = q.shape
    NB, bs, _ = k_pool.shape
    nblk = tables.shape[1]
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            # VIOLATION: axis 1 folds into one output tile; its extent is
            # the runtime table reach
            grid=(B, nblk),
            in_specs=[
                pl.BlockSpec((None, D), lambda b, j, t: (b, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, bs), lambda b, j, t: (b, 0)),
            scratch_shapes=[
                pltpu.VMEM((nblk, bs, D), BF16),
                pltpu.VMEM((1, bs), BF16),
                pltpu.SemaphoreType.DMA((1,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, bs), jnp.float32),
        interpret=True,
    )(tables.reshape(-1), q, k_pool)
