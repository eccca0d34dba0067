"""The plain references against the program's forward pass, at smoke size.

Both run in f32 on weights drawn by the benchmark's initializer; the
program's forward runs its verify schedule (one f32 pass per product, the
canonical four-chunk tree), so the two differ by f32 rounding alone.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from bench.tests.conftest import smoke_cell

TOL = 2e-4  # f32 rounding through 2 layers, relative to the largest logit


@pytest.mark.parametrize("workload", ["phi3-chat-det50", "rwkv6-chat-det50"])
def test_reference_matches_program_forward(workload):
    from bench import harness, spec
    from bench import weights as weights_mod
    from repro.core.determinism import VERIFY_SCHEDULE
    from repro.models.transformer import forward, init_cache

    conf = smoke_cell(workload).config
    cfg = dataclasses.replace(harness.model_config(conf), dtype="float32")
    params = weights_mod.make(cfg, 3, conf["weights"])
    T = 24
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, conf["vocab_size"], T), jnp.int32)
    want, _, _ = forward(params, cfg, tokens[None], cache=init_cache(cfg, 1, 64),
                         start_pos=jnp.zeros((1,), jnp.int32),
                         schedule=VERIFY_SCHEDULE)
    got = spec.reference(conf["reference"]).logits(params, conf, tokens)
    err = float(jnp.max(jnp.abs(got - want[0])))
    scale = float(jnp.max(jnp.abs(want)))
    assert got.shape == (T, conf["vocab_size"])
    assert err <= TOL * scale, (err, scale)


@pytest.mark.parametrize("workload", ["phi3-chat-det50", "rwkv6-chat-det50"])
def test_fp8_control_departs_from_reference(workload):
    """The control's float8 products move the logits far more than f32
    rounding does."""
    from bench import harness, spec
    from bench import weights as weights_mod

    conf = smoke_cell(workload).config
    cfg = dataclasses.replace(harness.model_config(conf), dtype="float32")
    params = weights_mod.make(cfg, 4, conf["weights"])
    tokens = jnp.arange(16, dtype=jnp.int32)
    ref = spec.reference(conf["reference"])
    a = ref.logits(params, conf, tokens)
    b = ref.logits(params, conf, tokens, quant="fp8")
    assert float(jnp.max(jnp.abs(a - b))) > 100 * TOL * float(jnp.max(jnp.abs(a)))


def test_quantize_rounds_through_fp8():
    from bench.refs.dense import quantize

    x = jnp.asarray([[1.0, 0.3, -0.07, 448.0]])
    q = quantize(x, -1)
    assert float(q[0, 3]) == 448.0  # the absmax maps onto fp8's largest value
    assert float(jnp.max(jnp.abs(q - x))) > 0.0
    assert float(jnp.max(jnp.abs(q - x) / jnp.abs(x))) <= 2.0 ** -3
