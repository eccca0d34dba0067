"""The check that decides ``correct``, driven through a whole run at smoke
size on the CPU (the harness's look for a chip skipped, no warm-up):

* a sound run is correct;
* the control, the float8 reference in the program's place, reads a gap
  at least three times the program's;
* each fault a serving cell can have, planted in the timed path, makes
  ``correct`` false: a token altered where it is produced, and a step that
  returns its state unchanged (the KV or recurrent state it wrote dropped).
"""

from __future__ import annotations

import time

import pytest

from bench.tests.conftest import smoke_cell

SECONDS = 25.0


def _run(workload, **kw):
    from bench import harness

    cell = smoke_cell(workload, prompt_max=32, output_max=16)
    return harness.run_cell(cell, 2 ** 31 + 17, SECONDS, False,
                            time.perf_counter(), require_chip=False,
                            warm=False, log=lambda msg: None, **kw)


@pytest.fixture(autouse=True)
def _no_cache():
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    yield


def test_sound_run_is_correct_and_control_separates():
    sound = _run("phi3-chat-det50")
    assert sound["correct"], sound["check"]
    ctl = _run("phi3-chat-det50", control=True)
    assert ctl["check"]["logit_gap"]["value"] >= 3 * max(
        sound["check"]["logit_gap"]["value"], 1e-3)


def _token_altered(monkeypatch):
    from repro.serving import engine

    real = engine.sample_batch

    def altered(logits, *a, **k):
        return (real(logits, *a, **k) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "sample_batch", altered)


def _state_unchanged(monkeypatch):
    from repro.serving import kv_cache

    monkeypatch.setattr(kv_cache, "scatter_mixed",
                        lambda pool, *a, **k: pool)
    monkeypatch.setattr(kv_cache, "scatter", lambda pool, *a, **k: pool)


@pytest.mark.parametrize("workload,fault", [
    ("phi3-chat-det0", _token_altered),
    ("phi3-chat-det50", _state_unchanged),
    ("rwkv6-chat-det50", _state_unchanged),
])
def test_fault_makes_run_incorrect(monkeypatch, workload, fault):
    fault(monkeypatch)
    assert not _run(workload)["correct"]
