"""The per-layer metrics read from the program's span counters:

* each reader on a hand-made ``Run``, and None where its counters are
  missing or count nothing (a program without the spans);
* one traced run of each cell at smoke size on the CPU reports every
  metric the cell lists as a number.
"""

from __future__ import annotations

import time

import pytest

from bench import harness, spec
from bench.tests.conftest import smoke_cell

NEW = ("step_host_ms", "verify_host_ms", "prefill_ms", "queue_wait_ms")


def _run(counters):
    return harness.Run(model={}, steps=[], traced=[], counters=counters,
                       compiles=0, trace=None, peaks={}, flops=None,
                       delivered=0)


def _read(name, counters):
    return spec.metric_reader(name).read(_run(counters))


#: a window of 100 steps: 9 s in steps, 6 s of it waiting on the device
WINDOW = {
    "span.engine.step.s": 9.0, "span.engine.step.n": 100.0,
    "span.engine.wait.s": 6.0, "span.engine.wait.n": 100.0,
    "span.engine.verdicts.s": 0.2, "span.engine.verdicts.n": 100.0,
    "span.engine.verify_prep.s": 0.15, "span.engine.verify_prep.n": 40.0,
    "span.engine.verify_post.s": 0.05, "span.engine.verify_post.n": 40.0,
    "span.engine.prefill.s": 1.2, "span.engine.prefill.n": 20.0,
    "latency.queue_wait.sum": 0.5, "latency.queue_wait.count": 20.0,
}


@pytest.mark.parametrize("name,value", [
    ("step_host_ms", 30.0),  # (9 - 6) s / 100 steps
    ("verify_host_ms", 4.0),  # (0.2 + 0.15 + 0.05) s / 100 steps
    ("prefill_ms", 60.0),  # 1.2 s / 20 prefills
    ("queue_wait_ms", 25.0),  # 0.5 s / 20 admissions
])
def test_reader_on_counters(name, value):
    assert _read(name, WINDOW) == pytest.approx(value)


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_without_its_counters(name):
    assert _read(name, {}) is None
    zero = {k: 0.0 for k in WINDOW}
    assert _read(name, zero) is None


def test_verify_host_needs_a_verify_group():
    no_verify = {k: v for k, v in WINDOW.items() if "verify_" not in k}
    assert _read("verify_host_ms", no_verify) is None
    assert _read("step_host_ms", no_verify) == pytest.approx(30.0)


@pytest.fixture(autouse=True)
def _no_cache():
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    yield


@pytest.mark.parametrize("workload", ["phi3-chat-det50", "phi3-chat-det0"])
def test_traced_smoke_run_reports_the_cells_span_metrics(workload):
    cell = smoke_cell(workload, prompt_max=32, output_max=16)
    listed = [m["name"] for m in cell.per_layer if m["name"] in NEW]
    assert set(listed) == ({"step_host_ms", "prefill_ms", "queue_wait_ms"}
                           | ({"verify_host_ms"} if "det50" in workload
                              else set()))
    result = harness.run_cell(cell, 2 ** 31 + 29, 12.0, True,
                              time.perf_counter(), require_chip=False,
                              warm=False, log=lambda msg: None)
    for name in listed:
        value = result["metrics"][name]["value"]
        assert isinstance(value, float) and value >= 0, (name, value)
