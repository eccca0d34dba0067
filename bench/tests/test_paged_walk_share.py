"""``paged_walk_share``, read from the program's paged-walk counters:

* the reader on a hand-made ``Run``, and None where the counters are
  missing or count nothing (a program without them, or no decode pass);
* one traced run of each cell at smoke size on the CPU reports it as a
  share between 0 and 100.
"""

from __future__ import annotations

import time

import pytest

from bench import harness, spec
from bench.tests.conftest import smoke_cell

NAME = "paged_walk_share"


def _read(counters):
    run = harness.Run(model={}, steps=[], traced=[], counters=counters,
                      compiles=0, trace=None, peaks={}, flops=None,
                      delivered=0)
    return spec.metric_reader(NAME).read(run)


@pytest.mark.parametrize("counters,value", [
    ({"paged.walk_blocks": 120.0, "paged.reach_blocks": 400.0}, 30.0),
    ({"paged.walk_blocks": 400.0, "paged.reach_blocks": 400.0}, 100.0),
    ({}, None),
    ({"paged.walk_blocks": 0.0, "paged.reach_blocks": 0.0}, None),
])
def test_reader_on_counters(counters, value):
    got = _read(counters)
    assert got is None if value is None else got == pytest.approx(value)


@pytest.fixture(autouse=True)
def _no_cache():
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    yield


@pytest.mark.parametrize("workload", ["phi3-chat-det50", "phi3-chat-det0"])
def test_traced_smoke_run_reports_the_walk_share(workload):
    cell = smoke_cell(workload, prompt_max=32, output_max=16)
    assert NAME in [m["name"] for m in cell.per_layer]
    result = harness.run_cell(cell, 2 ** 31 + 31, 12.0, True,
                              time.perf_counter(), require_chip=False,
                              warm=False, log=lambda msg: None)
    value = result["metrics"][NAME]["value"]
    assert isinstance(value, float) and 0 < value <= 100, value
