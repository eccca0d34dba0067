"""The trace reduction on a small recorded trace, against values worked
out by hand from ``data/small_trace.pbtxt``:

* loop annotations span 500 .. 8000 ns: a 7500 ns window;
* device ops (the "XLA Ops" line; "XLA Modules" is not read) cover
  1000..3000 and 2500..4000 (one busy interval 1000..4000) and
  6000..7000: 4000 ns busy;
* idle gaps 500..1000 and 7000..8000 fall in ``bench.step`` (1500 ns);
  4000..6000 overlaps step 200, collect 1600, step 200: ``bench.collect``;
* the paged kernel ran 1500 ns.
"""

from __future__ import annotations

from pathlib import Path

import pytest

from bench import loop, tracing

DATA = Path(__file__).parent / "data" / "small_trace.pbtxt"


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_text_proto(DATA.read_text()).planes)
    return tracing.reduce(planes, (loop.STEP, loop.SUBMIT, loop.COLLECT))


def test_window_and_busy(summary):
    assert summary.window_s == pytest.approx(7.5e-6)
    assert summary.busy_s == pytest.approx(4.0e-6)
    assert summary.n_devices == 1


def test_idle_by_host(summary):
    assert summary.idle_by_host == pytest.approx(
        {"bench.step": 1.5e-6, "bench.collect": 2.0e-6})
    assert summary.idle_gaps[0] == ("bench.collect", pytest.approx(2e-6))
    assert sum(s for _, s in summary.idle_gaps) == pytest.approx(3.5e-6)


def test_kernel_time_and_top_ops(summary):
    assert summary.kernel_seconds("paged_attention") == pytest.approx(1.5e-6)
    assert [n for n, _ in summary.device_ops] == [
        "fusion.1", "paged_attention_fast", "fusion.2"]
    assert "jit_fused" not in summary.op_seconds


def test_union_and_gaps():
    assert tracing.union([(5, 6), (1, 3), (2, 4)]) == [(1, 4), (5, 6)]
    assert tracing.gaps([(1, 4), (5, 6)], 0, 8) == [(0, 1), (4, 5), (6, 8)]


def test_no_device_plane_reads_nothing():
    assert tracing.reduce([], (loop.STEP,)) is None
