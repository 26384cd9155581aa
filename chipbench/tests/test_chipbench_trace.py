"""The trace reduction, on a small trace recorded on one TPU v5e chip by
chipbench/testdata/record_trace.py (two steps of matmul, ring ppermute,
matmul inside the harness's host spans), against numbers worked out by
hand from its events:

    window  [44,936,557 ; 52,151,136] ns = 7,214,579 ns
    step 1  copy-start 13, copy-done 3, convolution_tanh_fusion 89,692,
            collective-permute-start 1,367, collective-permute-done 6,
            fusion 90,872 ns, back to back from 46,867,101 ns
    step 2  the same from 50,067,617 ns (copy-done 2, fusion 90,875)

busy = 181,953 + 181,955 = 363,908 ns; the two collectives of a step
run while no compute does: 2 x 1,373 ns exposed; the longest idle gaps
are 3,018,556 ns (host in ``wait``), 1,930,544 (``data``) and 1,901,557
(``data``)."""
import os

import pytest

from chipbench import tracing

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(os.path.dirname(HERE), "testdata", "one_chip.xplane.pb")
WINDOW = 7_214_579


@pytest.fixture(scope="module")
def trace():
    return tracing.reduce_xplane(TRACE)


def test_reduction_keeps_the_device_ops_and_host_spans(trace):
    assert sorted(trace["devices"]) == ["0"]
    ops = trace["devices"]["0"]
    assert len(ops) == 12
    assert [o[3] for o in ops[:6]] == [
        "copy-start", "copy-done", "fusion", "collective-permute-start",
        "collective-permute-done", "fusion"]
    assert [h[0] for h in trace["host"]] == [
        "window", "data", "dispatch", "wait", "data", "dispatch", "wait"]
    assert tracing.window(trace) == (44_936_557.0, 52_151_136.0)


def test_busy_union_and_idle_share(trace):
    assert tracing.busy(trace, "0") == 363_908
    assert tracing.busy_share(trace) == pytest.approx(363_908 / WINDOW)


def test_kernel_sums(trace):
    assert tracing.kernel_events(trace, "convolution_tanh_fusion") == (
        2, 179_384)
    assert tracing.kernel_events(trace, "flash_attention") == (0, 0.0)


def test_exposed_collective_share(trace):
    assert tracing.exposed_collective_share(trace) == pytest.approx(
        2 * 1_373 / WINDOW)


def test_gap_attribution(trace):
    gaps = tracing.idle_gaps(trace, "0")
    assert gaps[:3] == [("wait", 3_018_556e-9), ("data", 1_930_544e-9),
                        ("data", 1_901_557e-9)]
    assert sum(g for _, g in gaps) == pytest.approx(
        (WINDOW - 363_908) * 1e-9)


def test_interval_arithmetic():
    assert tracing.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tracing.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tracing.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]
