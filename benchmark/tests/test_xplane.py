"""The trace reduction, checked on a small trace recorded on a TPU v5e
(``data/small.xplane.pb``, from ``scratch``'s ``small_trace.py`` of PR 22):
a jitted ``kern`` on a [256, 1024] int32 array, run three times, each
call inside a ``bench.pass`` span and followed by a 10 ms sleep inside a
``bench.gap`` span, all inside ``bench.window``."""

from pathlib import Path

import pytest

import xplane

SMALL = Path(__file__).parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def small():
    return xplane.reduce(str(SMALL))


def test_window_and_device(small):
    assert small.devices == 1
    assert small.window_s == pytest.approx(0.034964096)
    # three calls of ~2.3 us of device time each
    assert small.busy_s[0] == pytest.approx(7.057e-6)
    assert small.mean_busy_s == small.busy_s[0]


def test_time_by_module_and_op(small):
    assert small.module_s == {"jit_kern": pytest.approx(7.057e-6)}
    assert small.module_time(r"^jit_kern$") == pytest.approx(7.057e-6)
    assert small.module_time(r"^jit__lambda$") == 0
    assert list(small.op_s) == ["jit_kern/%add_reduce_fusion"]
    assert small.collective_s == 0


def test_gaps_are_labelled_by_the_host_span(small):
    # the three sleeps are the longest idle stretches, longest first
    assert [g[0] for g in small.gaps[:3]] == ["bench.gap"] * 3
    secs = [g[1] for g in small.gaps]
    assert secs == sorted(secs, reverse=True)
    assert 0.010 < secs[0] < 0.013
    idle = small.window_s - small.mean_busy_s
    assert sum(secs) == pytest.approx(idle)


def test_breakdown(small):
    bd = xplane.breakdown(small)
    assert bd["device_ops"] == [["jit_kern/%add_reduce_fusion",
                                 pytest.approx(7.057e-6)]]
    assert len(bd["idle_gaps"]) <= 10
    assert bd["idle_gaps"][0][0] == "bench.gap"


def test_a_trace_without_a_tpu_reads_nothing():
    t = xplane.Trace()
    assert t.devices == 0 and xplane.breakdown(t) is None
    assert t.window_s == 0.0 and t.mean_busy_s is None
