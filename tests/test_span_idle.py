"""``tools/span_idle.py``: the reduction of a profiler capture to device
idle time by the innermost span, and to the share of an outer
annotation the program's spans cover."""

from __future__ import annotations

import importlib.util
import time
from pathlib import Path

import jax
import pytest

from jepsen_tpu.obs import trace as obs_trace

TOOL = Path(__file__).resolve().parents[1] / "tools" / "span_idle.py"


@pytest.fixture(scope="module")
def span_idle():
    spec = importlib.util.spec_from_file_location("span_idle_under_test",
                                                  TOOL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_flatten_labels_the_innermost_span(span_idle):
    segs = span_idle.flatten([(0, 10, "a"), (1, 3, "b"), (2, 3, "c"),
                              (5, 6, "d"), (12, 14, "e")])
    assert segs == [(0, 1, "a"), (1, 2, "b"), (2, 3, "c"), (3, 5, "a"),
                    (5, 6, "d"), (6, 10, "a"), (12, 14, "e")]


def test_overlap_by_label(span_idle):
    segs = [(0, 4e9, "a"), (4e9, 6e9, "b"), (8e9, 9e9, "a")]
    idle = [(1e9, 5e9), (5.5e9, 8.5e9)]
    got = span_idle.overlap_by_label(segs, idle)
    assert got == pytest.approx({"a": 3.5, "b": 1.5})


def test_coverage_of_a_cpu_capture(span_idle, tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.pass"):
                with obs_trace.span("pipeline.wait"):
                    time.sleep(0.03)
                time.sleep(0.01)
    (path,) = tmp_path.rglob("*.xplane.pb")
    got = span_idle.analyse(str(path))
    assert got["devices"] == 0  # a CPU capture has no device plane
    cov = got["coverage"]["bench.pass"]
    assert cov["by_span_s"]["pipeline.wait"] == pytest.approx(0.03, rel=0.5)
    assert 0.5 < cov["covered"] < 0.95


def test_idle_split_of_a_recorded_v5e_trace(span_idle):
    """The benchmark's recorded trace (one v5e device plane): the idle
    split adds up to the window less the device's busy time."""
    path = TOOL.parents[1] / "benchmark" / "tests" / "data" / "small.xplane.pb"
    got = span_idle.analyse(str(path))
    assert got["devices"] == 1
    assert 0 < got["idle_s"] < got["window_s"]
    split = got["idle_by_span"]["bench.pass"]
    assert sum(split.values()) == pytest.approx(got["idle_s"])
    assert split["bench.gap"] > 0
