"""The peaks table and the roofline arithmetic of the kernel readers."""

import importlib.util
from pathlib import Path

import pytest

import peaks

METRICS = Path(__file__).resolve().parents[1] / "metrics"


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_unknown_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v9 imaginary")


def test_roofline_takes_the_larger_bound():
    # 819 MB in 1 ms is exactly the v5e's HBM bandwidth
    share, bound = peaks.roofline(819e6, 1.0, 1e-3, "TPU v5 lite")
    assert bound == "bytes" and share == pytest.approx(100.0)
    share, bound = peaks.roofline(1.0, 393e9, 2e-3, "TPU v5 lite")
    assert bound == "ops" and share == pytest.approx(50.0)


def test_queue_work_from_shapes():
    m = _reader("queue_check_roofline")
    # 1000 rows of values < 256: 1 + 1 bytes a row; 8 classes of 200 bits
    assert m.work([{"rows": 1000, "space": 200}]) == (2000 + 200, 8000)
    # values past 256 take two bytes
    assert m.work([{"rows": 10, "space": 300}]) == (30 + 300, 80)


def test_stream_work_from_shapes():
    m = _reader("stream_check_roofline")
    # 54,000 rows over 10,000 values and offsets: 1 + 2 bytes a row;
    # 6 verdict sets of 10,000 bits
    assert m.work([{"rows": 54_000, "space": 10_000}]) == (
        54_000 * 3 + 7_500, 540_000)
