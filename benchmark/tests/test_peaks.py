"""The peaks table and the roofline arithmetic of the kernel readers.

Each roofline reader's ``work(shapes)`` is held to its file
``work/<metric>.json``: shapes of histories, the ``(bytes, ops)`` they
take, and why."""

import importlib.util
import json

import pytest

import peaks
from conftest import BENCH, BENCHMARK, HOOKS

METRICS = BENCH / "metrics"
WORK = {p.stem: json.loads(p.read_text())
        for p in sorted((HOOKS / "work").glob("*.json"))}


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


def test_every_roofline_has_its_work_cases():
    for m in BENCHMARK["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["name"] in WORK


@pytest.mark.parametrize("name,case", [
    (n, c) for n, w in WORK.items() for c in w["cases"]])
def test_work_from_shapes(name, case):
    assert _reader(name).work(case["shapes"]) == tuple(case["work"]), \
        case["why"]
