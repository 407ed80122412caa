"""The per-layer metrics read from the program's spans and counters: a
traced CPU rehearsal reports each of them in the cells it names, and the
run leaves every file of the benchmark as it was."""

import hashlib

import pytest

from conftest import BENCH, BENCHMARK, CELLS, run_tiny

import run

#: per cell, the metrics its traced runs read from the program's spans
SPAN_METRICS = {
    cell: {m["name"] for m in run.find_cell(cell).per_layer
           if m["source"] == "program_span"}
    for cell in CELLS}


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "benchmark").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.mark.parametrize("cell", sorted(SPAN_METRICS))
def test_traced_rehearsal_reports_the_span_metrics(tiny, cell):
    before = _digests(tiny)
    line = run_tiny(tiny, cell, trace=True)
    assert line["correct"] is True
    for name in SPAN_METRICS[cell]:
        got = line["metrics"][name]
        assert got["unit"] == "ms" and got["value"] > 0, name
    assert _digests(tiny) == before


def test_each_span_metric_names_its_cells():
    for m in BENCHMARK["per_layer"]:
        if m["source"] == "program_span":
            assert set(m.get("workloads", CELLS)) <= set(CELLS)
            assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
