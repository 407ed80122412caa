"""The per-layer metrics read from the program's spans and counters: a
traced CPU rehearsal reports each of them in the cells it names, and the
run leaves every file of the benchmark as it was."""

import hashlib
import json

import pytest

from conftest import BENCH, run_tiny

SPAN_METRICS = {
    "qq-replay.fresh": {"assemble_ms_per_hist.replay",
                        "produce_wait_ms_per_hist.replay"},
    "qq-replay.sidecar": {"server_place_p50_ms.sidecar",
                          "server_kernel_p50_ms.sidecar",
                          "server_render_p50_ms.sidecar",
                          "server_frame_p50_ms.sidecar",
                          "client_pack_p50_ms.sidecar"},
}


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
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cells = {m["name"]: set(m["workloads"]) for m in bench["per_layer"]}
    for cell, names in SPAN_METRICS.items():
        for name in names:
            assert cell in cells[name]
            assert (BENCH / "metrics" / f"{name}.py").is_file()
