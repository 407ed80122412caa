"""The verdict read-back's per-layer metric: a traced CPU rehearsal of
each replay cell reports ``collect_ms_per_hist.replay``, and the metric
names those cells."""

import json

import pytest

from conftest import BENCH, run_tiny

NAME = "collect_ms_per_hist.replay"
CELLS = ["qq-replay.fresh", "stream-10k.fresh", "qq-replay.mesh4"]


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_collect(tiny, cell):
    line = run_tiny(tiny, cell, trace=True)
    assert line["correct"] is True
    got = line["metrics"][NAME]
    assert got["unit"] == "ms" and got["value"] > 0


def test_collect_metric_names_the_replay_cells():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    (m,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert m["workloads"] == CELLS and m["moves"] == "histories_per_s"
    assert (BENCH / "metrics" / f"{NAME}.py").is_file()
