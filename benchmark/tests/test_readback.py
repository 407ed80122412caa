"""The verdict read-back's per-layer metric: a traced CPU rehearsal of
each cell it names reports ``collect_ms_per_hist.replay``, and it names
the replay cells."""

import pytest

from conftest import BENCH, BENCHMARK, run_tiny

import run

NAME = "collect_ms_per_hist.replay"
(METRIC,) = [m for m in BENCHMARK["per_layer"] if m["name"] == NAME]
CELLS = METRIC["workloads"]


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_collect(tiny, cell):
    line = run_tiny(tiny, cell, trace=True)
    assert line["correct"] is True
    got = line["metrics"][NAME]
    assert got["unit"] == "ms" and got["value"] > 0


def test_collect_metric_names_the_replay_cells():
    replay = [w["name"] for w in BENCHMARK["workloads"]
              if run.find_cell(w["name"]).traffic["driver"] == "replay"]
    assert CELLS == replay and METRIC["moves"] == "histories_per_s"
    assert (BENCH / "metrics" / f"{NAME}.py").is_file()
