"""The CPU rehearsal: every cell of ``BENCHMARK.json`` driven end to end
at a tiny size, and the command itself refusing a machine with no chip."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, run_tiny

import run

BENCHMARK = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
DEVICE_METRICS = {m["name"] for m in BENCHMARK["per_layer"]
                  if m["source"] == "device_trace"}


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_line(tiny, cell, trace):
    line = run_tiny(tiny, cell, trace=trace)
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["count"] >= 1
    c = run.find_cell(cell, root=tiny)
    want = c.per_layer if trace else c.end_to_end
    for m in want:
        if m["name"] in DEVICE_METRICS:  # a CPU run reads no device number
            assert m["name"] not in line["metrics"]
        else:
            got = line["metrics"][m["name"]]
            assert got["unit"] == m["unit"] and got["value"] >= 0
    assert set(line["metrics"]) <= {m["name"] for m in want}
    assert "busy_s" not in dev and "breakdown" not in line


def test_command_refuses_a_machine_without_a_chip(tiny):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=tiny, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not p.stdout.strip()
