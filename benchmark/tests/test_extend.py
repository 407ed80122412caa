"""A later PR adds a configuration, a traffic mix, a cell, a per-layer
metric, a checker family and the alteration its cell's faults make as
files and entries alone: the harness and its tests find them by name, and
no file that was there changes."""

import hashlib
import json
import os
import subprocess
import sys

from conftest import run_tiny
from test_correct import check_hooks, fault_cases, plant

READER = '''"""Campaign passes the window completed."""


def read(ctx):
    return ctx.counts.get("passes") or None
'''


def _digests(root):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "benchmark").rglob("*")) if p.is_file()}


def test_new_cell_mix_and_metric_from_files(tiny):
    """A smaller configuration of the first replay cell's, a mix of its
    own and a reader, as one more cell."""
    before = _digests(tiny)
    bench = tiny / "benchmark"
    path = tiny / "BENCHMARK.json"
    b = json.loads(path.read_text())
    base = next(w for w in b["workloads"] if json.loads(
        (bench / "traffic" / f"{w['traffic']}.json").read_text()
    )["driver"] == "replay")
    (entry,) = [c for c in b["configs"] if c["name"] == base["config"]]
    conf = json.loads((tiny / entry["file"]).read_text())
    conf.update(name="small", spec={**conf["spec"], "n_ops": 40},
                campaign={**conf["campaign"], "histories": 16})
    (bench / "configs" / "small.json").write_text(json.dumps(conf))
    (bench / "traffic" / "replay-blocks8.json").write_text(json.dumps({
        "driver": "replay", "use_cache": False, "fail_fast": True,
        "shuffle_block": 8}))
    (bench / "metrics" / "passes.blocks8.py").write_text(READER)

    b["configs"].append({"name": "small", "source": "a test",
                         "file": "benchmark/configs/small.json",
                         "reduced": ["n_ops"], "why": "a test"})
    b["workloads"].append({"name": "small.blocks8", "config": "small",
                           "traffic": "replay-blocks8", "chips": 1,
                           "why": "a test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if base["name"] in m.get("workloads", ()):
            m["workloads"].append("small.blocks8")
    b["per_layer"].append({"name": "passes.blocks8", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "pipeline", "moves": "histories_per_s",
                           "workloads": ["small.blocks8"]})
    path.write_text(json.dumps(b))

    line = run_tiny(tiny, "small.blocks8")
    assert line["correct"] is True
    assert set(line["metrics"]) == {"histories_per_s", "setup_s"}
    line = run_tiny(tiny, "small.blocks8", trace=True)
    assert line["correct"] is True
    assert line["metrics"]["passes.blocks8"]["value"] >= 1
    assert "ingest_ms_per_hist.replay" in line["metrics"]

    after = _digests(tiny)
    assert {p: d for p, d in after.items() if p in before} == before


FAMILY = '''"""A toy family: one process adds even numbers; ``odd`` adds a 1."""

from gen import _history

INJECT = ("odd",)
PROGRAM_OPTS = frozenset()
LABELS = frozenset()


def ops(seed, spec, inject):
    rng, ops, tick, lat, emit = _history(seed, spec)
    values = [2 * rng.randrange(50) for _ in range(spec["n_ops"])]
    for v in values + [1] * inject.get("odd", 0):
        t0 = tick()
        emit("invoke", "add", 0, v, t0)
        emit("ok", "add", 0, v, t0 + lat())
    return ops


def shape(ops):
    return {"ops": len(ops)}


def check(ops, opts):
    total = sum(op["value"] for op in ops if op["type"] == "ok")
    return {"tally": {"valid?": total % 2 == 0, "total": total}}
'''

DRIVER = '''"""A toy entry: each history's total, summed on the device."""

import json
import time


class Driver:
    def __init__(self, cell, campaign, devices):
        self.paths = [h["path"] for h in campaign]
        self.got = []
        self.failed = 0

    def _answer(self, path):
        import jax.numpy as jnp

        with open(path) as fh:
            vals = [op["value"] for op in map(json.loads, fh)
                    if op["type"] == "ok"]
        total = int(jnp.sum(jnp.asarray(vals)))
        return {"tally": {"valid?": total % 2 == 0, "total": total}}

    def warm(self):
        self._answer(self.paths[0])

    def window(self, seconds):
        t0 = time.perf_counter()
        self.got = [(i, self._answer(p)) for i, p in enumerate(self.paths)]
        self.seconds = time.perf_counter() - t0

    def close(self):
        pass

    @property
    def attempted(self):
        return len(self.got)

    def end_to_end(self):
        return {"histories_per_s": len(self.got) / self.seconds}

    def counts(self):
        return {"histories": len(self.got)}

    def answers(self):
        return "per_history", self.got
'''

#: run in the checkout, with its own ``gen``, ``reference`` and ``run``:
#: the planner's refusal, then a sound run and one whose answers are off
SCRIPT = '''
import json, os, sys
sys.path[:0] = [os.path.abspath("benchmark"), os.path.abspath(".")]
import gen, run

try:
    gen.plan("tally", {"histories": 2, "anomalies": [{"lost": 1}]}, 1)
    refused = None
except ValueError as e:
    refused = str(e)


def off(driver):
    right = driver._answer
    driver._answer = lambda p: {"tally": {**right(p)["tally"], "total": -1}}


lines = []
for hook in (None, off):
    cell = run.find_cell("tally-small.sum")
    cell.seed = 2**31 + 21
    lines.append(run.run_cell(cell, 0.5, False, accept=("cpu",), workers=2,
                              hook=hook))
print(json.dumps({"refused": refused, "lines": lines}))
'''


def test_new_family_from_files(tiny):
    """A configuration of a family the harness has never seen, and its
    cell, added as files and entries alone: synthesis and the reference
    find the family's file inside the spawned pool, the planner refuses
    an injection the family does not declare, and no file that was there
    changes."""
    before = _digests(tiny)
    bench = tiny / "benchmark"
    (bench / "families" / "tally.py").write_text(FAMILY)
    (bench / "drivers" / "tally.py").write_text(DRIVER)
    (bench / "traffic" / "tally-once.json").write_text(
        json.dumps({"driver": "tally"}))
    (bench / "configs" / "tally-small.json").write_text(json.dumps({
        "name": "tally-small", "family": "tally", "spec": {"n_ops": 40},
        "campaign": {"histories": 8, "clean_share": 0.5,
                     "anomalies": [{"odd": 1}]},
        "checker": {}, "control": {}}))
    path = tiny / "BENCHMARK.json"
    b = json.loads(path.read_text())
    b["configs"].append({"name": "tally-small", "source": "a test",
                         "file": "benchmark/configs/tally-small.json",
                         "reduced": [], "why": "a test"})
    b["workloads"].append({"name": "tally-small.sum", "config": "tally-small",
                           "traffic": "tally-once", "chips": 1,
                           "why": "a test"})
    for m in b["end_to_end"]:
        if m["name"] == "histories_per_s":
            m["workloads"].append("tally-small.sum")
    path.write_text(json.dumps(b))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tiny / ".bench_cache"))
    p = subprocess.run([sys.executable, "-c", SCRIPT], cwd=tiny, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.splitlines()[-1])
    assert "cannot inject ['lost']" in got["refused"]
    sound, off = got["lines"]
    assert sound["correct"] is True
    assert sound["checks"]["compared"]["value"] == 8
    assert set(sound["metrics"]) == {"histories_per_s", "setup_s"}
    assert off["correct"] is False
    assert off["checks"]["mismatched"]["value"] == 8

    after = _digests(tiny)
    assert {p: d for p, d in after.items() if p in before} == before


FLIP = '''"""Turns over the first history's first sub-verdict alone."""

import dataclasses


def answer(out):
    first, *rest = out if isinstance(out, tuple) else (out,)
    first = dataclasses.replace(first, valid=first.valid.at[0].set(
        ~first.valid[0]))
    return (first, *rest) if isinstance(out, tuple) else first
'''


def test_new_alteration_from_files(tiny, monkeypatch):
    """A copy of the first replay cell, under a mix of its own, whose
    faults file names an alteration file that is new: the fault listing
    finds its cases, its sound run is correct, its planted answer fault
    reads not correct, and no file that was there changes."""
    before = _digests(tiny)
    bench = tiny / "benchmark"
    path = tiny / "BENCHMARK.json"
    b = json.loads(path.read_text())
    base = next(w for w in b["workloads"] if json.loads(
        (bench / "traffic" / f"{w['traffic']}.json").read_text()
    )["driver"] == "replay")
    cell = f"{base['config']}.flip"
    (bench / "traffic" / "replay-flip.json").write_bytes(
        (bench / "traffic" / f"{base['traffic']}.json").read_bytes())
    hooks = bench / "tests"
    site = json.loads((hooks / "faults" / f"{base['name']}.json")
                      .read_text())["verdict"]
    (hooks / "faults" / f"{cell}.json").write_text(json.dumps(
        {"verdict": site, "alter": "flip"}))
    (hooks / "alter" / "flip.py").write_text(FLIP)
    b["workloads"].append({**base, "name": cell, "traffic": "replay-flip"})
    for m in b["end_to_end"] + b["per_layer"]:
        if base["name"] in m.get("workloads", ()):
            m["workloads"].append(cell)
    path.write_text(json.dumps(b))

    check_hooks(cell, tiny)
    assert [f for f, c in fault_cases(tiny) if c == cell] == [
        "answer_altered", "state_unchanged"]
    assert run_tiny(tiny, cell)["correct"] is True
    plant(monkeypatch, "answer_altered", cell, tiny)
    line = run_tiny(tiny, cell)
    assert line["correct"] is False
    assert line["checks"]["mismatched"]["value"] > 0

    after = _digests(tiny)
    assert {p: d for p, d in after.items() if p in before} == before
