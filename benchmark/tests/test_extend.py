"""A later PR adds a configuration, a traffic mix, a cell and a per-layer
metric as files and entries alone: the harness finds them by name, and
no file that was there changes."""

import hashlib
import json

from conftest import run_tiny

READER = '''"""Campaign passes the window completed."""


def read(ctx):
    return ctx.counts.get("passes") or None
'''


def _digests(root):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "benchmark").rglob("*")) if p.is_file()}


def test_new_cell_mix_and_metric_from_files(tiny):
    before = _digests(tiny)
    bench = tiny / "benchmark"
    conf = json.loads((bench / "configs" / "qq-replay.json").read_text())
    conf.update(name="qq-small", spec={**conf["spec"], "n_ops": 40},
                campaign={**conf["campaign"], "histories": 16})
    (bench / "configs" / "qq-small.json").write_text(json.dumps(conf))
    (bench / "traffic" / "replay-blocks8.json").write_text(json.dumps({
        "driver": "replay", "use_cache": False, "fail_fast": True,
        "shuffle_block": 8}))
    (bench / "metrics" / "passes.blocks8.py").write_text(READER)

    path = tiny / "BENCHMARK.json"
    b = json.loads(path.read_text())
    b["configs"].append({"name": "qq-small", "source": "a test",
                         "file": "benchmark/configs/qq-small.json",
                         "reduced": ["n_ops"], "why": "a test"})
    b["workloads"].append({"name": "qq-small.blocks8", "config": "qq-small",
                           "traffic": "replay-blocks8", "chips": 1,
                           "why": "a test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "qq-replay.fresh" in m.get("workloads", ()):
            m["workloads"].append("qq-small.blocks8")
    b["per_layer"].append({"name": "passes.blocks8", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "pipeline", "moves": "histories_per_s",
                           "workloads": ["qq-small.blocks8"]})
    path.write_text(json.dumps(b))

    line = run_tiny(tiny, "qq-small.blocks8")
    assert line["correct"] is True
    assert set(line["metrics"]) == {"histories_per_s", "setup_s"}
    line = run_tiny(tiny, "qq-small.blocks8", trace=True)
    assert line["correct"] is True
    assert line["metrics"]["passes.blocks8"]["value"] >= 1
    assert "ingest_ms_per_hist.replay" in line["metrics"]

    after = _digests(tiny)
    assert {p: d for p, d in after.items() if p in before} == before
