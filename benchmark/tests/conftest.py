"""The benchmark's tests run on the CPU, with four virtual devices for the
mesh cell; ``tiny`` makes a checkout whose configurations are cut to a
size a test run holds."""

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT)]

#: per configuration: the sizes a test run holds
TINY = {
    "qq-replay": {"spec": {"n_ops": 60}, "campaign": {"histories": 24}},
    "stream-10k": {"spec": {"n_ops": 300}, "campaign": {"histories": 6}},
}


def make_tiny(dest: Path) -> Path:
    """A checkout at ``dest``: ``BENCHMARK.json`` and ``benchmark/`` copied
    with each configuration cut to ``TINY``; the program linked in."""
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(BENCH, dest / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for name in ("jepsen_tpu", "native"):
        (dest / name).symlink_to(ROOT / name)
    for conf, cut in TINY.items():
        p = dest / "benchmark" / "configs" / f"{conf}.json"
        c = json.loads(p.read_text())
        for k, v in cut.items():
            c[k] = {**c[k], **v}
        p.write_text(json.dumps(c))
    side = dest / "benchmark" / "traffic" / "sidecar-closed.json"
    side.write_text(json.dumps({**json.loads(side.read_text()),
                                "histories": 8}))
    return dest


@pytest.fixture
def tiny(tmp_path):
    return make_tiny(tmp_path)


def run_tiny(root: Path, cell: str, seed: int = 2**31 + 17,
             seconds: float = 0.5, trace: bool = False, hook=None) -> dict:
    import run

    c = run.find_cell(cell, root=root)
    c.seed = seed
    return run.run_cell(c, seconds, trace, accept=("cpu",), workers=2,
                        hook=hook)
