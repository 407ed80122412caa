"""The benchmark's tests run on the CPU, with four virtual devices for the
mesh cell; ``tiny`` makes a checkout whose configurations are cut to a
size a test run holds.

Everything a test needs of one configuration, traffic mix or cell is a
file of its own, found by name:

- ``tiny/configs/<config>.json``: the configuration's cut, merged into
  each of its top-level groups (every configuration has one);
- ``tiny/traffic/<mix>.json``: a traffic mix's cut, where it needs one;
- ``faults/<cell>.json``: where the cell's faults are planted, and its
  ``alter`` key naming ``alter/<name>.py``: what each fault does to the
  output there (``test_correct.py``);
- ``parity/<family>.json``: the program's synth that the family's
  generator copies (``test_gen.py``);
- ``work/<metric>.json``: a roofline reader's work cases
  (``test_peaks.py``).
"""

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
HOOKS = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(ROOT)]

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())


def cells(root: Path = ROOT) -> list[str]:
    """The cells of the checkout at ``root``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    return [w["name"] for w in bench["workloads"]]


CELLS = cells()


def _cut(path: Path, cut: dict) -> None:
    c = json.loads(path.read_text())
    for k, v in cut.items():
        c[k] = {**c[k], **v} if isinstance(v, dict) else v
    path.write_text(json.dumps(c))


def make_tiny(dest: Path) -> Path:
    """A checkout at ``dest``: ``BENCHMARK.json`` and ``benchmark/`` (its
    tests and their hooks too) copied with each configuration and traffic
    mix cut to its ``tiny`` file; the program linked in."""
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(BENCH, dest / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("jepsen_tpu", "native"):
        (dest / name).symlink_to(ROOT / name)
    for conf in BENCHMARK["configs"]:
        cut = HOOKS / "tiny" / "configs" / f"{conf['name']}.json"
        if not cut.is_file():
            raise FileNotFoundError(f"configuration {conf['name']!r} has no "
                                    f"test cut {cut}")
        _cut(dest / conf["file"], json.loads(cut.read_text()))
    for cut in sorted((HOOKS / "tiny" / "traffic").glob("*.json")):
        _cut(dest / "benchmark" / "traffic" / cut.name,
             json.loads(cut.read_text()))
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
