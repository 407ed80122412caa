"""The readings the correctness limits are set from.

    python benchmark/control.py --workload <cell> --seeds 11,12,13 --seconds 5 [--side program|control|both]

For each seed, one run of the cell (its own campaign, entry, window and
comparison, as ``run.py`` makes them) with, on the ``control`` side, the
configuration's ``control`` guarantees in the program's place: the
program's own path where the entry takes those options (the replay's
``check_sources`` takes ``delivery`` and ``append_fail``), else the plain
reference computed under them.  The control has to come out not correct.
Each run prints ``{"side", "seed", "mismatched", "compared", "correct"}``.

All runs share this one process and its chip.  The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parent))

import gen  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402


def _reference_check(cell, opts):
    """A stand-in for ``check_sources`` that answers with the reference."""
    fam = cell.config["family"]

    def check(_family, paths, reduce=False, **_kw):
        maps = [reference.check(fam, reference.parse(Path(p).read_text()),
                                opts) for p in paths]
        stats = SimpleNamespace(produce_busy_s=0.0, place_busy_s=0.0,
                                quarantined=0)
        if reduce:
            bad = [k for k, m in enumerate(maps) if not reference.valid(m)]
            return {"invalid": len(bad),
                    "first_invalid": bad[0] if bad else -1}, stats
        return maps, stats

    return check


def control_hook(cell):
    """``hook(driver)`` putting the configuration's control in place."""
    opts = dict(cell.config["checker"], **cell.config["control"])
    fam = cell.config["family"]
    own = set(cell.config["control"]) <= gen.family(fam).PROGRAM_OPTS

    def hook(driver):
        if cell.traffic["driver"] == "replay":
            if own:
                driver.opts.update(cell.config["control"])
            else:
                driver._check = _reference_check(cell, opts)
            return

        def request(ops):
            ops = [op.to_json() for op in ops]
            return reference.check(fam, ops, opts), 0.0, 0.0

        driver._request = request

    return hook


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--side", choices=("program", "control", "both"),
                   default="control")
    args = p.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE)
    sys.path.insert(0, str(run.ROOT))
    sides = ("program", "control") if args.side == "both" else (args.side,)
    for seed in (int(s) for s in args.seeds.split(",")):
        for side in sides:
            cell = run.find_cell(args.workload)
            cell.seed = seed
            hook = control_hook(cell) if side == "control" else None
            line = run.run_cell(cell, args.seconds, False, hook=hook)
            print(json.dumps({
                "side": side, "seed": seed,
                "mismatched": line["checks"]["mismatched"]["value"],
                "compared": line["checks"]["compared"]["value"],
                "correct": line["correct"],
                "metrics": line["metrics"],
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
