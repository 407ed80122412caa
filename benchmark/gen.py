"""The benchmark's history synthesis: seeded, pure Python, no JAX.

A configuration's ``family`` names its file, ``benchmark/families/<family>.py``,
found by path beside this one (so spawned pool workers find it too).  A
family file defines:

- ``INJECT``: the injections its generator can make;
- ``ops(seed, spec, inject)``: the generator, a copy of the program's
  synth (``jepsen_tpu/history/synth.py``) kept here so that a change to
  the program cannot change the traffic.  It draws from
  ``random.Random`` in exactly the program's order, so a history of one
  seed is op for op the program's (``benchmark/tests/test_gen.py``);
- ``shape(ops)``: what the family's roofline readers count;
- ``check(ops, opts)``, ``LABELS``: the plain reference
  (``benchmark/reference.py``);
- ``PROGRAM_OPTS``: the checker options the program's entry takes
  (``benchmark/control.py``).

A configuration's ``campaign`` says how many histories, which share is
clean, and the injections the anomalous ones cycle through.  History
``i`` of a campaign takes seed ``seed + i`` (clean ones first, as
``chip_smoke.py`` numbers them); the campaign's order is then shuffled
from the seed, so that the first invalid history of a pass falls
anywhere.  A history is written in the JSONL form ``Store.save_history``
writes: one ``json.dumps`` object per op with the keys
``index, type, f, process, time[, value][, error]``.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import random
from pathlib import Path

FAMILY_DIR = Path(__file__).resolve().parent / "families"


@functools.cache
def family(name: str):
    """The family file of that name, loaded once per process."""
    path = FAMILY_DIR / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"no family file {path}")
    spec = importlib.util.spec_from_file_location(f"bench_family_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _history(seed: int, spec: dict):
    """``(rng, ops, tick, lat, emit)``: the generators' shared clock."""
    rng = random.Random(seed)
    ops: list[list] = []  # [type, f, process, value, time, error]
    clock = [0]
    mean = spec.get("mean_latency_ns", 2_000_000)

    def tick() -> int:
        clock[0] += rng.randint(100_000, 2_000_000)
        return clock[0]

    def lat() -> int:
        return max(1, int(rng.expovariate(1.0 / mean)))

    def emit(type_, f, process, value, time, error=None) -> None:
        ops.append([type_, f, process, value, time, error])

    return rng, ops, tick, lat, emit


def rows_space(ops: list[list]) -> dict:
    """A history's shape as the row kernels' rooflines count it: ``ops``
    entries; ``rows``, one per op and one more per further value a list
    carries (a drain or a read of ``k`` values is ``k`` rows, an empty
    one 1); ``space``, the largest value or offset + 1."""
    rows, space = 0, 0
    for _t, _f, _p, value, _time, _e in ops:
        if isinstance(value, list):
            rows += max(len(value), 1)
            for x in value:
                space = max(space, *x) if isinstance(x, list) else max(space, x)
        else:
            rows += 1
            if isinstance(value, int):
                space = max(space, value)
    return {"ops": len(ops), "rows": rows, "space": space + 1}


def jsonl(ops: list[list]) -> str:
    """The history as ``Store.save_history`` writes it."""
    out = []
    for i, (type_, f, process, value, time, error) in enumerate(ops):
        d = {"index": i, "type": type_, "f": f, "process": process,
             "time": time}
        if value is not None:
            d["value"] = value
        if error is not None:
            d["error"] = error
        out.append(json.dumps(d))
    return "\n".join(out) + "\n"


def plan(fam: str, campaign: dict, seed: int,
         count: int | None = None) -> list[tuple]:
    """``[(history seed, injections)]`` in the order a pass checks them.
    An injection the family's generator cannot make is refused rather
    than silently ignored."""
    n = count if count is not None else campaign["histories"]
    n_clean = int(n * campaign.get("clean_share", 0.5))
    mixes = campaign.get("anomalies") or [{}]
    for m in mixes:
        bad = set(m) - set(family(fam).INJECT)
        if bad:
            raise ValueError(f"the {fam} generator cannot inject "
                             f"{sorted(bad)}")
    out = [(seed + i, {}) for i in range(n_clean)]
    out += [(seed + n_clean + j, mixes[j % len(mixes)])
            for j in range(n - n_clean)]
    random.Random(seed).shuffle(out)
    return out


def make(fam: str, spec: dict, hseed: int, inject: dict,
         path: str) -> dict:
    """One history (a pool worker's job), written to ``path``: returns
    the path and the history's shape."""
    f = family(fam)
    ops = f.ops(hseed, spec, inject)
    Path(path).write_text(jsonl(ops))
    return {"path": path, **f.shape(ops)}
