"""The benchmark's generators make, for a seed, op for op the histories
the program's synth makes, in the bytes ``Store.save_history`` writes.

Each family's counterpart in the program is its file
``parity/<family>.json``: the program's batch function and spec class
(``module:name``; called as ``batch(count, spec_class(**spec,
seed=seed), **inject)``), and the seeds and injections compared."""

import importlib
import json

import pytest

import gen
from conftest import BENCHMARK, HOOKS, ROOT
from jepsen_tpu.history.store import Store

PARITY = {p.stem: json.loads(p.read_text())
          for p in sorted((HOOKS / "parity").glob("*.json"))}


def _program(ref: str):
    mod, name = ref.split(":")
    return getattr(importlib.import_module(mod), name)


def _program_jsonl(ops) -> str:
    return "".join(json.dumps(o.to_json()) + "\n" for o in ops)


def _synth(fam, seed, inject, count):
    p = PARITY[fam]
    spec = _program(p["spec_class"])(**p["spec"], seed=seed)
    return _program(p["batch"])(count, spec, **inject)


@pytest.mark.parametrize("fam,seed,inject", [
    (f, s, i) for f, p in PARITY.items() for s in p["seeds"]
    for i in p["injects"]])
def test_generator_matches_program(fam, seed, inject):
    want = _synth(fam, seed, inject, PARITY[fam]["count"])
    for i, sh in enumerate(want):
        got = gen.jsonl(gen.family(fam).ops(seed + i, PARITY[fam]["spec"],
                                            inject))
        assert got == _program_jsonl(sh.ops)


def test_every_family_has_its_parity_case():
    for conf in BENCHMARK["configs"]:
        fam = json.loads((ROOT / conf["file"]).read_text())
        assert fam["family"] in PARITY


@pytest.mark.parametrize("fam", PARITY)
def test_jsonl_is_what_the_store_writes(tmp_path, fam):
    seed, inject = PARITY[fam]["seeds"][-1], PARITY[fam]["injects"][-1]
    (sh,) = _synth(fam, seed, inject, 1)
    store = Store(tmp_path / "s")
    p = store.save_history(store.run_dir("t", "0"), sh.ops)
    assert p.read_text() == gen.jsonl(
        gen.family(fam).ops(seed, PARITY[fam]["spec"], inject))


@pytest.mark.parametrize("fam", PARITY)
def test_plan_is_seeded_and_keeps_the_campaign(fam):
    inject = gen.family(fam).INJECT
    camp = {"histories": 10, "clean_share": 0.5,
            "anomalies": [{k: 1} for k in inject]}
    a, b = gen.plan(fam, camp, 2**31 + 5), gen.plan(fam, camp, 2**31 + 5)
    assert a == b
    assert sorted(s for s, _ in a) == list(range(2**31 + 5, 2**31 + 15))
    assert sum(1 for _, inj in a if not inj) == 5
    with pytest.raises(ValueError, match="cannot inject"):
        gen.plan(fam, {"histories": 2, "anomalies": [{"no-such": 1}]}, 1)


def test_shape_counts_rows_and_space():
    ops = [["invoke", "drain", 0, None, 1, None],
           ["ok", "drain", 0, [3, 9], 2, None],
           ["ok", "drain", 1, [], 3, None],
           ["ok", "read", 3, [[0, 5], [1, 12]], 4, None]]
    assert gen.rows_space(ops) == {"ops": 4, "rows": 1 + 2 + 1 + 2,
                                   "space": 13}
