"""The benchmark's generator makes, for a seed, op for op the history the
program's ``synth_batch`` / ``synth_stream_batch`` make, in the bytes
``Store.save_history`` writes."""

import json

import pytest

import gen
from jepsen_tpu.history import synth
from jepsen_tpu.history.store import Store


def _program_jsonl(ops) -> str:
    return "".join(json.dumps(o.to_json()) + "\n" for o in ops)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
@pytest.mark.parametrize("inject", [{}, {"lost": 1, "duplicated": 1},
                                    {"duplicated": 1}, {"lost": 3}])
def test_queue_matches_program(seed, inject):
    spec = {"n_ops": 120, "n_processes": 5}
    want = synth.synth_batch(3, synth.SynthSpec(**spec, seed=seed), **inject)
    for i, sh in enumerate(want):
        got = gen.jsonl(gen.queue_ops(seed + i, spec, inject))
        assert got == _program_jsonl(sh.ops)


@pytest.mark.parametrize("seed", [0, 2**31 + 99])
@pytest.mark.parametrize("inject", [{}, {"lost": 1, "duplicated": 1}])
def test_stream_matches_program(seed, inject):
    spec = {"n_ops": 300}
    want = synth.synth_stream_batch(
        2, synth.StreamSynthSpec(**spec, seed=seed), **inject)
    for i, sh in enumerate(want):
        got = gen.jsonl(gen.stream_ops(seed + i, spec, inject))
        assert got == _program_jsonl(sh.ops)


def test_jsonl_is_what_the_store_writes(tmp_path):
    sh = synth.synth_history(synth.SynthSpec(n_ops=50, seed=3, lost=1))
    store = Store(tmp_path / "s")
    p = store.save_history(store.run_dir("t", "0"), sh.ops)
    assert p.read_text() == gen.jsonl(gen.queue_ops(3, {"n_ops": 50},
                                                    {"lost": 1}))


def test_plan_is_seeded_and_keeps_the_campaign():
    camp = {"histories": 10, "clean_share": 0.5,
            "anomalies": [{"lost": 1}, {"duplicated": 1}]}
    a, b = gen.plan(camp, 2**31 + 5), gen.plan(camp, 2**31 + 5)
    assert a == b
    assert sorted(s for s, _ in a) == list(range(2**31 + 5, 2**31 + 15))
    assert sum(1 for _, inj in a if not inj) == 5
    with pytest.raises(ValueError):
        gen.plan({"histories": 2, "anomalies": [{"phantom": 1}]}, 1)


def test_shape_counts_rows_and_space():
    ops = [["invoke", "drain", 0, None, 1, None],
           ["ok", "drain", 0, [3, 9], 2, None],
           ["ok", "drain", 1, [], 3, None],
           ["ok", "read", 3, [[0, 5], [1, 12]], 4, None]]
    assert gen.shape(ops) == {"ops": 4, "rows": 1 + 2 + 1 + 2, "space": 13}
