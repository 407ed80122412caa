"""The queue family's verdict read-back in one transfer.

On a single device the queue family's program returns its whole verdict
as one ``[B, K]`` int32 buffer (``parallel/pipeline.py``:
``flatten_verdict``, traced into the jitted program), starts the buffer's
host copy at dispatch, and its collect reads it once and splits it back
(``split_verdict``).  The result maps must be identical to the per-leaf
read (``_default_collect`` over the same program's pytree), for clean
and anomalous histories, both delivery contracts, small and large value
spaces and a padded tail chunk; ``pipeline.readbacks`` says which path
each chunk took, and a collect that raises quarantines as before.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from jepsen_tpu.checkers.fused import combined_tensor_check
from jepsen_tpu.checkers.queue_lin import QueueLinTensorsPacked
from jepsen_tpu.history.rows import _rows_for
from jepsen_tpu.history.store import write_history_jsonl
from jepsen_tpu.history.synth import (
    StreamSynthSpec,
    SynthSpec,
    synth_batch,
    synth_stream_batch,
)
from jepsen_tpu.obs.metrics import REGISTRY
from jepsen_tpu.parallel import pipeline
from jepsen_tpu.parallel.pipeline import (
    _default_collect,
    check_sources,
    family_for,
    flatten_verdict,
    split_verdict,
)

CHUNK = 4
N_HIST = 6  # two chunks: the second a tail of 2, padded to CHUNK


def _remap_values(mats, offset: int):
    """The corpus with its values renamed ``offset``, ``offset + 1``, …
    (an injective renaming: every queue verdict is the same up to it),
    so a chunk's value space lands in a chosen bucket."""
    vals = np.unique(np.concatenate([m[:, 4] for m in mats]))
    vals = vals[vals >= 0]
    out = []
    for m in mats:
        m = m.copy()
        has = m[:, 4] >= 0
        m[has, 4] = offset + np.searchsorted(vals, m[has, 4])
        out.append(m)
    return out


def _corpus(kind: str, value_space: int):
    if kind == "clean":
        shs = synth_batch(N_HIST, SynthSpec(n_ops=60, seed=11))
    else:
        shs = synth_batch(
            N_HIST, SynthSpec(n_ops=60, seed=11),
            lost=1, duplicated=1, unexpected=1, phantom_fail=1,
        )
    # ~40 distinct values: from 5/8 of the space they stay inside it
    offset = value_space * 5 // 8 if value_space > 128 else 0
    return _remap_values([_rows_for(sh.ops) for sh in shs], offset)


def _per_leaf(mats, delivery: str):
    """The same family's maps, its program's verdict pytree read leaf
    by leaf (``_default_collect``)."""
    fam = family_for("queue", delivery=delivery, chunk_pad=CHUNK)
    out, spaces = [], set()
    for lo in range(0, len(mats), CHUNK):
        item = mats[lo : lo + CHUNK]
        host = fam.produce(item)
        spaces.add(host.value_space)
        raw = combined_tensor_check(
            jax.device_put(host), delivery=delivery, packed_out=True
        )
        out.extend(fam.convert(item, _default_collect(raw)))
    return out, spaces


def _typed(rows):
    """Result maps with every value's type beside it: ``==`` alone
    would take ``np.int32(1)`` for ``1``."""
    return [
        {
            sub: {k: (type(v).__name__, v) for k, v in r[sub].items()}
            for sub in r
        }
        for r in rows
    ]


def _readbacks(path: str) -> float:
    return REGISTRY.value("pipeline.readbacks", path=path)


@pytest.mark.parametrize("value_space", [128, 512, 4096])
@pytest.mark.parametrize("delivery", ["exactly-once", "at-least-once"])
@pytest.mark.parametrize("kind", ["clean", "anomalous"])
def test_flat_readback_renders_the_per_leaf_maps(kind, delivery, value_space):
    mats = _corpus(kind, value_space)
    want, spaces = _per_leaf(mats, delivery)
    assert spaces == {value_space}
    flat0 = _readbacks("flat")
    got, stats = check_sources(
        "queue", mats, chunk=CHUNK, delivery=delivery
    )
    assert _readbacks("flat") - flat0 == 2  # one per chunk, tail included
    assert stats.histories == N_HIST
    assert _typed(got) == _typed(want)
    if kind == "anomalous":
        # the corpus reaches every class the converters render
        for cls in ("lost", "duplicated", "unexpected", "recovered"):
            assert any(r["queue"][cls] for r in got), cls
        if delivery == "at-least-once":
            assert any(r["linear"]["recovered"] for r in got)
    else:
        assert all(r["queue"]["valid?"] for r in got)


def test_split_round_trips_mixed_leaves_bit_for_bit():
    rng = np.random.default_rng(7)
    b = 5
    tree = {
        "flag": rng.integers(0, 2, b).astype(bool),
        "signed": np.array([-1, -(2**31), 2**31 - 1, 0, -7], np.int32),
        "planes": np.array(
            [[2**31, 2**32 - 1, 0], [1, 2**31 + 5, 7]] * 2 + [[3, 4, 5]],
            np.uint32,
        ),
        "cube": rng.integers(-(2**31), 2**31, (b, 2, 3), dtype=np.int32),
        "floats": np.array([-0.0, np.inf, 1.5, -2.25, 3e38], np.float32),
        "masks": rng.integers(0, 2, (b, 4)).astype(bool),
        "lin": QueueLinTensorsPacked(
            valid=np.array([True, False, True, True, False]),
            duplicate=np.full((b, 2), 0xFFFF0000, np.uint32),
            phantom=np.zeros((b, 2), np.uint32),
            causality=np.full((b, 2), 2**31, np.uint32),
            recovered=np.ones((b, 2), np.uint32),
            read_value_count=np.array([0, -3, 9, 2**30, 1], np.int32),
            value_space=64,
        ),
    }
    buf = jax.jit(flatten_verdict)(tree)
    # 1 + 1 + 3 + 6 + 1 + 4 columns, and 10 of the dataclass
    assert buf.dtype == np.int32 and buf.shape == (b, 26)
    host = np.asarray(buf)
    got = split_verdict(host, jax.eval_shape(lambda t: t, tree))
    assert jax.tree.structure(got) == jax.tree.structure(tree)
    assert got["lin"].value_space == 64
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(tree)):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()
        if w.dtype != bool:
            assert np.shares_memory(g, host)  # a view, not a copy


@pytest.mark.parametrize("dtype", [np.float16, np.int8, np.uint16])
def test_flatten_refuses_a_leaf_no_int32_column_holds(dtype):
    with pytest.raises(TypeError, match=np.dtype(dtype).name):
        jax.jit(flatten_verdict)({"x": np.zeros((2, 3), dtype)})


def test_mesh_keeps_the_per_leaf_read(cpu_devices):
    from jepsen_tpu.parallel.mesh import checker_mesh

    mats = _corpus("anomalous", 128)
    flat0, leaves0 = _readbacks("flat"), _readbacks("leaves")
    meshed, _ = check_sources(
        "queue", mats, chunk=CHUNK, mesh=checker_mesh(cpu_devices)
    )
    assert _readbacks("flat") == flat0
    assert _readbacks("leaves") - leaves0 == 2
    want, _ = _per_leaf(mats, "exactly-once")
    assert _typed(meshed) == _typed(want)


def test_stream_family_keeps_its_own_collect(tmp_path):
    files = []
    for i, sh in enumerate(
        synth_stream_batch(3, StreamSynthSpec(n_ops=20), lost=1)
    ):
        files.append(tmp_path / f"h{i}.jsonl")
        write_history_jsonl(files[-1], sh.ops)
    flat0, leaves0 = _readbacks("flat"), _readbacks("leaves")
    res, _ = check_sources("stream", files, chunk=2)
    assert len(res) == 3
    assert (_readbacks("flat"), _readbacks("leaves")) == (flat0, leaves0)


@pytest.mark.parametrize(
    "executor",
    [
        dict(fail_fast=True),
        dict(fail_fast=False),
        dict(lanes=2),
        dict(lanes=2, fail_fast=True),
        dict(serial=True),
    ],
    ids=["failfast", "elastic", "lanes", "lanes-failfast", "serial"],
)
def test_every_executor_reads_flat_and_times_collect(executor):
    mats = _corpus("anomalous", 128)
    want, _ = _per_leaf(mats, "exactly-once")
    flat0 = _readbacks("flat")
    busy0 = REGISTRY.value("pipeline.stage_busy_s", stage="collect")
    got, stats = check_sources("queue", mats, chunk=CHUNK, **executor)
    assert _readbacks("flat") - flat0 == stats.batches
    assert REGISTRY.value("pipeline.stage_busy_s", stage="collect") > busy0
    assert stats.metrics.value("pipeline.stage_busy_s", stage="collect") > 0
    assert _typed(got) == _typed(want)


def test_collect_failure_quarantines_only_its_history(monkeypatch):
    """A chunk whose flat collect raises is retried, quarantined, then
    isolated per history: only the history that makes collect raise
    reports ``unknown``, and every other verdict survives."""
    mats = _corpus("anomalous", 128)
    want, _ = _per_leaf(mats, "exactly-once")
    poison = mats[5]
    real = pipeline.family_for

    def poisoned_family(workload, **opts):
        fam = real(workload, **opts)
        assert fam.collect is not None

        def produce(chunk):
            return fam.produce(chunk), any(m is poison for m in chunk)

        def place(pair):
            return fam.place(pair[0]), pair[1]

        def check(pair):
            return fam.check(pair[0]), pair[1]

        def collect(raw):
            got, bad = raw
            if bad:
                raise RuntimeError("verdict read-back failed")
            return fam.collect(got)

        return dataclasses.replace(
            fam, produce=produce, place=place, check=check, collect=collect
        )

    monkeypatch.setattr(pipeline, "family_for", poisoned_family)
    got, stats = check_sources("queue", mats, chunk=CHUNK)
    assert stats.quarantined == 1
    assert stats.unit_retries >= 1
    for sub in ("queue", "linear"):
        assert got[5][sub]["valid?"] == "unknown"
        ev = got[5][sub]["quarantined"]
        assert ev["stage"] == "collect"
        assert "verdict read-back failed" in ev["errors"][-1]
    assert _typed(got[:5]) == _typed(want[:5])
