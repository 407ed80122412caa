"""What decides ``correct``: the control comes out not correct, and so
does a run whose timed path is broken underneath, once for each fault a
cell can have.  Every run here skips the harness's look for a chip (it
accepts the CPU) and drives the rest of a run at a tiny size."""

import dataclasses

import numpy as np
import pytest

from conftest import run_tiny

import control
import run

CELLS = ["qq-replay.fresh", "stream-10k.fresh", "qq-replay.sidecar",
         "qq-replay.mesh4"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tiny, cell):
    line = run_tiny(tiny, cell)
    assert line["correct"] is True
    assert line["checks"]["mismatched"]["value"] == 0
    assert line["checks"]["compared"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tiny, cell):
    c = run.find_cell(cell, root=tiny)
    line = run_tiny(tiny, cell, hook=control.control_hook(c))
    assert line["correct"] is False
    assert line["checks"]["mismatched"]["value"] > 0


# -- faults planted in the program ------------------------------------------


def _half_left_out(monkeypatch, cell):
    """Every other history of each batch is left out of the check (its
    rows masked off), the verdicts of the rest kept."""
    from jepsen_tpu.checkers import stream_lin
    from jepsen_tpu.history import encode

    def masked(orig):
        def f(*a, **kw):
            out = orig(*a, **kw)
            m = np.array(out.mask)
            m[1::2] = False
            return dataclasses.replace(out, mask=m)
        return f

    monkeypatch.setattr(encode, "pack_row_matrices",
                        masked(encode.pack_row_matrices))
    monkeypatch.setattr(stream_lin, "pack_stream_rows",
                        masked(stream_lin.pack_stream_rows))


def _site(cell):
    """The program function that produces the cell's verdict tensors."""
    from jepsen_tpu.checkers import fused, stream_lin
    from jepsen_tpu.parallel import mesh

    return {
        "qq-replay.fresh": (fused, "combined_tensor_check"),
        "qq-replay.sidecar": (fused, "_combined_batch"),
        "qq-replay.mesh4": (mesh, "sharded_check"),
        "stream-10k.fresh": (stream_lin, "stream_lin_tensor_check"),
    }[cell]


def _answer_altered(monkeypatch, cell):
    """The first history's verdict of every batch is turned over where
    the kernel produces it."""
    mod, name = _site(cell)
    orig = getattr(mod, name)

    def f(*a, **kw):
        out = orig(*a, **kw)
        if isinstance(out, tuple):  # the queue's (total-queue, linear)
            tq, ql = out
            both = tq.valid[0] & ql.valid[0]
            return (dataclasses.replace(tq, valid=tq.valid.at[0].set(True)),
                    dataclasses.replace(ql, valid=ql.valid.at[0].set(~both)))
        return dataclasses.replace(
            out, valid=out.valid.at[0].set(~out.valid[0]))

    monkeypatch.setattr(mod, name, f)


def _state_unchanged(monkeypatch, cell):
    """The kernel answers every batch with its first batch's verdicts."""
    mod, name = _site(cell)
    orig, kept = getattr(mod, name), []

    def f(*a, **kw):
        if not kept:
            kept.append(orig(*a, **kw))
        return kept[0]

    monkeypatch.setattr(mod, name, f)


def _no_exchange(monkeypatch, cell):
    """The verdict reduction without its psum / pmin: each device keeps
    its own shard's count."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jepsen_tpu.parallel import mesh

    def program(m):
        def body(valid, gidx):
            big = jnp.iinfo(jnp.int32).max
            first = jnp.min(jnp.where(valid, big, gidx), initial=big)
            return (jnp.sum(~valid).astype(jnp.int32),
                    jnp.where(first == big, -1, first))

        return jax.jit(jax.shard_map(
            body, mesh=m, in_specs=(P(mesh.HIST_AXIS), P(mesh.HIST_AXIS)),
            out_specs=(P(), P()), check_vma=False))

    monkeypatch.setattr(mesh, "_verdict_reduce_program", program)


FAULTS = {
    "half_left_out": (_half_left_out, ["qq-replay.fresh",
                                       "stream-10k.fresh",
                                       "qq-replay.mesh4"]),
    "answer_altered": (_answer_altered, CELLS),
    "state_unchanged": (_state_unchanged, CELLS),
    "no_exchange": (_no_exchange, ["qq-replay.mesh4"]),
}


@pytest.mark.parametrize("fault,cell", [
    (f, c) for f, (_, cells) in FAULTS.items() for c in cells])
def test_fault_is_not_correct(tiny, monkeypatch, fault, cell):
    FAULTS[fault][0](monkeypatch, cell)
    line = run_tiny(tiny, cell)
    assert line["correct"] is False, line["checks"]
