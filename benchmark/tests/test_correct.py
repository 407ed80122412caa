"""What decides ``correct``: the control comes out not correct, and so
does a run whose timed path is broken underneath, once for each fault a
cell can have.  Every run here skips the harness's look for a chip (it
accepts the CPU) and drives the rest of a run at a tiny size.

The cells are ``BENCHMARK.json``'s; where each cell's faults are planted
is its file ``faults/<cell>.json``, each key naming a program function
(``module:function``) that the fault of that key replaces:

- ``verdict`` (every cell): the function that produces the cell's
  verdict tensors: an answer altered, a state left unchanged;
- ``packer``: the function that packs a batch's rows: half of the batch
  left out;
- ``exchange``: the factory of the verdict's reduction across chips: the
  exchange left out.
"""

import dataclasses
import importlib
import json

import numpy as np
import pytest

from conftest import CELLS, HOOKS, run_tiny

import control
import run


def _hooks(cell):
    path = HOOKS / "faults" / f"{cell}.json"
    return json.loads(path.read_text()) if path.is_file() else {}


def _site(cell, key):
    """``(module, name)`` of the function the cell's ``key`` names."""
    mod, name = _hooks(cell)[key].split(":")
    return importlib.import_module(mod), name


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
    mod, name = _site(cell, "packer")
    orig = getattr(mod, name)

    def f(*a, **kw):
        out = orig(*a, **kw)
        m = np.array(out.mask)
        m[1::2] = False
        return dataclasses.replace(out, mask=m)

    monkeypatch.setattr(mod, name, f)


def _answer_altered(monkeypatch, cell):
    """The first history's verdict of every batch is turned over where
    the kernel produces it."""
    mod, name = _site(cell, "verdict")
    orig = getattr(mod, name)

    def f(*a, **kw):
        out = orig(*a, **kw)
        if isinstance(out, tuple):
            # sub-verdicts, the history valid where all are: the others
            # read valid, the last the opposite of the whole
            *rest, last = out
            both = last.valid[0]
            for r in rest:
                both = both & r.valid[0]
            return (*(dataclasses.replace(r, valid=r.valid.at[0].set(True))
                      for r in rest),
                    dataclasses.replace(last,
                                        valid=last.valid.at[0].set(~both)))
        return dataclasses.replace(
            out, valid=out.valid.at[0].set(~out.valid[0]))

    monkeypatch.setattr(mod, name, f)


def _state_unchanged(monkeypatch, cell):
    """The kernel answers every batch with its first batch's verdicts."""
    mod, name = _site(cell, "verdict")
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

    mod, name = _site(cell, "exchange")
    axis = mod.HIST_AXIS

    def program(m):
        def body(valid, gidx):
            big = jnp.iinfo(jnp.int32).max
            first = jnp.min(jnp.where(valid, big, gidx), initial=big)
            return (jnp.sum(~valid).astype(jnp.int32),
                    jnp.where(first == big, -1, first))

        return jax.jit(jax.shard_map(
            body, mesh=m, in_specs=(P(axis), P(axis)),
            out_specs=(P(), P()), check_vma=False))

    monkeypatch.setattr(mod, name, program)


#: each fault, and the key of ``faults/<cell>.json`` it needs
FAULTS = {
    "half_left_out": (_half_left_out, "packer"),
    "answer_altered": (_answer_altered, "verdict"),
    "state_unchanged": (_state_unchanged, "verdict"),
    "no_exchange": (_no_exchange, "exchange"),
}


def test_every_cell_names_its_verdict_site():
    for cell in CELLS:
        hooks = _hooks(cell)
        assert "verdict" in hooks, f"no faults/{cell}.json with a verdict"
        assert set(hooks) <= {key for _, key in FAULTS.values()}
        for key in hooks:
            mod, name = _site(cell, key)
            assert callable(getattr(mod, name))


@pytest.mark.parametrize("fault,cell", [
    (f, c) for f, (_, key) in FAULTS.items() for c in CELLS
    if key in _hooks(c)])
def test_fault_is_not_correct(tiny, monkeypatch, fault, cell):
    FAULTS[fault][0](monkeypatch, cell)
    line = run_tiny(tiny, cell)
    assert line["correct"] is False, line["checks"]
