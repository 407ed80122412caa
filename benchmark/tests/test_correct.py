"""What decides ``correct``: the control comes out not correct, and so
does a run whose timed path is broken underneath, once for each fault a
cell can have.  Every run here skips the harness's look for a chip (it
accepts the CPU) and drives the rest of a run at a tiny size.

The cells are a checkout's ``BENCHMARK.json``'s.  Where each cell's
faults are planted is its file ``benchmark/tests/faults/<cell>.json``:
each site key names a program function (``module:function``) that the
fault of that key replaces, and ``alter`` names the file
``benchmark/tests/alter/<name>.py`` that says what a fault does to the
output of the function there, whose shape is the program's:

- ``verdict`` (every cell): the function that produces the cell's
  verdict tensors: an answer altered (the alter file's ``answer(out)``:
  the first history's answer turned over, in a field its verdict is
  rendered from), a state left unchanged;
- ``packer``: the function that packs a batch's rows: half of the batch
  left out (``half(packed)``: every other history left out);
- ``exchange``: the factory of the verdict's reduction across chips: the
  exchange left out.
"""

import importlib
import importlib.util
import json

import pytest

from conftest import CELLS, ROOT, cells, run_tiny

import control
import run


def _hooks(cell, root=ROOT):
    path = root / "benchmark" / "tests" / "faults" / f"{cell}.json"
    return json.loads(path.read_text()) if path.is_file() else {}


def _site(cell, key, root=ROOT):
    """``(module, name)`` of the function the cell's ``key`` names."""
    mod, name = _hooks(cell, root)[key].split(":")
    return importlib.import_module(mod), name


def _alter(cell, root=ROOT):
    """The cell's alteration file, loaded."""
    name = _hooks(cell, root)["alter"]
    path = root / "benchmark" / "tests" / "alter" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_alter_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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


def _altered(key, what):
    """The fault that passes the output of the cell's ``key`` site through
    its alteration file's ``what``."""

    def plant(monkeypatch, cell, root):
        mod, name = _site(cell, key, root)
        orig, alter = getattr(mod, name), getattr(_alter(cell, root), what)
        monkeypatch.setattr(mod, name,
                            lambda *a, **kw: alter(orig(*a, **kw)))

    return plant


def _state_unchanged(monkeypatch, cell, root):
    """The kernel answers every batch with its first batch's verdicts.
    A trace of the kernel (for its output's shapes) answers no batch: it
    is passed through, and nothing it makes is kept."""
    import jax

    mod, name = _site(cell, "verdict", root)
    orig, kept = getattr(mod, name), []

    def f(*a, **kw):
        out = orig(*a, **kw)
        if any(isinstance(x, jax.core.Tracer) for x in jax.tree.leaves(out)):
            return out
        if not kept:
            kept.append(out)
        return kept[0]

    monkeypatch.setattr(mod, name, f)


def _no_exchange(monkeypatch, cell, root):
    """The verdict reduction without its psum / pmin: each device keeps
    its own shard's count."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mod, name = _site(cell, "exchange", root)
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


#: each fault: its planter, the key of ``faults/<cell>.json`` it needs,
#: and what it needs of the cell's alteration file
FAULTS = {
    "half_left_out": (_altered("packer", "half"), "packer", "half"),
    "answer_altered": (_altered("verdict", "answer"), "verdict", "answer"),
    "state_unchanged": (_state_unchanged, "verdict", None),
    "no_exchange": (_no_exchange, "exchange", None),
}
SITES = {key for _, key, _ in FAULTS.values()}


def fault_cases(root=ROOT):
    """``(fault, cell)`` for each fault that each cell of the checkout at
    ``root`` can have: those whose site its faults file names."""
    return [(f, c) for f, (_, key, _) in FAULTS.items() for c in cells(root)
            if key in _hooks(c, root)]


def plant(monkeypatch, fault, cell, root=ROOT):
    FAULTS[fault][0](monkeypatch, cell, root)


def check_hooks(cell, root=ROOT):
    """The cell's faults file names its verdict site and its alteration
    file, every site is a program function, and the alteration file
    defines what the cell's sites need."""
    hooks = _hooks(cell, root)
    assert "verdict" in hooks, f"no faults/{cell}.json with a verdict"
    assert "alter" in hooks, f"faults/{cell}.json names no alter file"
    assert set(hooks) <= SITES | {"alter"}
    for key in SITES & set(hooks):
        mod, name = _site(cell, key, root)
        assert callable(getattr(mod, name))
    alter = _alter(cell, root)
    for _, key, what in FAULTS.values():
        if what and key in hooks:
            assert callable(getattr(alter, what, None)), \
                f"alter/{hooks['alter']}.py defines no {what}()"


def test_every_cell_names_its_verdict_site():
    for cell in CELLS:
        check_hooks(cell)


@pytest.mark.parametrize("fault,cell", fault_cases())
def test_fault_is_not_correct(tiny, monkeypatch, fault, cell):
    plant(monkeypatch, fault, cell, tiny)
    line = run_tiny(tiny, cell)
    assert line["correct"] is False, line["checks"]
