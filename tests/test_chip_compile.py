"""The main-path kernels compile for a TPU v5e, at real widths.

Each test lowers one program with the shapes a user's run gives it and
compiles it for one chip of a described ``v5e:2x2`` topology: what the
chip's compiler would refuse (a tiling it cannot lay out, more fast
memory than a kernel may use, a program that does not fit the device)
fails here, at no chip time.  Nothing runs: a compile that passes is not
a chip run.

The topology is described inside a fixture of this file only: libtpu
belongs to one process at a time, and the worker that runs this file
keeps it until it exits.
"""

from __future__ import annotations

import os

import numpy as np
import pytest


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any refusal means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off around it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _sds(one_chip, x, batch: int | None = None):
    """``x``'s shape and dtype on the described chip, its leading (batch)
    axis resized to ``batch``."""
    import jax

    shape = tuple(x.shape)
    if batch is not None:
        shape = (batch, *shape[1:])
    return jax.ShapeDtypeStruct(shape, np.dtype(x.dtype), sharding=one_chip)


def _compile(fn, *args, **static):
    compiled = fn.lower(*args, **static).compile()
    assert compiled.as_text()
    return compiled


def test_queue_combined_verdict_L1024_V1024(one_chip):
    """``_combined_batch`` (total-queue + queue-lin, one program) at the
    batched-replay shape: L=1024 rows, V=1024 values."""
    from jepsen_tpu.checkers.fused import _combined_batch
    from jepsen_tpu.history.encode import pack_histories
    from jepsen_tpu.history.synth import SynthSpec, synth_batch

    packed = pack_histories(
        [sh.ops for sh in synth_batch(2, SynthSpec(n_ops=470))],
        length=1024, value_space=1024, to_device=False,
    )
    cols = [_sds(one_chip, c, 1024)
            for c in (packed.f, packed.type, packed.value, packed.mask)]
    _compile(_combined_batch, *cols, value_space=1024)


def test_queue_flat_verdict_B64_L1024_V512(one_chip):
    """The single-device pipeline's queue program as the replay runs it:
    the combined verdict flattened into one int32 buffer inside one
    donating jit of a lambda, whose module name (``jit__lambda``) the
    benchmark's roofline reader matches; 64 histories, L=1024, V=512."""
    import jax

    from jepsen_tpu.checkers.fused import combined_tensor_check
    from jepsen_tpu.history.encode import pack_histories
    from jepsen_tpu.history.synth import SynthSpec, synth_batch
    from jepsen_tpu.parallel.pipeline import flatten_verdict

    packed = pack_histories(
        [sh.ops for sh in synth_batch(2, SynthSpec(n_ops=470))],
        length=1024, value_space=512, to_device=False,
    )
    batch = jax.tree.map(lambda c: _sds(one_chip, c, 64), packed)
    program = jax.jit(
        lambda p: flatten_verdict(combined_tensor_check(p, packed_out=True)),
        donate_argnums=0,
    )
    compiled = _compile(program, batch)
    assert compiled.as_text().startswith("HloModule jit__lambda,")
    # 10 count and flag columns, 8 classes of 512 / 32 bitplanes
    assert "s32[64,138]" in compiled.as_text()


def test_pallas_queue_stats_compiles_through_mosaic(one_chip):
    """The Pallas kernel with ``interpret=False`` lowers to a Mosaic
    custom call, not to the interpreter's XLA."""
    import jax
    import jax.numpy as jnp

    from jepsen_tpu.ops.pallas_stats import _fused_queue_stats

    col = jax.ShapeDtypeStruct((1024, 1024), jnp.int32, sharding=one_chip)
    compiled = _compile(
        _fused_queue_stats, col, col, col, col,
        value_space=1024, interpret=False,
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_stream_checker_10k_ops(one_chip):
    """The stream linearizability scan at BASELINE config #4's length."""
    from jepsen_tpu.checkers.stream_lin import (
        _stream_lin_batch,
        pack_stream_histories,
    )
    from jepsen_tpu.history.synth import StreamSynthSpec, synth_stream_batch

    b = pack_stream_histories(
        [sh.ops for sh in synth_stream_batch(
            2, StreamSynthSpec(n_ops=10_000))]
    )
    assert b.type.shape[1] >= 10_000
    cols = [_sds(one_chip, c, 64) for c in (
        b.type, b.f, b.value, b.offset, b.pos, b.mask, b.first, b.full_read,
    )]
    _compile(_stream_lin_batch, *cols, space=b.space)


def test_elle_bitplane_closure_64_txns(one_chip):
    """The elle cycle search with the packed (uint32 bitplane) closure,
    1024 graphs of 64 txns (BASELINE config #5)."""
    from jepsen_tpu.checkers.elle import (
        _elle_batch,
        infer_txn_graph,
        pack_txn_graphs,
    )
    from jepsen_tpu.history.synth import ElleSynthSpec, synth_elle_batch

    b = pack_txn_graphs([
        infer_txn_graph(sh.ops)
        for sh in synth_elle_batch(2, ElleSynthSpec(n_txns=64))
    ])
    cols = [_sds(one_chip, c, 1024)
            for c in (b.ww, b.wr, b.rw, b.txn_mask, b.host_bad)]
    _compile(_elle_batch, *cols, n_txns=b.n_txns, closure="packed")


def test_pcomp_wgl_frontier_mutex(one_chip):
    """The P-compositional WGL frontier search over the first shape
    bucket of a 200-op mutex history (``chip_smoke.py``'s size).  The
    other buckets differ only in candidate width, so one stands for
    all."""
    from jepsen_tpu.checkers.wgl import _wgl_program_cached, mutex_wgl_ops
    from jepsen_tpu.checkers.wgl_pcomp import (
        _subset_program_cached,
        bucketize,
        decompose,
    )
    from jepsen_tpu.history.synth import MutexSynthSpec, synth_mutex_batch
    from jepsen_tpu.models.core import OwnedMutex

    model_key = (OwnedMutex, ())
    decomps = [
        decompose(mutex_wgl_ops(sh.ops), model_key)
        for sh in synth_mutex_batch(2, MutexSynthSpec(n_ops=200))
    ]
    bk = bucketize(decomps, to_device=False)[0]
    if bk.engine == "subset":
        prog = _subset_program_cached(bk.batch.n)
        cols = (bk.batch.enq, bk.batch.deq, bk.batch.ret_op, bk.batch.cands)
    else:
        prog = _wgl_program_cached(
            bk.model_key, bk.batch.n, bk.capacity,
            int(bk.batch.cands.shape[-1]),
        )
        cols = (bk.batch.f, bk.batch.a0, bk.batch.a1, bk.batch.ret_op,
                bk.batch.cands)
    _compile(prog, *[_sds(one_chip, c, 64) for c in cols])
