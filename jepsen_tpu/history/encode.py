"""Packing histories into fixed-shape ``int32`` tensors for TPU checking.

Design (SURVEY.md §7.1): an op becomes a row of int32 columns
``(index, process, type, f, value, time_ms, latency_ms)``; a batch of
histories is a struct-of-arrays of shape ``[B, L]`` per column plus a
``mask``.  Struct-of-arrays (not an ``[B, L, 7]`` array-of-structs) so each
column lays out contiguously along the TPU lane dimension and kernels touch
only the columns they need.

Two encoding rules make irregular Jepsen histories regular:

1. **Drain explosion.**  A drain completion carries a *list* of values
   (reference: ``Utils.java:140-145`` returns a vector of ints).  The packer
   explodes it into one row per drained value (same process/time, ``f=DRAIN``,
   ``type=OK``) so every row has a scalar value.  An empty drain becomes a
   single row with ``value = NO_VALUE``.
2. **Padding/bucketing.**  Histories are padded to a fixed length ``L``
   (rounded up to a multiple of 128 — the TPU lane width — by default);
   padded rows have ``mask=False`` and must be no-ops in every kernel.

A ``first`` flag marks the first row of every op (False on the 2nd..kth rows
of an exploded drain), so per-op statistics — e.g. perf completion rates —
can count ops rather than rows.

``latency_ms`` is precomputed host-side on completion rows (completion time −
invocation time, per process) so the perf checker is pure tensor math; it is
``-1`` on invocations, pads, and unmatched completions.

Values are dense small ints from a single incrementing counter (reference:
``rabbitmq.clj:245-247``), so a per-history value-space of size ``V ≈ L`` is
enough: no enqueue attempt can exist without occupying an op slot.  ``V`` is
recorded on the packed batch and is the scatter width of the count kernels.
"""

from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from typing import Sequence

import jax
import numpy as np

from jepsen_tpu.history.ops import NO_VALUE, Op, OpF, OpType
from jepsen_tpu.obs import metrics as obs_metrics
from jepsen_tpu.obs import trace as obs_trace

LANE = 128  # TPU lane width; default padding granule


def _round_up(n: int, k: int) -> int:
    return ((max(n, 1) + k - 1) // k) * k


@jax.tree_util.register_dataclass
@dataclass
class PackedHistories:
    """A batch of histories as ``[B, L]`` integer columns (+ bool mask).

    The checker-hot columns (``type``/``f``/``value``/``mask``) use the
    narrowest dtype that holds their range — the checkers are
    HBM-bandwidth-bound, so bytes are throughput.  ``value_space``
    (static): scatter width V of per-value count kernels.  All values are
    either in ``[0, V)`` or ``NO_VALUE``.
    """

    index: jax.Array  # [B, L] i32 — original history index of the row
    process: jax.Array  # [B, L] i32
    type: jax.Array  # [B, L] i8 — OpType codes
    f: jax.Array  # [B, L] i8 — OpF codes
    value: jax.Array  # [B, L] i16 (i32 when V > 32767) — value or NO_VALUE
    time_ms: jax.Array  # [B, L] i32 — ms since history start
    latency_ms: jax.Array  # [B, L] i32 — completion latency or -1
    mask: jax.Array  # [B, L] bool
    first: jax.Array  # [B, L] bool — first exploded row of its op
    value_space: int = dataclasses.field(metadata=dict(static=True), default=0)

    @property
    def batch(self) -> int:
        return self.type.shape[0]

    @property
    def length(self) -> int:
        return self.type.shape[1]


from jepsen_tpu.history.rows import _COLUMNS, _rows_for  # noqa: E402,F401


def pack_histories(
    histories: Sequence[Sequence[Op]],
    length: int | None = None,
    value_space: int | None = None,
    to_device: bool = True,
) -> PackedHistories:
    """Pack a batch of histories into one ``PackedHistories``.

    ``length``: target L; default = max exploded length rounded up to 128.
    ``value_space``: scatter width V; default = max(value)+1 across the batch
    rounded up to 128 (at least 128).
    ``to_device=False`` keeps the columns as host (numpy) arrays — packing
    then never touches a JAX backend, which callers that must stay
    backend-neutral (the driver's ``entry()`` contract) rely on; the first
    jit call places them.
    """
    if not histories:
        raise ValueError("cannot pack an empty batch of histories")
    return pack_row_matrices(
        [_rows_for(h) for h in histories],
        length=length,
        value_space=value_space,
        to_device=to_device,
    )


def pack_row_matrices(
    mats: Sequence[np.ndarray],
    length: int | None = None,
    value_space: int | None = None,
    to_device: bool = True,
) -> PackedHistories:
    """Assemble pre-exploded ``[n, 8]`` row matrices (``_rows_for``) into
    a :class:`PackedHistories`.  Split out of :func:`pack_histories` so
    row explosion — the per-op half of packing — can run in parallel
    worker processes (``history.parpack``) while this assembly stays in
    the parent.

    Runs as the ``encode.pack`` span, its seconds (the conversion, and
    with ``to_device`` the upload, included) in the global sketch
    ``encode.pack_s{to_device=true|false}``."""
    if not mats:
        raise ValueError("cannot pack an empty batch of histories")
    t0 = time.perf_counter()
    with obs_trace.span("encode.pack"):
        packed = _assemble(mats, length, value_space, to_device)
    obs_metrics.REGISTRY.sketch(
        "encode.pack_s", to_device="true" if to_device else "false"
    ).add(time.perf_counter() - t0)
    return packed


def _assemble(
    mats: Sequence[np.ndarray],
    length: int | None,
    value_space: int | None,
    to_device: bool,
) -> PackedHistories:
    n_max = max(m.shape[0] for m in mats)
    L = length if length is not None else _round_up(n_max, LANE)
    if n_max > L:
        raise ValueError(f"history of exploded length {n_max} exceeds L={L}")
    B = len(mats)

    vmax = max(
        (int(m[:, 4].max(initial=0)) for m in mats if m.shape[0]), default=0
    )
    V = (
        value_space
        if value_space is not None
        else _round_up(vmax + 1, LANE)
    )
    if vmax >= V:
        # values outside [0, V) would be silently dropped by the scatter
        # kernels — exactly the values an "unexpected" anomaly produces
        raise ValueError(
            f"history contains value {vmax} >= value_space {V}; "
            "raise value_space (or omit it to size automatically)"
        )

    # The hot checker path is HBM-bandwidth-bound, so the columns it reads
    # ship in the narrowest dtype that holds their range (measured ~1.8×
    # on-chip throughput vs all-int32): op codes in i8, values in i16 when
    # the value space allows (the scatter kernels route selected rows to
    # index V, so V itself must be representable).  Host-analysis columns
    # (index/process/times) stay i32.  Columns are allocated in their
    # final dtype (no whole-array astype copies — they were ~40% of
    # assembly time at 10k×1k scale).
    val_dt = np.int16 if V <= np.iinfo(np.int16).max else np.int32
    dtypes = {
        "index": np.int32,
        "process": np.int32,
        "type": np.int8,
        "f": np.int8,
        "value": val_dt,
        "time_ms": np.int32,
        "latency_ms": np.int32,
        "first": bool,
    }
    cols = {
        c: np.full((B, L), -1, dtype=dt)
        if c != "first"
        else np.zeros((B, L), dtype=bool)
        for c, dt in dtypes.items()
    }
    cols["value"][:] = NO_VALUE
    mask = np.zeros((B, L), dtype=bool)
    for b, m in enumerate(mats):
        n = m.shape[0]
        for ci, c in enumerate(_COLUMNS):
            cols[c][b, :n] = m[:, ci]
        mask[b, :n] = True

    conv = jax.numpy.asarray if to_device else np.asarray
    return PackedHistories(
        index=conv(cols["index"]),
        process=conv(cols["process"]),
        type=conv(cols["type"]),
        f=conv(cols["f"]),
        value=conv(cols["value"]),
        time_ms=conv(cols["time_ms"]),
        latency_ms=conv(cols["latency_ms"]),
        mask=conv(mask),
        first=conv(cols["first"]),
        value_space=V,
    )


def pack_history(
    history: Sequence[Op],
    length: int | None = None,
    value_space: int | None = None,
    to_device: bool = True,
) -> PackedHistories:
    """Pack a single history (batch dim of 1)."""
    return pack_histories(
        [history],
        length=length,
        value_space=value_space,
        to_device=to_device,
    )
