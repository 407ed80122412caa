"""Pipelined bytes-to-verdict executor: overlap pack, staging, check.

BENCH_r05 measured the stream checker's end-to-end rate at ~8% of its
device-only rate (20,055 device vs 1,638 e2e hist/s; stream_10k 211 vs
19): parse → pack → transfer → check ran strictly serially on one
thread, so the device idled through every host phase and vice versa.
This module is the input-pipeline subsystem that closes that gap — the
same overlap discipline a training stack applies to data loading
(tf.data / Grain-style prefetch), applied to history verification:

    producer thread                 consumer (caller) thread
    ───────────────                 ────────────────────────
    chunk k+1: native thread-pool   chunk k:   device_put (async H2D)
               parse + host pack               dispatch checker program
               (GIL released for               block on chunk k-1's
               the whole native                verdict, convert to host
               batch)                          results

- **Host stage** (``produce``): runs on a dedicated producer thread.
  The family producers parse history bytes through the native
  thread-pool entry points (``fastpack.pack_files`` /
  ``stream_rows_files`` / ``elle_mops_files`` — ctypes releases the GIL
  for the whole multi-file call) with the digest-keyed per-file caches
  consulted first, then assemble HOST (numpy) batches with
  power-of-two shape bucketing so chunked packing reuses the jitted
  programs instead of recompiling per chunk.
- **Staging stage** (``place``): ``jax.device_put`` of the host batch —
  asynchronous H2D; with a mesh, the sharded placement from
  ``parallel.mesh``.
- **Check stage** (``check``): the family's jitted verdict program,
  optionally wrapped with ``donate_argnums=0`` so the staged input
  buffers are donated to the computation (the recycled staging slot:
  XLA reuses the donated bytes for temporaries/outputs instead of
  holding both generations live — double-buffer depth bounds peak
  footprint at 2 staged batches).  At most ``depth`` batches are in
  flight; the executor blocks on the OLDEST dispatch, so the device
  works through chunk k while the host packs chunk k+1.

Crash semantics are ELASTIC by default (PR 13): failure isolation is
work-unit-granular, not run-granular.  A chunk whose
produce/place/dispatch/collect raises is retried once (on another lane
when one exists), then QUARANTINED — ``check_sources`` isolates the
quarantined chunk per history (each member re-runs alone, so one poison
history cannot condemn its chunk-mates) and the crasher(s) report
``unknown`` with the captured exception as evidence while every other
history's verdict survives.  A quarantine can never fold into ``valid``
(the composed verdict is at best ``unknown``; ``invalid`` still trumps
everything — the PR-8 precedence rule).  ``fail_fast=True`` restores
the PR-4 contract verbatim: a stage failure on ANY chunk aborts the
whole run with :class:`PipelineError` and NO verdicts — partial results
never escape.  ``tests/test_pipeline.py`` holds the differential
contract (pipelined ≡ serial for every family, including
degenerate-history host-fallback splices) and both crash contracts;
``tests/test_elastic.py`` holds the poison-history quarantine proofs.
"""

from __future__ import annotations

import contextlib
import dataclasses
import queue
import threading
import time
import traceback
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Sequence

import numpy as np

from jepsen_tpu.obs import metrics as obs_metrics
from jepsen_tpu.obs import trace as obs_trace

#: histories per pipeline chunk — small enough that the first chunk
#: reaches the device quickly, large enough to amortize dispatch
DEFAULT_CHUNK = 64

#: history bytes per pipeline chunk: a call whose files hold more than
#: this many bytes a chunk of ``DEFAULT_CHUNK`` is cut into more,
#: smaller chunks, so that the producer packs the next chunk while the
#: device checks this one (:func:`_plan_chunk`)
CHUNK_BYTES = 24 << 20

#: path sources stat-ed to estimate a call's bytes (:func:`_sample_sizes`)
SIZE_SAMPLE = 8


class PipelineError(RuntimeError):
    """A pipeline stage crashed; no verdicts were emitted (the
    ``fail_fast=True`` contract — elastic runs quarantine instead)."""


def _scrub_exc(e):
    """Drop frame locals from a captured exception's traceback before
    retaining it as quarantine evidence: the produce/place/check frames
    pin whole packed batches and device trees, and the evidence only
    ever formats the exception chain, never the frames.  Walks the
    whole __cause__/__context__ chain — an exception raised while
    handling another still carries the original's frames."""
    seen: set[int] = set()
    cur = e if isinstance(e, BaseException) else None
    while cur is not None and id(cur) not in seen:
        seen.add(id(cur))
        try:
            traceback.clear_frames(cur.__traceback__)
        except Exception:  # pragma: no cover - evidence must never raise
            pass
        cur = cur.__cause__ or cur.__context__
    return e


class Quarantined:
    """The final 'collected result' of a work unit (or single history)
    whose stage failures exhausted the retry budget: the executor keeps
    going and this object carries the captured evidence in the unit's
    result slot.  ``check_sources`` turns it into explicit
    ``unknown``-with-evidence verdict entries — a quarantine is always
    visible, never a silent drop, and can never fold into ``valid``."""

    __slots__ = ("index", "stage", "attempts", "errors")

    def __init__(self, index: int, stage: str, attempts, errors):
        self.index = index
        self.stage = stage
        self.attempts = list(attempts)
        self.errors = [_scrub_exc(e) for e in errors]

    def evidence(self) -> dict:
        return {
            "stage": self.stage,
            "attempts": self.attempts,
            "errors": [
                f"{type(e).__name__}: {e}" for e in self.errors
            ],
        }

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (
            f"Quarantined(unit={self.index}, stage={self.stage!r}, "
            f"errors={self.evidence()['errors']})"
        )


def _counter_field(name: str, cast=int, **labels):
    """A PipelineStats attribute backed by its run-scoped registry —
    the stats object is a VIEW; the registry is the storage."""

    def get(self):
        return cast(self.metrics.value(name, **labels))

    def set(self, v):
        self.metrics.counter(name, **labels).set(v)

    return property(get, set)


class PipelineStats:
    """Executor timing evidence (the bench's utilization schema) — a
    VIEW over an obs metrics registry (``jepsen_tpu/obs/metrics.py``).
    Every field except the two derived fractions is backed by a counter
    in ``self.metrics`` (a per-run :class:`~jepsen_tpu.obs.metrics.Registry`);
    the stage busy-seconds bookkeeping that used to live separately in
    ``run_pipeline``, ``run_lanes``, and the serial path now goes
    through ONE accounting point (:meth:`add_busy`), which also mirrors
    cumulative totals into the process-global registry (the service
    ``/metrics`` endpoint reads those) and records the stage as a trace
    span when the flight recorder is on.

    ``stage_overlap_frac``: fraction of total stage busy time that ran
    concurrently with another stage — 0.0 for a strictly serial run,
    approaching ``1 - 1/stages`` for a perfectly overlapped one.
    ``device_idle_frac``: fraction of wall clock with no device work in
    flight (the executor's target is to drive this toward 0 once the
    first batch is staged).  Multi-lane runs (``lanes > 1``) report
    busy seconds SUMMED across lanes, and ``device_idle_frac`` against
    the ``lanes × wall`` device-time budget.  ``dropped`` counts
    sources excluded by the lanes path's size census (unreadable /
    zero-length files — each is logged AND counted in the global
    ``pipeline.files_dropped`` counter; never a silent truncation)."""

    def __init__(self, lanes: int = 1, dropped: int = 0):
        self.metrics = obs_metrics.Registry()
        self.lanes = lanes
        self.wall_s = 0.0
        self.stage_overlap_frac = 0.0
        self.device_idle_frac = 0.0
        if dropped:
            self.dropped = dropped

    batches = _counter_field("pipeline.batches")
    histories = _counter_field("pipeline.histories")
    dropped = _counter_field("pipeline.files_dropped")
    quarantined = _counter_field("pipeline.quarantined")
    unit_retries = _counter_field("pipeline.unit_retries")
    produce_busy_s = _counter_field(
        "pipeline.stage_busy_s", cast=float, stage="produce"
    )
    place_busy_s = _counter_field(
        "pipeline.stage_busy_s", cast=float, stage="place"
    )
    check_busy_s = _counter_field(
        "pipeline.stage_busy_s", cast=float, stage="check"
    )

    def add_busy(
        self, stage: str, t0: float, t1: float, track: str | None = None,
        traced: bool = False,
    ) -> None:
        """THE stage accounting point (``t0``/``t1`` from
        ``time.perf_counter()``): run-scoped counter + global cumulative
        counter + per-batch check-latency sketch + trace span, in one
        call, so no executor keeps private busy-second arithmetic.
        ``traced``: a live span already covered the interval
        (:meth:`busy`), so the ring gets no second record of it."""
        dt = t1 - t0
        self.metrics.counter("pipeline.stage_busy_s", stage=stage).inc(dt)
        obs_metrics.REGISTRY.counter(
            "pipeline.stage_busy_s", stage=stage
        ).inc(dt)
        if stage == "check":
            # the device-interval latency of one batch — the p50/p99
            # source for obs_overhead and the stats snapshot
            self.metrics.sketch("pipeline.check_batch_s").add(dt)
            obs_metrics.REGISTRY.sketch("pipeline.check_batch_s").add(dt)
        if not traced:
            obs_trace.complete(f"pipeline.{stage}", t0, t1, track=track)

    @contextlib.contextmanager
    def busy(self, stage: str, track: str | None = None):
        """Account the block as ``stage`` busy time under a live
        ``pipeline.<stage>`` span (busy time counted on success; a
        crashing stage aborts or quarantines anyway)."""
        with obs_trace.span(f"pipeline.{stage}", track=track):
            t0 = time.perf_counter()
            yield
            t1 = time.perf_counter()
        self.add_busy(stage, t0, t1, track=track, traced=True)

    def run_stage(self, stage: str, fn, arg, track: str | None = None):
        """Run ``fn(arg)`` as an accounted stage (:meth:`busy`)."""
        with self.busy(stage, track=track):
            return fn(arg)

    def note_retry(
        self, stage: str, index: int, exc: BaseException,
        lane: int | None = None,
    ) -> None:
        """One work-unit retry (elastic mode): run-scoped + global
        counters, and a flight-recorder event when the tracer is on —
        the requeue is countable after the run, never just a log line."""
        self.metrics.counter("pipeline.unit_retries").inc()
        obs_metrics.REGISTRY.counter("pipeline.unit_retries").inc()
        if obs_trace.is_enabled():
            obs_trace.event(
                "checker.unit_retry",
                args={
                    "unit": index,
                    "stage": stage,
                    "lane": lane,
                    "error": f"{type(exc).__name__}: {exc}",
                },
            )

    def note_quarantine(self, evidence: dict, histories: int = 1) -> None:
        """``histories`` final quarantined verdicts (elastic mode):
        counted per HISTORY in the run-scoped and global registries,
        plus a flight-recorder event carrying the evidence."""
        self.metrics.counter("pipeline.quarantined").inc(histories)
        obs_metrics.REGISTRY.counter("pipeline.quarantined").inc(histories)
        if obs_trace.is_enabled():
            obs_trace.event(
                "checker.quarantine",
                args={"histories": histories, **evidence},
            )

    def check_batch_quantile(self, q: float) -> float:
        return self.metrics.sketch("pipeline.check_batch_s").quantile(q)

    def finalize(self) -> "PipelineStats":
        # the executor's three stages; ``collect`` (inside check's
        # interval) and ``assemble`` (verdict assembly after the
        # executor returns) stay out of both fractions
        busy = self.produce_busy_s + self.place_busy_s + self.check_busy_s
        self.stage_overlap_frac = (
            max(0.0, busy - self.wall_s) / busy if busy > 0 else 0.0
        )
        budget = self.wall_s * max(self.lanes, 1)
        self.device_idle_frac = (
            max(0.0, budget - self.check_busy_s) / budget
            if budget > 0
            else 0.0
        )
        return self


_STOP = object()
_UNSET = object()


class _Crash:
    def __init__(self, index: int, exc: BaseException):
        self.index = index
        self.exc = exc


class _Poison:
    """Producer → consumer marker (elastic mode): item ``index``'s
    produce stage failed past its retry; quarantine it and keep going."""

    def __init__(self, index: int, stage: str, errors):
        self.index = index
        self.stage = stage
        self.errors = list(errors)


def _take_produced(q: queue.Queue):
    """The consumer's blocking take from the producer's queue: the
    ``pipeline.wait`` span and the global ``pipeline.wait_s{on=produce}``
    seconds (the place/check thread idle on ingest)."""
    with obs_trace.span("pipeline.wait"):
        t0 = time.perf_counter()
        got = q.get()
        dt = time.perf_counter() - t0
    obs_metrics.REGISTRY.counter("pipeline.wait_s", on="produce").inc(dt)
    return got


def _default_collect(raw):
    """The collect contract every executor and the serial oracle share:
    block on the device tree, then convert it to host numpy, one
    device-to-host read per leaf (counted as ``pipeline.readbacks
    {path=leaves}``; the single-device queue family reads its verdict
    in one, :func:`_flat_readback`)."""
    import jax

    jax.block_until_ready(raw)
    obs_metrics.REGISTRY.counter("pipeline.readbacks", path="leaves").inc()
    return jax.tree.map(np.asarray, raw)


def run_pipeline(
    items: Sequence[Any],
    produce: Callable[[Any], Any],
    check: Callable[[Any], Any],
    *,
    place: Callable[[Any], Any] | None = None,
    collect: Callable[[Any], Any] | None = None,
    depth: int = 2,
    fail_fast: bool = False,
) -> tuple[list[Any], PipelineStats]:
    """Run ``items`` through produce → place → check with overlap.

    ``produce(item)`` runs on the producer thread (host pack);
    ``place(host_batch)`` and ``check(placed)`` on the caller's thread —
    ``check`` must DISPATCH asynchronously (a jitted JAX program does);
    the executor blocks on the oldest in-flight result via
    ``collect(raw)`` (default: ``jax.block_until_ready`` + numpy
    conversion), keeping at most ``depth`` dispatches outstanding.

    Returns ``(results, stats)`` with one collected result per item, in
    order.  Failure isolation is per work unit by default: a stage
    exception on item k is retried once, then item k's result slot
    holds a :class:`Quarantined` carrying the evidence while every
    other item completes.  ``fail_fast=True`` restores the abort-all
    contract: any stage exception raises :class:`PipelineError` and NO
    results escape (see module docstring).
    """
    import jax

    if place is None:
        place = jax.device_put
    if collect is None:
        collect = _default_collect

    stats = PipelineStats()
    n = len(items)
    if n == 0:
        return [], stats
    t_start = time.perf_counter()
    results: list[Any] = [None] * n
    if fail_fast:
        _run_pipeline_failfast(
            items, produce, check, place, collect, depth, stats, results,
            t_start,
        )
    else:
        _run_pipeline_elastic(
            items, produce, check, place, collect, depth, stats, results,
            t_start,
        )
    stats.batches = n
    stats.wall_s = time.perf_counter() - t_start
    return results, stats.finalize()


def _run_pipeline_failfast(
    items, produce, check, place, collect, depth, stats, results, t_start
) -> None:
    """The PR-4 abort-all executor: any stage exception raises
    :class:`PipelineError`, partial results never escape."""
    q: queue.Queue = queue.Queue(maxsize=max(1, depth))
    abort = threading.Event()

    def put(obj) -> None:
        # bounded puts re-check the abort flag so a crashed consumer
        # can never wedge the producer behind a full queue
        while not abort.is_set():
            try:
                q.put(obj, timeout=0.1)
                return
            except queue.Full:
                continue

    def producer() -> None:
        i = 0
        try:
            for i, item in enumerate(items):
                if abort.is_set():
                    return
                host = stats.run_stage("produce", produce, item)
                put((i, host))
            put(_STOP)
        except BaseException as e:  # noqa: BLE001 - re-raised by consumer
            put(_Crash(i, e))

    prod = threading.Thread(
        target=producer, name="pipeline-producer", daemon=True
    )
    prod.start()

    in_flight: list[tuple[int, Any, float]] = []  # (index, raw, dispatch_t)
    last_ready = t_start

    def drain_one() -> None:
        nonlocal last_ready
        i, raw, t_disp = in_flight.pop(0)
        with stats.busy("collect"):
            results[i] = collect(raw)
        t_ready = time.perf_counter()
        # device occupancy: the interval this batch actually had the
        # device, serialized against the previous batch's completion
        stats.add_busy("check", max(t_disp, last_ready), t_ready)
        last_ready = t_ready

    try:
        while True:
            got = _take_produced(q)
            if got is _STOP:
                break
            if isinstance(got, _Crash):
                raise PipelineError(
                    f"pipeline produce stage crashed on batch "
                    f"{got.index}: {type(got.exc).__name__}: {got.exc}"
                ) from got.exc
            i, host = got
            placed = stats.run_stage("place", place, host)
            t_disp = time.perf_counter()
            raw = check(placed)
            in_flight.append((i, raw, t_disp))
            del placed  # the staged slot recycles once check holds it
            while len(in_flight) >= max(1, depth):
                drain_one()
        while in_flight:
            drain_one()
    except PipelineError:
        abort.set()
        raise
    except Exception as e:
        abort.set()
        raise PipelineError(
            f"pipeline check stage crashed: {type(e).__name__}: {e}"
        ) from e
    finally:
        abort.set()
        prod.join(timeout=10.0)


def _run_pipeline_elastic(
    items, produce, check, place, collect, depth, stats, results, t_start
) -> None:
    """Work-unit-granular failure isolation, single-lane shape: a
    failing stage is retried once in place (one producer, one consumer
    — there is no other lane to move to), then the item quarantines and
    every other item's verdict survives."""
    q: queue.Queue = queue.Queue(maxsize=max(1, depth))
    abort = threading.Event()

    def put(obj) -> None:
        while not abort.is_set():
            try:
                q.put(obj, timeout=0.1)
                return
            except queue.Full:
                continue

    def producer() -> None:
        i = 0
        try:
            for i, item in enumerate(items):
                if abort.is_set():
                    return
                errors: list[BaseException] = []
                host = _UNSET
                for attempt in range(2):
                    try:
                        host = stats.run_stage("produce", produce, item)
                        break
                    except Exception as e:
                        errors.append(e)
                        if attempt == 0:
                            stats.note_retry("produce", i, e)
                if host is _UNSET:
                    put(_Poison(i, "produce", errors))
                else:
                    put((i, host))
            put(_STOP)
        except BaseException as e:  # noqa: BLE001 - re-raised by consumer
            # SystemExit-class: quarantine is for failures, not for
            # cancellation — crash loud, same as the fail-fast path
            put(_Crash(i, e))

    prod = threading.Thread(
        target=producer, name="pipeline-producer", daemon=True
    )
    prod.start()

    in_flight: list[tuple[int, Any, float]] = []
    last_ready = t_start

    def drain_one() -> None:
        nonlocal last_ready
        i, raw, t_disp = in_flight.pop(0)
        errors: list[BaseException] = []
        got = _UNSET
        for attempt in range(2):
            try:
                # a dispatch error surfaces here (async programs raise
                # at block time)
                with stats.busy("collect"):
                    got = collect(raw)
                break
            except Exception as e:
                errors.append(e)
                if attempt == 0:
                    stats.note_retry("collect", i, e)
                    # a failed dispatch poisons the raw tree — blocking
                    # on it again just re-raises, so the one genuine
                    # retry re-runs the whole chain from items[i]
                    # (deterministic; retaining the packed host tree
                    # for this rare path would pin `depth` full chunks
                    # of host memory on the no-fault hot path)
                    try:
                        raw = check(
                            stats.run_stage(
                                "place",
                                place,
                                stats.run_stage("produce", produce, items[i]),
                            )
                        )
                    except Exception as e2:
                        errors.append(e2)
                        break
        if got is _UNSET:
            results[i] = Quarantined(i, "collect", ["main"], errors)
            last_ready = time.perf_counter()
            return
        results[i] = got
        t_ready = time.perf_counter()
        stats.add_busy("check", max(t_disp, last_ready), t_ready)
        last_ready = t_ready

    try:
        while True:
            got = _take_produced(q)
            if got is _STOP:
                break
            if isinstance(got, _Crash):
                raise PipelineError(
                    f"pipeline produce stage crashed on batch "
                    f"{got.index}: {type(got.exc).__name__}: {got.exc}"
                ) from got.exc
            if isinstance(got, _Poison):
                results[got.index] = Quarantined(
                    got.index, got.stage, ["producer"], got.errors
                )
                continue
            i, host = got
            errors = []
            raw = _UNSET
            stage = "place"
            for attempt in range(2):
                try:
                    stage = "place"
                    placed = stats.run_stage("place", place, host)
                    stage = "check"
                    t_disp = time.perf_counter()
                    raw = check(placed)
                    break
                except Exception as e:
                    errors.append(e)
                    if attempt == 0:
                        stats.note_retry(stage, i, e)
            if raw is _UNSET:
                results[i] = Quarantined(i, stage, ["main"], errors)
                continue
            in_flight.append((i, raw, t_disp))
            del placed  # the staged slot recycles once check holds it
            while len(in_flight) >= max(1, depth):
                drain_one()
        while in_flight:
            drain_one()
    finally:
        abort.set()
        prod.join(timeout=10.0)


def run_lanes(
    units: Sequence[Any],
    fams: Sequence["_Family"],
    *,
    depth: int = 2,
    fail_fast: bool = False,
) -> tuple[list[Any], PipelineStats]:
    """The N-lane generalization of :func:`run_pipeline`: one lane per
    family in ``fams`` (one per addressable device), each running the
    full produce → place → dispatch → collect loop on its own thread
    with its own double-buffered staging slot.  Lanes claim work units
    off ONE shared queue — an idle lane immediately takes the next
    (largest-remaining) unit, so no device waits on another lane's
    packing (steal-on-idle by construction).

    Failure isolation matches :func:`run_pipeline`: elastic by default
    — a unit whose stage raises is retried once on ANOTHER lane (when
    one is alive), then its result slot holds a :class:`Quarantined`
    while every other unit completes.  ``fail_fast=True`` restores the
    PR-5 contract: any lane failure aborts the whole run with
    :class:`PipelineError` and NO results."""
    n = len(units)
    results: list[Any] = [None] * n
    stats = PipelineStats(lanes=len(fams))
    if n == 0:
        return results, stats
    if not fail_fast:
        t_start = time.perf_counter()
        _run_lanes_elastic(units, fams, depth, stats, results)
        stats.wall_s = time.perf_counter() - t_start
        stats.batches = n
        return results, stats.finalize()
    return _run_lanes_failfast(units, fams, depth, stats, results)


def _run_lanes_failfast(
    units, fams, depth, stats, results
) -> tuple[list[Any], PipelineStats]:
    n = len(units)
    abort = threading.Event()
    failures: list[tuple[int, BaseException]] = []
    unit_q: queue.Queue = queue.Queue()
    for k in range(n):
        unit_q.put(k)

    def lane(i: int) -> None:
        # stage accounting goes straight through the shared stats view
        # (per-metric locks; no per-lane busy arrays to merge), with
        # each lane's spans on its own `laneN` track
        fam = fams[i]
        track = f"lane{i}"
        collect = fam.collect or _default_collect
        in_flight: list[tuple[int, Any, float]] = []
        last_ready = time.perf_counter()

        def drain_one():
            nonlocal last_ready
            k, raw, t_disp = in_flight.pop(0)
            with stats.busy("collect", track=track):
                results[k] = collect(raw)
            t_ready = time.perf_counter()
            stats.add_busy(
                "check", max(t_disp, last_ready), t_ready, track=track
            )
            last_ready = t_ready

        try:
            while not abort.is_set():
                try:
                    k = unit_q.get_nowait()
                except queue.Empty:
                    break
                host = stats.run_stage(
                    "produce", fam.produce, units[k], track=track
                )
                placed = stats.run_stage(
                    "place", fam.place, host, track=track
                )
                t_disp = time.perf_counter()
                raw = fam.check(placed)
                in_flight.append((k, raw, t_disp))
                del placed
                while len(in_flight) >= max(1, depth):
                    drain_one()
            while in_flight and not abort.is_set():
                drain_one()
        except BaseException as e:  # noqa: BLE001 - re-raised below
            abort.set()
            failures.append((i, e))

    t_start = time.perf_counter()
    threads_ = [
        threading.Thread(
            target=lane, args=(i,), name=f"lane{i}", daemon=True
        )
        for i in range(len(fams))
    ]
    for t in threads_:
        t.start()
    for t in threads_:
        t.join()
    stats.wall_s = time.perf_counter() - t_start
    if failures:
        i, e = failures[0]
        raise PipelineError(
            f"lane {i} crashed: {type(e).__name__}: {e}"
        ) from e
    stats.batches = n
    return results, stats.finalize()


def _run_lanes_elastic(units, fams, depth, stats, results) -> None:
    """The elastic N-lane executor: units carry their attempt history
    ``(k, attempts)`` through the shared queue; a unit that failed on
    lane i bounces back for a DIFFERENT live lane to retry (bounded
    bounce so the endgame cannot spin), and a second failure
    quarantines it.  Lanes run until every unit holds a final result —
    a lane never exits while a retried unit could still land on it."""
    n = len(units)
    n_lanes = len(fams)
    lock = threading.Lock()
    done = threading.Event()
    state = {"completed": 0}
    alive = set(range(n_lanes))
    errors_by_unit: dict[int, list[BaseException]] = {}
    bounce: dict[int, int] = {}
    unit_q: queue.Queue = queue.Queue()
    for k in range(n):
        unit_q.put((k, ()))

    def finalize(k: int, value) -> None:
        with lock:
            results[k] = value
            state["completed"] += 1
            if state["completed"] >= n:
                done.set()

    def fail(k: int, stage: str, attempts, e: BaseException) -> None:
        with lock:
            errors_by_unit.setdefault(k, []).append(_scrub_exc(e))
            errs = list(errors_by_unit[k])
        if len(attempts) >= 2:
            finalize(k, Quarantined(k, stage, list(attempts), errs))
        else:
            stats.note_retry(
                stage, k, e, lane=attempts[-1] if attempts else None
            )
            unit_q.put((k, tuple(attempts)))

    def lane(i: int) -> None:
        fam = fams[i]
        track = f"lane{i}"
        collect = fam.collect or _default_collect
        in_flight: list[tuple[int, Any, float, tuple]] = []
        last_ready = time.perf_counter()
        # the unit this lane holds that is in NEITHER unit_q nor
        # in_flight nor results — the lane-level crash handler must
        # return it to the pool or the run loses it and never finishes
        current: tuple[int, tuple] | None = None

        def drain_one() -> None:
            nonlocal last_ready, current
            k, raw, t_disp, attempts = in_flight.pop(0)
            current = (k, attempts)
            try:
                with stats.busy("collect", track=track):
                    got = collect(raw)
            except Exception as e:
                fail(k, "collect", attempts, e)
                current = None
                last_ready = time.perf_counter()
                return
            t_ready = time.perf_counter()
            stats.add_busy(
                "check", max(t_disp, last_ready), t_ready, track=track
            )
            last_ready = t_ready
            finalize(k, got)
            current = None

        try:
            while True:
                if done.is_set() and not in_flight:
                    break
                try:
                    k, attempts = unit_q.get(timeout=0.05)
                except queue.Empty:
                    if in_flight:
                        drain_one()
                    continue
                current = (k, attempts)
                if attempts and attempts[-1] == i:
                    # retried unit, and THIS lane failed it: hand it to
                    # a different live lane when one exists (bounded
                    # bounce — after that, run it here rather than spin)
                    with lock:
                        others = len(alive) > 1
                        if others and bounce.get(k, 0) < 4 * n_lanes:
                            bounce[k] = bounce.get(k, 0) + 1
                        else:
                            others = False
                    if others:
                        unit_q.put((k, attempts))
                        current = None
                        time.sleep(0.01)
                        continue
                att = attempts + (i,)
                stage = "produce"
                try:
                    host = stats.run_stage(
                        "produce", fam.produce, units[k], track=track
                    )
                    stage = "place"
                    placed = stats.run_stage(
                        "place", fam.place, host, track=track
                    )
                    stage = "check"
                    t_disp = time.perf_counter()
                    raw = fam.check(placed)
                except Exception as e:
                    fail(k, stage, att, e)
                    current = None
                    continue
                in_flight.append((k, raw, t_disp, att))
                current = None
                del placed
                while len(in_flight) >= max(1, depth):
                    drain_one()
            with lock:
                alive.discard(i)
        except BaseException as e:  # noqa: BLE001 - executor-level crash:
            # the lane dies; its in-flight units return to the pool, and
            # the LAST lane out quarantines whatever is still queued so
            # the run always terminates with one result per unit
            if current is not None:
                ck, catt = current
                try:
                    fail(ck, "lane", tuple(catt) + (i,), e)
                except BaseException:  # noqa: BLE001 - fail() itself broke
                    finalize(
                        ck, Quarantined(ck, "lane", list(catt) + [i], [e])
                    )
            for k, _raw, _t, attempts in in_flight:
                try:
                    fail(k, "collect", attempts, e)
                except BaseException:  # noqa: BLE001 - fail() itself broke
                    finalize(
                        k, Quarantined(k, "collect", list(attempts), [e])
                    )
            with lock:
                alive.discard(i)
                last = not alive
            if last and not done.is_set():
                while True:
                    try:
                        k, attempts = unit_q.get_nowait()
                    except queue.Empty:
                        break
                    finalize(
                        k, Quarantined(k, "lane", list(attempts) + [i], [e])
                    )

    threads_ = [
        threading.Thread(
            target=lane, args=(i,), name=f"lane{i}", daemon=True
        )
        for i in range(n_lanes)
    ]
    for t in threads_:
        t.start()
    for t in threads_:
        t.join()


_DONATED_CACHE: dict = {}
#: the verdict layout :func:`_flat_readback` splits by, per program key
#: and input signature (``jax.eval_shape`` of the verdict program)
_FLAT_SPECS: dict = {}


def donated(
    check_fn: Callable[[Any], Any], key: tuple | None = None
) -> Callable[[Any], Any]:
    """Wrap a verdict program so the staged input batch is DONATED to
    the computation (``jax.jit(..., donate_argnums=0)``): XLA may reuse
    the staged buffers for temporaries and outputs, which is what makes
    the recycled double-buffered staging slot hold at ~2 batches of
    device memory instead of accumulating one per in-flight dispatch.

    ``key`` memoizes the wrapper: jit caches are per wrapper OBJECT, so
    a fresh ``jax.jit`` per family construction would re-trace every
    batch shape in every ``check_sources`` call (and defeat warm-up
    runs).  Families pass ``(kind, *contract_params)``; the same key
    always returns the same jitted program."""
    import jax

    if key is None:
        key = ("_fn", check_fn)
    got = _DONATED_CACHE.get(key)
    if got is None:
        got = _DONATED_CACHE[key] = jax.jit(check_fn, donate_argnums=0)
    return got


def flatten_verdict(tree):
    """Every leaf of the verdict pytree ``tree`` (each ``[B, ...]`` on
    one leading batch axis) laid side by side as the columns of ONE
    ``[B, K]`` int32 array: bools as 0/1, 32-bit leaves bitcast.
    Traced inside the verdict program, so the flatten is part of its
    module, not a second dispatch; :func:`split_verdict` is the host
    inverse."""
    import jax
    import jax.numpy as jnp

    cols = []
    for leaf in jax.tree.leaves(tree):
        col = leaf.reshape(leaf.shape[0], -1)
        if col.dtype == jnp.bool_:
            col = col.astype(jnp.int32)
        elif col.dtype.itemsize == 4:
            col = jax.lax.bitcast_convert_type(col, jnp.int32)
        else:
            raise TypeError(f"no int32 column holds a {col.dtype} leaf")
        cols.append(col)
    return jnp.concatenate(cols, axis=1)


def split_verdict(buf: np.ndarray, like):
    """Cut the read-back ``[B, K]`` int32 ``buf`` of
    :func:`flatten_verdict` into the verdict pytree again: each leaf a
    numpy view of its columns (bools compared back from 0/1), shaped and
    typed as the matching leaf of ``like``, the verdict program's output
    avals (``jax.eval_shape``) — so no offset is kept by hand."""
    import jax

    leaves, treedef = jax.tree.flatten(like)
    out, at = [], 0
    for aval in leaves:
        shape, dt = tuple(aval.shape), np.dtype(aval.dtype)
        width = int(np.prod(shape[1:], dtype=np.int64))
        col = buf[:, at : at + width]
        at += width
        col = col != 0 if dt == np.bool_ else col.view(dt)
        out.append(col.reshape(shape))
    return jax.tree.unflatten(treedef, out)


def _flat_readback(fn: Callable[[Any], Any], key: tuple, donate: bool):
    """The verdict program ``fn`` (batch → verdict pytree) as a
    ``(check, collect)`` pair that reads a chunk's whole verdict back in
    ONE device-to-host transfer: the program returns the verdict
    flattened (:func:`flatten_verdict`); ``check`` starts that buffer's
    host copy as it dispatches, so the copy runs behind the kernel while
    the next chunk is placed; ``collect`` reads it once
    (``pipeline.readbacks{path=flat}``) and splits it into the tree
    ``fn`` returns.  Donating (the chip), the program is one ``donated``
    jit of a lambda, the flatten traced into it; otherwise ``fn`` runs
    as called, per chunk, and the flatten after it, as the undonated
    verdict program always ran (the benchmark's CPU tests patch ``fn``'s
    kernel per batch)."""
    import jax

    flat = lambda x: flatten_verdict(fn(x))
    program = donated(flat, key=key) if donate else flat

    def check(x):
        sig = (
            key,
            jax.tree.structure(x),
            tuple((a.shape, a.dtype) for a in jax.tree.leaves(x)),
        )
        like = _FLAT_SPECS.get(sig)
        if like is None:  # before the dispatch donates x
            like = _FLAT_SPECS[sig] = jax.eval_shape(fn, x)
        buf = program(x)
        buf.copy_to_host_async()
        return buf, like

    def collect(raw):
        buf, like = raw
        host = np.asarray(buf)
        obs_metrics.REGISTRY.counter("pipeline.readbacks", path="flat").inc()
        return split_verdict(host, like)

    return check, collect


def _pow2_bucket(n: int, floor: int = 128) -> int:
    b = floor
    while b < n:
        b <<= 1
    return b


class StagingRing:
    """Donation-aware staging-buffer ring for one coalescing bucket
    (ISSUE 20): ``depth`` recycled slots of preallocated host arrays at
    the bucket's fixed ``[batch, length]`` shape, so steady-state
    dispatch allocates NOTHING on the host side — entries are copied
    into a recycled slot, staged to the device in one put per plane,
    and the device copies are donated to the batched program
    (:func:`jepsen_tpu.checkers.segmented.seg_queue_batch_program`).

    Discipline: the dispatcher ``acquire``s a slot, fills it, and
    launches; the collector ``release``s it only AFTER materializing
    the results (``np.asarray`` blocks on the computation), so a slot
    is never overwritten while a launch could still read it.  ``depth``
    is the dispatch pipelining bound — with depth 2 the next
    super-batch stages while the previous one computes."""

    def __init__(self, batch: int, length: int, depth: int = 2):
        self.batch = batch
        self.length = length
        self._free: queue.Queue = queue.Queue()
        for _ in range(max(1, depth)):
            self._free.put({
                "f": np.full((batch, length), -1, np.int32),
                "typ": np.full((batch, length), -1, np.int32),
                "val": np.zeros((batch, length), np.int32),
                "pos": np.zeros((batch, length), np.int32),
                "mask": np.zeros((batch, length), bool),
            })

    def acquire(self, timeout: float | None = None):
        try:
            return self._free.get(timeout=timeout)
        except queue.Empty:
            return None

    def release(self, slot) -> None:
        self._free.put(slot)

    def fill(self, slot, preps) -> None:
        """Copy ``len(preps)`` prepared segments into the slot's rows;
        rows past the fill are masked out (the program sees them as
        empty segments), so every launch runs at the ONE compiled
        ``[batch, length]`` shape the warmup covered."""
        n = len(preps)
        for i, p in enumerate(preps):
            slot["f"][i] = p["f"]
            slot["typ"][i] = p["typ"]
            slot["val"][i] = p["val"]
            slot["pos"][i] = p["pos"]
            slot["mask"][i] = p["mask"]
        if n < self.batch:
            slot["mask"][n:] = False


def dispatch_coalesced(slot, V: int, donate: bool | None = None):
    """Stage one filled ring slot and launch the batched queue program
    — the pre-coalesced-bucket dispatch entry the service batcher
    calls.  Returns the six ``[batch, V]`` device stat planes (async on
    real accelerators; the caller demuxes after materializing)."""
    import jax.numpy as jnp

    from jepsen_tpu.checkers.segmented import seg_queue_batch_program

    if donate is None:
        donate = _default_donate()
    return seg_queue_batch_program(
        jnp.asarray(slot["f"]), jnp.asarray(slot["typ"]),
        jnp.asarray(slot["val"]), jnp.asarray(slot["pos"]),
        jnp.asarray(slot["mask"]), int(V), donate=donate,
    )


def _chunks(seq: Sequence[Any], size: int) -> list[Sequence[Any]]:
    size = max(1, size)
    return [seq[i : i + size] for i in range(0, len(seq), size)]


# ---------------------------------------------------------------------------
# Family producers: history BYTES (file paths) -> host-packed batches.
# Cache-first — since PR 7 the cache IS the `.jtc` columnar substrate
# (COLUMNAR.md: one mmap-able CRC-checksummed file per history,
# consulted by the load_* functions below with legacy-npz fallback for
# pre-format stores) — then the native thread-pool multi-file pass
# (which itself serves stat-fresh `.jtc` blocks with zero parse, GIL
# released), then the Python twin — identical substrate contract to the
# serial paths, differential-tested in tests/test_pipeline.py and
# tests/test_columnar.py.
# ---------------------------------------------------------------------------


def _stripe_indices(n: int, part: int, n_parts: int) -> list[int]:
    return list(range(part, n, n_parts))


def _native_stripe(
    native_fn, paths, misses, stripe, threads, part, n_parts,
    use_jtc=True,
):
    """Native multi-file results aligned with ``misses`` (stripe-local
    positions).  A fully-missed stripe goes through the striped-cursor
    native entry over the SHARED full path list (no per-lane sublist,
    no shared cursor between concurrent lanes); partial misses (cache
    hits in between) fall back to a compacted per-subset call.
    ``use_jtc=False`` (a ``use_cache=False`` caller) disables the native
    ``.jtc`` substrate serve so the batch genuinely parses."""
    if n_parts > 1 and len(misses) == len(stripe):
        got = native_fn(
            paths, threads, part=part, n_parts=n_parts, use_jtc=use_jtc
        )
        if got is None:
            return None
        return [got[i] for i in stripe]
    return native_fn(
        [paths[stripe[j]] for j in misses], threads, use_jtc=use_jtc
    )


def _stream_substrates(
    paths: Sequence[Path],
    threads: int,
    use_cache: bool,
    part: int = 0,
    n_parts: int = 1,
):
    """``(cols, full)`` stream substrates for indices ``part::n_parts``
    of ``paths`` (default: all), cache → native → Python."""
    from jepsen_tpu.checkers.stream_lin import _stream_rows
    from jepsen_tpu.history.fastpack import stream_rows_files
    from jepsen_tpu.history.store import read_history
    from jepsen_tpu.history.storecache import (
        load_stream_rows_cache,
        save_stream_rows_cache,
    )

    stripe = _stripe_indices(len(paths), part, n_parts)
    out: list = [None] * len(stripe)
    misses = []
    if use_cache:
        for j, i in enumerate(stripe):
            got = load_stream_rows_cache(paths[i])
            if got is not None:
                out[j] = got
            else:
                misses.append(j)
    else:
        misses = list(range(len(stripe)))
    if misses:
        native = _native_stripe(
            stream_rows_files, paths, misses, stripe, threads, part,
            n_parts, use_jtc=use_cache,
        )
        for k, j in enumerate(misses):
            got = native[k] if native is not None else None
            if got is None:
                got = _stream_rows(read_history(paths[stripe[j]]))
            out[j] = got
            if use_cache:
                save_stream_rows_cache(paths[stripe[j]], got[0], got[1])
    return out


def _queue_substrates(
    paths: Sequence[Path],
    threads: int,
    use_cache: bool,
    part: int = 0,
    n_parts: int = 1,
):
    """``[n, 8]`` row matrices for indices ``part::n_parts`` of
    ``paths`` (default: all), cache → native → Python."""
    from jepsen_tpu.history.fastpack import pack_files
    from jepsen_tpu.history.rows import (
        load_rows_cache,
        rows_with_cache,
        save_rows_cache,
    )

    stripe = _stripe_indices(len(paths), part, n_parts)
    out: list = [None] * len(stripe)
    misses = []
    if use_cache:
        for j, i in enumerate(stripe):
            got = load_rows_cache(paths[i])
            if got is not None:
                out[j] = got[1]
            else:
                misses.append(j)
    else:
        misses = list(range(len(stripe)))
    if misses:
        native = _native_stripe(
            pack_files, paths, misses, stripe, threads, part, n_parts,
            use_jtc=use_cache,
        )
        for k, j in enumerate(misses):
            got = native[k] if native is not None else None
            if got is not None:
                if use_cache:
                    save_rows_cache(paths[stripe[j]], got[0], got[1])
                out[j] = got[1]
            elif use_cache:
                out[j] = rows_with_cache(paths[stripe[j]])[1]
            else:
                # no-cache caller: the fallback must parse too, not
                # sneak the substrate/npz in through the load-through
                from jepsen_tpu.history.rows import _rows_for
                from jepsen_tpu.history.store import read_history

                out[j] = _rows_for(read_history(paths[stripe[j]]))
    return out


def _elle_substrates(
    paths: Sequence[Path],
    threads: int,
    use_cache: bool,
    part: int = 0,
    n_parts: int = 1,
):
    """``(mat, meta)`` elle cell substrates for indices
    ``part::n_parts`` of ``paths`` (default: all), cache → native →
    Python (the ``elle_mops.npz`` layer)."""
    from jepsen_tpu.checkers.elle import elle_mops_for
    from jepsen_tpu.history.fastpack import elle_mops_files
    from jepsen_tpu.history.store import read_history
    from jepsen_tpu.history.storecache import (
        load_elle_mops_cache,
        save_elle_mops_cache,
    )

    stripe = _stripe_indices(len(paths), part, n_parts)
    out: list = [None] * len(stripe)
    misses = []
    if use_cache:
        for j, i in enumerate(stripe):
            got = load_elle_mops_cache(paths[i])
            if got is not None:
                out[j] = got
            else:
                misses.append(j)
    else:
        misses = list(range(len(stripe)))
    if misses:
        native = _native_stripe(
            elle_mops_files, paths, misses, stripe, threads, part,
            n_parts, use_jtc=use_cache,
        )
        for k, j in enumerate(misses):
            got = native[k] if native is not None else None
            if got is None:
                got = elle_mops_for(read_history(paths[stripe[j]]))
            out[j] = got
            if use_cache:
                save_elle_mops_cache(paths[stripe[j]], got[0], got[1])
    return out


def _wgl_substrates(
    paths: Sequence[Path],
    threads: int,
    use_cache: bool,
    part: int = 0,
    n_parts: int = 1,
):
    """``[n, 8]`` mutex WGL cell matrices for indices ``part::n_parts``
    of ``paths`` (default: all), cache → native → Python.  An entry may
    be None (a history with out-of-int32 fields — unrepresentable as
    cells); the family producer then derives the ops from the parsed
    history instead."""
    from jepsen_tpu.checkers.wgl_pcomp import wgl_cells_for
    from jepsen_tpu.history.fastpack import wgl_cells_files
    from jepsen_tpu.history.store import read_history
    from jepsen_tpu.history.storecache import (
        load_wgl_cells_cache,
        save_wgl_cells_cache,
    )

    stripe = _stripe_indices(len(paths), part, n_parts)
    out: list = [None] * len(stripe)
    misses = []
    if use_cache:
        for j, i in enumerate(stripe):
            got = load_wgl_cells_cache(paths[i])
            if got is not None:
                out[j] = got
            else:
                misses.append(j)
    else:
        misses = list(range(len(stripe)))
    if misses:
        native = _native_stripe(
            wgl_cells_files, paths, misses, stripe, threads, part,
            n_parts, use_jtc=use_cache,
        )
        for k, j in enumerate(misses):
            got = native[k] if native is not None else None
            if got is None:
                got = wgl_cells_for(read_history(paths[stripe[j]]))
            out[j] = got
            if use_cache and got is not None:
                save_wgl_cells_cache(paths[stripe[j]], got)
    return out


class _Stripe(Sequence):
    """A work unit of the lanes executor: the ``part``-th residue class
    (mod ``n_parts``) of one SHARED size-ordered path list.  Behaves
    like the list of its paths (the family producers index and measure
    it), while the producers' native calls stride the shared array via
    the striped-cursor entry points instead of materializing sublists.
    ``gids`` carries each stripe position's ORIGINAL source index (the
    size ordering permutes them) for reduce-mode counterexamples."""

    def __init__(
        self, paths: list, part: int, n_parts: int, gids: list | None = None
    ):
        self.paths = paths
        self.part = part
        self.n_parts = n_parts
        self._idx = _stripe_indices(len(paths), part, n_parts)
        self.gids = gids

    def indices(self) -> list[int]:
        return self._idx

    def __len__(self) -> int:
        return len(self._idx)

    def __getitem__(self, j):
        return self.paths[self._idx[j]]


class _Unit(list):
    """A plain chunk that also carries its sources' global indices
    (reduce mode: the device-side index-pmin reduces over these)."""

    def __init__(self, items, gids):
        super().__init__(items)
        self.gids = gids


#: int32 max — the gid of pad/sentinel batch positions (always valid,
#: and even if one misclassified it would lose every index-pmin)
_GID_PAD = np.iinfo(np.int32).max


def _gids_of(chunk) -> list[int]:
    gids = getattr(chunk, "gids", None)
    if gids is None:
        return list(range(len(chunk)))
    return list(gids)


# ---------------------------------------------------------------------------
# Global-mesh lane staging (PIPELINE.md §Global mesh): each cooperating
# process owns one Podracer-style input lane — census → stripes → pack →
# stage — and produces exactly its contiguous row block of every global
# device batch.  These helpers are the cross-host SHAPE CONTRACT: all
# lanes must agree on the padded lane height and on every packed static
# (L/V for queue, T/M/V/K/R for elle), or the processes would jit
# different programs and the collectives would deadlock.  Agreement
# costs one small KV exchange of raw maxima per chunk (never cell
# data); `pack_row_matrices`/`pack_elle_mop_mats` then bucket those
# maxima identically on every host.
# ---------------------------------------------------------------------------


def gm_lane_plan(
    n_rows: int, lanes: int, quantum: int
) -> tuple[int, list[tuple[int, int]]]:
    """``(b_l, bounds)`` — the common padded lane height (a multiple of
    ``quantum``, the rows-per-device granule of the global hist axis)
    and each lane's real-row interval ``[lo, hi)`` of the chunk.  Lane
    blocks are contiguous in chunk order, so one lane's parse output IS
    its process-local block of the global batch — no row shuffling
    between hosts."""
    import math

    from jepsen_tpu.history.encode import _round_up

    b_l = _round_up(math.ceil(n_rows / max(1, lanes)), quantum)
    bounds = [
        (min(p * b_l, n_rows), min((p + 1) * b_l, n_rows))
        for p in range(lanes)
    ]
    return b_l, bounds


def gm_stage_queue_lane(paths, threads: int = 0, use_cache: bool = True):
    """Stage one lane's queue rows (cache → native → Python, exactly the
    serial substrate contract) and report the raw pack maxima the lanes
    must exchange: ``(mats, (n_max, vmax))``."""
    mats = (
        _queue_substrates([Path(p) for p in paths], threads, use_cache)
        if paths
        else []
    )
    n_max = max((m.shape[0] for m in mats), default=0)
    vmax = max(
        (int(m[:, 4].max(initial=0)) for m in mats if m.shape[0]), default=0
    )
    return mats, (n_max, vmax)


def gm_pack_queue_lane(mats, b_l: int, length: int, value_space: int):
    """Pack one lane's row matrices — sentinel-padded to the agreed lane
    height ``b_l`` — into host-side ``PackedHistories`` columns with the
    fleet-agreed ``(L, V)`` statics (pad rows are all-masked, synthesized
    valid by every checker)."""
    from jepsen_tpu.history.encode import pack_row_matrices

    empty = np.zeros((0, 8), np.int32)
    mats = list(mats) + [empty] * (b_l - len(mats))
    return pack_row_matrices(
        mats, length=length, value_space=value_space, to_device=False
    )


def gm_stage_elle_lane(paths, threads: int = 0, use_cache: bool = True):
    """Stage one lane's elle micro-op substrates and split them on THE
    degeneracy contract (``split_elle_mops`` semantics): returns
    ``(mats_metas, live, degen, maxima)`` where ``live``/``degen`` are
    lane-local row positions and ``maxima`` is the raw ``(n_txns, cells,
    val, key, rpos)`` tuple the lanes exchange to agree on packed
    statics.  Degenerate rows stay on THIS lane's host for the oracle
    fallback — the splice point is the lane (= shard) boundary."""
    mm = (
        _elle_substrates([Path(p) for p in paths], threads, use_cache)
        if paths
        else []
    )
    live = [i for i, (_, g) in enumerate(mm) if not g.degenerate]
    degen = [i for i, (_, g) in enumerate(mm) if g.degenerate]
    t_max = max((mm[i][1].n_txns for i in live), default=0)
    m_max = max((mm[i][0].shape[0] for i in live), default=0)

    def col(c: int) -> int:
        return max(
            (
                int(mm[i][0][:, c].max(initial=-1))
                for i in live
                if mm[i][0].shape[0]
            ),
            default=-1,
        )

    return mm, live, degen, (t_max, m_max, col(3), col(2), col(5))


def gm_pack_elle_lane(mats_metas, live, b_live: int, n_txns: int, at_least):
    """Pack one lane's LIVE elle rows — sentinel-padded to the agreed
    live lane height — with fleet-agreed statics: ``n_txns`` (= T) plus
    the raw ``(cells, val, key, rpos)`` fleet maxima folded into the
    pow2 buckets by ``pack_elle_mop_mats(at_least=...)``."""
    from jepsen_tpu.checkers.elle import ElleMopsMeta, pack_elle_mop_mats

    mats = [mats_metas[i][0] for i in live]
    metas = [mats_metas[i][1] for i in live]
    pad = b_live - len(mats)
    mats += [np.zeros((0, 8), np.int32)] * pad
    metas += [
        ElleMopsMeta(n_txns=0, txn_index=[], keys=[], degenerate=False)
    ] * pad
    return pack_elle_mop_mats(
        mats, metas, n_txns=n_txns, to_device=False, at_least=tuple(at_least)
    )


# ---------------------------------------------------------------------------
# Family pipelines: produce / place / check / convert per family.
# ---------------------------------------------------------------------------


@dataclass
class _Family:
    produce: Callable[[Any], Any]
    check: Callable[[Any], Any]
    place: Callable[[Any], Any]
    convert: Callable[[Any, Any], list[dict]]  # (chunk_item, collected)
    collect: Callable[[Any], Any] | None = None  # default: block + numpy
    # reduce mode only: (chunk_item, collected) -> {"n_invalid",
    # "first_invalid" (chunk-local), ...} — the two-scalar batch verdict
    reduce_convert: Callable[[Any, Any], dict] | None = None


def _default_donate() -> bool:
    """Donate staged buffers only where the runtime can actually reuse
    them: the CPU backend leaves most donations unusable (and warns per
    compile), so donation is a chip-path behavior."""
    import jax

    return jax.default_backend() != "cpu"


def _pad_chunk(subs: list, n: int, sentinel) -> list:
    """Pad a short (tail) chunk up to ``n`` with sentinel substrates so
    every chunk shares one batch shape — the jitted program compiles
    once, not once more for the remainder chunk.  ``convert`` trims the
    pad rows by the true chunk length."""
    if len(subs) < n:
        subs = list(subs) + [sentinel] * (n - len(subs))
    return subs


#: empty-history sentinel substrate (``_stream_rows`` on no ops) — used
#: to pad tail chunks to the uniform batch shape
_STREAM_SENTINEL = (
    np.asarray([[0, 5, -1, -1, 0, 1]], np.int32),
    False,
)


def _stream_family(
    threads: int,
    use_cache: bool,
    append_fail: str,
    mesh=None,
    donate: bool | None = None,
    chunk_pad: int = 0,
    device=None,
    reduce: bool = False,
) -> _Family:
    import jax

    from jepsen_tpu.checkers.stream_lin import (
        pack_stream_rows,
        stream_lin_tensor_check,
        stream_lin_tensors_to_results,
    )

    if donate is None:
        donate = _default_donate()

    def produce(chunk):
        if isinstance(chunk, _Stripe):
            subs = _stream_substrates(
                chunk.paths, threads, use_cache, chunk.part, chunk.n_parts
            )
        elif chunk and isinstance(chunk[0], (str, Path)):
            subs = _stream_substrates(chunk, threads, use_cache)
        else:
            subs = list(chunk)
        subs = _pad_chunk(subs, chunk_pad, _STREAM_SENTINEL)
        n_max = max(m.shape[0] for m, _ in subs)
        hi = max(
            max(int(m[:, 2].max(initial=0)), int(m[:, 3].max(initial=0)))
            for m, _ in subs
        )
        batch = pack_stream_rows(
            subs,
            length=_pow2_bucket(n_max),
            space=_pow2_bucket(hi + 1),
            to_device=False,
        )
        return batch, [f for _, f in subs]

    base_check = lambda b: stream_lin_tensor_check(b, append_fail=append_fail)
    if mesh is not None:
        from jepsen_tpu.parallel.mesh import (
            sharded_stream_lin,
            sharded_stream_verdict,
        )

        check = lambda b: sharded_stream_lin(
            b, mesh, append_fail=append_fail
        )
        place = _mesh_stream_place(mesh)
    else:
        if reduce:
            raise ValueError("reduce mode needs a mesh")
        check = (
            donated(base_check, key=("stream", append_fail))
            if donate
            else base_check
        )
        place = _device_put_on(device)

    if reduce:
        return _reduced_family(
            lambda chunk: produce(chunk)[0],  # drop the fulls channel
            lambda batch: batch.type.shape[0],
            place,
            lambda batch, g: sharded_stream_verdict(
                batch, mesh, append_fail=append_fail, gidx=g
            ),
        )

    def convert(item, collected):
        tensors, fulls = collected
        out = stream_lin_tensors_to_results(tensors, fulls)[: len(item)]
        for r in out:
            r["append-fail"] = append_fail
        return [{"stream": r} for r in out]

    def place_pair(pair):
        batch, fulls = pair
        return place(batch), fulls

    def check_pair(pair):
        batch, fulls = pair
        return check(batch), fulls

    def collect_pair(raw):
        tensors, fulls = raw
        jax.block_until_ready(tensors)
        return jax.tree.map(np.asarray, tensors), fulls

    return _Family(
        produce, check_pair, place_pair, convert, collect_pair
    )


def _mesh_stream_place(mesh):
    from jepsen_tpu.parallel.mesh import SEQ_AXIS, _hist_sharded

    def place(batch):
        if mesh.shape[SEQ_AXIS] == 1:
            return _hist_sharded(batch, mesh)
        return batch  # seq>1: sharded_stream_lin pads + places itself

    return place


def _device_put_on(device):
    """``jax.device_put`` pinned to one lane's device (``None``: the
    default device, the classic single-lane behavior)."""
    import jax

    if device is None:
        return jax.device_put
    return lambda tree: jax.device_put(tree, device)


def _no_convert(item, collected):  # reduce-mode families have no
    raise RuntimeError(            # per-history conversion
        "reduce-mode family has no per-history convert"
    )


def _reduced_family(base_produce, batch_len, place_batch, verdict) -> _Family:
    """The reduce-mode family shape shared by stream and queue (elle
    adds the degenerate host-fallback fold and keeps its own): thread
    the chunk's global-id vector (pads carry the never-wins gid) through
    place to the family's sharded verdict, and unpack the two on-device
    scalars.  ``verdict(host_batch, gidx)`` must return
    ``(n_invalid, first_invalid)``."""

    def produce_r(chunk):
        host = base_produce(chunk)
        g = np.full((batch_len(host),), _GID_PAD, np.int32)
        gd = _gids_of(chunk)
        g[: len(gd)] = gd
        return host, g

    def place_r(pair):
        host, g = pair
        return place_batch(host), g

    def check_r(pair):
        host, g = pair
        return verdict(host, g)

    def reduce_convert(item, collected):
        n_invalid, first = collected
        return {"n_invalid": int(n_invalid), "first_invalid": int(first)}

    return _Family(
        produce_r, check_r, place_r, _no_convert,
        reduce_convert=reduce_convert,
    )


def _queue_family(
    threads: int,
    use_cache: bool,
    delivery: str,
    mesh=None,
    donate: bool | None = None,
    chunk_pad: int = 0,
    device=None,
    reduce: bool = False,
) -> _Family:
    import jax

    from jepsen_tpu.checkers.fused import combined_tensor_check
    from jepsen_tpu.checkers.queue_lin import queue_lin_tensors_to_results
    from jepsen_tpu.checkers.total_queue import _tensors_to_results
    from jepsen_tpu.history.encode import pack_row_matrices

    if donate is None:
        donate = _default_donate()

    def produce(chunk):
        if isinstance(chunk, _Stripe):
            mats = _queue_substrates(
                chunk.paths, threads, use_cache, chunk.part, chunk.n_parts
            )
        elif chunk and isinstance(chunk[0], (str, Path)):
            mats = _queue_substrates(chunk, threads, use_cache)
        else:
            mats = list(chunk)
        mats = _pad_chunk(mats, chunk_pad, np.zeros((0, 8), np.int32))
        n_max = max(m.shape[0] for m in mats)
        vmax = max(
            (int(m[:, 4].max(initial=0)) for m in mats if m.shape[0]),
            default=0,
        )
        return pack_row_matrices(
            mats,
            length=_pow2_bucket(max(n_max, 1)),
            value_space=_pow2_bucket(vmax + 1),
            to_device=False,
        )

    # packed verdict buffers (round 14): the per-value class masks ship
    # as uint32 presence bitplanes — 8–32× fewer verdict bytes per
    # batch; the *_to_results converters render identical maps
    base_check = lambda p: combined_tensor_check(
        p, delivery=delivery, packed_out=True
    )
    if mesh is not None:
        from jepsen_tpu.parallel.mesh import (
            shard_packed,
            sharded_check,
            sharded_queue_verdict,
        )

        # sharded outputs: one flat buffer would add a gather, so the
        # mesh keeps the per-leaf read (_default_collect)
        check = lambda p: sharded_check(
            p, mesh, delivery=delivery, packed_out=True
        )
        collect = None
        place = lambda p: shard_packed(p, mesh)
    else:
        if reduce:
            raise ValueError("reduce mode needs a mesh")
        check, collect = _flat_readback(
            base_check, ("queue", delivery, "flat"), donate
        )
        place = _device_put_on(device)

    if reduce:
        return _reduced_family(
            produce,
            lambda packed: packed.f.shape[0],
            lambda packed: shard_packed(packed, mesh),
            lambda packed, g: sharded_queue_verdict(
                packed, mesh, delivery=delivery, gidx=g
            ),
        )

    def convert(item, collected):
        tq, ql = collected
        tq_rows = _tensors_to_results(tq)[: len(item)]
        ql_rows = queue_lin_tensors_to_results(ql)[: len(item)]
        for b in ql_rows:
            # the serial path (check_queue_lin_batch) records the judged
            # contract level; a bare re-check inherits it from
            # results.json — dropping it would silently tighten verdicts
            b["delivery"] = delivery
        return [
            {"queue": a, "linear": b} for a, b in zip(tq_rows, ql_rows)
        ]

    return _Family(produce, check, place, convert, collect)


def _elle_family(
    threads: int,
    use_cache: bool,
    model: str,
    mesh=None,
    donate: bool | None = None,
    chunk_pad: int = 0,
    device=None,
    reduce: bool = False,
) -> _Family:
    """Elle chunks carry a degenerate-history splice: tensor-
    representable histories go through the fused device inference,
    degenerate ones through the host-inference oracle — the SAME splice
    contract as ``check_elle_batch`` (``split_elle_mops``)."""
    import jax

    from jepsen_tpu.checkers.elle import (
        ElleMopsMeta,
        _classify,
        _txn_graph_from_inferred,
        check_elle_cpu,
        elle_mops_check,
        split_elle_mops,
    )
    from jepsen_tpu.history.store import read_history

    if donate is None:
        donate = _default_donate()
    sentinel = (
        np.zeros((0, 8), np.int32),
        ElleMopsMeta(n_txns=0, txn_index=[], keys=[], degenerate=False),
    )

    if mesh is not None:
        from jepsen_tpu.parallel.mesh import HIST_AXIS

        mesh_h = mesh.shape[HIST_AXIS]
    else:
        mesh_h = 1

    def produce(chunk):
        from_paths = bool(chunk) and isinstance(chunk[0], (str, Path))
        if isinstance(chunk, _Stripe):
            subs = _elle_substrates(
                chunk.paths, threads, use_cache, chunk.part, chunk.n_parts
            )
        elif from_paths:
            subs = _elle_substrates(chunk, threads, use_cache)
        else:
            subs = [(m, g) for m, g in chunk]
        subs = _pad_chunk(subs, chunk_pad, sentinel)
        live, mops, degen = split_elle_mops(subs)
        if mesh_h > 1 and live and len(live) % mesh_h:
            # degenerate histories shrank the LIVE batch below the
            # mesh's hist-axis divisibility: extend the sentinel pad
            # (tensor-checkable, trimmed by convert) and re-split
            subs = _pad_chunk(
                subs, len(subs) + mesh_h - len(live) % mesh_h, sentinel
            )
            live, mops, degen = split_elle_mops(subs)
        degen_results = []
        for i in degen:
            # tensor-unrepresentable history: host oracle (rare; see
            # elle_mops_for's degeneracy conditions)
            h = read_history(chunk[i]) if from_paths else None
            if h is None:
                raise PipelineError(
                    "degenerate elle history needs its ops for the host "
                    "fallback; pass file paths (or pre-check via "
                    "check_elle_batch)"
                )
            degen_results.append(check_elle_cpu(h, model=model))
        metas = [subs[i][1] for i in live]
        return mops, metas, live, degen, degen_results

    if mesh is not None:
        from jepsen_tpu.parallel.mesh import (
            _hist_sharded,
            sharded_elle_mops_verdict,
        )

        place_mops = lambda m: _hist_sharded(m, mesh)
        check_mops = elle_mops_check
    else:
        if reduce:
            raise ValueError("reduce mode needs a mesh")
        place_mops = _device_put_on(device)
        check_mops = (
            donated(elle_mops_check) if donate else elle_mops_check
        )

    if reduce:
        base_produce = produce

        def produce_r(chunk):
            mops, _metas, live, degen, degen_results = base_produce(chunk)
            gd = _gids_of(chunk)
            g = None
            if mops is not None:
                # device-batch position b holds chunk position live[b];
                # sentinel pads (live[b] beyond the true chunk) carry
                # the never-wins pad gid
                g = np.asarray(
                    [gd[i] if i < len(gd) else _GID_PAD for i in live],
                    np.int32,
                )
            return mops, g, degen, degen_results, gd

        def place_r(item):
            mops, g, degen, degen_results, gd = item
            if mops is not None:
                mops = place_mops(mops)
            return mops, g, degen, degen_results, gd

        def check_r(item):
            mops, g, degen, degen_results, gd = item
            raw = (
                sharded_elle_mops_verdict(mops, mesh, gidx=g)
                if mops is not None
                else None
            )
            return raw, degen, degen_results, gd

        def collect_r(raw_tuple):
            raw, degen, degen_results, gd = raw_tuple
            if raw is not None:
                jax.block_until_ready(raw)
                raw = jax.tree.map(np.asarray, raw)
            return raw, degen, degen_results, gd

        def reduce_convert(chunk, collected):
            # fold the host-fallback (degenerate) verdicts into the
            # reduced device verdict: counts add, first-invalid takes
            # the minimum GLOBAL source index across both populations
            raw, degen, degen_results, gd = collected
            n_invalid = sum(
                1 for r in degen_results if r["valid?"] is not True
            )
            first = -1
            for i, r in zip(degen, degen_results):
                if r["valid?"] is not True and (
                    first < 0 or gd[i] < first
                ):
                    first = gd[i]
            if raw is not None:
                nb, fdev = int(raw[0]), int(raw[1])
                n_invalid += nb
                if fdev >= 0 and (first < 0 or fdev < first):
                    first = fdev
            return {"n_invalid": n_invalid, "first_invalid": first}

        return _Family(
            produce_r, check_r, place_r, _no_convert, collect_r,
            reduce_convert,
        )

    def place(item):
        mops, metas, live, degen, degen_results = item
        if mops is not None:
            mops = place_mops(mops)
        return mops, metas, live, degen, degen_results

    def check(item):
        mops, metas, live, degen, degen_results = item
        raw = check_mops(mops) if mops is not None else None
        return raw, metas, live, degen, degen_results

    def collect(raw_tuple):
        raw, metas, live, degen, degen_results = raw_tuple
        if raw is not None:
            jax.block_until_ready(raw)
            raw = jax.tree.map(np.asarray, raw)
        return raw, metas, live, degen, degen_results

    def convert(chunk, collected):
        raw, metas, live, degen, degen_results = collected
        out: list = [None] * (len(live) + len(degen))
        for i, r in zip(degen, degen_results):
            out[i] = {"elle": r}
        if raw is not None:
            t, inf = raw
            g0, g1c, g2 = (np.asarray(x) for x in (t.g0, t.g1c, t.g2))
            g1a, g1b, bad = (
                np.asarray(x) for x in (inf.g1a, inf.g1b, inf.bad_keys)
            )
            counts = tuple(
                np.asarray(getattr(inf, f"{n}_edges"))
                for n in ("ww", "wr", "rw")
            )
            for b, i in enumerate(live):
                g = _txn_graph_from_inferred(b, metas[b], g1a, g1b, bad)
                out[i] = {
                    "elle": _classify(
                        g,
                        set(np.nonzero(g0[b])[0].tolist()),
                        set(np.nonzero(g1c[b])[0].tolist()),
                        set(np.nonzero(g2[b])[0].tolist()),
                        model=model,
                        edge_counts=tuple(int(c[b]) for c in counts),
                    )
                }
        return out[: len(chunk)]

    return _Family(produce, check, place, convert, collect)


def _mutex_family(
    threads: int,
    use_cache: bool,
    mesh=None,
    donate: bool | None = None,
    chunk_pad: int = 0,
    device=None,
    reduce: bool = False,
) -> _Family:
    """The mutex/WGL family: bytes → WGL cells (``SEC_WGL`` of the
    ``.jtc`` substrate, native ``jt_wgl_cells_files`` thread pool) →
    P-compositional decomposition → shape-bucketed vmapped frontier
    searches (``checkers/wgl_pcomp.py``).  An overflowed sub-history
    surfaces as *unknown* and takes the exact CPU escape hatch inside
    ``convert`` — the same contract as the serial ``MutexWgl`` checker,
    never a silent per-piece skip.  ``chunk_pad``/``donate`` are
    accepted for interface symmetry; bucket shapes are already pinned
    by the (n_ops, capacity, cands) buckets, so chunk padding adds
    nothing — and since round 14 donation lives INSIDE ``run_bucket``
    (the staged bucket arrays are one-shot, donated by the row and
    subset programs alike on backends whose runtime can use it)."""
    import jax

    from jepsen_tpu.checkers.wgl import (
        fenced_mutex_wgl_ops,
        mutex_history_is_fenced,
        mutex_wgl_ops,
    )
    from jepsen_tpu.checkers.wgl_pcomp import (
        bucketize,
        decompose,
        finish_buckets,
        mutex_ops_from_cells,
        pcomp_check_cpu,
        pcomp_result,
        run_bucket,
    )
    from jepsen_tpu.history.store import read_history
    from jepsen_tpu.models.core import FencedMutex, OwnedMutex

    if reduce:
        raise ValueError(
            "the mutex family has no reduce mode: the device batch axis "
            "is the SUB-HISTORY axis, not the history axis, so the "
            "collective index-pmin would name a class, not a history"
        )
    if mesh is not None:
        from jepsen_tpu.parallel.mesh import HIST_AXIS, _hist_sharded

        mesh_h = mesh.shape[HIST_AXIS]
    else:
        mesh_h = 1

    def _ops_of(item):
        """→ ``(wgl_ops, model_key)`` from a cell matrix or an Op list."""
        if isinstance(item, np.ndarray):
            return mutex_ops_from_cells(item)
        if mutex_history_is_fenced(item):
            return fenced_mutex_wgl_ops(item), (FencedMutex, ())
        return mutex_wgl_ops(item), (OwnedMutex, ())

    def produce(chunk):
        if isinstance(chunk, _Stripe):
            cells = _wgl_substrates(
                chunk.paths, threads, use_cache, chunk.part, chunk.n_parts
            )
            items = [
                c if c is not None else read_history(chunk[j])
                for j, c in enumerate(cells)
            ]
        elif chunk and isinstance(chunk[0], (str, Path)):
            cells = _wgl_substrates(chunk, threads, use_cache)
            items = [
                c if c is not None else read_history(p)
                for c, p in zip(cells, chunk)
            ]
        else:
            items = list(chunk)
        pairs = [_ops_of(it) for it in items]
        decomps = [decompose(ops, mk) for ops, mk in pairs]  # per-key:
        #   always sound for the mutex family
        buckets = bucketize(decomps, pad_to=mesh_h, to_device=False)
        return decomps, buckets, pairs

    def _place_batch(b):
        # mutex classes always ride the row engine (order-dependent
        # state), but the placement stays engine-aware so a packed
        # subset bucket placed through this family would not misroute
        cols = (
            ("enq", "deq", "ret_op", "cands")
            if hasattr(b, "enq")
            else ("f", "a0", "a1", "ret_op", "cands")
        )
        vals = tuple(getattr(b, c) for c in cols)
        if mesh is not None:
            vals = _hist_sharded(vals, mesh)
        else:
            vals = _device_put_on(device)(vals)
        return dataclasses.replace(b, **dict(zip(cols, vals)))

    def place(item):
        decomps, buckets, pairs = item
        return (
            decomps,
            [
                dataclasses.replace(bk, batch=_place_batch(bk.batch))
                for bk in buckets
            ],
            pairs,
        )

    def check(item):
        decomps, buckets, pairs = item
        raws = [run_bucket(bk) for bk in buckets]  # async dispatches
        return decomps, buckets, pairs, raws

    def collect(raw_tuple):
        decomps, buckets, pairs, raws = raw_tuple
        jax.block_until_ready(raws)
        return decomps, buckets, pairs, jax.tree.map(np.asarray, raws)

    def convert(chunk, collected):
        decomps, buckets, pairs, raws = collected
        # escalation (rare) re-dispatches on the caller's thread — plain
        # vmapped programs, no collectives, safe outside the mesh gate
        ok, unknown, info = finish_buckets(decomps, buckets, raws)
        out = []
        for i, d in enumerate(decomps):
            cls, args = d.model_key
            r = pcomp_result(d, bool(ok[i]), bool(unknown[i]), info[i])
            if unknown[i]:
                # frontier overflow even escalated: the exact CPU search
                # (itself per-class) decides, the offending class stays
                # visible
                cpu = pcomp_check_cpu(pairs[i][0], d.model_key)
                cpu["pcomp-overflow-class"] = r.get("overflow-class")
                r = cpu
            r["model"] = cls.name
            out.append({"mutex": r})
        return out[: len(chunk)]

    return _Family(produce, check, place, convert, collect)


def family_for(workload: str, **opts) -> _Family:
    common = dict(
        mesh=opts.get("mesh"),
        donate=opts.get("donate"),
        chunk_pad=opts.get("chunk_pad", 0),
        device=opts.get("device"),
        reduce=opts.get("reduce", False),
    )
    if workload == "stream":
        return _stream_family(
            opts.get("threads", 0),
            opts.get("use_cache", True),
            opts.get("append_fail", "definite"),
            **common,
        )
    if workload == "queue":
        return _queue_family(
            opts.get("threads", 0),
            opts.get("use_cache", True),
            opts.get("delivery", "exactly-once"),
            **common,
        )
    if workload == "elle":
        return _elle_family(
            opts.get("threads", 0),
            opts.get("use_cache", True),
            opts.get("model", "serializable"),
            **common,
        )
    if workload == "mutex":
        return _mutex_family(
            opts.get("threads", 0),
            opts.get("use_cache", True),
            **common,
        )
    raise ValueError(f"no pipeline family for workload {workload!r}")


def _hist_extent(opts: dict) -> int:
    """The mesh's hist extent (1 without a mesh): sharded placement
    needs a chunk's batch axis divisible by it."""
    if opts.get("mesh") is None:
        return 1
    from jepsen_tpu.parallel.mesh import HIST_AXIS

    return opts["mesh"].shape[HIST_AXIS]


def _pad_for(chunk: int, opts: dict) -> int:
    # sentinel-pad each chunk up to the next multiple of the hist extent
    h = _hist_extent(opts)
    return ((chunk + h - 1) // h) * h


def _sample_sizes(sources: Sequence[Any]) -> list[int] | None:
    """On-disk bytes of the first ``SIZE_SAMPLE`` path sources (0 where
    one cannot be stat-ed: the producer reports that file), or None
    unless every source is a path.  A sample, not every file: where the
    files sit on a 9p root file system (a TPU v5e host VM) a stat costs
    ~70 us, and 1000 of them 70 ms of a 0.21 s replay pass."""
    import os

    if not sources or not all(isinstance(s, (str, Path)) for s in sources):
        return None
    sizes = []
    for p in sources[:SIZE_SAMPLE]:
        try:
            sizes.append(os.stat(p).st_size)
        except OSError:
            sizes.append(0)
    return sizes


def _plan_chunk(
    n: int, sizes: Sequence[int] | None, chunk: int, hist: int = 1
) -> tuple[int, str]:
    """Histories per chunk for a call of ``n`` sources, and the rule
    that set it (``"count"`` or ``"bytes"``).

    ``chunk`` caps the histories of a chunk: ``ceil(n / chunk)`` chunks.
    Where the call's bytes, ``n`` times the mean of the sampled
    ``sizes``, ask for more chunks than that, ``ceil(bytes /
    CHUNK_BYTES)``, the call is cut into that many, each ``ceil(n /
    chunks)`` histories rounded up to the mesh's ``hist`` extent.  A
    campaign of large histories then runs as several chunks, so the
    producer's packing overlaps the kernel; one of small histories keeps
    its ``chunk``-sized chunks.  Without ``sizes`` (sources that are not
    paths) the count rule holds."""
    chunk = max(1, chunk)
    n_count = -(-n // chunk)
    if not sizes:
        return chunk, "count"
    n_bytes = -(-sum(sizes) * n // (len(sizes) * CHUNK_BYTES))
    if n_bytes <= n_count:
        return chunk, "count"
    length = -(-n // n_bytes)
    length = -(-length // hist) * hist
    if length >= chunk:
        return chunk, "count"
    return length, "bytes"


def _merge_reduced(fam: "_Family", items, collected) -> dict:
    """Fold per-chunk two-scalar verdicts into one batch verdict dict.
    Each chunk's ``first_invalid`` is already a GLOBAL source index
    (the device reduction pmin-ed over the chunk's gid vector).
    Quarantined members (elastic mode) are COUNTED, never silently
    folded: ``quarantined > 0`` forces the composed verdict to at best
    ``unknown`` (:func:`reduced_valid`)."""
    merged = {
        "histories": 0, "invalid": 0, "first_invalid": -1,
        "quarantined": 0,
    }
    for it, col in zip(items, collected):
        merged["histories"] += len(it)
        if isinstance(col, Quarantined):
            merged["quarantined"] += len(it)
            continue
        if isinstance(col, _SalvagedUnit):
            for sub, sub_col in col.members:
                if isinstance(sub_col, Quarantined):
                    merged["quarantined"] += 1
                    continue
                d = fam.reduce_convert(sub, sub_col)
                merged["invalid"] += d["n_invalid"]
                g = d["first_invalid"]
                if g >= 0 and (
                    merged["first_invalid"] < 0
                    or g < merged["first_invalid"]
                ):
                    merged["first_invalid"] = g
            continue
        d = fam.reduce_convert(it, col)
        merged["invalid"] += d["n_invalid"]
        g = d["first_invalid"]
        if g >= 0 and (
            merged["first_invalid"] < 0 or g < merged["first_invalid"]
        ):
            merged["first_invalid"] = g
    return merged


def reduced_valid(merged: dict):
    """The composed verdict of a reduce-mode batch dict under the PR-8
    precedence rule: ``invalid`` trumps everything; any quarantined
    history caps the verdict at ``unknown``; only a clean batch is
    ``True``.  A quarantine can never be folded into valid."""
    from jepsen_tpu.checkers.protocol import UNKNOWN

    if merged.get("invalid", 0) > 0:
        return False
    if merged.get("quarantined", 0) > 0:
        return UNKNOWN
    return True


class _SalvagedUnit:
    """A quarantined unit after per-history isolation: ``members`` is
    one ``(single_item_unit, collected_or_Quarantined)`` pair per
    member, in unit order."""

    def __init__(self, members):
        self.members = members


def _salvage_unit(fam: "_Family", unit, q: Quarantined) -> _SalvagedUnit:
    """Per-history isolation of a quarantined unit: each member re-runs
    ALONE through the same produce → place → check → collect stages
    (chunk of one — the sentinel pad keeps the compiled batch shape),
    so one poison history cannot condemn its chunk-mates.  Members that
    still crash quarantine individually, carrying both the unit-level
    and their own evidence."""
    collect = fam.collect or _default_collect
    gids = _gids_of(unit)
    members = []
    for j in range(len(unit)):
        sub = _Unit([unit[j]], [gids[j]])
        stage = "produce"
        try:
            host = fam.produce(sub)
            stage = "place"
            placed = fam.place(host)
            stage = "check"
            raw = fam.check(placed)
            stage = "collect"
            col = collect(raw)
        except Exception as e:
            members.append(
                (
                    sub,
                    Quarantined(
                        q.index, stage, q.attempts + ["salvage"],
                        q.errors + [e],
                    ),
                )
            )
            continue
        members.append((sub, col))
    return _SalvagedUnit(members)


def _resolve_quarantines(
    fam: "_Family", items, collected, stats: PipelineStats
) -> list:
    """Elastic post-pass: isolate every quarantined unit per history
    (:func:`_salvage_unit`) and count the FINAL per-history quarantines
    into the stats/obs registries."""
    out = list(collected)
    for k, col in enumerate(out):
        if not isinstance(col, Quarantined):
            continue
        salvaged = _salvage_unit(fam, items[k], col)
        n_q = sum(
            1 for _s, c in salvaged.members if isinstance(c, Quarantined)
        )
        if n_q:
            stats.note_quarantine(col.evidence(), histories=n_q)
        out[k] = salvaged
    return out


def _quarantined_result(workload: str, evidence: dict) -> dict:
    """An explicit per-history ``unknown``-with-evidence verdict for a
    quarantined history — same shape discipline as
    :func:`_dropped_result`: one entry per source, never a silent
    truncation, and ``unknown`` can never compose into ``valid``."""
    from jepsen_tpu.checkers.protocol import UNKNOWN

    errs = evidence.get("errors") or ["?"]
    row = {
        "valid?": UNKNOWN,
        "error": f"quarantined at {evidence.get('stage')}: {errs[-1]}",
        "quarantined": dict(evidence),
    }
    if workload == "queue":
        return {"queue": dict(row), "linear": dict(row)}
    return {workload: dict(row)}


def _convert_unit(
    fam: "_Family", workload: str, unit, col, stats: PipelineStats,
    fail_fast: bool,
) -> list[dict]:
    """One unit's collected result → per-history result dicts, with the
    elastic guards: a salvaged unit converts member by member, and a
    ``convert`` crash (the last stage outside the executor) quarantines
    the unit's histories instead of sinking the run."""
    if isinstance(col, _SalvagedUnit):
        out = []
        for sub, sub_col in col.members:
            if isinstance(sub_col, Quarantined):
                out.append(_quarantined_result(workload, sub_col.evidence()))
            else:
                out.extend(
                    _convert_unit(
                        fam, workload, sub, sub_col, stats, fail_fast
                    )
                )
        return out
    if fail_fast:  # the PR-4 contract: a convert crash propagates raw
        return fam.convert(unit, col)
    try:
        return fam.convert(unit, col)
    except Exception as e:
        q = Quarantined(-1, "convert", ["main"], [e])
        stats.note_quarantine(q.evidence(), histories=len(unit))
        return [
            _quarantined_result(workload, q.evidence()) for _ in unit
        ]


def _dropped_result(workload: str, reason: str) -> dict:
    """An explicit per-source verdict for a file the lane census dropped
    — the results list keeps one entry per source, never a silent
    truncation."""
    from jepsen_tpu.checkers.protocol import UNKNOWN

    row = {"valid?": UNKNOWN, "error": reason}
    if workload == "queue":
        return {"queue": dict(row), "linear": dict(row)}
    return {workload: dict(row)}


def _lane_census(sources, workload):
    """Stat every path source; split into (kept indices, sizes,
    {dropped index: reason}).  Unreadable and zero-length files cannot
    be size-balanced (and a 0-byte history carries no ops) — each drop
    is LOGGED, incremented in the global ``pipeline.files_dropped``
    obs counter (the after-the-run countable record the log line never
    was), and later counted in the run's stats."""
    import logging
    import os

    log = logging.getLogger(__name__)
    kept, sizes, dropped = [], [], {}
    for i, p in enumerate(sources):
        try:
            sz = os.stat(p).st_size
        except OSError as e:
            reason = f"unreadable history file: {e}"
            kind = "unreadable"
            log.warning(
                "lane census: dropping %s (%s) — counted in stats.dropped",
                p, e,
            )
        else:
            if sz > 0:
                kept.append(i)
                sizes.append(sz)
                continue
            reason = "zero-length history file"
            kind = "zero-length"
            log.warning(
                "lane census: dropping zero-length %s — counted in "
                "stats.dropped", p,
            )
        dropped[i] = reason
        obs_metrics.REGISTRY.counter(
            "pipeline.files_dropped", reason=kind
        ).inc()
        if obs_trace.is_enabled():
            obs_trace.event(
                "pipeline.file_dropped", args={"path": str(p), "reason": kind}
            )
    return kept, sizes, dropped


def _check_sources_lanes(
    workload: str,
    sources: list,
    *,
    chunk: int,
    depth: int,
    lanes: int,
    reduce: bool = False,
    fail_fast: bool = False,
    **opts,
):
    """N-lane bytes-to-verdict: size-aware unit balancing (largest-first
    round-robin stripes of one shared ordered path list) over per-device
    lanes claiming units off a shared queue (steal-on-idle)."""
    import jax

    devices = jax.local_devices()
    n_lanes = len(devices) if lanes <= 0 else max(1, min(lanes, len(devices)))
    paths_mode = bool(sources) and all(
        isinstance(s, (str, Path)) for s in sources
    )
    if paths_mode:
        kept, sizes, dropped = _lane_census(sources, workload)
    else:
        kept, sizes, dropped = list(range(len(sources))), [1] * len(sources), {}
    # largest-first ordering; round-robin striping over it yields
    # byte-balanced units of at most ``chunk`` files each
    order = sorted(range(len(kept)), key=lambda j: -sizes[j])
    ordered_idx = [kept[j] for j in order]
    ordered = [sources[i] for i in ordered_idx]
    if not ordered:  # nothing survived the census (or empty input)
        stats = PipelineStats(lanes=n_lanes, dropped=len(dropped))
        if reduce:
            return (
                {
                    "histories": 0,
                    "invalid": 0,
                    "first_invalid": -1,
                    "quarantined": 0,
                    "dropped": len(dropped),
                },
                stats,
            )
        out = [
            _dropped_result(workload, dropped[i])
            for i in range(len(sources))
        ]
        stats.histories = len(out)
        return out, stats
    n_units = max(1, (len(ordered) + chunk - 1) // chunk)
    unit_len = (len(ordered) + n_units - 1) // n_units
    opts = dict(opts)
    opts["reduce"] = reduce
    opts.setdefault("chunk_pad", _pad_for(max(unit_len, 1), opts))
    unit_indices = [
        _stripe_indices(len(ordered), k, n_units) for k in range(n_units)
    ]
    if paths_mode:
        units = [
            _Stripe(
                ordered, k, n_units,
                gids=[ordered_idx[i] for i in unit_indices[k]],
            )
            for k in range(n_units)
        ]
    else:
        units = [
            _Unit(
                ordered[k::n_units],
                [ordered_idx[i] for i in unit_indices[k]],
            )
            for k in range(n_units)
        ]
    mesh = opts.get("mesh")
    if mesh is not None:
        # all lanes feed the shared mesh (sharded staging/dispatch);
        # the lanes still overlap each other's host packing.  Dispatch
        # is serialized through one gate: concurrent enqueues of
        # collective programs from different threads interleave the
        # per-device queues inconsistently and deadlock the CPU
        # backend's all-reduce rendezvous (in-order in-flight programs
        # — the single-thread pipelined shape — are safe)
        base = family_for(workload, **opts)
        gate = threading.Lock()

        def locked_check(placed, _check=base.check):
            with gate:
                return _check(placed)

        fams = [
            dataclasses.replace(base, check=locked_check)
            for _ in range(n_lanes)
        ]
    else:
        fams = [
            family_for(workload, device=devices[i], **opts)
            for i in range(n_lanes)
        ]
    collected, stats = run_lanes(
        units, fams, depth=depth, fail_fast=fail_fast
    )
    if not fail_fast:
        collected = _resolve_quarantines(fams[0], units, collected, stats)
    stats.dropped = len(dropped)
    if reduce:
        with stats.busy("assemble"):
            merged = _merge_reduced(fams[0], units, collected)
        merged["dropped"] = len(dropped)
        stats.histories = merged["histories"]
        return merged, stats
    out: list = [None] * len(sources)
    with stats.busy("assemble"):
        for k, (unit, col) in enumerate(zip(units, collected)):
            conv = _convert_unit(
                fams[0], workload, unit, col, stats, fail_fast
            )
            for j, r in enumerate(conv):
                out[ordered_idx[unit_indices[k][j]]] = r
    for i, reason in dropped.items():
        out[i] = _dropped_result(workload, reason)
    stats.histories = len(out)
    return out, stats


def check_sources(
    workload: str,
    sources: Sequence[Any],
    *,
    chunk: int = DEFAULT_CHUNK,
    serial: bool = False,
    depth: int = 2,
    lanes: int | None = None,
    reduce: bool = False,
    fail_fast: bool = False,
    **opts,
) -> tuple[list[dict], PipelineStats]:
    """Bytes-to-verdict over ``sources`` (file paths, or pre-exploded
    family substrates) through the pipeline executor.

    Returns ``(results, stats)``: one result dict per source, in order
    — ``{"queue": ..., "linear": ...}`` / ``{"stream": ...}`` /
    ``{"elle": ...}`` with exactly the serial checkers' content (the
    differential contract).  ``serial=True`` is the triage escape
    hatch: the same stages run strictly serially on the calling thread
    — byte-identical results, no overlap.

    ``chunk`` caps the histories of a chunk.  Path sources whose bytes,
    estimated from the first few files, exceed ``CHUNK_BYTES`` a chunk
    are cut into more, smaller chunks (:func:`_plan_chunk`), so the
    producer has a next chunk to pack while the device checks one;
    pre-exploded substrates and the ``lanes`` executor keep
    ``chunk``-sized chunks.

    ``lanes`` opts into the scale-out executor: one input lane (producer
    + staging slot) per addressable device (``lanes=0``: all local
    devices), with size-aware largest-first unit balancing and
    steal-on-idle — see :func:`run_lanes`.  Unreadable/zero-length path
    sources are dropped from lane balancing with a logged warning, an
    explicit ``unknown`` verdict entry, and a ``stats.dropped`` count.

    ``reduce=True`` (requires ``mesh``) returns the collective-reduced
    batch verdict instead of per-history results: one dict
    ``{"histories", "invalid", "first_invalid", "quarantined"}`` whose
    scalars were combined ON DEVICE (psum / index-pmin) — the host
    never gathers the per-history verdict tensors.

    Failure isolation is ELASTIC by default: a chunk whose stage
    raises is retried once, then isolated per history — the crasher(s)
    report ``unknown`` with the exception as evidence (``quarantined``
    key in the result row / reduce-dict count) while every other
    history's verdict survives.  ``fail_fast=True`` restores the
    abort-all :class:`PipelineError` contract; the ``serial=True``
    triage path always fails fast (it exists to surface the first
    error loudly).

    Every call adds its histories to the global ``pipeline.histories``
    counter: the denominator of the per-history stage seconds there."""
    if lanes is not None and not serial:
        out = _check_sources_lanes(
            workload,
            list(sources),
            chunk=chunk,
            depth=depth,
            lanes=lanes,
            reduce=reduce,
            fail_fast=fail_fast,
            **opts,
        )
    else:
        out = _check_sources_chunked(
            workload, sources, chunk=chunk, serial=serial, depth=depth,
            reduce=reduce, fail_fast=fail_fast, **opts,
        )
    obs_metrics.REGISTRY.counter("pipeline.histories").inc(out[1].histories)
    return out


def _check_sources_chunked(
    workload: str,
    sources: Sequence[Any],
    *,
    chunk: int,
    serial: bool,
    depth: int,
    reduce: bool,
    fail_fast: bool,
    **opts,
):
    """One producer, one consumer over contiguous chunks (or the serial
    triage path): :func:`check_sources` without ``lanes``.  The chunk
    length is :func:`_plan_chunk`'s, counted in the global
    ``pipeline.chunk_plans{by=count|bytes}``."""
    opts = dict(opts)
    opts["reduce"] = reduce
    sources = list(sources)
    length, by = _plan_chunk(
        len(sources), _sample_sizes(sources), chunk, _hist_extent(opts)
    )
    obs_metrics.REGISTRY.counter("pipeline.chunk_plans", by=by).inc()
    opts.setdefault("chunk_pad", _pad_for(length, opts))
    fam = family_for(workload, **opts)
    items = _chunks(sources, length)
    if reduce:
        # contiguous chunks: chunk k's gids are its source offsets
        items = [
            _Unit(it, list(range(k * length, k * length + len(it))))
            for k, it in enumerate(items)
        ]
    if serial:
        import jax

        collect = fam.collect or _default_collect
        stats = PipelineStats()

        def check_collect(placed):
            raw = fam.check(placed)
            with stats.busy("collect"):
                return collect(raw)

        t0 = time.perf_counter()
        collected = []
        for it in items:
            host = stats.run_stage("produce", fam.produce, it)
            placed = stats.run_stage("place", fam.place, host)
            collected.append(stats.run_stage("check", check_collect, placed))
        stats.batches = len(items)
        stats.wall_s = time.perf_counter() - t0
        stats.finalize()
    else:
        collected, stats = run_pipeline(
            items,
            fam.produce,
            fam.check,
            place=fam.place,
            collect=fam.collect,
            depth=depth,
            fail_fast=fail_fast,
        )
        if not fail_fast:
            collected = _resolve_quarantines(fam, items, collected, stats)
    if reduce:
        with stats.busy("assemble"):
            merged = _merge_reduced(fam, items, collected)
        merged["dropped"] = 0
        stats.histories = merged["histories"]
        return merged, stats
    results: list[dict] = []
    with stats.busy("assemble"):
        for it, col in zip(items, collected):
            results.extend(
                _convert_unit(
                    fam, workload, it, col, stats, fail_fast or serial
                )
            )
    stats.histories = len(results)
    return results, stats


class PipelinedChecker:
    """Checker-protocol adapter for the CLI ``check`` path and the test
    runner: the family verdict computed from the history FILE through
    the pipeline (cache-first native substrate, device check), not from
    re-packed Op objects.  One shared run serves every sub-checker of
    the family (the queue workload surfaces as two keys).

    ``path=None`` resolves lazily from the runner's ``opts["out_dir"]``
    at check time (``run_test`` saves ``history.jsonl`` before the
    analysis phase) — the soak/test assembly wires checkers before the
    run dir exists.  When no file can be found (a storeless unit-test
    run), :meth:`_from_ops` checks the in-memory ops through the same
    convert path instead."""

    def __init__(self, workload: str, path, subkey: str, **opts):
        self.workload = workload
        self.path = path
        self.subkey = subkey
        self.name = subkey
        self._opts = dict(opts)
        self._shared = self._opts.pop("shared", None)

    def _resolve_path(self, opts):
        if self.path is not None:
            return self.path
        out_dir = (opts or {}).get("out_dir")
        if out_dir is None:
            return None
        from jepsen_tpu.history.store import HISTORY_FILE

        p = Path(out_dir) / HISTORY_FILE
        return p if p.is_file() else None

    def check(self, test, history, opts=None):
        if self._shared is not None and self.workload in self._shared:
            return self._shared[self.workload][0][self.subkey]
        path = self._resolve_path(opts)
        if path is not None:
            results, _ = check_sources(
                self.workload, [path], chunk=1, **self._resolved_opts()
            )
        else:
            # no file (e.g. a storeless unit-test run): serial family
            # substrates from the in-memory ops — same convert path
            results = self._from_ops(history)
        if self._shared is not None:
            self._shared[self.workload] = results
        return results[0][self.subkey]

    def _resolved_opts(self) -> dict:
        """``mesh=True`` resolves lazily to a device mesh at check time
        (checkers are wired before any device use; a mesh object must
        not be built at soak-assembly time).  A single long soak
        history has ONE file, so the scale-out axis is the op axis:
        queue/stream get a seq-parallel mesh over all local devices
        (the per-history count/scan programs shard their op blocks and
        psum-combine); elle's seq path needs txn-lane divisibility, so
        it keeps the plain single-device dispatch."""
        o = dict(self._opts)
        if o.get("mesh") is True:
            import jax

            from jepsen_tpu.parallel.mesh import checker_mesh

            n = len(jax.local_devices())
            if self.workload in ("queue", "stream") and n > 1:
                o["mesh"] = checker_mesh(seq=n)
            else:
                o.pop("mesh")
        return o

    def _from_ops(self, history):
        if self.workload == "stream":
            from jepsen_tpu.checkers.stream_lin import _stream_rows

            subs = [_stream_rows(history)]
        elif self.workload == "queue":
            from jepsen_tpu.history.rows import _rows_for

            subs = [_rows_for(history)]
        elif self.workload == "mutex":
            # the mutex producer takes Op lists directly (it derives the
            # model + decomposition from them, same as from cells)
            subs = [list(history)]
        else:
            from jepsen_tpu.checkers.elle import elle_mops_for

            # degenerate single histories need their ops for the host
            # oracle; check_elle_batch handles the splice directly
            from jepsen_tpu.checkers.elle import check_elle_batch

            model = self._opts.get("model", "serializable")
            return [
                {"elle": check_elle_batch([history], model=model)[0]}
            ]
        results, _ = check_sources(
            self.workload, subs, chunk=1, serial=True,
            **self._resolved_opts(),
        )
        return results


def attach_pipelined_checkers(test, workload: str, **scale_opts) -> bool:
    """Swap a built test's family checkers for pipeline-backed ones
    (``tools/soak.py`` and friends: the post-run analysis then runs
    bytes-to-verdict from the stored ``history.jsonl`` through the
    executor instead of re-packing Op objects on one thread).  Contract
    levels (delivery / append-fail / consistency model) are inherited
    from the checkers being replaced, so the verdict semantics cannot
    drift.  ``scale_opts`` forward scale-out knobs (``lanes`` — 0 = one
    lane per local device) into :func:`check_sources`.  Returns True
    when the swap applied (False: no composed checkers to swap, or an
    explicitly monolithic mutex checker)."""
    checkers = getattr(getattr(test, "checker", None), "checkers", None)
    if checkers is None:
        return False
    shared: dict = {}
    scale_opts = {k: v for k, v in scale_opts.items() if v is not None}
    if workload == "queue" and {"queue", "linear"} <= set(checkers):
        delivery = getattr(
            checkers["linear"], "delivery", "exactly-once"
        )
        for sub in ("queue", "linear"):
            checkers[sub] = PipelinedChecker(
                "queue", None, sub, shared=shared, delivery=delivery,
                **scale_opts,
            )
        return True
    if workload == "stream" and "stream" in checkers:
        append_fail = getattr(
            checkers["stream"], "append_fail", "definite"
        )
        checkers["stream"] = PipelinedChecker(
            "stream", None, "stream", shared=shared,
            append_fail=append_fail, **scale_opts,
        )
        return True
    if workload == "elle" and "elle" in checkers:
        model = getattr(checkers["elle"], "model", "serializable")
        checkers["elle"] = PipelinedChecker(
            "elle", None, "elle", shared=shared, model=model,
            **scale_opts,
        )
        return True
    if workload == "mutex" and "mutex" in checkers:
        if getattr(checkers["mutex"], "pcomp", True) is False:
            return False  # an explicitly monolithic checker stays
        opts = {k: v for k, v in scale_opts.items() if k != "reduce"}
        checkers["mutex"] = PipelinedChecker(
            "mutex", None, "mutex", shared=shared, **opts
        )
        return True
    return False


# ---------------------------------------------------------------------------
# segment-producer mode (ISSUE 15 / SEGMENTED.md)
# ---------------------------------------------------------------------------


def check_source_segmented(
    workload: str,
    src,
    *,
    segment_ops: int,
    resume: bool = False,
    carry_cap: int | None = None,
    device: bool = True,
    keep_checkpoint: bool = False,
    prefix_index=None,
    **opts,
) -> tuple[dict, "PipelineStats"]:
    """The pipeline's segment-producer mode: ONE history streamed
    through the segmented carry engine (``checkers/segmented.py``) in
    fixed-shape segments — bounded memory regardless of history
    length, durable per-segment checkpoints, ``resume=True`` to
    continue a killed check from the last one.  ``prefix_index`` (a
    directory path or :class:`~jepsen_tpu.history.prefix_index.
    PrefixCheckpointIndex`) arms fleet memory: a re-submitted history
    resumes from the deepest published anchor whose content hash
    matches its bytes (SEGMENTED.md §Prefix resume).

    The producer here is the op axis, not the file axis: per-segment
    check latency lands in the run registry's
    ``segmented.segment_check_s`` sketch (the same PR-9 substrate the
    batch executor's ``check_batch_s`` uses) and the returned
    :class:`PipelineStats` view reports segments as checked batches,
    so ``bench-check``-style consumers read one accounting surface for
    both modes.
    """
    from jepsen_tpu.checkers.segmented import segmented_check_file
    from jepsen_tpu.obs.metrics import REGISTRY

    stats = PipelineStats()
    t0 = time.perf_counter()
    before = REGISTRY.value("segmented.segments")
    result = segmented_check_file(
        src,
        workload=workload,
        segment_ops=segment_ops,
        opts={k: v for k, v in opts.items() if v is not None},
        resume=resume,
        carry_cap=carry_cap,
        device=device,
        keep_checkpoint=keep_checkpoint,
        prefix_index=prefix_index,
    )
    t1 = time.perf_counter()
    segs = int(REGISTRY.value("segmented.segments") - before)
    stats.histories = 1
    stats.batches = segs
    stats.add_busy("check", t0, t1)
    return result, stats
