"""Ring-buffer span tracer: the flight recorder's timeline substrate.

Design constraints (ISSUE 10 / OBSERVABILITY.md):

- **Off is free.**  Tracing is disabled by default; a disabled
  ``span()``/``event()`` call is one module-global read (plus, for
  ``span()``, the profiler's ``is_enabled()`` probe) and returns a
  shared no-op context manager — ZERO allocations per span (pinned by
  ``tests/test_obs.py::TestDisabledOverhead``).  Hot paths therefore
  instrument unconditionally; the 2% ``obs_overhead`` bench done-bar is
  about the ENABLED path.
- **Recording never blocks.**  Spans land in a fixed-capacity
  preallocated ring: each record claims a monotonically increasing slot
  (``itertools.count`` — atomic under the GIL) and writes one tuple into
  ``ring[slot % capacity]``.  No lock on the hot path; when the ring
  wraps, the OLDEST records are overwritten (a flight recorder keeps
  the tail, and :func:`dropped` reports how many fell off).
- **Tracks, not just threads.**  Every record carries a track id — by
  default the recording thread's name, explicitly e.g. ``lane0`` /
  ``device:TFRT_CPU_0`` / ``nemesis`` — so the exported trace groups
  pipeline lanes, device dispatch, and fault windows as parallel
  timelines.  Records on one track come from one thread at a time in
  practice (lanes own their thread; the nemesis has its own), which is
  what keeps Perfetto's same-tid nesting sound.
- **Clock.**  ``time.perf_counter_ns()`` — monotonic, ns, comparable
  across threads of one process.  :func:`complete` accepts the float
  ``time.perf_counter()`` seconds the pipeline already measures, so
  stage timing is paid ONCE for stats and trace both.

Nesting needs no explicit parent ids: Chrome-trace/Perfetto "X"
(complete) events nest by containment of ``[ts, ts+dur]`` on one tid,
and a ``with span(...)`` exits LIFO per thread by construction.

**Second sink: the JAX profiler.**  While a ``jax.profiler`` session is
recording, :func:`span` also opens a ``jax.profiler.TraceAnnotation`` of
the same name, so the span lands on the capture's host plane on the
device trace's clock.  The probe is ``TraceAnnotation.is_enabled()``,
bound once ``jax`` has been imported by someone else (importing ``obs``
never imports jax; a process without jax has no session to record).
Each span reaches each active sink once: the ring (when enabled) and
the profiler (while it records).  :func:`complete` and :func:`event`
stay ring-only: their intervals are known only after the fact.
"""

from __future__ import annotations

import itertools
import sys
import threading
import time

#: record kinds (index 0 of every ring tuple)
KIND_SPAN = "X"  # complete span: (X, name, track, t0_ns, dur_ns, args)
KIND_EVENT = "i"  # instant event: (i, name, track, t_ns, None, args)

_DEFAULT_CAPACITY = 1 << 16


class _State:
    """One enabled tracing session: the ring and its slot counter."""

    __slots__ = ("ring", "capacity", "slots", "high", "t0_ns",
                 "track_spans")

    def __init__(self, capacity: int):
        self.capacity = max(256, int(capacity))
        self.ring: list = [None] * self.capacity
        self.slots = itertools.count()
        # highest claimed slot count, maintained by _emit: the read APIs
        # (snapshot/spans_recorded) must not consume the counter.  The
        # unlocked write races only with other emitters and converges to
        # the max within one in-flight record — read-side accuracy, not
        # a correctness invariant
        self.high = 0
        self.t0_ns = time.perf_counter_ns()
        # per-track RECORDED span totals (monotonic, survive ring
        # wrap) — maintained at emit time so the /metrics health block
        # never has to scan the whole ring per scrape.  Same accuracy
        # contract as `high`: unlocked read-modify-write, a rare lost
        # increment under emitter races costs gauge accuracy only
        self.track_spans: dict = {}


#: None = disabled.  Read once per call; enable/disable swap the whole
#: object so a mid-flight recorder thread sees either the old ring or
#: the new one, never a half-initialized state.
_state: _State | None = None


def enable(capacity: int = _DEFAULT_CAPACITY) -> None:
    """Start a fresh recording (clears any previous ring)."""
    global _state
    _state = _State(capacity)


def disable() -> None:
    """Stop recording.  The ring stays readable via :func:`snapshot`
    until the next :func:`enable`."""
    global _state
    st = _state
    _state = None
    # keep the last session readable for post-run export
    if st is not None:
        _last[0] = st


#: the most recently disabled session (export-after-disable)
_last: list = [None]


def is_enabled() -> bool:
    return _state is not None


def _track() -> str:
    return threading.current_thread().name


def _emit(st: _State, rec: tuple) -> None:
    i = next(st.slots)
    st.ring[i % st.capacity] = rec
    if i >= st.high:
        st.high = i + 1
    if rec[0] == KIND_SPAN:
        d = st.track_spans
        d[rec[2]] = d.get(rec[2], 0) + 1


class _Span:
    """An enabled span: records one KIND_SPAN tuple on exit into the
    ring (``st``, when the ring is on) and wraps the block in the
    profiler's annotation (``ann``, while a session records)."""

    __slots__ = ("_st", "name", "track", "args", "t0", "_ann")

    def __init__(self, st: _State | None, name: str, track: str | None,
                 args, ann=None):
        self._st = st
        self.name = name
        self.track = track
        self.args = args
        self.t0 = 0
        self._ann = ann

    def __enter__(self) -> "_Span":
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self._st is None:
            return
        _emit(
            self._st,
            (
                KIND_SPAN,
                self.name,
                self.track or _track(),
                self.t0,
                t1 - self.t0,
                self.args,
            ),
        )


class _Noop:
    """The disabled path: one shared reentrant no-op context manager."""

    __slots__ = ()

    def __enter__(self) -> "_Noop":
        return self

    def __exit__(self, *exc) -> None:
        return None


_NOOP = _Noop()

#: ``jax.profiler.TraceAnnotation`` once jax is imported (None before)
_annotation = None


def _bind_profiler():
    """The profiler's annotation class, or None while no jax is loaded
    in this process (then no session can be recording)."""
    global _annotation
    if "jax" not in sys.modules:
        return None
    from jax.profiler import TraceAnnotation

    _annotation = TraceAnnotation
    return TraceAnnotation


def span(name: str, track: str | None = None, args: dict | None = None):
    """``with span("pipeline.produce", track="lane0"): ...`` — records a
    complete span over the block into the ring, and into a recording
    ``jax.profiler`` session.  Both off: returns the shared no-op (no
    allocation; the cost is one ``is_enabled()`` probe).  ``args`` must
    be a pre-built dict or None — build it behind :func:`is_enabled` on
    hot paths so the off-path never allocates; the profiler's copy of
    the span carries the name only."""
    st = _state
    ann = _annotation or _bind_profiler()
    if ann is not None and ann.is_enabled():
        return _Span(st, name, track, args, ann(name))
    if st is None:
        return _NOOP
    return _Span(st, name, track, args)


def event(name: str, track: str | None = None, args: dict | None = None) -> None:
    """Record an instant annotation (a point on a track's timeline)."""
    st = _state
    if st is None:
        return
    _emit(
        st,
        (KIND_EVENT, name, track or _track(), time.perf_counter_ns(), None, args),
    )


def complete(
    name: str,
    t0_s: float,
    t1_s: float,
    track: str | None = None,
    args: dict | None = None,
) -> None:
    """Record a span from already-measured ``time.perf_counter()``
    seconds (same clock as ``perf_counter_ns``) — the pipeline's
    serialized check-interval accounting and the nemesis START/STOP
    pairing measure once and feed stats and trace both."""
    st = _state
    if st is None:
        return
    _emit(
        st,
        (
            KIND_SPAN,
            name,
            track or _track(),
            int(t0_s * 1e9),
            max(0, int((t1_s - t0_s) * 1e9)),
            args,
        ),
    )


def _session() -> _State | None:
    return _state if _state is not None else _last[0]


def snapshot() -> list[tuple]:
    """The recorded tuples, oldest first (ring order), from the live
    session or — after :func:`disable` — the last one."""
    st = _session()
    if st is None:
        return []
    n = st.high
    if n <= st.capacity:
        recs = st.ring[:n]
    else:
        k = n % st.capacity
        recs = st.ring[k:] + st.ring[:k]
    return [r for r in recs if r is not None]


def spans_recorded() -> int:
    """Total records claimed this session (including any the ring has
    since overwritten)."""
    st = _session()
    return st.high if st is not None else 0


def dropped() -> int:
    """Records overwritten by ring wrap-around (0 when capacity held)."""
    st = _session()
    if st is None:
        return 0
    return max(0, st.high - st.capacity)


def ring_capacity() -> int:
    """The ring's slot count (live session or — after :func:`disable`
    — the last one); 0 when no session ever ran."""
    st = _session()
    return st.capacity if st is not None else 0


def track_span_counts() -> dict:
    """``{track: spans recorded}`` for the live (or last) session —
    monotonic emit-time totals (wrap-dropped spans stay counted), so a
    scrape never scans the ring."""
    st = _session()
    return dict(st.track_spans) if st is not None else {}


def session_t0_ns() -> int:
    """The session's epoch (perf_counter_ns at enable) — export
    subtracts it so trace timestamps start near zero."""
    st = _session()
    return st.t0_ns if st is not None else 0
