"""Chrome-trace/Perfetto JSON emission of the recorded span ring.

The output is the Trace Event Format (the ``{"traceEvents": [...]}``
JSON Perfetto and ``chrome://tracing`` both open — OBSERVABILITY.md has
the how-to): one process ("jepsen-tpu"), one tid per TRACK (pipeline
lane, device, nemesis, soak phase...), "X" complete events for spans and
"i" instants for events, timestamps in µs relative to the session epoch.

Artifact discipline (the soak/fuzz capture rule): :func:`write_trace`
writes tmp → fsync → rename, and the CLI/tool callers only invoke it on
a COMPLETED run — a crashed run leaves no half-artifact behind.

This export is for runs without a ``jax.profiler`` capture.  Under a
capture the spans also land in the profiler's own trace, next to the
device's ops on one clock (``obs/trace.py``; ``jepsen-tpu trace
--jax-profile DIR`` writes its ``perfetto_trace.json.gz``).
"""

from __future__ import annotations

import json
import os
from pathlib import Path

from jepsen_tpu.obs import trace as _trace

PID = 1


def chrome_trace(records=None, t0_ns: int | None = None) -> dict:
    """The Trace Event Format dict for ``records`` (default: the live
    or last-disabled session's ring)."""
    if records is None:
        records = _trace.snapshot()
    if t0_ns is None:
        t0_ns = _trace.session_t0_ns()
    tids: dict[str, int] = {}
    events: list[dict] = []
    for rec in records:
        kind, name, track, t_ns, dur_ns, args = rec
        tid = tids.get(track)
        if tid is None:
            tid = tids[track] = len(tids) + 1
        ev = {
            "ph": kind,
            "name": name,
            "pid": PID,
            "tid": tid,
            "ts": (t_ns - t0_ns) / 1e3,
        }
        if kind == _trace.KIND_SPAN:
            ev["dur"] = dur_ns / 1e3
        else:
            ev["s"] = "t"  # thread-scoped instant
        if args:
            ev["args"] = args
        events.append(ev)
    meta = [
        {
            "ph": "M",
            "name": "process_name",
            "pid": PID,
            "tid": 0,
            "args": {"name": "jepsen-tpu"},
        }
    ]
    for track, tid in sorted(tids.items(), key=lambda kv: kv[1]):
        meta.append(
            {
                "ph": "M",
                "name": "thread_name",
                "pid": PID,
                "tid": tid,
                "args": {"name": track},
            }
        )
    return {"traceEvents": meta + events, "displayTimeUnit": "ms"}


def write_trace(path: str | Path, records=None) -> dict:
    """Export the ring to ``path`` (tmp → fsync → rename).  Returns a
    summary ``{"path", "events", "tracks", "dropped"}`` — callers print
    it so the artifact's provenance is in the run log."""
    doc = chrome_trace(records)
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(f"{path.name}.{os.getpid()}.tmp")
    try:
        with open(tmp, "w") as fh:
            json.dump(doc, fh)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    n_tracks = sum(
        1
        for ev in doc["traceEvents"]
        if ev.get("ph") == "M" and ev.get("name") == "thread_name"
    )
    return {
        "path": str(path),
        "events": len(doc["traceEvents"]),
        "tracks": n_tracks,
        "dropped": _trace.dropped(),
    }
