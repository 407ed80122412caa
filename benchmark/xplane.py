"""From a profiler trace (``.xplane.pb``) to the numbers the readers take.

``reduce(path)`` reads the trace with ``jax.profiler.ProfileData`` and
returns a ``Trace``:

- ``window``: the harness's ``bench.window`` annotation (start, end) on
  the host plane; every device interval is clipped to it;
- per device plane (``/device:TPU:n``): the union of the intervals of its
  ``XLA Ops`` events (busy), their time by HLO instruction and by jit
  module (the ``XLA Modules`` event around the op; the TPU's trace
  carries no module stat on an op), and the time of collective ops;
- ``gaps``: the device's longest idle intervals in the window, each
  labelled with the innermost ``bench.*`` host annotation around its
  midpoint.

A trace with no device plane (a CPU run) has ``devices == 0``, and the
readers then report nothing.
"""

from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field

#: op-name fragments of the collectives XLA emits on TPU
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|psum|pmin|pmax|send|recv",
    re.I,
)
ANNOTATION = "bench."
WINDOW = "bench.window"


@dataclass
class Trace:
    window: tuple[float, float] | None = None  # ns
    devices: int = 0
    busy_s: list[float] = field(default_factory=list)  # per device
    op_s: dict[str, float] = field(default_factory=dict)  # summed
    module_s: dict[str, float] = field(default_factory=dict)  # summed
    collective_s: float = 0.0  # summed over devices
    gaps: list[tuple[str, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9 if self.window else 0.0

    @property
    def mean_busy_s(self) -> float | None:
        return sum(self.busy_s) / len(self.busy_s) if self.busy_s else None

    def module_time(self, pattern: str) -> float:
        """Seconds of device time in jit modules whose name matches."""
        rx = re.compile(pattern)
        return sum(s for m, s in self.module_s.items() if rx.search(m))


def _module_name(name: str) -> str:
    """``jit_f(123)`` / ``jit_f.4`` -> ``jit_f``: the id differs by program."""
    return re.sub(r"(\(\d+\)|\.\d+)$", "", name)


def _op_name(name: str) -> str:
    """``%fusion.5 = s32[32768]{...} fusion(...)`` -> ``%fusion.5``: the
    HLO instruction's own name, without its text."""
    return name.split(" = ", 1)[0]


def _union(iv: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _label(spans, t: float) -> str:
    """The innermost ``bench.*`` host span around ``t``."""
    inner = None
    for name, s, e in spans:
        if s <= t <= e and (inner is None or e - s < inner[2] - inner[1]):
            inner = (name, s, e)
    return inner[0] if inner else "outside bench spans"


def reduce(path: str, top_gaps: int = 10) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans: list[tuple[str, float, float]] = []
    device_planes = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(ANNOTATION):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
    out = Trace()
    win = [s for s in spans if s[0] == WINDOW]
    if win:
        out.window = (win[0][1], win[0][2])
    if not device_planes or out.window is None:
        return out
    lo, hi = out.window
    gaps: list[tuple[float, float]] = []
    for plane in sorted(device_planes, key=lambda p: p.name):
        lines = {ln.name: ln for ln in plane.lines}
        modules = []
        if "XLA Modules" in lines:
            modules = sorted(
                (ev.start_ns, ev.end_ns, _module_name(ev.name))
                for ev in lines["XLA Modules"].events
            )
        starts = [m[0] for m in modules]
        ops = lines.get("XLA Ops")
        iv = []
        for ev in (ops.events if ops is not None else ()):
            s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
            if e <= s:
                continue
            dur = (e - s) / 1e9
            iv.append((s, e))
            k = bisect.bisect_right(starts, ev.start_ns) - 1
            mod = (modules[k][2] if k >= 0 and ev.start_ns <= modules[k][1]
                   else "unknown module")
            name = f"{mod}/{_op_name(ev.name)}"
            out.op_s[name] = out.op_s.get(name, 0.0) + dur
            out.module_s[mod] = out.module_s.get(mod, 0.0) + dur
            if COLLECTIVE.search(_op_name(ev.name)):
                out.collective_s += dur
        busy = _union(iv)
        out.busy_s.append(sum(e - s for s, e in busy) / 1e9)
        edge = lo
        for s, e in busy + [(hi, hi)]:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
    out.devices = len(device_planes)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top_gaps]
    out.gaps = [(_label(spans, (s + e) / 2), (e - s) / 1e9)
                for s, e in longest]
    return out


def breakdown(tr: Trace, top: int = 10) -> dict | None:
    """The result line's ``breakdown``: the device ops that took most
    time and the longest idle gaps, each ``[name, seconds]``."""
    if not tr.devices:
        return None
    ops = sorted(tr.op_s.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in tr.gaps[:top]]}
