"""Where the device's idle time goes, by the program's own spans.

    python tools/span_idle.py --root <checkout> --workload <cell> \\
        --seed <n> --seconds <s> [--out <file>.json]

Runs one benchmark cell of the checkout at ``--root`` traced (its
``benchmark/run.py`` with ``--trace 1``: the same warm-up, window and
comparison), keeps the profiler capture that run takes, and reduces it
with ``jax.profiler.ProfileData`` as ``benchmark/xplane.py`` does:

- ``idle_by_span``: per host thread that holds an outer annotation
  (``bench.pass`` on the replay driver's thread, ``bench.request`` on the
  sidecar's client, ``service.check`` on its handler), the device's idle
  time in the window split by the innermost span that thread was in.
  Spans are the program's (``pipeline.*``, ``service.*``, ``encode.*``),
  the harness's (``bench.*``) and JAX's dispatch (``PjitFunction``);
  device idle is averaged over the devices;
- ``coverage``: the share of each outer annotation's time that the spans
  nested in it cover;
- the run's own timings: each replay pass's seconds, or the sidecar's
  request latencies (p50, p95), tracing on.

The result line of the run is kept under ``line``.  A checkout whose
program has no such spans (an older commit) reads as uncovered time.
Needs the chip: the command refuses a machine without one.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
from pathlib import Path

PROGRAM = ("pipeline.", "service.", "encode.", "bench.")
DISPATCH = "PjitFunction"
OUTER = ("bench.pass", "bench.request", "service.check")


def _label(name: str) -> str | None:
    if name.startswith(PROGRAM):
        return name
    if name.startswith(DISPATCH):
        return "dispatch (PjitFunction)"
    return None


def flatten(spans: list[tuple[float, float, str]]):
    """Nested spans of one thread -> disjoint ``(start, end, innermost)``
    segments (time in no span is left out)."""
    out = []
    stack: list[tuple[float, float, str]] = []
    t = None
    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            if top[1] > t:
                out.append((t, top[1], top[2]))
            t = max(t, top[1])
        if stack and s > t:
            out.append((t, s, stack[-1][2]))
        t = s if t is None else max(t, s)
        stack.append((s, e, name))
    while stack:
        top = stack.pop()
        if top[1] > t:
            out.append((t, top[1], top[2]))
        t = max(t, top[1])
    return out


def overlap_by_label(segments, intervals) -> dict[str, float]:
    """Seconds of ``intervals`` (sorted, disjoint) inside each label's
    ``segments`` (sorted, disjoint)."""
    out: dict[str, float] = {}
    j = 0
    for s, e, name in segments:
        while j < len(intervals) and intervals[j][1] <= s:
            j += 1
        k = j
        while k < len(intervals) and intervals[k][0] < e:
            d = min(e, intervals[k][1]) - max(s, intervals[k][0])
            if d > 0:
                out[name] = out.get(name, 0.0) + d / 1e9
            k += 1
    return out


def analyse(path: str) -> dict:
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)  # a one-shot iterator
    lines: list[list[tuple[float, float, str]]] = []
    window = None
    busy_per_device = []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = []
                for ev in line.events:
                    lab = _label(ev.name)
                    if lab == "bench.window":
                        window = (ev.start_ns, ev.end_ns)
                    elif lab is not None:
                        spans.append((ev.start_ns, ev.end_ns, lab))
                if spans:
                    lines.append(spans)
    if window is None:
        return {"error": "no bench.window in the capture"}
    lo, hi = window
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                busy_per_device.append(sorted(
                    (max(ev.start_ns, lo), min(ev.end_ns, hi))
                    for ev in line.events
                    if min(ev.end_ns, hi) > max(ev.start_ns, lo)))
    idles = []
    for iv in busy_per_device:
        merged: list[list[float]] = []
        for s, e in iv:
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        idle, edge = [], lo
        for s, e in merged + [[hi, hi]]:
            if s > edge:
                idle.append((edge, s))
            edge = max(edge, e)
        idles.append(idle)
    n_dev = max(1, len(idles))
    out = {"window_s": (hi - lo) / 1e9, "devices": len(idles),
           "idle_s": sum(sum(e - s for s, e in idle) for idle in idles)
           / n_dev / 1e9,
           "idle_by_span": {}, "coverage": {}}
    for spans in lines:
        names = {n for _, _, n in spans}
        outer = next((o for o in OUTER if o in names), None)
        if outer is None:
            continue
        segs = flatten([(max(s, lo), min(e, hi), n) for s, e, n in spans
                        if min(e, hi) > max(s, lo)])
        split: dict[str, float] = {}
        for idle in idles:
            for k, v in overlap_by_label(segs, idle).items():
                split[k] = split.get(k, 0.0) + v / n_dev
        split["(no span)"] = out["idle_s"] - sum(split.values())
        key = outer if outer not in out["idle_by_span"] else f"{outer}#2"
        out["idle_by_span"][key] = dict(
            sorted(split.items(), key=lambda kv: -kv[1]))
        outer_iv = [(max(s, lo), min(e, hi)) for s, e, n in sorted(spans)
                    if n == outer and min(e, hi) > max(s, lo)]
        total = sum(e - s for s, e in outer_iv) / 1e9
        inside = overlap_by_label(
            flatten([(max(s, lo), min(e, hi), n) for s, e, n in spans
                     if not n.startswith("bench.")
                     and min(e, hi) > max(s, lo)]),
            outer_iv)
        inside.pop(outer, None)  # the outer's own time is not covered
        out["coverage"][key] = {
            "outer_s": total,
            "covered": sum(inside.values()) / total if total else None,
            "by_span_s": dict(sorted(inside.items(), key=lambda kv: -kv[1])),
        }
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--root", required=True)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    root = Path(args.root).resolve()
    sys.path[:0] = [str(root / "benchmark"), str(root)]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
        root / ".bench_cache" / "jax")
    import run
    import xplane

    reduce = xplane.reduce
    got: dict = {}

    def keep(path, *a, **kw):
        got["capture"] = analyse(path)
        return reduce(path, *a, **kw)

    xplane.reduce = keep
    drivers = []
    cell = run.find_cell(args.workload, root=root)
    cell.seed = args.seed
    try:
        line = run.run_cell(cell, args.seconds, True, hook=drivers.append)
    except run.Unfit as e:
        print(f"span_idle: {e}", file=sys.stderr)
        return 1
    d = drivers[0]
    result = {"root": str(root), "workload": args.workload,
              "seed": args.seed, "line": line, **got}
    if hasattr(d, "passes"):
        result["pass_s"] = [p["seconds"] for p in d.passes if p["results"]]
    if hasattr(d, "latency") and d.latency:
        q = statistics.quantiles(d.latency, n=100, method="inclusive")
        result["request_p50_ms"] = statistics.median(d.latency) * 1e3
        result["request_p95_ms"] = q[94] * 1e3
        result["requests"] = len(d.latency)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(result, indent=1))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
