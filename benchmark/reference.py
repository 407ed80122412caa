"""The plain reference: the same semantics as the program's checkers,
written straight from their definitions, importing nothing of the program.

It reads a history from its JSONL bytes (the very bytes the timed path
parses) and returns the verdict maps the program returns for it:

- ``queue``: ``{"queue": total-queue, "linear": queue linearizability}``
  (the definitions in ``checkers/total_queue.py`` and
  ``checkers/queue_lin.py``: per-value multiset reconciliation, and the
  per-value duplicate / phantom / causality rule over history positions);
- ``stream``: ``{"stream": single-partition log linearizability}``
  (``checkers/stream_lin.py``: divergent offsets, duplicated values,
  phantoms, real-time reorder by suffix-min over offsets, non-monotonic
  read batches, and loss once a full read completed).

``same`` compares two verdict maps exactly: every key, every anomaly
set, every count.  A map that is missing, or an ``unknown`` verdict,
is a mismatch.
"""

from __future__ import annotations

import json
from typing import Any

FULL_READ = "full"
_INF = 2**31 - 1
_NEG = -(2**31)


def parse(text: str) -> list[dict]:
    return [json.loads(line) for line in text.splitlines() if line.strip()]


def _values(op: dict) -> list:
    v = op.get("value")
    return v if isinstance(v, list) else [v]


def _total_queue(ops: list[dict]) -> dict:
    attempts: dict[int, int] = {}
    acked: dict[int, int] = {}
    reads: dict[int, int] = {}
    for op in ops:
        f, t = op["f"], op["type"]
        if f == "enqueue" and type(op.get("value")) is int:
            v = op["value"]
            if t == "invoke":
                attempts[v] = attempts.get(v, 0) + 1
            elif t == "ok":
                acked[v] = acked.get(v, 0) + 1
        elif f in ("dequeue", "drain") and t == "ok":
            for v in _values(op):
                if type(v) is int:
                    reads[v] = reads.get(v, 0) + 1
    ok = lost = dup = unexp = recov = 0
    lost_s, dup_s, unexp_s, recov_s = set(), set(), set(), set()
    for v in set(attempts) | set(acked) | set(reads):
        a, e, d = attempts.get(v, 0), acked.get(v, 0), reads.get(v, 0)
        ok += min(d, a)
        if a == 0 and d > 0:
            unexp += d
            unexp_s.add(v)
        if a > 0 and d > a:
            dup += d - a
            dup_s.add(v)
        if e > d:
            lost += e - d
            lost_s.add(v)
        if min(d, a) > e:
            recov += min(d, a) - e
            recov_s.add(v)
    return {
        "valid?": lost == 0 and unexp == 0,
        "attempt-count": sum(attempts.values()),
        "acknowledged-count": sum(acked.values()),
        "ok-count": ok,
        "lost-count": lost, "lost": lost_s,
        "unexpected-count": unexp, "unexpected": unexp_s,
        "duplicated-count": dup, "duplicated": dup_s,
        "recovered-count": recov, "recovered": recov_s,
    }


def _queue_lin(ops: list[dict], delivery: str) -> dict:
    invokes: dict[int, int] = {}
    fails: dict[int, int] = {}
    start: dict[int, int] = {}  # first history position of an invoke
    nread: dict[int, int] = {}
    end: dict[int, int] = {}  # first history position of an ok read
    for pos, op in enumerate(ops):
        f, t = op["f"], op["type"]
        if f == "enqueue" and type(op.get("value")) is int:
            v = op["value"]
            if t == "invoke":
                invokes[v] = invokes.get(v, 0) + 1
                start.setdefault(v, pos)
            elif t == "fail":
                fails[v] = fails.get(v, 0) + 1
        elif f in ("dequeue", "drain") and t == "ok":
            for v in _values(op):
                if type(v) is int:
                    nread[v] = nread.get(v, 0) + 1
                    end.setdefault(v, pos)
    exactly_once = delivery == "exactly-once"
    dup, phantom, causal, recovered = set(), set(), set(), set()
    for v, r in nread.items():
        a, x = invokes.get(v, 0), fails.get(v, 0)
        if r > 1:
            dup.add(v)
        if a == 0 or (x >= a and exactly_once):
            phantom.add(v)
        elif end[v] < start[v]:
            causal.add(v)
        elif x >= a:
            recovered.add(v)
    return {
        "valid?": not ((dup and exactly_once) or phantom or causal),
        "delivery": delivery,
        "duplicate-count": len(dup), "duplicate": dup,
        "phantom-count": len(phantom), "phantom": phantom,
        "causality-count": len(causal), "causality": causal,
        "recovered-count": len(recovered), "recovered": recovered,
        "read-value-count": len(nread),
    }


def _pairs(value: Any) -> list:
    def pair(x):
        return (isinstance(x, list) and len(x) == 2
                and all(type(e) is int for e in x))

    if value is None:
        return []
    if pair(value):
        return [value]
    if isinstance(value, list):
        return [p for p in value if pair(p)]
    return []


def _stream(ops: list[dict], append_fail: str,
            duplicates: str = "judged") -> dict:
    invokes: dict[int, int] = {}
    acks: dict[int, int] = {}
    fails: dict[int, int] = {}
    s_v: dict[int, int] = {}  # first append-invoke position
    e_v: dict[int, int] = {}  # first append-ok position
    at: dict[int, set] = {}  # value -> offsets it was read at
    seen: dict[int, set] = {}  # offset -> values read there
    nonmono = 0
    full_read = False
    open_full: set[int] = set()
    for pos, op in enumerate(ops):
        f, t, p = op["f"], op["type"], op["process"]
        if f == "append" and type(op.get("value")) is int:
            v = op["value"]
            if t == "invoke":
                invokes[v] = invokes.get(v, 0) + 1
                s_v.setdefault(v, pos)
            elif t == "ok":
                acks[v] = acks.get(v, 0) + 1
                e_v.setdefault(v, pos)
            elif t == "fail":
                fails[v] = fails.get(v, 0) + 1
        elif f == "read":
            if t == "invoke":
                open_full.discard(p)
                if op.get("value") == FULL_READ:
                    open_full.add(p)
            else:
                if t == "ok" and p in open_full:
                    full_read = True
                open_full.discard(p)
            if t == "ok":
                prev = None
                for o, v in _pairs(op.get("value")):
                    at.setdefault(v, set()).add(o)
                    seen.setdefault(o, set()).add(v)
                    if prev is not None and o <= prev:
                        nonmono += 1
                    prev = o
    divergent = {o for o, vs in seen.items() if len(vs) > 1}
    duplicate = ({v for v, os_ in at.items() if len(os_) > 1}
                 if duplicates == "judged" else set())
    all_fail = {v for v in at if 0 < invokes.get(v, 0) <= fails.get(v, 0)}
    phantom = {v for v in at if invokes.get(v, 0) == 0}
    if append_fail == "definite":
        phantom |= all_fail
        recovered: set = set()
    else:
        recovered = all_fail
    reorder: set = set()
    suffix = _INF  # least completion position at any later offset
    for o in sorted(seen, reverse=True):
        ss = [s_v[v] for v in seen[o] if v in s_v]
        s = max(ss) if ss else _NEG
        if s != _NEG and suffix < s:
            reorder.add(o)
        suffix = min(suffix, min(e_v.get(v, _INF) for v in seen[o]))
    lost = ({v for v in acks if v not in at} if full_read else set())
    return {
        "valid?": not (divergent or duplicate or phantom or reorder
                       or nonmono or lost),
        "attempt-count": sum(invokes.values()),
        "acknowledged-count": sum(acks.values()),
        "read-value-count": len(at),
        "divergent": divergent, "divergent-count": len(divergent),
        "duplicate": duplicate, "duplicate-count": len(duplicate),
        "phantom": phantom, "phantom-count": len(phantom),
        "recovered": recovered, "recovered-count": len(recovered),
        "reorder": reorder, "reorder-count": len(reorder),
        "nonmonotonic-count": nonmono,
        "lost": lost, "lost-count": len(lost),
        "full-read": full_read,
        "append-fail": append_fail,
    }


def check(family: str, ops: list[dict], opts: dict) -> dict:
    """The verdict map of one history under the configuration's
    guarantees (``opts``: ``delivery`` / ``append_fail``)."""
    if family == "queue":
        return {"queue": _total_queue(ops),
                "linear": _queue_lin(ops, opts["delivery"])}
    if family == "stream":
        return {"stream": _stream(ops, opts["append_fail"],
                                  opts.get("duplicates", "judged"))}
    raise ValueError(f"no reference for family {family!r}")


def valid(result: dict) -> bool:
    """A history's verdict: every sub-verdict valid."""
    return all(r.get("valid?") is True for r in result.values())


def norm(x: Any) -> Any:
    """JSON-plain form: sets sorted, numpy scalars unwrapped."""
    if isinstance(x, dict):
        return {str(k): norm(v) for k, v in x.items()}
    if isinstance(x, (set, frozenset)):
        return sorted(norm(v) for v in x)
    if isinstance(x, (list, tuple)):
        return [norm(v) for v in x]
    if hasattr(x, "item"):
        return x.item()
    return x


#: keys that echo the options a check was asked for; an answer may leave
#: them out (the sidecar's ``check`` op does), but may not contradict them
LABELS = frozenset({"delivery", "append-fail"})


def same(got: Any, want: dict) -> bool:
    """``got`` (the program's answer) says what ``want`` (the reference's
    normalized map) says: every sub-verdict, key for key.  A top-level
    ``valid?`` beside the sub-verdicts (the sidecar's) must be their
    conjunction."""
    if not isinstance(got, dict):
        return False
    got = norm(got)
    extra = set(got) - set(want)
    if extra - {"valid?"}:
        return False
    if "valid?" in extra and got["valid?"] is not valid(want):
        return False
    for k, w in want.items():
        g = got.get(k)
        if not isinstance(g, dict):
            return False
        for key in set(g) | set(w):
            if key in LABELS and key not in g:
                continue
            if key not in g or key not in w or g[key] != w[key]:
                return False
    return True


def run(job: tuple) -> Any:
    """A pool worker's job: ``(family, opts, path, reduce)`` -> the
    normalized verdict map of the history in that JSONL file, or with
    ``reduce`` only its validity."""
    family, opts, path, only_valid = job
    with open(path) as fh:
        result = check(family, parse(fh.read()), opts)
    return valid(result) if only_valid else norm(result)
