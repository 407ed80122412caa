"""The stream family: single-partition stream append / read histories.

Generator: a copy of ``synth_stream_history``
(``jepsen_tpu/history/synth.py``), drawing from ``random.Random`` in
exactly the program's order.  Only the injections the configurations use
are copied (``INJECT``).

Reference: ``{"stream": single-partition log linearizability}``, the
definitions in ``checkers/stream_lin.py`` (divergent offsets, duplicated
values, phantoms, real-time reorder by suffix-min over offsets,
non-monotonic read batches, and loss once a full read completed),
written straight from them.
"""

from __future__ import annotations

from typing import Any

from gen import _history
from gen import rows_space as shape  # noqa: F401 - what the roofline counts

#: the injections ``ops`` can make
INJECT = ("lost", "duplicated")
#: checker options the program's replay entry (``check_sources``) takes
PROGRAM_OPTS = frozenset({"append_fail"})
#: verdict keys that echo an option; an answer may leave them out
LABELS = frozenset({"append-fail"})

FULL_READ = "full"
_INF = 2**31 - 1
_NEG = -(2**31)


def ops(seed: int, spec: dict, inject: dict) -> list[list]:
    """``synth_stream_history(StreamSynthSpec(**spec, seed=seed, **inject))``."""
    n_prod = spec.get("n_producers", 3)
    n_cons = spec.get("n_consumers", 2)
    p_fail = spec.get("p_app_fail", 0.02)
    p_info = spec.get("p_app_info", 0.03)
    read_batch = spec.get("read_batch", 4)
    rng, ops, tick, lat, emit = _history(seed, spec)
    log: list[int] = []
    acked: list[int] = []
    cursor = {c: 0 for c in range(n_cons)}
    next_value = 0
    for _ in range(spec["n_ops"]):
        p = rng.randrange(n_prod)
        v = next_value
        next_value += 1
        t0 = tick()
        emit("invoke", "append", p, v, t0)
        roll = rng.random()
        if roll < p_fail:
            emit("fail", "append", p, v, t0 + lat(), "publish-failed")
        elif roll < p_fail + p_info:
            emit("info", "append", p, v, t0 + lat(), "timeout")
            if rng.random() < 0.5:
                log.append(v)
        else:
            emit("ok", "append", p, v, t0 + lat())
            log.append(v)
            acked.append(v)
        if n_cons and rng.random() < 0.3:
            c = rng.randrange(n_cons)
            proc = n_prod + c
            lo = cursor[c]
            batch = [[o, log[o]] for o in range(lo, min(lo + read_batch, len(log)))]
            t1 = tick()
            emit("invoke", "read", proc, lo, t1)
            if batch:
                cursor[c] = batch[-1][0] + 1
                emit("ok", "read", proc, batch, t1 + lat())
            else:
                emit("fail", "read", proc, lo, t1 + lat(), "empty")

    acked_set = set(acked)
    hi = max(cursor.values(), default=0)
    mutable = [v for v in log[hi:] if v in acked_set]
    rng.shuffle(mutable)
    for _ in range(inject.get("lost", 0)):
        if not mutable:
            break
        log.remove(mutable.pop())
    for _ in range(inject.get("duplicated", 0)):
        if not mutable:
            break
        log.append(mutable.pop())  # the value lands at a second offset

    if spec.get("full_reads", True):
        for c in range(n_cons or 1):
            proc = n_prod + (c if n_cons else 0)
            t0 = tick()
            emit("invoke", "read", proc, FULL_READ, t0)
            emit("ok", "read", proc, [[o, v] for o, v in enumerate(log)],
                 t0 + lat())
    return ops


def check(ops: list[dict], opts: dict) -> dict:
    """The verdict map under ``opts["append_fail"]`` (and ``duplicates``,
    which only a control sets)."""
    return {"stream": _stream(ops, opts["append_fail"],
                              opts.get("duplicates", "judged"))}


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
