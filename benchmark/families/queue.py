"""The queue family: quorum-queue enqueue / dequeue / drain histories.

Generator: a copy of ``synth_history`` (``jepsen_tpu/history/synth.py``),
drawing from ``random.Random`` in exactly the program's order.  Only the
injections the configurations use are copied (``INJECT``).

Reference: ``{"queue": total-queue, "linear": queue linearizability}``,
the definitions in ``checkers/total_queue.py`` and
``checkers/queue_lin.py`` (per-value multiset reconciliation, and the
per-value duplicate / phantom / causality rule over history positions),
written straight from them.
"""

from __future__ import annotations

from gen import _history
from gen import rows_space as shape  # noqa: F401 - what the roofline counts

#: the injections ``ops`` can make
INJECT = ("lost", "duplicated")
#: checker options the program's replay entry (``check_sources``) takes
PROGRAM_OPTS = frozenset({"delivery"})
#: verdict keys that echo an option; an answer may leave them out
LABELS = frozenset({"delivery"})


def ops(seed: int, spec: dict, inject: dict) -> list[list]:
    """``synth_history(SynthSpec(**spec, seed=seed, **inject))``."""
    n_proc = spec.get("n_processes", 5)
    p_enq = spec.get("p_enqueue", 0.5)
    p_info = spec.get("p_enq_info", 0.03)
    p_fail = spec.get("p_enq_fail", 0.02)
    p_deq_fail = spec.get("p_deq_fail", 0.05)
    rng, ops, tick, lat, emit = _history(seed, spec)
    next_value = 0
    queue: list[int] = []
    acked: list[int] = []
    failed_enq: list[int] = []
    for _ in range(spec["n_ops"]):
        p = rng.randrange(n_proc)
        t0 = tick()
        if rng.random() < p_enq:
            v = next_value
            next_value += 1
            emit("invoke", "enqueue", p, v, t0)
            roll = rng.random()
            if roll < p_fail:
                emit("fail", "enqueue", p, v, t0 + lat(), "publish-failed")
                failed_enq.append(v)
            elif roll < p_fail + p_info:
                emit("info", "enqueue", p, v, t0 + lat(), "timeout")
                if rng.random() < 0.5:
                    queue.append(v)
            else:
                emit("ok", "enqueue", p, v, t0 + lat())
                queue.append(v)
                acked.append(v)
        else:
            emit("invoke", "dequeue", p, None, t0)
            if queue and rng.random() >= p_deq_fail:
                v = queue.pop(rng.randrange(len(queue)))
                emit("ok", "dequeue", p, v, t0 + lat())
            else:
                emit("fail", "dequeue", p, None, t0 + lat(), "exhausted")

    acked_set = set(acked)
    in_queue_acked = [v for v in queue if v in acked_set]
    rng.shuffle(in_queue_acked)
    for _ in range(inject.get("lost", 0)):
        if not in_queue_acked:
            break
        queue.remove(in_queue_acked.pop())
    delivered = [o[3] for o in ops if o[1] == "dequeue" and o[0] == "ok"]
    rng.shuffle(delivered)
    for _ in range(inject.get("duplicated", 0)):
        if not delivered:
            break
        queue.append(delivered.pop())  # the broker delivers it again
    rng.shuffle(failed_enq)  # the program draws here for phantom_fail

    rng.shuffle(queue)  # per-process drain
    per: dict[int, list[int]] = {p: [] for p in range(n_proc)}
    for i, v in enumerate(queue):
        per[i % n_proc].append(v)
    for p in range(n_proc):
        t0 = tick()
        emit("invoke", "drain", p, None, t0)
        emit("ok", "drain", p, per[p], t0 + lat())
    return ops


def check(ops: list[dict], opts: dict) -> dict:
    """The verdict map under ``opts["delivery"]``."""
    return {"queue": _total_queue(ops),
            "linear": _queue_lin(ops, opts["delivery"])}


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
