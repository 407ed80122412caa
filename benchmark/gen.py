"""The benchmark's one history generator: seeded, pure Python, no JAX.

A copy of the queue and stream generators of ``jepsen_tpu/history/synth.py``
(``synth_history``, ``synth_stream_history``), kept here so that a change
to the program cannot change the traffic.  It draws from ``random.Random``
in exactly the program's order, so a history of one seed is op for op the
program's (``benchmark/tests/test_gen.py``), and it writes the JSONL form
``Store.save_history`` writes: one ``json.dumps`` object per op with the
keys ``index, type, f, process, time[, value][, error]``.

Only the injections the configurations use are copied (``lost``,
``duplicated``); any other is refused rather than silently ignored.

A configuration's ``campaign`` says how many histories, which share is
clean, and the injections the anomalous ones cycle through.  History
``i`` of a campaign takes seed ``seed + i`` (clean ones first, as
``chip_smoke.py`` numbers them); the campaign's order is then shuffled
from the seed, so that the first invalid history of a pass falls
anywhere.
"""

from __future__ import annotations

import json
import random
from pathlib import Path

FULL_READ = "full"
_INJECT = ("lost", "duplicated")


def _history(seed: int, spec: dict):
    """``(rng, ops, tick, lat, emit)``: the generators' shared clock."""
    rng = random.Random(seed)
    ops: list[list] = []  # [type, f, process, value, time, error]
    clock = [0]
    mean = spec.get("mean_latency_ns", 2_000_000)

    def tick() -> int:
        clock[0] += rng.randint(100_000, 2_000_000)
        return clock[0]

    def lat() -> int:
        return max(1, int(rng.expovariate(1.0 / mean)))

    def emit(type_, f, process, value, time, error=None) -> None:
        ops.append([type_, f, process, value, time, error])

    return rng, ops, tick, lat, emit


def queue_ops(seed: int, spec: dict, inject: dict) -> list[list]:
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


def stream_ops(seed: int, spec: dict, inject: dict) -> list[list]:
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


FAMILIES = {"queue": queue_ops, "stream": stream_ops}


def jsonl(ops: list[list]) -> str:
    """The history as ``Store.save_history`` writes it."""
    out = []
    for i, (type_, f, process, value, time, error) in enumerate(ops):
        d = {"index": i, "type": type_, "f": f, "process": process,
             "time": time}
        if value is not None:
            d["value"] = value
        if error is not None:
            d["error"] = error
        out.append(json.dumps(d))
    return "\n".join(out) + "\n"


def shape(ops: list[list]) -> dict:
    """The history's shape as the roofline counts it: ``ops`` entries;
    ``rows``, one per op and one more per further value a list carries
    (a drain or a read of ``k`` values is ``k`` rows, an empty one 1);
    ``space``, the largest value or offset + 1."""
    rows, space = 0, 0
    for _t, _f, _p, value, _time, _e in ops:
        if isinstance(value, list):
            rows += max(len(value), 1)
            for x in value:
                space = max(space, *x) if isinstance(x, list) else max(space, x)
        else:
            rows += 1
            if isinstance(value, int):
                space = max(space, value)
    return {"ops": len(ops), "rows": rows, "space": space + 1}


def plan(campaign: dict, seed: int, count: int | None = None) -> list[tuple]:
    """``[(history seed, injections)]`` in the order a pass checks them."""
    n = count if count is not None else campaign["histories"]
    n_clean = int(n * campaign.get("clean_share", 0.5))
    mixes = campaign.get("anomalies") or [{}]
    for m in mixes:
        bad = set(m) - set(_INJECT)
        if bad:
            raise ValueError(f"the generator cannot inject {sorted(bad)}")
    out = [(seed + i, {}) for i in range(n_clean)]
    out += [(seed + n_clean + j, mixes[j % len(mixes)])
            for j in range(n - n_clean)]
    random.Random(seed).shuffle(out)
    return out


def make(family: str, spec: dict, hseed: int, inject: dict,
         path: str) -> dict:
    """One history (a pool worker's job), written to ``path``: returns
    the path and the history's shape."""
    ops = FAMILIES[family](hseed, spec, inject)
    Path(path).write_text(jsonl(ops))
    return {"path": path, **shape(ops)}
