"""Command-line interface.

Mirrors the surface the reference gets from ``jepsen.cli``
(``rabbitmq.clj:329-334``): subcommand dispatch, merged opt specs, and a
run/analysis lifecycle whose console output the CI triage greps —
``Analysis invalid`` marks a genuine consistency violation
(``ci/jepsen-test.sh:180-184``), and a valid run prints the reference's
"Everything looks good!" banner (``README.md:55``).

Subcommands:

- ``test``        — run a partition test for any of the four workload
                    families (all the reference's flags; ``--db sim`` for
                    the in-process cluster, ``--db local`` for the full
                    rabbitmq assembly over local broker OS processes,
                    ``--db rabbitmq`` for a real cluster over SSH).
- ``check``       — re-check a recorded history (``--checker tpu|cpu``);
                    the ``--checker`` dispatch point is the north-star seam.
- ``bench-check`` — batched replay: verify many stored/synthetic histories
                    at once on the device mesh, report histories/sec.
- ``synth``       — generate synthetic histories (with injectable
                    anomalies) into a store, for demos and differential
                    testing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

# JAX (and the jax-importing checker modules) are imported lazily, inside
# the subcommands that need them: subcommands that never touch a device
# (``synth``, ``serve``, ``matrix --print-configs``) must not initialize a
# JAX backend at all — a chip belongs to one process at a time, and e.g.
# the CI matrix introspection path is spawned as a subprocess by shell
# tooling that may run beside a process holding the chip.

from jepsen_tpu.checkers.protocol import UNKNOWN
from jepsen_tpu.history.store import (
    HISTORY_FILE,
    Store,
    read_history,
    read_history_jsonl,
    save_results,
    _json_default,
)

GOOD_BANNER = "Everything looks good! ヽ('ー`)ノ"
INVALID_BANNER = "Analysis invalid! ಠ~ಠ"
UNKNOWN_BANNER = "Analysis result unknown ¯\\_(ツ)_/¯"


def _verdict_exit(verdict) -> int:
    """jepsen tri-state → banner + exit code.

    0 = valid, 1 = invalid (genuine violation), 3 = analysis undecided
    ("unknown", e.g. a capped search).  2 stays the usage/environment
    error code (missing history, bad config) so CI shells can tell an
    undecided analysis from a broken run."""
    if verdict is True:
        print(GOOD_BANNER)
        return 0
    if verdict == UNKNOWN:
        print(UNKNOWN_BANNER)
        return 3
    print(INVALID_BANNER)
    return 1


def _stamp_device(args, result: dict) -> dict:
    """Name the device a tensor-checker result ran on, so a CPU run can
    never pass for a chip run (``--checker tpu`` on a CPU-only host runs
    the tensor checker on the CPU, and says so here)."""
    if getattr(args, "checker", None) == "tpu":
        from jepsen_tpu.utils.jaxenv import device_info

        result["device"] = device_info()
    return result


def _resolve_history_path(path: Path) -> Path:
    """Accept a history file (JSONL or jepsen EDN), a run dir, or a
    store root (→ latest run)."""
    if path.is_file():
        return path
    for name in (HISTORY_FILE, "history.edn"):
        if (path / name).is_file():
            return path / name
        latest = path / "latest"
        if latest.exists() and (latest / name).is_file():
            return (latest / name).resolve()
    raise FileNotFoundError(f"no {HISTORY_FILE} (or history.edn) under {path}")


def _workload_of(history) -> str:
    from jepsen_tpu.history.ops import workload_of

    return workload_of(history)


def _history_paths(root: str) -> list:
    """Every stored history under ``root`` — ``history.jsonl`` plus EDN
    files that are not just an exported twin of a JSONL in the same run
    dir (the same run must not load twice)."""
    from jepsen_tpu.history.store import EDN_FILE

    return sorted(Path(root).glob(f"**/{HISTORY_FILE}")) + [
        p
        for p in sorted(Path(root).glob(f"**/{EDN_FILE}"))
        if not (p.parent / HISTORY_FILE).exists()
    ]


def _pipelined_checkers(args, workload: str, hpath) -> dict | None:
    """Family checkers routed through the bytes-to-verdict pipeline
    executor (``parallel/pipeline.py``): cache-first native substrate,
    device check — instead of re-packing the already-parsed Op objects.
    Only for the pipelined families on the tpu backend with a real
    history file; ``--serial`` is the triage escape hatch.  One shared
    run serves every sub-checker of the family (queue surfaces as two
    result keys)."""
    if (
        args.checker != "tpu"
        or getattr(args, "serial", False)
        or hpath is None
        or workload not in ("queue", "stream", "elle", "mutex")
    ):
        return None
    from jepsen_tpu.parallel.pipeline import PipelinedChecker

    shared: dict = {}
    if workload == "mutex":
        if getattr(args, "no_pcomp", False):
            return None  # --no-pcomp: the monolithic MutexWgl path
        return {
            "mutex": PipelinedChecker("mutex", hpath, "mutex", shared=shared)
        }
    if workload == "queue":
        opts = {"delivery": getattr(args, "delivery", None) or "exactly-once"}
        return {
            sub: PipelinedChecker(
                "queue", hpath, sub, shared=shared, **opts
            )
            for sub in ("queue", "linear")
        }
    if workload == "stream":
        opts = {
            "append_fail": getattr(args, "append_fail", None) or "definite"
        }
        return {
            "stream": PipelinedChecker(
                "stream", hpath, "stream", shared=shared, **opts
            )
        }
    opts = {
        "model": getattr(args, "consistency_model", None) or "serializable"
    }
    return {
        "elle": PipelinedChecker("elle", hpath, "elle", shared=shared, **opts)
    }


def _checker_for(args, out_dir=None, history=None, hpath=None):
    from jepsen_tpu.checkers.perf import Perf
    from jepsen_tpu.checkers.protocol import compose
    from jepsen_tpu.checkers.queue_lin import QueueLinearizability
    from jepsen_tpu.checkers.total_queue import TotalQueue

    backend = args.checker
    workload = getattr(args, "workload", "auto")
    if workload == "auto":
        workload = _workload_of(history) if history is not None else "queue"
    pipelined = _pipelined_checkers(args, workload, hpath)
    if pipelined is not None:
        checkers = {"perf": Perf(out_dir=out_dir), **pipelined}
        if workload == "queue" and getattr(args, "wgl", False):
            from jepsen_tpu.checkers.wgl import QueueWgl

            checkers["wgl"] = QueueWgl(
                backend=backend,
                pcomp=not getattr(args, "no_pcomp", False),
            )
        return compose(checkers)
    if workload == "stream":
        from jepsen_tpu.checkers.stream_lin import StreamLinearizability

        return compose(
            {
                "perf": Perf(out_dir=out_dir),
                "stream": StreamLinearizability(
                    backend=backend,
                    append_fail=getattr(args, "append_fail", None)
                    or "definite",
                ),
            }
        )
    if workload == "elle":
        from jepsen_tpu.checkers.elle import ElleListAppend

        return compose(
            {
                "perf": Perf(out_dir=out_dir),
                "elle": ElleListAppend(
                    backend=backend,
                    model=getattr(args, "consistency_model", None)
                    or "serializable",
                ),
            }
        )
    if workload == "mutex":
        from jepsen_tpu.checkers.wgl import MutexWgl

        return compose(
            {
                "perf": Perf(out_dir=out_dir),
                "mutex": MutexWgl(
                    backend=backend,
                    pcomp=not getattr(args, "no_pcomp", False),
                ),
            }
        )
    checkers = {
        "perf": Perf(out_dir=out_dir),
        "queue": TotalQueue(backend=backend),
        "linear": QueueLinearizability(
            backend=backend,
            delivery=getattr(args, "delivery", None) or "exactly-once",
        ),
    }
    if getattr(args, "wgl", False):
        from jepsen_tpu.checkers.wgl import QueueWgl

        checkers["wgl"] = QueueWgl(
            backend=backend, pcomp=not getattr(args, "no_pcomp", False)
        )
    return compose(checkers)


def _cmd_check_procs(args, paths, workload: str, prev: dict) -> int:
    """``check --procs N`` over SEVERAL stored histories: the
    multi-process checker harness (``parallel/distributed.py``) — N
    worker processes (CPU workers: a local chip is exclusive to one
    process, so the host cores are the multi-process resource),
    deterministic size-striped file assignment, per-process multi-lane
    pipelines, one merged verdict set.  Elastic by default: dead
    workers degrade the run (requeue + quarantine + provenance)
    instead of aborting it; ``--fail-fast`` restores the loud
    no-partial-verdicts abort verbatim."""
    import os as _os

    from jepsen_tpu.checkers.protocol import VALID, merge_valid
    from jepsen_tpu.parallel.distributed import run_multiprocess_check

    opts: dict = {}
    if workload == "queue":
        opts["delivery"] = (
            getattr(args, "delivery", None)
            or prev.get("linear", {}).get("delivery")
            or "exactly-once"
        )
    elif workload == "stream":
        opts["append_fail"] = (
            getattr(args, "append_fail", None)
            or prev.get("stream", {}).get("append-fail")
            or "definite"
        )
    elif workload == "elle":
        opts["model"] = (
            getattr(args, "consistency_model", None)
            or prev.get("elle", {}).get("consistency-model")
            or "serializable"
        )
    avail = len(_os.sched_getaffinity(0))
    if getattr(args, "global_mesh", False):
        # one jax.distributed fleet, one global (hist, seq) mesh, the
        # shard_map verdict programs with cross-host collectives; the
        # verdict arrives reduced to two scalars (PIPELINE.md §Global
        # mesh) rather than per-history result sets
        t0 = time.perf_counter()
        verdict, info = run_multiprocess_check(
            workload,
            paths,
            args.procs,
            devices_per_proc=max(1, avail // args.procs),
            reduce=True,
            global_mesh=True,
            seq=max(1, getattr(args, "gm_seq", 1) or 1),
            **opts,
        )
        dt = time.perf_counter() - t0
        from jepsen_tpu.parallel.distributed import degraded_active

        deg = info.get("degraded")
        doc = {
            "valid?": verdict["invalid"] == 0
            and verdict["quarantined"] == 0,
            "verdict": verdict,
            "global_mesh": {
                "procs": info["n_procs"],
                "devices_per_proc": info["devices_per_proc"],
                "seq": info["seq"],
            },
        }
        if degraded_active(deg):
            doc["degraded"] = deg
            print(
                f"# DEGRADED check: {len(deg['dead_workers'])} dead "
                f"worker(s), {len(deg['requeued_stripes'])} requeued "
                f"stripe(s), {deg['quarantined_histories']} quarantined "
                "histories",
                file=sys.stderr,
            )
        print(json.dumps(doc, indent=1, default=_json_default))
        print(
            f"# checked {verdict['histories']} histories on one global "
            f"mesh ({info['n_procs']} processes x "
            f"{info['devices_per_proc']} devices, seq={info['seq']}) "
            f"in {dt:.2f} s",
            file=sys.stderr,
        )
        return _verdict_exit(doc["valid?"])
    t0 = time.perf_counter()
    results, info = run_multiprocess_check(
        workload,
        paths,
        args.procs,
        devices_per_proc=max(1, avail // args.procs),
        mesh=True,
        fail_fast=getattr(args, "fail_fast", False),
        **opts,
    )
    dt = time.perf_counter() - t0
    from jepsen_tpu.parallel.distributed import degraded_active

    degraded = info.get("degraded")
    if not degraded_active(degraded):
        degraded = None
    if degraded is not None:
        # the per-history copy stays machine-readable but drops each
        # dead worker's log tail — replicating the same multi-KB text
        # into every history's results.json adds nothing the pid/rc
        # fields don't already identify
        degraded = dict(degraded)
        degraded["dead_workers"] = [
            {k: v for k, v in d.items() if k != "log_tail"}
            for d in degraded.get("dead_workers", ())
        ]
    composed = []
    for p, row in zip(paths, results):
        result = dict(row)
        result[VALID] = merge_valid(
            r.get(VALID, False)
            for r in result.values()
            if isinstance(r, dict)
        )
        if degraded is not None:
            # machine-readable batch provenance beside the verdict: the
            # report's degraded row and any later triage read it from
            # results.json (attached AFTER the merge — it carries no
            # "valid?" and must never vote)
            result["degraded"] = degraded
        save_results(Path(p).parent, result)
        composed.append(result)
    if degraded is not None:
        print(
            f"# DEGRADED check: {len(degraded['dead_workers'])} dead "
            f"worker(s), {len(degraded['requeued_stripes'])} requeued "
            f"stripe(s), {degraded['quarantined_histories']} quarantined "
            f"histories (verdicts at those positions are explicit "
            f"unknowns; provenance saved in results.json)",
            file=sys.stderr,
        )
    if getattr(args, "report", False):
        # per-run artifacts for the whole tree; `jepsen-tpu report`
        # builds the cross-run index over the same pages
        from jepsen_tpu.report.render import render_run_report

        for p in paths:
            try:
                render_run_report(Path(p).parent)
            except Exception as e:  # noqa: BLE001 — verdicts already saved
                print(f"# report rendering failed for {p}: {e}",
                      file=sys.stderr)
    if len(composed) == 1:
        print(json.dumps(composed[0], indent=1, default=_json_default))
    else:
        print(
            json.dumps(
                [
                    {"history": str(p), "valid?": r[VALID]}
                    for p, r in zip(paths, composed)
                ],
                indent=1,
                default=_json_default,
            )
        )
    print(
        f"# checked {len(paths)} histories through {info['n_procs']} "
        f"processes in {dt:.2f} s",
        file=sys.stderr,
    )
    return _verdict_exit(merge_valid(r[VALID] for r in composed))


def cmd_check(args) -> int:
    from jepsen_tpu.checkers.protocol import VALID

    hpath = _resolve_history_path(Path(args.history)).resolve()
    out_dir = hpath.parent
    # inherit the contract levels the run was judged at: a live run is
    # valid at its SUT's contractual level (read-committed for AMQP tx;
    # at-least-once delivery for the queue), and a bare re-check must not
    # silently tighten the verdict
    try:
        prev = json.loads((out_dir / "results.json").read_text())
    except (OSError, ValueError):
        prev = {}
    if getattr(args, "consistency_model", None) is None:
        args.consistency_model = prev.get("elle", {}).get(
            "consistency-model"
        )
    if getattr(args, "delivery", None) is None:
        args.delivery = prev.get("linear", {}).get("delivery")
    if getattr(args, "append_fail", None) is None:
        args.append_fail = prev.get("stream", {}).get("append-fail")
    if getattr(args, "segment_ops", None):
        # the segmented engine streams the file — the whole-history
        # parse below is exactly what bounded memory must avoid
        return _cmd_check_segmented(args, hpath, out_dir)
    history = read_history(hpath)
    if getattr(args, "procs", 0) and args.procs > 1:
        workload = getattr(args, "workload", "auto")
        if workload == "auto":
            workload = _workload_of(history)
        if workload not in ("queue", "stream", "elle"):
            print(
                f"# --procs applies to the pipelined families "
                f"(queue/stream/elle); {workload} runs in-process",
                file=sys.stderr,
            )
        else:
            root = Path(args.history)
            paths = (
                _history_paths(str(root)) if root.is_dir() else [hpath]
            )
            if len(paths) > 1:
                # every history in the tree, checked as one family
                # (the resolved history's) — a mixed-family store
                # should use bench-check --pipeline per family
                return _cmd_check_procs(args, paths, workload, prev)
            print(
                "# --procs: a single history gives the worker fleet "
                "nothing to divide — running in-process (point --procs "
                "at a store tree to fan N histories across processes)",
                file=sys.stderr,
            )
    checker = _checker_for(args, out_dir=out_dir, history=history, hpath=hpath)
    log_pat = getattr(args, "log_file_pattern", None) or prev.get(
        "log-file-pattern", {}
    ).get("pattern")
    if log_pat:
        # same no-silent-loosening rule as the levels above: a run the
        # log scan invalidated must not re-check back to valid just
        # because the bare re-check forgot the pattern
        from jepsen_tpu.checkers.logpattern import LogFilePattern

        checker.checkers["log-file-pattern"] = LogFilePattern(
            log_pat, out_dir=str(out_dir)
        )
    t0 = time.perf_counter()
    result = _stamp_device(args, checker.check({}, history))
    dt = time.perf_counter() - t0
    print(json.dumps(result, indent=1, default=_json_default))
    print(
        f"# checked {len(history)} ops with backend={args.checker} "
        f"in {dt * 1e3:.1f} ms",
        file=sys.stderr,
    )
    save_results(out_dir, result)
    if getattr(args, "report", False):
        from jepsen_tpu.report.render import render_run_report

        paths = render_run_report(out_dir, history=history, results=result)
        print(
            "# report: " + " ".join(sorted(paths.values())),
            file=sys.stderr,
        )
    return _verdict_exit(result[VALID])


def _cmd_check_segmented(args, hpath: Path, out_dir: Path) -> int:
    """``check --segment-ops N [--resume]``: stream the history through
    the segmented carry engine (``checkers/segmented.py``) — bounded
    memory in history length, a durable checkpoint after every
    segment, verdicts equal to the monolithic engine wherever both can
    run (SEGMENTED.md)."""
    from jepsen_tpu.checkers.protocol import VALID
    from jepsen_tpu.obs.metrics import REGISTRY
    from jepsen_tpu.parallel.pipeline import check_source_segmented

    workload = getattr(args, "workload", "auto")
    opts: dict = {}
    if getattr(args, "delivery", None):
        opts["delivery"] = args.delivery
    if getattr(args, "append_fail", None):
        opts["append_fail"] = args.append_fail
    if getattr(args, "consistency_model", None):
        opts["model"] = args.consistency_model
    t0 = time.perf_counter()
    result, stats = check_source_segmented(
        None if workload == "auto" else workload,
        hpath,
        segment_ops=args.segment_ops,
        resume=getattr(args, "resume", False),
        carry_cap=getattr(args, "carry_cap", None),
        device=args.checker == "tpu",
        prefix_index=getattr(args, "prefix_index", None),
        **opts,
    )
    dt = time.perf_counter() - t0
    _stamp_device(args, result)
    print(json.dumps(result, indent=1, default=_json_default))
    meta = result["segmented"]
    sk = REGISTRY.sketch("segmented.segment_check_s")
    resumed = (
        f", resumed from segment {meta['resumed_from']}"
        if meta.get("resumed")
        else ""
    )
    pfx = meta.get("resumed_from_prefix")
    if pfx:
        print(
            f"# fleet memory: resumed from prefix anchor @ segment "
            f"{pfx['segment_idx']} (offset {pfx['offset']}, "
            f"{pfx['substrate']})",
            file=sys.stderr,
        )
    print(
        f"# segmented check: {meta['ops']} ops in {meta['segments']} "
        f"segments of {meta['segment_ops']} in {dt:.2f} s "
        f"(segment p50 {sk.quantile(0.5) * 1e3:.1f} ms / "
        f"p99 {sk.quantile(0.99) * 1e3:.1f} ms{resumed})",
        file=sys.stderr,
    )
    if meta.get("quarantined-segments"):
        print(
            f"# QUARANTINED: {meta['quarantined-segments']} poisoned "
            f"segment(s) — verdict capped at unknown with evidence",
            file=sys.stderr,
        )
    save_results(out_dir, result)
    return _verdict_exit(result[VALID])


def _valid_regex(s: str) -> str:
    """argparse type for user-supplied patterns: a clean usage error
    instead of a raw re.error traceback mid-run."""
    import re as _re

    try:
        _re.compile(s)
    except _re.error as e:
        raise argparse.ArgumentTypeError(f"invalid regex {s!r}: {e}")
    return s


def _parse_bool_flag(s: str) -> bool:
    import argparse as _argparse

    v = s.strip().lower()
    if v in ("true", "1", "yes"):
        return True
    if v in ("false", "0", "no"):
        return False
    raise _argparse.ArgumentTypeError(
        f"expected true/false, got {s!r}"
    )


def _select_family(pairs, workload: str, src: str):
    """Filter ``(kind, item)`` pairs to one family, with the mixed-store
    note; None (after an error message) when nothing remains.  One
    implementation for the worker/serial/queue/non-queue paths so the
    skip message and exit contract cannot drift apart."""
    keep = [item for kind, item in pairs if kind == workload]
    if len(keep) != len(pairs):
        print(
            f"# mixed store: benching {len(keep)} {workload} histories, "
            f"skipping {len(pairs) - len(keep)} of other families",
            file=sys.stderr,
        )
    if not keep:
        print(f"no {workload} histories under {src}", file=sys.stderr)
        return None
    return keep


def _cmd_bench_check_pipeline(args) -> int:
    """``bench-check --pipeline``: bytes-to-verdict over a stored history
    tree through the overlapped executor (``parallel/pipeline.py``) —
    native thread-pool packing on the producer thread, async H2D
    staging, device checking — with the executor's utilization evidence
    in the output JSON.  ``--serial`` runs the identical stages strictly
    serially (the triage twin: byte-identical results, no overlap)."""
    import jax

    from jepsen_tpu.parallel.pipeline import check_sources
    from jepsen_tpu.utils.jaxenv import device_info

    paths = _history_paths(args.histories)
    if not paths:
        print(f"no histories under {args.histories}", file=sys.stderr)
        return 2
    # classify each file (native tag, cache, or parse) — same majority
    # rule on auto as the serial path
    from jepsen_tpu.history.fastpack import pack_file as _fastpack
    from jepsen_tpu.history.rows import load_rows_cache, save_rows_cache

    kinds = []
    for p in paths:
        got = load_rows_cache(p)
        if got is not None:
            kinds.append(got[0])
            continue
        fast = _fastpack(p)
        if fast is not None:
            save_rows_cache(p, fast[0], fast[1])
            kinds.append(fast[0])
        else:
            kinds.append(_workload_of(read_history(p)))
    workload = getattr(args, "workload", "auto")
    if workload == "auto":
        workload = max(sorted(set(kinds)), key=kinds.count)
    if workload == "mutex" and getattr(args, "engine", "pcomp") != "pcomp":
        # an explicit --engine classic/tensor must be HONORED, not
        # silently swapped for pcomp (the engine field exists so
        # classic-vs-tensor-vs-pcomp numbers can never be conflated) —
        # those engines run through the standard batched path
        print(
            f"# --pipeline runs the mutex family's pcomp engine; "
            f"--engine {args.engine} requested — running the standard "
            f"path instead",
            file=sys.stderr,
        )
        return cmd_bench_check(args, _pipeline=False)
    keep = _select_family(list(zip(kinds, paths)), workload, args.histories)
    if keep is None:
        return 2
    opts: dict = {}
    if workload == "mutex" and getattr(args, "reduce", False):
        print(
            "error: the mutex family has no reduce mode (its device "
            "batch axis is the sub-history axis, not the history axis)",
            file=sys.stderr,
        )
        return 2
    if workload == "queue":
        opts["delivery"] = getattr(args, "delivery", None) or "exactly-once"
    elif workload == "stream":
        opts["append_fail"] = (
            getattr(args, "append_fail", None) or "definite"
        )
    elif workload == "elle":
        opts["model"] = (
            getattr(args, "consistency_model", None) or "serializable"
        )
    if getattr(args, "mesh", False):
        from jepsen_tpu.parallel.mesh import checker_mesh

        opts["mesh"] = checker_mesh()
    reduce = getattr(args, "reduce", False)
    if reduce and "mesh" not in opts:
        print(
            "error: --reduce needs --mesh (the collective reduction "
            "runs on the device mesh)",
            file=sys.stderr,
        )
        return 2
    results, stats = check_sources(
        workload,
        keep,
        chunk=getattr(args, "chunk", None) or 64,
        serial=getattr(args, "serial", False),
        lanes=getattr(args, "lanes", None),
        reduce=reduce,
        fail_fast=getattr(args, "fail_fast", False),
        **opts,
    )
    if reduce:
        n_invalid = results["invalid"]
        extra = {
            "reduce": True,
            "first_invalid": results["first_invalid"],
        }
    else:
        extra = {}
        if workload == "queue":
            n_invalid = sum(
                1
                for r in results
                if not (
                    r["queue"]["valid?"] is True
                    and r["linear"]["valid?"] is True
                )
            )
        else:
            key = workload  # stream / elle / mutex: one sub-verdict key
            n_invalid = sum(
                1 for r in results if r[key]["valid?"] is not True
            )
    print(
        json.dumps(
            {
                "histories": stats.histories,
                "batches": stats.batches,
                "mode": "serial" if getattr(args, "serial", False)
                else "pipeline",
                "lanes": stats.lanes,
                "dropped": stats.dropped,
                **extra,
                "wall_s": round(stats.wall_s, 3),
                "pipeline_e2e_histories_per_sec": round(
                    stats.histories / max(stats.wall_s, 1e-9), 1
                ),
                "stage_overlap_frac": round(stats.stage_overlap_frac, 3),
                "device_idle_frac": round(stats.device_idle_frac, 3),
                "invalid": n_invalid,
                "quarantined": stats.quarantined,
                "backend": jax.default_backend(),
                "device": device_info(),
            }
        )
    )
    return 0


def cmd_bench_check(args, _pipeline: bool | None = None) -> int:
    if _pipeline is None:
        _pipeline = getattr(args, "pipeline", False)
    if _pipeline and args.histories:
        return _cmd_bench_check_pipeline(args)
    from jepsen_tpu.checkers.queue_lin import queue_lin_tensor_check
    from jepsen_tpu.checkers.total_queue import total_queue_tensor_check
    from jepsen_tpu.history.encode import pack_histories, pack_row_matrices
    from jepsen_tpu.utils.jaxenv import device_info
    import jax

    workload = getattr(args, "workload", "auto")
    workers = getattr(args, "workers", 0)
    if workers < 0:
        print(f"error: --workers must be >= 0, got {workers}", file=sys.stderr)
        return 2
    if workers:
        import os as _os

        avail = len(_os.sched_getaffinity(0))
        if workers > avail:
            # on a core-starved host extra workers are pure spawn/pickle
            # overhead (measured 120 s vs 68 s serial on a 1-core box)
            print(
                f"# --workers {workers} capped to {avail} available "
                f"core(s){' — running serially' if avail <= 1 else ''}",
                file=sys.stderr,
            )
            workers = avail if avail > 1 else 0
    mats = None  # pre-exploded row matrices from parallel pack workers
    t_produce = None  # worker phase wall clock (reported as produce_s)
    packed_pre = None  # store-level packed cache hit (no assembly at all)
    store_cache_dst = None  # (root, paths) to save after a fresh pack
    pre_paths = None  # one store walk, reused by every branch below
    elle_mops = None  # (src, cell matrix, meta) triples (device inference)
    stream_mats = None  # native-exploded stream columns (file path)
    if args.histories and workload in ("auto", "queue"):
        # store-level packed cache: one file holding the ASSEMBLED
        # columns for the exact (stat-stamped) file set — a hit skips
        # per-file cache reads AND assembly (history/storecache.py)
        from jepsen_tpu.history.storecache import load_packed_store_cache

        pre_paths = _history_paths(args.histories)
        if pre_paths:
            t0 = time.perf_counter()
            packed_pre = load_packed_store_cache(args.histories, pre_paths)
            if packed_pre is not None:
                workload = "queue"
                print(
                    f"# store cache hit: {packed_pre.batch} packed "
                    f"histories in {time.perf_counter() - t0:.2f}s "
                    f"(no per-file reads, no assembly)",
                    file=sys.stderr,
                )
    if packed_pre is not None:
        pass  # nothing to produce
    elif workers and workload in ("auto", "queue") and not args.histories:
        workload = "queue"  # the synthetic default family
        # parallel host packing (the north-star wall clock): workers
        # synthesize their seed ranges and explode rows; only compact
        # row matrices cross the process boundary.  Queue-family only —
        # the other families' packers are already sub-dominant.
        from jepsen_tpu.history.parpack import synth_queue_rows_parallel

        t0 = time.perf_counter()
        mats = synth_queue_rows_parallel(
            args.count, args.ops, lost=1, workers=workers
        )
        t_produce = time.perf_counter() - t0
        print(
            f"# {workers} workers synthesized+exploded {len(mats)} "
            f"histories in {t_produce:.1f}s",
            file=sys.stderr,
        )
    elif workers and args.histories and workload in ("auto", "queue"):
        from jepsen_tpu.history.parpack import read_rows_parallel

        paths = (
            pre_paths
            if pre_paths is not None
            else _history_paths(args.histories)
        )
        if not paths:
            print(f"no histories under {args.histories}", file=sys.stderr)
            return 2
        t0 = time.perf_counter()
        tagged = read_rows_parallel(paths, workers)
        t_produce = time.perf_counter() - t0
        if workload == "auto":
            # the workers already classified each history — resolve auto
            # from their tags instead of silently dropping to the serial
            # path (advisor r3 #3); same majority rule as the serial path
            kinds = [kind for kind, _m in tagged]
            workload = max(sorted(set(kinds)), key=kinds.count)
        if workload == "queue":
            # the same family filter the serial path applies — a mixed
            # store must not have its other families checked as queue
            mats = _select_family(tagged, workload, args.histories)
            if mats is None:
                return 2
            if len(mats) == len(paths):
                # same pure-queue condition as the serial path: a first
                # check with --workers must also leave the store-level
                # packed cache behind
                store_cache_dst = (args.histories, paths)
            print(
                f"# {workers} workers read+exploded {len(tagged)} stored "
                f"histories in {t_produce:.1f}s",
                file=sys.stderr,
            )
        else:
            print(
                f"# stored histories are {workload}; --workers applies "
                f"to the queue family only — running serially",
                file=sys.stderr,
            )
    elif workers:
        print(
            f"# --workers applies to the queue workload only; running "
            f"{workload} serially",
            file=sys.stderr,
        )
    if mats is not None or packed_pre is not None:
        pass  # skip serial production entirely
    elif args.histories:
        from jepsen_tpu.history.rows import load_rows_cache, rows_with_cache

        paths = (
            pre_paths
            if pre_paths is not None
            else _history_paths(args.histories)
        )
        if not paths:
            print(f"no histories under {args.histories}", file=sys.stderr)
            return 2
        # packed-row store cache (VERDICT r3 #3; PR 7 backing): the
        # loader consults each history's `.jtc` columnar substrate
        # first (mmap'd column blocks, zero parse — COLUMNAR.md), then
        # the legacy rows.npz for pre-format stores, read ONCE per
        # file; files without a fresh cache are parsed once and the
        # ops reused (queue misses reuse them for the explode,
        # non-queue families pack from them).
        from jepsen_tpu.history.fastpack import pack_file as _fastpack
        from jepsen_tpu.history.rows import save_rows_cache

        t0 = time.perf_counter()
        kinds, parsed, rowcache = [], {}, {}
        n_fast = 0
        for p in paths:
            got = load_rows_cache(p)
            if got is not None:
                kinds.append(got[0])
                rowcache[p] = got[1]
                continue
            fast = _fastpack(p)  # native parse+classify+explode
            if fast is not None and fast[0] == "queue":
                kind, rows = fast
                save_rows_cache(p, kind, rows)  # first check cuts the cache
                kinds.append(kind)
                rowcache[p] = rows
                n_fast += 1
            elif fast is not None:
                # non-queue family: the native pass classified it; the
                # family-specific native substrates (elle_graph_file /
                # stream_rows_file) build the checker inputs below
                # without ever materializing Python Op objects.  Persist
                # the rows cache so re-checks classify from it instead
                # of re-parsing (the substrate pass is then this store's
                # only native parse)
                save_rows_cache(p, fast[0], fast[1])
                kinds.append(fast[0])
            else:
                parsed[p] = read_history(p)
                kinds.append(_workload_of(parsed[p]))
        # a store may hold several families; bench the majority on auto
        # (sorted → deterministic tie-break, favoring "elle" < "queue"
        # < "stream" alphabetically on equal counts)
        if workload == "auto":
            workload = max(sorted(set(kinds)), key=kinds.count)
        print(
            f"# loaded {len(paths)} stored histories in "
            f"{time.perf_counter() - t0:.1f}s "
            f"({len(rowcache) - n_fast} from the packed-row cache, "
            f"{n_fast} native-packed)",
            file=sys.stderr,
        )
        if workload == "queue":
            tagged = [
                (
                    kind,
                    rowcache.get(p)
                    if p in rowcache
                    else rows_with_cache(p, history=parsed.get(p))[1],
                )
                if kind == workload
                else (kind, None)
                for p, kind in zip(paths, kinds)
            ]
            mats = _select_family(tagged, workload, args.histories)
            if mats is None:
                return 2
            if len(mats) == len(paths):
                # pure-queue store: leave the assembled columns behind so
                # the next re-check skips per-file reads and assembly
                # (a mixed store stays per-file — a cached pack of a
                # subset would be ambiguous under --workload auto)
                store_cache_dst = (args.histories, paths)
        elif workload == "elle":
            # cached / native micro-op cell emission per file
            # (elle_mops.npz -> jt_elle_mops_file -> Python twin): the
            # fresh-pack path never materializes Op objects, a re-check
            # loads cells straight from the digest-keyed cache, and the
            # edge inference itself runs ON DEVICE (checkers/elle.py)
            from jepsen_tpu.history.storecache import elle_mops_with_cache

            n_hit = 0
            triples = []
            for p, kind in zip(paths, kinds):
                if kind != workload:
                    triples.append((kind, None))
                    continue
                mat, meta, hit = elle_mops_with_cache(
                    p, history=parsed.get(p)
                )
                n_hit += hit
                triples.append((kind, (p, mat, meta)))
            elle_mops = _select_family(triples, workload, args.histories)
            if elle_mops is None:
                return 2
            print(
                f"# elle cells: {n_hit} of {len(elle_mops)} histories "
                f"from the packed-cell cache",
                file=sys.stderr,
            )
        elif workload == "stream":
            # digest-cached native row explosion per file
            # (stream_rows.npz -> jt_stream_rows_file -> Python twin):
            # a re-check loads the exploded columns straight from the
            # cache, same scheme as elle_mops.npz (history/storecache)
            from jepsen_tpu.history.storecache import (
                stream_rows_with_cache,
            )

            n_hit = 0

            def _srows(p, hist):
                nonlocal n_hit
                cols, full, hit = stream_rows_with_cache(p, history=hist)
                n_hit += hit
                return cols, full

            pairs = [
                (kind, _srows(p, parsed.get(p)))
                if kind == workload
                else (kind, None)
                for p, kind in zip(paths, kinds)
            ]
            print(
                f"# stream rows: {n_hit} of "
                f"{sum(1 for k in kinds if k == workload)} histories "
                f"from the exploded-row cache",
                file=sys.stderr,
            )
            stream_mats = _select_family(pairs, workload, args.histories)
            if stream_mats is None:
                return 2
        else:
            # the mutex family packs from Op lists
            pairs = [
                (kind, parsed.get(p) or read_history(p))
                if kind == workload
                else (kind, None)
                for p, kind in zip(paths, kinds)
            ]
            histories = _select_family(pairs, workload, args.histories)
            if histories is None:
                return 2
    else:
        if workload == "stream":
            from jepsen_tpu.history.synth import (
                StreamSynthSpec,
                synth_stream_batch,
            )

            histories = [
                sh.ops
                for sh in synth_stream_batch(
                    args.count, StreamSynthSpec(n_ops=args.ops), lost=1
                )
            ]
        elif workload == "elle":
            from jepsen_tpu.history.synth import (
                ElleSynthSpec,
                synth_elle_batch,
            )

            histories = [
                sh.ops
                for sh in synth_elle_batch(
                    args.count,
                    ElleSynthSpec(n_txns=max(args.ops // 2, 8)),
                    g2_cycle=1,
                )
            ]
        elif workload == "mutex":
            from jepsen_tpu.history.synth import (
                MutexSynthSpec,
                synth_mutex_batch,
            )

            histories = [
                sh.ops
                for sh in synth_mutex_batch(
                    args.count,
                    MutexSynthSpec(n_ops=args.ops),
                    double_grant=1,
                )
            ]
        else:
            workload = "queue"
            from jepsen_tpu.history.synth import SynthSpec, synth_batch

            histories = [
                sh.ops
                for sh in synth_batch(
                    args.count, SynthSpec(n_ops=args.ops), lost=1
                )
            ]
        print(f"# generated {len(histories)} synthetic histories", file=sys.stderr)

    if getattr(args, "profile", None):
        # device + host trace of the pack/compile/check phases, viewable
        # in XProf/TensorBoard (the checker's own tracing story — the
        # analog of the reference's gnuplot perf artifacts, SURVEY.md §5)
        jax.profiler.start_trace(args.profile)

    if workload == "stream":
        from jepsen_tpu.checkers.stream_lin import (
            pack_stream_histories,
            pack_stream_rows,
            stream_lin_tensor_check,
        )

        t0 = time.perf_counter()
        packed = (
            pack_stream_rows(stream_mats)
            if stream_mats is not None
            else pack_stream_histories(histories)
        )
        t_pack = time.perf_counter() - t0
        jax.block_until_ready(stream_lin_tensor_check(packed))  # compile
        t1 = time.perf_counter()
        sl = stream_lin_tensor_check(packed)
        jax.block_until_ready(sl)
        t_check = time.perf_counter() - t1
        n_invalid = int((~sl.valid).sum())
    elif workload == "mutex":
        from jepsen_tpu.checkers.wgl import (
            check_wgl_cpu,
            fenced_mutex_wgl_ops,
            mutex_history_is_fenced,
            mutex_wgl_ops,
            pack_wgl_batch,
            wgl_tensor_check,
        )
        from jepsen_tpu.models.core import FencedMutex, OwnedMutex

        t0 = time.perf_counter()
        # per-history model selection, like the standard checker pipeline:
        # fenced histories (token-valued acquires) check token order
        pairs = [
            (fenced_mutex_wgl_ops(h), FencedMutex)
            if mutex_history_is_fenced(h)
            else (mutex_wgl_ops(h), OwnedMutex)
            for h in histories
        ]
        engine = getattr(args, "engine", "pcomp")
        if engine == "pcomp":
            # the default: P-compositional decomposition — every
            # history's per-class sub-histories pool into shape buckets
            # and check as thousands of narrow vmapped frontiers
            # (WGL_BENCH.md round 6: the measured fast path on hard
            # histories, on BOTH backends)
            from jepsen_tpu.checkers.wgl_pcomp import (
                decompose,
                pcomp_tensor_check,
            )

            decomps = [
                decompose(ops, (model, ())) for ops, model in pairs
            ]
            t_pack = time.perf_counter() - t0
            pcomp_tensor_check(decomps)  # compile
            t1 = time.perf_counter()
            ok, unknown, _info = pcomp_tensor_check(decomps)
            n_invalid = int((~ok & ~unknown).sum())
            n_unknown = int(unknown.sum())
            t_check = time.perf_counter() - t1
        elif engine == "tensor":
            # opt-in ONLY: the batched frontier-bitset device search —
            # measured ~650x slower per history than the classic host
            # search on this family (WGL_BENCH.md re-scope); it exists
            # for general-model correctness, not throughput.  One packed
            # batch per model — a compiled program is model-specific.
            by_model: dict = {}
            for ops, model in pairs:
                by_model.setdefault(model, []).append(ops)
            packs = {
                m: pack_wgl_batch(opss) for m, opss in by_model.items()
            }
            t_pack = time.perf_counter() - t0
            for m, packed in packs.items():
                wgl_tensor_check(packed, (m, ()))  # compile
            t1 = time.perf_counter()
            n_invalid = n_unknown = 0
            for m, packed in packs.items():
                ok, unknown = wgl_tensor_check(packed, (m, ()))
                n_invalid += int((~ok & ~unknown).sum())
                n_unknown += int(unknown.sum())
            t_check = time.perf_counter() - t1
        else:
            # the classic Wing-Gong host search — still the fastest
            # single-history engine on easy histories (WGL_BENCH.md)
            t_pack = time.perf_counter() - t0
            t1 = time.perf_counter()
            results = [
                check_wgl_cpu(ops, model()) for ops, model in pairs
            ]
            t_check = time.perf_counter() - t1
            # tri-state: "valid?" is True / False / the truthy string
            # "unknown" (config-cap overflow) — an undecided history is
            # neither passing nor a violation
            n_invalid = sum(1 for r in results if r["valid?"] is False)
            n_unknown = sum(
                1 for r in results if r["valid?"] not in (True, False)
            )
    elif workload == "elle":
        import numpy as np

        from jepsen_tpu.checkers.elle import (
            elle_mops_check,
            elle_mops_for,
            elle_tensor_check,
            infer_txn_graph,
            pack_txn_graphs,
        )

        from jepsen_tpu.checkers.elle import split_elle_mops

        t0 = time.perf_counter()
        if elle_mops is None:  # synthetic histories: pack in-process
            elle_mops = [(h, *elle_mops_for(h)) for h in histories]
        live_ix, packed_mops, degen_ix = split_elle_mops(
            [(m, g) for _, m, g in elle_mops]
        )
        degen = [elle_mops[i] for i in degen_ix]
        degen_batch = None
        if degen:
            # tensor-unrepresentable histories (see elle_mops_for): the
            # host inference twin stays their source of truth
            from jepsen_tpu.history.fastpack import elle_graph_file

            def _graph(src):
                if isinstance(src, list):  # synthetic ops, no file
                    return infer_txn_graph(src)
                g = elle_graph_file(src)
                return g if g is not None else infer_txn_graph(
                    read_history(src)
                )

            degen_batch = pack_txn_graphs(
                [_graph(src) for src, _, _ in degen]
            )
            print(
                f"# {len(degen)} histories fell back to host inference "
                f"(tensor-degenerate)",
                file=sys.stderr,
            )
        t_pack = time.perf_counter() - t0
        if packed_mops is not None:  # compile
            jax.block_until_ready(elle_mops_check(packed_mops))
        if degen_batch is not None:
            jax.block_until_ready(elle_tensor_check(degen_batch))
        t1 = time.perf_counter()
        n_invalid = 0
        if packed_mops is not None:
            el, _ = elle_mops_check(packed_mops)
            jax.block_until_ready(el)
            # ElleTensors.valid folds cycle + device-inferred anomalies
            n_invalid += int((~np.asarray(el.valid)).sum())
        if degen_batch is not None:
            el = elle_tensor_check(degen_batch)
            jax.block_until_ready(el)
            n_invalid += int((~np.asarray(el.valid)).sum())
        t_check = time.perf_counter() - t1
    else:
        t0 = time.perf_counter()
        if packed_pre is not None:
            packed = packed_pre
        else:
            packed = (
                pack_row_matrices(mats)
                if mats is not None
                else pack_histories(histories)
            )
        t_pack = time.perf_counter() - t0
        if packed_pre is None and store_cache_dst is not None:
            from jepsen_tpu.history.storecache import (
                save_packed_store_cache,
            )

            save_packed_store_cache(*store_cache_dst, packed)

        jax.block_until_ready(
            (total_queue_tensor_check(packed), queue_lin_tensor_check(packed))
        )  # compile
        t1 = time.perf_counter()
        tq, ql = total_queue_tensor_check(packed), queue_lin_tensor_check(packed)
        jax.block_until_ready((tq, ql))
        t_check = time.perf_counter() - t1
        n_invalid = int((~(tq.valid & ql.valid)).sum())

    if getattr(args, "profile", None):
        jax.profiler.stop_trace()
        print(f"# wrote profiler trace under {args.profile}", file=sys.stderr)
    # elle packs txn *graphs*, where .length is padded txn slots, not op
    # rows — report recorded op rows for every workload so the stat is
    # comparable across families
    if workload == "elle" and elle_mops is not None and not isinstance(
        elle_mops[0][0], list
    ):
        # store path: Op lists were never materialized — count ops as
        # non-blank JSONL lines so the stat matches the Python path's
        # max(len(history)) exactly (same store, same number either way)
        def _op_count(p):
            with open(p, "rb") as fh:
                return sum(1 for line in fh if line.strip())

        ops_per_history = max(
            _op_count(src) for src, _, _ in elle_mops
        )
    elif workload in ("elle", "mutex"):
        ops_per_history = max(len(h) for h in histories)
    else:
        ops_per_history = packed.length
    n_hist = (
        packed.batch
        if packed_pre is not None
        else len(mats)
        if mats is not None
        else len(elle_mops)
        if elle_mops is not None
        else len(stream_mats)
        if stream_mats is not None
        else len(histories)
    )
    stats_extra = {}
    if workload == "mutex":
        # tri-state honesty: a frontier overflow is undecided, which is
        # neither a pass nor a violation — surface it.  The engine field
        # keeps classic-vs-tensor numbers from ever being conflated.
        stats_extra["unknown"] = n_unknown
        stats_extra["engine"] = getattr(args, "engine", "pcomp")
    print(
        json.dumps(
            {
                "histories": n_hist,
                **stats_extra,
                "ops_per_history": ops_per_history,
                # produce_s: the parallel workers' synth/read + row
                # explosion — work that the SERIAL path counts inside
                # pack_s; reported so machine-readable stats never make
                # --workers look like packing itself got cheaper
                **(
                    {"produce_s": round(t_produce, 3)}
                    if t_produce is not None
                    else {}
                ),
                "pack_s": round(t_pack, 3),
                "check_s": round(t_check, 5),
                "histories_per_sec": round(n_hist / max(t_check, 1e-9), 1),
                "invalid": n_invalid,
                "backend": jax.default_backend(),
                "device": device_info(),
            }
        )
    )
    return 0


def cmd_test(args) -> int:
    import logging

    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    from jepsen_tpu.control.runner import run_test
    from jepsen_tpu.suite import build_rabbitmq_test, build_sim_test

    opts = {
        "rate": args.rate,
        "time-limit": args.time_limit,
        "time-before-partition": args.time_before_partition,
        "partition-duration": args.partition_duration,
        "network-partition": args.network_partition,
        "nemesis": args.nemesis,
        "publish-confirm-timeout": args.publish_confirm_timeout / 1000.0,
        "read-timeout": args.read_timeout / 1000.0,
        "full-read-confirm-empties": args.full_read_confirm_empties,
        "recovery-sleep": args.recovery_sleep,
        "consumer-type": args.consumer_type,
        "net-ticktime": args.net_ticktime,
        "quorum-initial-group-size": args.quorum_initial_group_size,
        "dead-letter": args.dead_letter,
        "fenced": args.fenced,
        "durable": args.durable,
        "seed": args.seed,
        "mixed-extended": args.mixed_extended,
        "slow-disk-mean-ms": args.slow_disk_mean_ms,
        "slow-disk-jitter-ms": args.slow_disk_jitter_ms,
        "wire-corrupt": args.wire_corrupt,
        "wire-duplicate": args.wire_duplicate,
        "wire-delay": args.wire_delay,
        "wire-delay-ms": args.wire_delay_ms,
    }
    if args.archive_url:
        opts["archive-url"] = args.archive_url
    if args.consistency_model:
        opts["consistency-model"] = args.consistency_model
    local_cluster = None
    if args.db == "rabbitmq":
        try:
            test = build_rabbitmq_test(
                opts=opts,
                nodes=args.nodes.split(","),
                concurrency=args.concurrency,
                checker_backend=args.checker,
                store_root=args.store,
                ssh_user=args.ssh_user,
                ssh_private_key=args.ssh_private_key,
                workload=args.workload,
            )
        except (NotImplementedError, ValueError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    elif args.db == "local":
        # the dress rehearsal: the full --db rabbitmq assembly (real
        # runner, native TCP clients, RabbitMQDB choreography, nemesis)
        # against local mini-broker OS processes (harness/localcluster.py)
        from jepsen_tpu.client import native as native_mod
        from jepsen_tpu.harness.localcluster import build_local_test

        # the drain once-latch (and client registry) is process-global in
        # the native driver: an earlier native run in this process would
        # otherwise make this run's drain return instantly empty
        native_mod.reset()

        # every family is multi-node-meaningful on the replicated
        # cluster: queue/mutex ops and stream/elle reads all route
        # through the Raft leader (stream reads commit through the log —
        # linearizable even from lagging followers)
        n = len(args.nodes.split(",")) if args.nodes else 3
        try:
            test, local_cluster = build_local_test(
                opts,
                n_nodes=n,
                concurrency=args.concurrency,
                checker_backend=args.checker,
                store_root=args.store,
                workload=args.workload,
                seed_bug=args.seed_bug,
                durable=args.durable,
            )
        except (NotImplementedError, ValueError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    else:
        try:
            test, _cluster = build_sim_test(
                opts=opts,
                nodes=args.nodes.split(","),
                concurrency=args.concurrency,
                checker_backend=args.checker,
                store_root=args.store,
                workload=args.workload,
            )
        except (NotImplementedError, ValueError) as e:
            # e.g. an asymmetric one-way partition on the sim's
            # symmetrizing net, or a refused nemesis/surface combo —
            # a clean usage error, not a traceback
            print(f"error: {e}", file=sys.stderr)
            return 2
    if getattr(args, "log_file_pattern", None):
        # jepsen.checker/log-file-pattern: scan the collected node logs
        # for SUT-crash indicators; a match invalidates the run even
        # when the history itself looks consistent
        from jepsen_tpu.checkers.logpattern import LogFilePattern

        test.checker.checkers["log-file-pattern"] = LogFilePattern(
            args.log_file_pattern
        )
    test.report = not getattr(args, "no_report", False)
    test.cluster_telemetry = not getattr(
        args, "no_cluster_telemetry", False
    )
    monitor = None
    if args.live_check:
        from jepsen_tpu.checkers.live import attach_live_monitor_for

        monitor_key = args.workload
        if args.workload == "mutex" and args.fenced:
            # fenced runs tolerate overlapping revoked/current holds —
            # LiveMutex's double-grant rule would false-positive; the
            # fenced monitor watches token reuse instead
            monitor_key = "fenced-mutex"
        monitor = attach_live_monitor_for(test, monitor_key)
        if monitor is None:
            print(
                f"warning: --live-check has no monitor for "
                f"{args.workload!r}",
                file=sys.stderr,
            )
    try:
        run = run_test(test)
    finally:
        if local_cluster is not None:
            local_cluster.close()
    if monitor is not None:
        snap = monitor.snapshot()
        counts = ", ".join(
            f"{v} {k}" for k, v in snap["anomalies"].items()
        )
        print(
            f"# live monitor ({monitor.name}): {counts} "
            f"(of {snap['observations']} observations); "
            f"violation-so-far={snap['violation-so-far']}",
            file=sys.stderr,
        )
        if run.run_dir is not None:  # a store artifact, like results.json
            from jepsen_tpu.history.store import LIVE_FILE

            (run.run_dir / LIVE_FILE).write_text(
                json.dumps({"monitor": monitor.name, **snap}, indent=1)
            )
    if getattr(args, "checker", None) == "tpu":
        _stamp_device(args, run.results)
        if run.run_dir is not None:
            save_results(run.run_dir, run.results)
    print(json.dumps(run.results, indent=1, default=_json_default))
    return _verdict_exit(run.verdict)


def cmd_matrix(args) -> int:
    if args.print_configs:
        # one line of `test` CLI flags per config — the CI shell layer and
        # any external driver consume the matrix from this single source
        # of truth instead of duplicating it.  Introspection only: no
        # logging setup, no runner/suite (and hence no JAX) imports.
        from jepsen_tpu.harness.matrix import (
            CI_MATRIX,
            EXTENDED_MATRIX,
            LOCAL_EXTENDED_MATRIX,
            matrix_cli_flags,
        )

        rows = CI_MATRIX + (EXTENDED_MATRIX if args.extended else [])
        if args.extended and args.db in ("local", "rabbitmq"):
            rows += LOCAL_EXTENDED_MATRIX
        for line in matrix_cli_flags(rows):
            print(line)
        return 0

    import logging

    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
    from jepsen_tpu.control.runner import run_test
    from jepsen_tpu.harness.matrix import (
        CI_MATRIX,
        EXTENDED_MATRIX,
        LOCAL_EXTENDED_MATRIX,
        MatrixRunner,
    )
    from jepsen_tpu.suite import (
        DEFAULT_OPTS,
        build_rabbitmq_test,
        build_sim_test,
    )

    scale = args.time_scale

    def _collect_queue_lengths(db, nodes):
        # out-of-band queue-empty cross-check straight from the brokers
        # (= the reference's rabbitmqctl loop, ci/jepsen-test.sh:144-155)
        lengths: dict[str, int] = {}
        read = getattr(db, "queue_lengths_settled", None) or db.queue_lengths
        for node in nodes:
            try:
                for q, n in read(node).items():
                    lengths[f"{q}@{node}"] = n
            except Exception as e:  # noqa: BLE001 — node may be down
                logging.warning(
                    "queue-length check failed on %s: %s", node, e
                )
        return lengths

    def run_fn(opts):
        scaled = dict(opts)
        for k in ("time-limit", "time-before-partition", "partition-duration"):
            scaled[k] = opts[k] * scale
        scaled["recovery-sleep"] = DEFAULT_OPTS["recovery-sleep"] * scale
        # the dead-letter TTL must shrink with the run, or scaled-down
        # smoke runs never see an expiry and the two dead-letter configs
        # degenerate into the plain ones
        scaled["message-ttl"] = DEFAULT_OPTS["message-ttl"] * scale
        scaled["rate"] = args.rate
        if args.db == "rabbitmq":
            if args.archive_url:
                scaled["archive-url"] = args.archive_url
            nodes = args.nodes.split(",")
            test = build_rabbitmq_test(
                opts=scaled,
                nodes=nodes,
                checker_backend=args.checker,
                store_root=args.store,
                ssh_user=args.ssh_user,
                ssh_private_key=args.ssh_private_key,
            )
            run = run_test(test)
            return run.results, _collect_queue_lengths(test.db, nodes)
        if args.db == "local":
            # the dress-rehearsal cluster: every config gets a FRESH set
            # of broker OS processes (like CI's per-run clusters) and a
            # driver-registry reset (the drain once-latch is per-run)
            from jepsen_tpu.client import native as native_mod
            from jepsen_tpu.harness.localcluster import build_local_test

            native_mod.reset(drain_wait_ms=200)
            test, t = build_local_test(
                scaled,
                checker_backend=args.checker,
                store_root=args.store,
                durable=bool(scaled.get("durable")),
            )
            try:
                run = run_test(test)
                return run.results, _collect_queue_lengths(
                    test.db, test.nodes
                )
            finally:
                t.close()
        test, cluster = build_sim_test(
            opts=scaled, checker_backend=args.checker, store_root=args.store
        )
        run = run_test(test)
        return run.results, {"jepsen.queue": cluster.queue_length()}

    matrix = CI_MATRIX + (EXTENDED_MATRIX if args.extended else [])
    if args.extended and args.db in ("local", "rabbitmq"):
        # clock-skew / membership-churn need fault surfaces the sim
        # cannot honestly provide (matrix.py LOCAL_EXTENDED_MATRIX)
        matrix = matrix + LOCAL_EXTENDED_MATRIX
    if args.limit:
        matrix = matrix[: args.limit]
    outcomes = MatrixRunner(run_fn, matrix).run()
    summary = [
        {
            "config": o.config_index + 1,
            "status": o.status,
            "attempts": o.attempts,
            "nemesis": o.opts.get("nemesis", "partition"),
            "partition": o.opts.get("network-partition"),
            "notes": o.notes,
        }
        for o in outcomes
    ]
    ok = all(o.status == "valid" for o in outcomes)
    if args.pins:
        # auto-grown regression rows: replay every pinned red the
        # fuzzer/campaign minted and hold it to its recorded
        # expectation — a pin flipping green is a LOUD failure here
        # (delete the row once the fix is confirmed deliberate)
        from jepsen_tpu.fuzz.pins import replay_pins

        pin_results = replay_pins(
            args.pins, store_root=args.store,
            log=lambda s: print(s, file=sys.stderr, flush=True),
        )
        summary.append({"pins": pin_results})
        ok = ok and all(
            r.get("matched", True) for r in pin_results
        )
    # stdout is exactly the JSON summary (the CI driver tees it into
    # matrix-summary.json); the banner goes to stderr
    print(json.dumps(summary, indent=1))
    print(GOOD_BANNER if ok else INVALID_BANNER, file=sys.stderr)
    return 0 if ok else 1


def cmd_serve(args) -> int:
    from jepsen_tpu.cli.serve import serve_forever

    serve_forever(args.store, host=args.host, port=args.port)
    return 0


def cmd_serve_checker(args) -> int:
    from jepsen_tpu.service.server import serve_forever

    buckets = []
    for part in str(args.warmup_buckets).split(","):
        part = part.strip()
        if not part:
            continue
        length, space = part.split(":", 1)
        buckets.append((int(length), int(space)))
    serve_forever(
        host=args.host, port=args.port, seq=args.seq, store=args.store,
        metrics_port=args.metrics_port, workers=args.workers,
        max_streams=args.max_streams, ingress_cap=args.ingress_cap,
        stream_deadline_s=args.stream_deadline,
        batch=args.batch, target_batch=args.target_batch,
        max_batch_wait_ms=args.max_batch_wait_ms,
        warmup=args.warmup, warmup_buckets=tuple(buckets),
    )
    return 0


def cmd_campaign(args) -> int:
    """``jepsen-tpu campaign``: the continuous-campaign supervisor
    (trial plan, live services, oracle comparison, durable ledger);
    stdout is the JSON summary, the banner goes to stderr."""
    from jepsen_tpu.campaign.supervisor import CampaignSupervisor

    sup = CampaignSupervisor(
        args.out,
        seed=args.seed,
        trials=args.trials,
        n_base=args.base,
        n_ops=args.ops,
        faults=tuple(
            f.strip() for f in args.faults.split(",") if f.strip()
        ),
        pins_dir=args.pins_dir,
        resume=args.resume,
        log=lambda s: print(s, file=sys.stderr, flush=True),
    )
    summary = sup.run()
    print(json.dumps(summary, indent=1))
    complete = summary["completed"] == summary["planned"]
    if args.expect_red:
        ok = complete and summary["reds"] > 0
    else:
        ok = complete and summary["reds"] == 0
    print(GOOD_BANNER if ok else INVALID_BANNER, file=sys.stderr)
    return 0 if ok else 1


def cmd_report(args) -> int:
    """``jepsen-tpu report <store-dir>``: render any missing per-run
    reports under the tree and (re)build the cross-run ``index.html``
    (verdict/latency-headline rows + trend sparkline).  Pointed at a
    single run dir, it renders just that run's artifacts."""
    from jepsen_tpu.history.store import RESULTS_FILE

    root = Path(args.store)
    if not root.is_dir():
        print(f"error: no such store dir {root}", file=sys.stderr)
        return 2
    if (root / HISTORY_FILE).is_file() or (root / RESULTS_FILE).is_file():
        from jepsen_tpu.report.render import render_run_report

        paths = render_run_report(root)
        for name in sorted(paths):
            print(f"{name}: {paths[name]}")
        return 0
    from jepsen_tpu.report.index import build_store_index

    idx = build_store_index(root, render_missing=not args.no_render)
    if idx is None:
        print(f"no runs under {root}", file=sys.stderr)
        return 2
    print(str(idx))
    # fleet memory: the index pass refreshed <store>/baselines.json —
    # surface its regression flags here so a terminal-only consumer
    # sees the drift without opening index.html
    try:
        doc = json.loads((root / "baselines.json").read_text())
        for f in doc.get("flags") or []:
            print(
                f"# REGRESSION: {f['series']} last={f.get('last')} "
                f"baseline={f.get('baseline')} "
                f"delta={f.get('delta_pct')}%",
                file=sys.stderr,
            )
    except (OSError, ValueError):
        pass
    return 0


def cmd_trace(args) -> int:
    """Record any CLI run through the flight recorder and export a
    Perfetto/Chrome trace: ``jepsen-tpu trace [--out F] -- check ...``.

    The wrapped command re-enters :func:`main` (so backend pinning and
    compile-cache wiring behave exactly as in a bare invocation) with
    the obs tracer enabled; the artifact is written ONLY when the
    wrapped command exits 0 — the soak/fuzz
    fail-loud capture discipline (a crashed run leaves no trace file
    pretending to be evidence)."""
    rest = list(args.rest)
    if rest and rest[0] == "--":
        rest = rest[1:]
    if not rest:
        print("error: trace needs a command to record, e.g. "
              "`jepsen-tpu trace -- check --store store`",
              file=sys.stderr)
        return 2
    if rest[0] == "trace":
        print("error: trace cannot wrap itself", file=sys.stderr)
        return 2

    from jepsen_tpu.obs import export as obs_export
    from jepsen_tpu.obs import trace as obs_trace

    out = args.out
    if out is None:
        stamp = time.strftime("%Y%m%dT%H%M%S", time.gmtime())
        out = os.path.join("store", f"trace_{rest[0]}_{stamp}.json")

    profile_dir = args.jax_profile
    if profile_dir:
        import jax

        # the tracer's spans land in this capture too, on the device
        # trace's clock (obs/trace.py's profiler sink).  The Python
        # tracer stays off: its per-call events fill the Perfetto
        # file's 1M-event cap before a run's first span
        popts = jax.profiler.ProfileOptions()
        popts.python_tracer_level = 0
        jax.profiler.start_trace(
            profile_dir, create_perfetto_trace=True, profiler_options=popts
        )
    obs_trace.enable(capacity=args.capacity)
    try:
        rc = main(rest)
    finally:
        obs_trace.disable()
        if profile_dir:
            import jax

            try:
                jax.profiler.stop_trace()
            except RuntimeError:
                pass  # trace never started (early arg error)
    if rc != 0:
        if getattr(args, "keep_on_failure", False):
            # failing runs are exactly the ones whose traces matter for
            # triage — keep the recording, but NEVER at the artifact
            # path: `<out>.failed` cannot be mistaken for committed
            # evidence (the soak/fuzz capture discipline)
            summary = obs_export.write_trace(f"{out}.failed")
            print(
                f"# wrapped command exited {rc}; trace kept at "
                f"{summary['path']} (--keep-on-failure; NOT evidence)",
                file=sys.stderr,
            )
        else:
            print(
                f"# trace NOT written: wrapped command exited {rc} (an "
                f"artifact only lands on a completed run; "
                f"--keep-on-failure writes {out}.failed instead)",
                file=sys.stderr,
            )
        return rc
    summary = obs_export.write_trace(out)
    if profile_dir:
        perfetto = sorted(Path(profile_dir).rglob("perfetto_trace.json.gz"))
        summary["perfetto"] = str(perfetto[-1]) if perfetto else None
    print(f"# trace: {json.dumps(summary)}")
    print(
        "# open it at https://ui.perfetto.dev (or chrome://tracing): "
        f"load {summary.get('perfetto') or summary['path']}",
        file=sys.stderr,
    )
    return 0


def cmd_synth(args) -> int:
    store = Store(args.store)
    if getattr(args, "workload", "queue") == "stream":
        from jepsen_tpu.history.synth import StreamSynthSpec, synth_stream_batch

        shs = synth_stream_batch(
            args.count,
            StreamSynthSpec(n_ops=args.ops, seed=args.seed),
            lost=args.lost,
            duplicated=args.duplicated,
            divergent=args.divergent,
            reorder=args.reorder,
            recovered=getattr(args, "recovered", 0),
        )
    elif getattr(args, "workload", "queue") == "elle":
        from jepsen_tpu.history.synth import ElleSynthSpec, synth_elle_batch

        shs = synth_elle_batch(
            args.count,
            ElleSynthSpec(n_txns=max(args.ops // 2, 8), seed=args.seed),
            g1a=args.g1a,
            g1b=args.g1b,
            g0_cycle=args.g0_cycle,
            g1c_cycle=args.g1c_cycle,
            g2_cycle=args.g2_cycle,
        )
    elif getattr(args, "workload", "queue") == "mutex":
        from jepsen_tpu.history.synth import MutexSynthSpec, synth_mutex_batch

        shs = synth_mutex_batch(
            args.count,
            MutexSynthSpec(n_ops=args.ops, seed=args.seed),
            double_grant=args.double_grant,
        )
    else:
        from jepsen_tpu.history.synth import SynthSpec, synth_batch

        shs = synth_batch(
            args.count,
            SynthSpec(n_ops=args.ops, seed=args.seed),
            lost=args.lost,
            duplicated=args.duplicated,
            unexpected=args.unexpected,
        )
    for i, sh in enumerate(shs):
        d = store.run_dir("synth", f"{time.strftime('%Y%m%dT%H%M%S')}-{i:04d}")
        if getattr(args, "format", "jsonl") == "edn":
            # jepsen's own on-disk layout: fixtures for its ecosystem
            store.save_history_edn(d, sh.ops)
        else:
            store.save_history(d, sh.ops)
    print(f"wrote {len(shs)} histories under {args.store}")
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="jepsen_tpu",
        description="TPU-native distributed-systems correctness testing",
    )
    sub = p.add_subparsers(dest="command", required=True)

    c = sub.add_parser("check", help="re-check a recorded history")
    c.add_argument("history", help="history.jsonl, run dir, or store root")
    c.add_argument(
        "--checker",
        choices=("tpu", "cpu"),
        default="tpu",
        help="analysis backend (the north-star dispatch seam)",
    )
    c.add_argument(
        "--consistency-model",
        choices=("serializable", "read-committed"),
        default=None,
        help="elle histories: isolation level to check against "
        "(default: the level recorded with the run's results, else "
        "serializable — so re-checking a live run that passed at its "
        "SUT's contractual level doesn't silently tighten it)",
    )
    c.add_argument(
        "--log-file-pattern",
        default=None,
        type=_valid_regex,
        metavar="REGEX",
        help="re-scan the run's collected node logs for this pattern "
        "(default: the pattern recorded with the run's results, if "
        "any — a log-invalidated run must not re-check back to valid)",
    )
    c.add_argument(
        "--delivery",
        choices=("exactly-once", "at-least-once"),
        default=None,
        help="queue histories: the SUT's delivery contract (default: the "
        "contract recorded with the run's results, else exactly-once — "
        "same no-silent-tightening rule as --consistency-model)",
    )
    c.add_argument(
        "--append-fail",
        dest="append_fail",
        choices=("definite", "indeterminate"),
        default=None,
        help="stream histories: whether a fail-typed append is "
        "authoritative (sim: definite, a read of it is a phantom) or "
        "the client's verdict only (real sockets: indeterminate, a "
        "materialized one is `recovered`); default: the contract "
        "recorded with the run's results, else definite",
    )
    c.add_argument(
        "--wgl",
        action="store_true",
        help="also run the full Wing-Gong linearizability search "
        "(in addition to the per-value decomposition)",
    )
    c.add_argument(
        "--serial",
        action="store_true",
        help="triage escape hatch: check from re-packed Op objects on "
        "the calling thread instead of the bytes-to-verdict pipeline "
        "executor (--checker tpu routes queue/stream/elle/mutex through "
        "parallel/pipeline.py by default; results are identical)",
    )
    c.add_argument(
        "--no-pcomp",
        dest="no_pcomp",
        action="store_true",
        help="mutex/queue WGL: disable the P-compositional decomposition "
        "(checkers/wgl_pcomp.py — thousands of narrow per-class "
        "frontiers, the measured fast path) and run the monolithic "
        "engine instead; verdicts are identical on single-lock "
        "histories (differential gate in tests/test_wgl_pcomp.py)",
    )
    c.add_argument(
        "--workload",
        choices=("auto", "queue", "stream", "elle", "mutex"),
        default="auto",
        help="checker family; auto-detected from the history's op kinds",
    )
    c.add_argument(
        "--report",
        action="store_true",
        help="after the check, render the per-run report artifacts into "
        "the run dir (report.html latency/throughput panels with "
        "nemesis windows shaded, timeline.html per-process op "
        "timeline, forensics.html on an invalid verdict — "
        "jepsen_tpu/report/)",
    )
    c.add_argument(
        "--procs",
        type=int,
        default=0,
        help="multi-process checking of a STORE TREE: spawn N checker "
        "worker processes (parallel/distributed.py) — deterministic "
        "size-striped assignment of every history under the tree, "
        "per-process multi-lane pipelines (CPU workers: a chip is "
        "exclusive to one process, so host cores are the multi-process "
        "resource), one merged verdict set.  ELASTIC by default: a "
        "dead/wedged worker's stripes requeue onto the survivors with "
        "bounded retry, exhausted stripes quarantine as explicit "
        "unknowns, and the merged verdict carries machine-readable "
        "degraded provenance.  A single history falls back to the "
        "in-process pipeline",
    )
    c.add_argument(
        "--fail-fast",
        dest="fail_fast",
        action="store_true",
        help="disable elastic degradation: any stage/worker failure "
        "aborts the whole run loudly with no partial verdicts (the "
        "pre-PR-13 PipelineError / DistributedCheckError contract, "
        "preserved verbatim — the triage escape hatch)",
    )
    c.add_argument(
        "--global-mesh",
        dest="global_mesh",
        action="store_true",
        help="with --procs N: the workers join ONE jax.distributed "
        "fleet and run the shard_map verdict programs over one global "
        "(hist, seq) mesh — collectives cross the host boundary (gloo "
        "on CPU) and each process feeds its own input lane; the "
        "verdict arrives device-reduced (two scalars), host deaths "
        "degrade by generation restart (queue/elle workloads)",
    )
    c.add_argument(
        "--gm-seq",
        dest="gm_seq",
        type=int,
        default=1,
        help="with --global-mesh: seq-axis extent of the global mesh "
        "(must be a multiple of --procs; >1 shards the packed "
        "transitive-closure plane axis ACROSS hosts)",
    )
    c.add_argument(
        "--segment-ops",
        dest="segment_ops",
        type=int,
        default=0,
        metavar="N",
        help="segmented online checking (SEGMENTED.md): stream the "
        "history N ops at a time through the carry engine — bounded "
        "memory in history length, a CRC'd checkpoint after every "
        "segment (tmp→fsync→rename beside the history), verdicts "
        "equal to the monolithic engine wherever both can run; a "
        "poisoned segment quarantines the verdict as unknown WITH "
        "evidence, never silently",
    )
    c.add_argument(
        "--resume",
        action="store_true",
        help="with --segment-ops: continue from the newest valid "
        "checkpoint (torn/corrupt ones are refused loudly and fall "
        "back to the previous, then to a from-scratch run); the "
        "resumed check reaches the identical verdict — proof harness "
        "in tools/chaos_check.py --segmented",
    )
    c.add_argument(
        "--carry-cap",
        dest="carry_cap",
        type=int,
        default=None,
        metavar="OPS",
        help="with --segment-ops on the mutex family: bound the "
        "open-class carry; a class that outgrows the cap escalates "
        "the verdict to unknown with the class named (the PR-8 "
        "honesty rule — never a silent truncation)",
    )
    c.add_argument(
        "--prefix-index",
        dest="prefix_index",
        default=None,
        metavar="DIR",
        help="with --segment-ops: fleet memory (SEGMENTED.md §Prefix "
        "resume) — publish every full-segment checkpoint into a "
        "content-hash-keyed index under DIR, and resume a "
        "re-submitted history from the deepest anchor whose "
        "(prefix sha256, offset) matches its bytes; the verdict is "
        "identical to from-zero, with resumed_from_prefix provenance "
        "in the result",
    )
    c.set_defaults(fn=cmd_check)

    b = sub.add_parser(
        "bench-check", help="batched replay of stored/synthetic histories"
    )
    b.add_argument("--histories", help="dir tree containing history.jsonl files")
    b.add_argument("--count", type=int, default=256, help="synthetic histories")
    b.add_argument("--ops", type=int, default=470, help="invocations per history")
    b.add_argument(
        "--workload",
        choices=("auto", "queue", "stream", "elle", "mutex"),
        default="auto",
    )
    b.add_argument(
        "--engine",
        choices=("classic", "tensor", "pcomp"),
        default="pcomp",
        help="mutex workload only: 'pcomp' (default) decomposes each "
        "history into per-class sub-histories and vmaps narrow frontier "
        "searches over them (checkers/wgl_pcomp.py — the measured fast "
        "path, WGL_BENCH.md round 6); 'classic' is the monolithic "
        "Wing-Gong host search; 'tensor' the monolithic batched device "
        "frontier search (kept for general-model correctness)",
    )
    b.add_argument(
        "--profile",
        help="write a jax.profiler (XProf) trace of the check to this dir",
    )
    b.add_argument(
        "--workers",
        type=int,
        default=0,
        help="parallel host-packing worker processes (queue workload "
        "only): workers synthesize their seed ranges / read their file "
        "chunks and explode rows; the device check is unchanged",
    )
    b.add_argument(
        "--pipeline",
        action="store_true",
        help="route stored-history checking (--histories; queue/stream/"
        "elle) through the overlapped bytes-to-verdict executor "
        "(parallel/pipeline.py): native thread-pool packing on a "
        "producer thread, async H2D staging, device checking — reports "
        "pipeline_e2e_histories_per_sec / stage_overlap_frac / "
        "device_idle_frac",
    )
    b.add_argument(
        "--serial",
        action="store_true",
        help="with --pipeline: run the identical stages strictly "
        "serially on the calling thread (triage twin — byte-identical "
        "results, no overlap)",
    )
    b.add_argument(
        "--fail-fast",
        dest="fail_fast",
        action="store_true",
        help="with --pipeline: disable the elastic per-chunk "
        "quarantine — any stage failure aborts the whole batch with "
        "PipelineError (the pre-PR-13 contract; also the baseline the "
        "bench's elastic_overhead section compares against)",
    )
    b.add_argument(
        "--chunk",
        type=int,
        default=64,
        help="with --pipeline: histories per pipeline chunk",
    )
    b.add_argument(
        "--mesh",
        action="store_true",
        help="with --pipeline: stage batches through the device mesh "
        "(parallel/mesh.py sharded dispatch over all devices)",
    )
    b.add_argument(
        "--lanes",
        type=int,
        default=None,
        metavar="N",
        help="with --pipeline: per-device input lanes — one producer "
        "thread + staging slot per device, size-aware largest-first "
        "unit balancing with steal-on-idle (0 = one lane per local "
        "device); unreadable/zero-length files are dropped loudly and "
        "counted in the stats",
    )
    b.add_argument(
        "--reduce",
        action="store_true",
        help="with --pipeline --mesh: collective verdict reduction — "
        "per-shard verdicts psum/index-pmin'ed ON DEVICE, the host "
        "receives one {invalid, first_invalid} pair per batch instead "
        "of per-history gathers",
    )
    b.add_argument(
        "--delivery",
        choices=("exactly-once", "at-least-once"),
        default=None,
        help="queue histories: delivery contract for the "
        "linearizability sub-checker (--pipeline path)",
    )
    b.add_argument(
        "--append-fail",
        dest="append_fail",
        choices=("definite", "indeterminate"),
        default=None,
        help="stream histories: fail-typed append contract "
        "(--pipeline path)",
    )
    b.add_argument(
        "--consistency-model",
        choices=("serializable", "read-committed"),
        default=None,
        help="elle histories: isolation level (--pipeline path)",
    )
    b.set_defaults(fn=cmd_bench_check)

    t = sub.add_parser(
        "test", help="run a quorum-queue partition test (reference flags)"
    )
    t.add_argument("--nodes", default="n1,n2,n3", help="comma-separated nodes")
    t.add_argument("--concurrency", type=int, default=5)
    t.add_argument("--db", choices=("sim", "local", "rabbitmq"), default="sim")
    t.add_argument(
        "--workload",
        choices=("queue", "stream", "elle", "mutex"),
        default="queue",
        help="test program: quorum-queue (reference), stream append/read, "
        "elle list-append transactions, or the legacy mutex variant "
        "(sim, or live as a single-token quorum-queue lock)",
    )
    t.add_argument("--store", default="store")
    t.add_argument("--checker", choices=("tpu", "cpu"), default="tpu")
    t.add_argument(
        "--seed-bug",
        choices=(
            "confirm-before-quorum",
            "drop-unacked-on-close",
            "ack-before-fsync",
            "no-wire-checksum",
        ),
        default=None,
        help="(--db local) inject a replication bug into every broker "
        "node: confirm-before-quorum acknowledges publishes on leader-"
        "local append (a partition+heal truncates confirmed writes); "
        "drop-unacked-on-close discards a dying connection's un-acked "
        "deliveries instead of requeueing them (the delivery plane's "
        "loss mode); ack-before-fsync commits against the in-memory log "
        "while the WAL falls behind (needs --durable + --nemesis "
        "crash-restart-cluster to surface); no-wire-checksum sends peer "
        "RPC frames without the integrity CRC, so wire corruption "
        "(--nemesis wire-chaos) is PROCESSED instead of dropped and the "
        "replicas diverge — either way the checker must go red",
    )
    t.add_argument(
        "--durable",
        action="store_true",
        help="(--db local) persist each broker node's Raft log + "
        "term/vote to a per-node data dir that survives SIGKILL — the "
        "real quorum-queue durability contract; enables the "
        "crash-restart-cluster power-failure nemesis to run green",
    )
    # the reference's cli-opts (rabbitmq.clj:288-327)
    t.add_argument(
        "-r", "--rate", type=float, default=50.0, help="ops/sec"
    )
    t.add_argument("--time-limit", type=float, default=30.0)
    t.add_argument("--time-before-partition", type=float, default=10.0)
    t.add_argument("--partition-duration", type=float, default=10.0)
    t.add_argument(
        "--network-partition",
        default="partition-random-halves",
        choices=(
            "partition-random-halves",
            "random-partition-halves",  # the reference's spelling (same)
            "partition-halves",
            "partition-majorities-ring",
            "partition-random-node",
            "partition-leader",
            "partition-one-way-in",
            "partition-one-way-out",
        ),
        help="the reference's four topologies (random-partition-halves "
        "is the reference's spelling of partition-random-halves; both "
        "parse), the targeted partition-leader (isolate the current "
        "Raft leader; --db local), plus the ASYMMETRIC pair: "
        "partition-one-way-in (a victim hears nobody, everyone hears "
        "it) and partition-one-way-out (nobody hears a victim, it "
        "hears everyone) — one-way drops need a direction-honoring "
        "net (--db local / rabbitmq; the sim symmetrizes and refuses)",
    )
    t.add_argument(
        "--log-file-pattern",
        default=None,
        type=_valid_regex,
        metavar="REGEX",
        help="scan the node logs collected into the store for this "
        "pattern (e.g. 'CRASH REPORT|Segmentation fault') and "
        "invalidate the run on any match — jepsen.checker/"
        "log-file-pattern; the SUT can be broken even when the "
        "history looks consistent",
    )
    t.add_argument(
        "--no-report",
        dest="no_report",
        action="store_true",
        help="skip the default-on per-run report artifacts "
        "(report.html/timeline.html — jepsen writes store/report for "
        "every run; this framework now does too)",
    )
    t.add_argument(
        "--no-cluster-telemetry",
        dest="no_cluster_telemetry",
        action="store_true",
        help="skip the default-on ~1 Hz cluster telemetry poller "
        "(per-node Raft/broker internals sampled over the admin STATS "
        "command into cluster.json + the report's cluster panel; "
        "jepsen_tpu/obs/cluster.py)",
    )
    t.add_argument(
        "--live-check",
        action="store_true",
        help="attach the mid-run anomaly monitor (all workloads: flags "
        "monotone anomalies — unexpected/duplicated deliveries, "
        "divergent/phantom/non-monotone stream reads, contradictory or "
        "failed-write txn reads, mutex double grants — the moment they "
        "are recorded, instead of only post-hoc)",
    )
    t.add_argument(
        "--nemesis",
        default="partition",
        choices=(
            "partition",
            "kill-random-node",
            "pause-random-node",
            "crash-restart-cluster",
            "clock-skew",
            "membership-churn",
            "slow-disk",
            "wire-chaos",
            "mixed",
        ),
        help="fault family: the reference's network partitions (shaped by "
        "--network-partition), process kill/pause of a random node, "
        "the whole-cluster power failure (SIGKILL every node, restart — "
        "pair with --durable or the checker will rightly flag loss), "
        "clock-skew (bump a random node's wall clock ±0.1-3s; not --db "
        "sim), membership-churn (kill a node, forget_cluster_node it - "
        "a real RemoveServer commit - then fresh rejoin on heal; needs "
        ">=3 nodes), slow-disk (fsync latency on a random node's WAL; "
        "needs --durable), wire-chaos (corrupt/duplicate/reorder a "
        "random node's peer frames; --db local/rabbitmq), or mixed "
        "(the jepsen.nemesis/compose soak: each cycle randomly picks "
        "partition/kill/pause/clock-skew/membership-churn, plus "
        "crash-restart when --durable; --mixed-extended adds the two "
        "new families to the draw)",
    )
    t.add_argument(
        "--mixed-extended",
        action="store_true",
        help="--nemesis mixed: add slow-disk (when --durable) and "
        "wire-chaos to the family draw (kept opt-in so default mixed "
        "schedules stay comparable with committed soak evidence)",
    )
    t.add_argument(
        "--slow-disk-mean-ms", type=float, default=120.0,
        help="slow-disk nemesis: mean injected fsync latency",
    )
    t.add_argument(
        "--slow-disk-jitter-ms", type=float, default=80.0,
        help="slow-disk nemesis: uniform +/- jitter on each fsync",
    )
    t.add_argument(
        "--wire-corrupt", type=float, default=0.25,
        help="wire-chaos: per-frame corruption probability [0,1]",
    )
    t.add_argument(
        "--wire-duplicate", type=float, default=0.15,
        help="wire-chaos: per-frame duplication probability "
        "(idempotent protocol RPCs only)",
    )
    t.add_argument(
        "--wire-delay", type=float, default=0.15,
        help="wire-chaos: per-frame delay/reorder probability",
    )
    t.add_argument(
        "--wire-delay-ms", type=float, default=40.0,
        help="wire-chaos: held-frame delay (concurrent frames overtake)",
    )
    t.add_argument(
        "--publish-confirm-timeout", type=float, default=5000.0, help="ms"
    )
    t.add_argument(
        "--consistency-model",
        choices=("serializable", "read-committed"),
        default=None,
        help="elle workload: the isolation level to check the SUT "
        "against (default: serializable for --db sim, read-committed "
        "for live brokers — AMQP tx promises atomic commit visibility, "
        "not read isolation, so G2 cycles are its contract)",
    )
    t.add_argument(
        "--read-timeout",
        type=float,
        default=5000.0,
        help="ms; stream workload: how long a cursor read waits for "
        "records (a live AMQP read at the log tail holds its consumer "
        "open this long when nothing arrives)",
    )
    t.add_argument(
        "--full-read-confirm-empties",
        type=int,
        default=1,
        help="stream workload: extra empty read batches required to "
        "conclude end-of-log on the final read when no offset proof is "
        "available (the x-stream-offset=last probe is tried first)",
    )
    t.add_argument("--recovery-sleep", type=float, default=20.0)
    t.add_argument(
        "--consumer-type",
        # the reference's default (rabbitmq.clj:253); was "polling" here
        # through round 3 — see MIGRATION.md's renames/defaults table
        default="asynchronous",
        choices=("asynchronous", "polling", "mixed"),
    )
    t.add_argument("--net-ticktime", type=int, default=15)
    t.add_argument(
        "--seed",
        type=int,
        default=0,
        help="workload-generator seed (elle micro-op mix; distinct "
        "trials should not replay identical txn programs)",
    )
    t.add_argument("--quorum-initial-group-size", type=int, default=0)
    t.add_argument(
        "--fenced",
        action="store_true",
        help="mutex workload: fencing-token mode — acquire returns a "
        "monotonically increasing token (the Raft log index of the "
        "grant commit), releases/protected operations carry it, and the "
        "broker REJECTS operations bearing a superseded token.  The "
        "same revocation schedules that double-grant the unfenced lock "
        "(kill/pause past the dead-owner window) then soak green: the "
        "checker verifies token order (FencedMutex model) instead of "
        "hold exclusivity, and a revoked holder degrades to a failed "
        "release + acquire-retry instead of split-brain",
    )
    t.add_argument(
        "--dead-letter",
        # the reference CI passes a VALUE ("--dead-letter true",
        # ci/jepsen-test.sh:105-107); bare --dead-letter also works.
        # Unrecognized values ERROR rather than silently meaning False —
        # a typo must not run the suite without the config it names.
        nargs="?",
        const=True,
        default=False,
        type=_parse_bool_flag,
    )
    t.add_argument(
        "--archive-url",
        default=None,
        help="RabbitMQ generic-unix archive (--db rabbitmq)",
    )
    t.add_argument("--ssh-user", default="root")
    t.add_argument("--ssh-private-key", default=None)
    t.set_defaults(fn=cmd_test)

    m = sub.add_parser(
        "matrix",
        help="run the CI test matrix (the reference's 14 configs; 18 with "
        "--extended, 25 with --extended --db local) against sim or "
        "rabbitmq — or generate configs beyond any static list with "
        "tools/fuzz_matrix.py",
    )
    m.add_argument("--limit", type=int, default=0, help="first N configs only")
    m.add_argument(
        "--time-scale",
        type=float,
        default=1.0,
        help="scale factor on all durations (smoke runs: ~0.01)",
    )
    m.add_argument("--rate", type=float, default=50.0)
    m.add_argument("--checker", choices=("tpu", "cpu"), default="tpu")
    m.add_argument("--store", default="store")
    m.add_argument("--db", choices=("sim", "local", "rabbitmq"), default="sim")
    m.add_argument("--nodes", default="n1,n2,n3")
    m.add_argument("--archive-url", default=None)
    m.add_argument("--ssh-user", default="root")
    m.add_argument("--ssh-private-key", default=None)
    m.add_argument(
        "--print-configs",
        action="store_true",
        help="print each matrix config as `test` CLI flags and exit",
    )
    m.add_argument(
        "--extended",
        action="store_true",
        help="append the extended configs (process-fault nemeses) to the "
        "reference's 14",
    )
    m.add_argument(
        "--pins",
        default=None,
        metavar="DIR",
        help="also replay the auto-grown regression corpus "
        "(fuzz_pins.json in DIR — rows minted by tools/fuzz_matrix.py "
        "and the campaign supervisor) and hold each pin to its "
        "recorded expectation",
    )
    m.set_defaults(fn=cmd_matrix)

    w = sub.add_parser("serve", help="browse recorded runs over the web")
    w.add_argument("--store", default="store")
    w.add_argument("--host", default="0.0.0.0")
    w.add_argument("--port", type=int, default=8080)
    w.set_defaults(fn=cmd_serve)

    sc = sub.add_parser(
        "serve-checker",
        help="run the TPU checker sidecar (RPC over packed int32 tensors)",
    )
    sc.add_argument("--host", default="0.0.0.0")
    sc.add_argument("--port", type=int, default=8640)
    sc.add_argument(
        "--seq",
        type=int,
        default=1,
        help="seq-parallel shards per history on the device mesh "
        "(multi-device runtimes shard batches across all devices)",
    )
    sc.add_argument(
        "--store",
        default="store",
        help="store root (run reports; the persistent XLA compile cache "
        "is JAX_COMPILATION_CACHE_DIR, else <repo>/store/xla_cache)",
    )
    sc.add_argument(
        "--metrics-port",
        type=int,
        default=9640,
        help="Prometheus-style text /metrics endpoint (p50/p99 check "
        "latency from the shared obs registry); 0 = ephemeral port, "
        "-1 = off",
    )
    sc.add_argument(
        "--workers",
        type=int,
        default=2,
        help="streaming-ingest checker workers (each runs segmented "
        "carry engines; a dead worker's streams requeue onto survivors)",
    )
    sc.add_argument(
        "--max-streams",
        type=int,
        default=256,
        help="admission cap on concurrently open streams — opens past "
        "it are rejected SATURATED, never queued silently",
    )
    sc.add_argument(
        "--ingress-cap",
        type=int,
        default=1024,
        help="total buffered-but-unchecked blocks across all streams; "
        "feeds past it are rejected SATURATED (backpressure, not drop)",
    )
    sc.add_argument(
        "--stream-deadline",
        type=float,
        default=120.0,
        help="seconds an open stream may sit idle before it is "
        "quarantined as overdue (unknown-with-evidence, slot freed)",
    )
    sc.add_argument(
        "--batch",
        action="store_true",
        help="continuous batching: coalesce ready segments across ALL "
        "admitted streams into full shape-bucketed super-batches "
        "(carries never mix — batching crosses streams only on the "
        "history axis), dispatched at target size or the latency "
        "budget, whichever first",
    )
    sc.add_argument(
        "--target-batch",
        type=int,
        default=32,
        help="--batch: segments per coalesced super-batch (the device "
        "batch width is the next pow2)",
    )
    sc.add_argument(
        "--max-batch-wait-ms",
        type=float,
        default=25.0,
        help="--batch: latency budget — a bucket's oldest parked "
        "segment never waits longer than this before dispatch, even "
        "in a partial batch (deadline-aware, never starvation)",
    )
    sc.add_argument(
        "--warmup",
        action="store_true",
        help="--batch: AOT-precompile the configured bucket set at "
        "service start (into the persistent XLA compile cache where "
        "enabled) so a cold bucket's first super-batch pays no "
        "compile on the latency path; hits/misses on /metrics",
    )
    sc.add_argument(
        "--warmup-buckets",
        default="128:128,256:256",
        help="--warmup: comma-separated L:V shape buckets to "
        "precompile (pow2 row/value size classes)",
    )
    sc.set_defaults(fn=cmd_serve_checker)

    cp = sub.add_parser(
        "campaign",
        help="run a crash-recoverable continuous campaign: service "
        "trials over {stream rate x admission pressure x checker-side "
        "fault}, every verdict held to a serial oracle, journaled to a "
        "durable ledger so SIGKILL -> --resume lands on the identical "
        "verdict set",
    )
    cp.add_argument("--out", required=True,
                    help="campaign dir (ledger + per-service stores)")
    cp.add_argument("--seed", type=int, default=17)
    cp.add_argument("--trials", type=int, default=8)
    cp.add_argument("--base", type=int, default=4,
                    help="distinct corpus histories (one carries a "
                    "known loss)")
    cp.add_argument("--ops", type=int, default=160,
                    help="ops per corpus history")
    cp.add_argument(
        "--faults",
        default=",".join(
            ("none", "kill-worker", "service-restart",
             "torn-subscription")
        ),
        help="comma list of checker-side faults the plan samples "
        "(drop service-restart for subprocess-free smoke runs)",
    )
    cp.add_argument("--pins-dir", default=None,
                    help="pin any minimized red into this dir's "
                    "fuzz_pins.json (the matrix replays it)")
    cp.add_argument("--resume", action="store_true",
                    help="resume from the ledger in --out (skips the "
                    "journaled prefix; refuses a foreign campaign)")
    cp.add_argument("--expect-red", action="store_true",
                    help="exit non-zero unless a red was found and "
                    "pinned (pair with the force-red chaos hook)")
    cp.set_defaults(fn=cmd_campaign)

    rp = sub.add_parser(
        "report",
        help="render run reports + the cross-run index.html for a "
        "store tree (jepsen_tpu/report/; runs a single run dir too)",
    )
    rp.add_argument(
        "store",
        help="store root (index + any missing per-run reports) or a "
        "single run dir (that run's artifacts only)",
    )
    rp.add_argument(
        "--no-render",
        action="store_true",
        help="index only what already has a report.json; render "
        "nothing new",
    )
    rp.set_defaults(fn=cmd_report)

    tr = sub.add_parser(
        "trace",
        help="record any CLI run through the flight recorder and "
        "export a Perfetto trace (obs/OBSERVABILITY.md)",
    )
    tr.add_argument(
        "--out",
        default=None,
        help="trace artifact path (default: "
        "store/trace_<cmd>_<utc-stamp>.json); written only when the "
        "wrapped command exits 0",
    )
    tr.add_argument(
        "--capacity",
        type=int,
        default=1 << 16,
        help="span ring capacity (oldest records drop past it)",
    )
    tr.add_argument(
        "--keep-on-failure",
        dest="keep_on_failure",
        action="store_true",
        help="when the wrapped command exits non-zero, still export the "
        "recording — to <out>.failed, never the artifact path "
        "(failing runs are the ones whose traces matter; the .failed "
        "suffix keeps the capture discipline honest)",
    )
    tr.add_argument(
        "--jax-profile",
        default=None,
        metavar="DIR",
        help="also run jax.profiler over the wrapped command, writing "
        "its capture under DIR; its perfetto_trace.json.gz holds the "
        "program's spans and the device's ops on one clock",
    )
    tr.add_argument(
        "rest",
        nargs=argparse.REMAINDER,
        help="the command to record (prefix with -- to end trace's own "
        "flags), e.g. `trace -- check --store store --checker tpu`",
    )
    tr.set_defaults(fn=cmd_trace)

    s = sub.add_parser("synth", help="generate synthetic histories into a store")
    s.add_argument(
        "--format",
        choices=("jsonl", "edn"),
        default="jsonl",
        help="history file format (edn = jepsen's own layout, e.g. for "
        "feeding jepsen-ecosystem tooling)",
    )
    s.add_argument("--store", default="store", help="store root dir")
    s.add_argument(
        "--seed",
        type=int,
        default=0,
        help="seed of the first history (history i uses seed + i)",
    )
    s.add_argument(
        "--workload",
        choices=("queue", "stream", "elle", "mutex"),
        default="queue",
    )
    s.add_argument("--count", type=int, default=16)
    s.add_argument("--ops", type=int, default=470)
    s.add_argument("--lost", type=int, default=0)
    s.add_argument("--duplicated", type=int, default=0)
    s.add_argument("--unexpected", type=int, default=0, help="queue workload")
    s.add_argument("--divergent", type=int, default=0, help="stream workload")
    s.add_argument(
        "--recovered", type=int, default=0,
        help="stream workload: appends completed FAIL whose value is in "
        "the log anyway (phantom under --append-fail definite, recovered "
        "under indeterminate)",
    )
    s.add_argument("--reorder", type=int, default=0, help="stream workload")
    s.add_argument("--g1a", type=int, default=0, help="elle workload")
    s.add_argument("--g1b", type=int, default=0, help="elle workload")
    s.add_argument("--g0-cycle", type=int, default=0, help="elle workload")
    s.add_argument("--g1c-cycle", type=int, default=0, help="elle workload")
    s.add_argument("--g2-cycle", type=int, default=0, help="elle workload")
    s.add_argument(
        "--double-grant", type=int, default=0, help="mutex workload"
    )
    s.set_defaults(fn=cmd_synth)

    return p


def _wants_device_backend(args) -> bool:
    """True when the subcommand benefits from the real default backend
    (a TPU if the environment has one)."""
    if args.command in ("synth", "serve", "report"):
        # host-only work (report's windowed-stats kernel is a tiny CPU
        # dispatch; rendering never takes the chip)
        return False
    if args.command in ("bench-check", "serve-checker"):
        return True  # device-throughput measurement / checker sidecar
    if args.command == "trace":
        return True  # the WRAPPED command decides on re-entry; pinning
        # here would override its choice before it parses
    if getattr(args, "print_configs", False):
        return False  # matrix introspection runs no checks
    return getattr(args, "checker", None) == "tpu"


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from jepsen_tpu.utils.jaxenv import (
        device_info,
        enable_compilation_cache,
        ensure_backend,
        pin_cpu_platform,
    )

    if not _wants_device_backend(args):
        # no device compute on these paths — never touch a chip
        pin_cpu_platform()
    elif args.command == "check" and (getattr(args, "procs", 0) or 0) > 1:
        # the worker fleet does the checking: a chip initialised here
        # would be held by this parent for the whole run
        pass
    elif args.command not in ("serve-checker", "trace"):
        # the sidecar inits its own backend; trace defers to the wrapped
        # command's own main() pass
        cache = enable_compilation_cache(ensure_backend())
        dev = device_info()
        print(
            f"# device: platform={dev['platform']} kind={dev['kind']} "
            f"count={dev['count']}; compile cache: {cache}",
            file=sys.stderr,
        )
    try:
        return args.fn(args)
    except FileNotFoundError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
