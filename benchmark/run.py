"""One run of one benchmark cell.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name (``BENCHMARK.json`` at the
checkout's root):

- the cell's ``config`` -> ``benchmark/configs/<config>.json`` (family,
  history shapes, campaign, the guarantees the checker is held to), and
  its ``family`` -> ``benchmark/families/<family>.py`` (the generator and
  the plain reference, ``benchmark/gen.py``);
- its ``traffic`` -> ``benchmark/traffic/<mix>.json`` (parameters, among
  them the ``driver``: ``benchmark/drivers/<driver>.py``, the entry the
  window drives);
- each per-layer metric -> ``benchmark/metrics/<metric>.py`` (a reader).

A run: start synthesizing the campaign from the seed in a pool of worker
processes (they never import JAX) and meanwhile bring JAX up on the
accelerator (exit 1 without one, or with fewer chips than the cell asks
for); warm every shape the window will use; measure for
``--seconds``; read the device's memory peak; free the program's state;
check every answer of the window against the plain reference
(``benchmark/reference.py``) in the pool; print the result line.

With ``--trace 1`` the window runs under the JAX profiler and the line
carries the cell's per-layer metrics and ``breakdown`` in place of its
end-to-end ones.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is measured from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import multiprocessing  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = ROOT / ".bench_cache" / "jax"
sys.path.insert(0, str(BENCH))

import gen  # noqa: E402
import reference  # noqa: E402

#: the numbers compared and their limits: answers that differ from the
#: reference (an exact comparison, so 0), and answers compared (a run
#: that compared none proves nothing)
LIMITS = {"mismatched": ("<=", 0), "compared": (">=", 1)}


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    seed: int = 0
    root: Path = ROOT


def _load(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def find_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell named ``name`` with its configuration, traffic mix and
    the metrics it reports."""
    bench = _load(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])

    def here(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(
        name=name,
        chips=w["chips"],
        config=_load(root / conf["file"]),
        traffic=_load(root / "benchmark" / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if here(m)],
        per_layer=[m for m in bench["per_layer"] if here(m)],
        root=root,
    )


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver_class(cell: Cell):
    d = cell.traffic["driver"]
    return _module(cell.root / "benchmark" / "drivers" / f"{d}.py",
                   f"bench_driver_{d}").Driver


def reader(cell: Cell, metric: str):
    path = cell.root / "benchmark" / "metrics" / f"{metric}.py"
    return _module(path, "bench_metric_" + metric.replace(".", "_")).read


def pool(workers: int):
    """Worker processes for synthesis and the reference.  They start from
    a fresh interpreter (``spawn``) and import only ``gen`` and
    ``reference``, never JAX: the chip stays this process's."""
    return multiprocessing.get_context("spawn").Pool(workers)


def synthesize(cell: Cell, workdir: Path, procs):
    """Start making the campaign in the pool: the pending result is, in
    pass order, one entry per history with its JSONL file's ``path`` (under
    ``workdir``) and its shape."""
    conf = cell.config
    count = cell.traffic.get("histories", conf["campaign"]["histories"])
    jobs = [
        (conf["family"], conf["spec"], hseed, inject,
         str(workdir / f"{k:05d}.jsonl"))
        for k, (hseed, inject) in enumerate(
            gen.plan(conf["family"], conf["campaign"], cell.seed, count))
    ]
    return procs.starmap_async(gen.make, jobs,
                               chunksize=max(1, len(jobs) // 64))


class Compiles:
    """Lowerings and compile seconds, from JAX's own monitoring events.
    A lowering inside the window means a program was traced there: a
    compile or a cache load the warm-up missed."""

    def __init__(self):
        import jax

        self.lowered = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_kw):
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs
            if name.endswith("jaxpr_to_mlir_module_duration"):
                self.lowered += 1


def device_record(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    rec = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if all(p is not None for p in peaks):
        rec["memory_peak_bytes"] = max(peaks)
    return rec


def compare(cell: Cell, campaign: list[dict], answers, procs) -> dict:
    """Every answer of the window against the reference: the numbers
    compared, each of which ``LIMITS`` bounds."""
    kind, got = answers
    fam, opts = cell.config["family"], cell.config["checker"]
    if kind == "reduced":
        jobs = [(fam, opts, h["path"], True) for h in campaign]
        ok = procs.map(reference.run, jobs, chunksize=8)
        mismatched = 0
        for order, g in got:
            bad = [p for p, i in enumerate(order or ()) if not ok[i]]
            want = (len(bad), bad[0] if bad else -1)
            mismatched += g is None or tuple(g) != want
        return {"mismatched": mismatched, "compared": len(got)}
    idx = sorted({i for i, _ in got})
    jobs = [(fam, opts, campaign[i]["path"], False) for i in idx]
    ref = dict(zip(idx, procs.map(reference.run, jobs, chunksize=1)))
    labels = gen.family(fam).LABELS
    mismatched = sum(1 for i, g in got
                     if not reference.same(g, ref[i], labels))
    return {"mismatched": mismatched, "compared": len(got)}


def layer_metrics(cell: Cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        got = reader(cell, m["name"])(ctx)
        if got is None:
            continue
        value, note = got if isinstance(got, tuple) else (got, None)
        out[m["name"]] = {"value": value, "unit": m["unit"]}
        if note:
            print(f"# {m['name']}: {note}", file=sys.stderr)
    return out


def run_cell(cell: Cell, seconds: float, trace: bool,
             accept=("tpu",), workers: int | None = None,
             hook=None) -> dict:
    """Everything after the arguments; returns the result line.  ``accept``
    is the platforms the run may take: the command takes only ``tpu``.
    ``hook(driver)`` may replace the timed path (the tests' faults and
    the control) before the warm-up."""
    workers = workers or max(1, min(16, (os.cpu_count() or 2) - 1))
    workdir = Path(tempfile.mkdtemp(prefix="bench-"))
    procs = pool(workers)
    try:
        pending = synthesize(cell, workdir, procs)

        import jax

        from jepsen_tpu.utils.jaxenv import (
            compile_cache_entries,
            enable_compilation_cache,
        )

        devices = jax.devices()
        t_jax = time.perf_counter() - T0
        if devices[0].platform not in accept:
            raise Unfit(f"JAX found no accelerator (platform "
                        f"{devices[0].platform!r})")
        if len(devices) < cell.chips:
            raise Unfit(f"the cell asks for {cell.chips} chips, JAX has "
                        f"{len(devices)}")
        campaign = pending.get()
        t_synth = time.perf_counter() - T0
        cache = enable_compilation_cache(devices[0].platform)
        # cache every program, also those that compile fast
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        before = compile_cache_entries(cache)
        compiles = Compiles()
        driver = driver_class(cell)(cell, campaign, devices)
        if hook:
            hook(driver)
        driver.warm()
        jax.effects_barrier()
        setup_s = time.perf_counter() - T0
        print(f"# set-up {setup_s:.3f} s: JAX up at {t_jax:.3f} s, "
              f"synthesis done at {t_synth:.3f} s "
              f"({len(campaign)} histories, {workers} workers), compile "
              f"{compiles.seconds:.3f} s; compile cache {cache}: "
              f"{before} entries before warm-up, "
              f"{compile_cache_entries(cache)} after", file=sys.stderr)

        lowered0 = compiles.lowered
        tdir = workdir / "trace"
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(tdir), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                driver.window(seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
        window_compiles = compiles.lowered - lowered0
        device = device_record(devices)
        driver.close()
        print(f"# window: {driver.attempted} attempted, {driver.failed} "
              f"failed, {window_compiles} programs lowered inside it",
              file=sys.stderr)

        if trace:
            import xplane as tr

            xs = sorted(tdir.rglob("*.xplane.pb"))
            summary = tr.reduce(str(xs[-1]))
            ctx = Context(cell, device["kind"], campaign, driver.counts(),
                          summary, window_compiles)
            metrics = layer_metrics(cell, ctx)
            if summary.devices:
                device["busy_s"] = summary.mean_busy_s
                device["window_s"] = summary.window_s
            bd = tr.breakdown(summary)
        else:
            e2e = dict(driver.end_to_end(), setup_s=setup_s)
            metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                       for m in cell.end_to_end}
            bd = None

        answers = driver.answers()
        line = {"correct": False, "attempted": driver.attempted,
                "failed": driver.failed, "metrics": metrics,
                "device": device}
        del driver
        t_ref = time.perf_counter()
        checks = compare(cell, campaign, answers, procs)
        print(f"# reference and comparison: "
              f"{time.perf_counter() - t_ref:.3f} s", file=sys.stderr)
        line["correct"] = all(
            checks[k] <= lim if op == "<=" else checks[k] >= lim
            for k, (op, lim) in LIMITS.items())
        if bd:
            line["breakdown"] = bd
        line["checks"] = {k: {"value": checks[k], "limit": f"{op} {lim}"}
                          for k, (op, lim) in LIMITS.items()}
        for k, v in line["checks"].items():
            print(f"# check {k}: {v['value']} (limit {v['limit']})",
                  file=sys.stderr)
        return line
    finally:
        procs.terminate()
        procs.join()
        shutil.rmtree(workdir, ignore_errors=True)


@dataclass
class Context:
    """What a per-layer reader reads."""

    cell: Cell
    device_kind: str
    campaign: list  # per history: rows, space, ops
    counts: dict  # the driver's counts over the window
    trace: object  # trace.Trace
    window_compiles: int


class Unfit(RuntimeError):
    """The machine cannot run this cell: no result is printed."""


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    cell = find_cell(args.workload)
    cell.seed = args.seed
    # the compile cache lives in the checkout, at a fixed path of the
    # benchmark's own (nothing else writes there)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    sys.path.insert(0, str(ROOT))
    try:
        line = run_cell(cell, args.seconds, bool(args.trace))
    except Unfit as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
