"""Replay of a recorded campaign through ``check_sources``.

The window runs campaign passes back to back: each pass checks every
history file of the campaign through the program's pipeline executor
(``parallel/pipeline.py::check_sources``), with the options the traffic
mix gives (``use_cache``, ``fail_fast``; ``mesh_hist`` with ``reduce``
for the collective-reduced verdict over a mesh of that many chips).  The
pass that straddles the deadline finishes and its time counts.

Every pass checks the campaign in an order of its own, drawn from the
seed: the histories are shuffled within aligned blocks of
``shuffle_block``.  So no pass hands the program the batch it checked
before (a program that answers from a stale batch is caught), while any
batch the program forms of a multiple of that many histories holds the
same histories in every pass, and with them the same shapes the warm-up
compiled.

End to end: ``histories_per_s``, all histories whose verdicts the window
completed over all of the window's time.  Counts for the readers: the
pipeline's produce and place busy seconds summed over the window.
"""

from __future__ import annotations

import gc
import random
import sys
import time
import traceback


def pass_order(n: int, block: int, seed: int, k: int) -> list[int]:
    """Campaign indices in the order pass ``k`` checks them; pass 0 (the
    warm-up) keeps the campaign's own order."""
    order = list(range(n))
    if k == 0:
        return order
    rng = random.Random(f"{seed}/{k}")
    for lo in range(0, n, block):
        part = order[lo:lo + block]
        rng.shuffle(part)
        order[lo:lo + block] = part
    return order


class Driver:
    def __init__(self, cell, campaign, devices):
        from jepsen_tpu.parallel.pipeline import check_sources

        self.family = cell.config["family"]
        self.paths = [h["path"] for h in campaign]
        self.seed = cell.seed
        mix = cell.traffic
        self.block = mix["shuffle_block"]
        self.opts = dict(
            cell.config["checker"],
            use_cache=mix["use_cache"],
            fail_fast=mix["fail_fast"],
        )
        self.reduce = bool(mix.get("reduce", False))
        if "mesh_hist" in mix:
            from jepsen_tpu.parallel.mesh import checker_mesh

            n = mix["mesh_hist"]
            self.opts.update(mesh=checker_mesh(devices[:n], hist=n),
                             reduce=self.reduce)
        self._check = check_sources
        self.passes: list[dict] = []
        self.failed = 0
        self.t_window = 0.0

    def _pass(self, k: int):
        import jax

        order = pass_order(len(self.paths), self.block, self.seed, k)
        with jax.profiler.TraceAnnotation("bench.pass"):
            out = self._check(self.family, [self.paths[i] for i in order],
                              **self.opts)
        return order, out

    def warm(self) -> None:
        """One whole pass: every chunk shape of the campaign compiles (or
        loads from the cache) here, and the native packer is built."""
        self._pass(0)

    def window(self, seconds: float) -> None:
        t0 = time.perf_counter()
        deadline = t0 + seconds
        k = 0
        while time.perf_counter() < deadline:
            k += 1
            s = time.perf_counter()
            try:
                order, (results, stats) = self._pass(k)
            except Exception:  # noqa: BLE001 - a failed pass is counted
                traceback.print_exc(file=sys.stderr)
                self.failed += len(self.paths)
                self.passes.append({"results": None})
                continue
            self.passes.append({
                "order": order,
                "results": results,
                "produce_s": stats.produce_busy_s,
                "place_s": stats.place_busy_s,
                "seconds": time.perf_counter() - s,
            })
            self.failed += stats.quarantined
            # the answers are kept for the comparison after the window;
            # kept, they would make each full collection of the cyclic
            # GC slower pass by pass (a pass of 0.55-0.61 s in place of
            # 0.32 s once a run, my chip run, PR 22): no user holds
            # dozens of passes, so the kept objects leave its care
            gc.freeze()
        self.t_window = time.perf_counter() - t0
        secs = [round(p["seconds"], 4) for p in self.passes if p["results"]]
        print(f"# pass seconds: {secs}", file=sys.stderr)

    def close(self) -> None:
        self.opts.pop("mesh", None)
        gc.unfreeze()

    @property
    def attempted(self) -> int:
        return len(self.passes) * len(self.paths)

    def end_to_end(self) -> dict:
        done = sum(len(self.paths) for p in self.passes if p["results"])
        return {"histories_per_s": done / self.t_window}

    def counts(self) -> dict:
        ok = [p for p in self.passes if p["results"]]
        return {
            "histories": len(ok) * len(self.paths),
            "passes": len(ok),
            "produce_s": sum(p["produce_s"] for p in ok),
            "place_s": sum(p["place_s"] for p in ok),
        }

    def answers(self):
        """``("reduced", [(order, (invalid, first_invalid)) per pass])``
        or ``("per_history", [(campaign index, verdict map)])``; a failed
        pass answers ``None``."""
        if self.reduce:
            return "reduced", [
                (p["order"], (p["results"]["invalid"],
                              p["results"]["first_invalid"]))
                if p["results"] else (None, None)
                for p in self.passes
            ]
        out = []
        for p in self.passes:
            if p["results"] is None:
                out.extend((i, None) for i in range(len(self.paths)))
            else:
                out.extend(zip(p["order"], p["results"]))
        return "per_history", out
