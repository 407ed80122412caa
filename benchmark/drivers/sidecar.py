"""One client in a closed loop against the checker sidecar.

The sidecar is the program's ``CheckerServer`` on ``127.0.0.1`` (an
ephemeral port) in this process; the client is the program's
``CheckerClient``.  Each request is one finished history, as a Jepsen
run's checker sends it: ``pack_histories([ops])`` then
``check_packed`` (what ``CheckerClient.check_histories`` does), timed
from the pack to the verdict map in the client.  The next request goes
when the verdict is back.  Requests take the histories of the pool in an
order shuffled from the seed, and again when the pool is used up.

The ``check`` op keeps no verdict cache, so a history that comes round
again is checked again.

End to end: ``check_p50_ms`` and ``check_p95_ms`` over every request of
the window, the one that straddles the deadline included.  Counts for
the readers: the client's pack time, and the server's own
``service.check_latency_s{op=check}`` sketch over the window.
"""

from __future__ import annotations

import random
import statistics
import sys
import time
import traceback


class Driver:
    def __init__(self, cell, campaign, devices):
        from jepsen_tpu.history.store import read_history
        from jepsen_tpu.obs.metrics import Registry
        from jepsen_tpu.service.client import CheckerClient
        from jepsen_tpu.service.server import CheckerServer

        self.pool = [read_history(h["path"]) for h in campaign]
        self.registry = Registry()
        self.srv = CheckerServer(host="127.0.0.1", port=0,
                                 metrics_registry=self.registry)
        self.thread = self.srv.start_background()
        self.client = CheckerClient("127.0.0.1", self.srv.port)
        self.order = random.Random(cell.seed).sample(
            range(len(self.pool)), len(self.pool))
        self.latency: list[float] = []
        self.pack: list[float] = []
        self.got: list[tuple[int, object]] = []
        self.failed = 0
        self._sketch0 = (0, 0.0)

    def _sketch(self) -> tuple[int, float]:
        sk = self.registry.sketch("service.check_latency_s", op="check")
        return sk.count, sk.sum

    def _request(self, ops):
        import jax

        from jepsen_tpu.history.encode import pack_histories

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.request"):
            with jax.profiler.TraceAnnotation("bench.pack"):
                packed = pack_histories([ops])
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.check_call"):
                verdict = self.client.check_packed(packed)[0]
        return verdict, t1 - t0, time.perf_counter() - t0

    def warm(self) -> None:
        """One request for each packed shape the pool holds: the server
        compiles (or loads) its check program for every shape here."""
        from jepsen_tpu.history.encode import pack_histories

        seen = set()
        for ops in self.pool:
            p = pack_histories([ops], to_device=False)
            key = (p.f.shape, p.value_space)
            if key not in seen:
                seen.add(key)
                self._request(ops)
        self._sketch0 = self._sketch()

    def window(self, seconds: float) -> None:
        deadline = time.perf_counter() + seconds
        k = 0
        while time.perf_counter() < deadline:
            i = self.order[k % len(self.order)]
            k += 1
            try:
                verdict, pack_s, total_s = self._request(self.pool[i])
            except Exception:  # noqa: BLE001 - a failed request is counted
                traceback.print_exc(file=sys.stderr)
                self.failed += 1
                self.got.append((i, None))
                continue
            self.got.append((i, verdict))
            self.pack.append(pack_s)
            self.latency.append(total_s)
        self._sketch1 = self._sketch()

    def close(self) -> None:
        self.client.close()
        self.srv.shutdown()
        self.srv.server_close()
        self.thread.join(timeout=30)
        self.pool = []

    @property
    def attempted(self) -> int:
        return len(self.got)

    def end_to_end(self) -> dict:
        q = statistics.quantiles(self.latency, n=100, method="inclusive")
        return {"check_p50_ms": statistics.median(self.latency) * 1e3,
                "check_p95_ms": q[94] * 1e3}

    def counts(self) -> dict:
        n = self._sketch1[0] - self._sketch0[0]
        s = self._sketch1[1] - self._sketch0[1]
        return {
            "requests": len(self.latency),
            "pack_s": sum(self.pack),
            "server_requests": n,
            "server_check_s": s,
        }

    def answers(self):
        return "per_history", list(self.got)
