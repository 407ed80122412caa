"""The checker sidecar server.

A long-lived process owning the JAX backend — one chip, or every device
the runtime can see sharded through a ``(hist, seq)`` mesh (pass
``mesh=`` / ``serve_forever(seq=...)``; multi-device runtimes build the
global mesh automatically, including pod-wide after ``init_multihost``).
Controllers connect over TCP, send packed histories, and get
reference-shaped verdicts back.  The jitted check program is cached per
``(B, L, V)`` shape, so a fleet of runs with bucketed shapes pays one
compile each.  Batches whose size doesn't divide the ``hist`` axis are
padded with fully-masked histories and sliced back on reply.

Ops:

- ``ping``  → backend info (devices, platform)
- ``check`` → arrays ``f``/``type``/``value``/``mask`` of shape ``[B, L]``
  + ``value_space`` → per-history ``total-queue`` and queue-linearizability
  verdicts
- ``check-stream`` → the packed stream columns + ``space`` → per-history
  stream-log linearizability verdicts
- ``check-elle`` → histories as op JSON in the header (edge inference is
  a host-side parse; the server runs it next to the device) → per-history
  Elle serializability verdicts
"""

from __future__ import annotations

import contextlib
import logging
import os
import socket
import socketserver
import threading
import time
from typing import Any

import numpy as np

from jepsen_tpu.obs import metrics as obs_metrics
from jepsen_tpu.obs import trace as obs_trace
from jepsen_tpu.service.protocol import (
    ProtocolError,
    TornPayloadError,
    recv_frame,
    send_frame,
)

logger = logging.getLogger("jepsen_tpu.service")

REQUIRED_ARRAYS = ("f", "type", "value", "mask")

#: the streaming ingestion surface (service/stream.py); everything else
#: is the original batch sidecar
_STREAM_OPS = frozenset({
    "stream-open", "stream-feed", "stream-finish", "stream-abort",
    "submit-batch", "collect", "cache-get", "service-stats",
})

#: chaos hook (tools/chaos_check.py vocabulary): ``"<n>"`` — the FIRST
#: subscription on this server is torn (socket closed abruptly) after
#: pushing n verdict-window frames; consumed once, so the client's
#: reconnect-with-replay lands on a healthy push loop
SUB_DROP_ENV = "JEPSEN_TPU_SERVE_SUB_DROP_AFTER"

#: bound on how long a push loop waits for the NEXT window before
#: answering with a machine-readable timeout frame (never a silent hang)
SUBSCRIBE_IDLE_TIMEOUT_S = 120.0


def _check_stage_s(stage: str):
    """The global sketch ``service.stage_s{op=check,stage=...}``."""
    return obs_metrics.REGISTRY.sketch(
        "service.stage_s", op="check", stage=stage
    )


@contextlib.contextmanager
def _check_stage(stage: str):
    """One host stage of the ``check`` op: the ``service.<stage>`` span,
    its seconds into :func:`_check_stage_s` (on success)."""
    with obs_trace.span(f"service.{stage}"):
        t0 = time.perf_counter()
        yield
        dt = time.perf_counter() - t0
    _check_stage_s(stage).add(dt)


def _pad_batch_axis(tree, multiple: int):
    """Zero/False-pad every leaf's axis 0 to a multiple (padded histories
    are fully masked → ignored); returns ``(padded, original_B)``."""
    import jax
    import jax.numpy as jnp

    B = jax.tree.leaves(tree)[0].shape[0]
    pad = (-B) % multiple
    if pad == 0:
        return tree, B

    def p(x):
        return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))

    return jax.tree.map(p, tree), B


def _check_arrays(
    arrays: dict[str, np.ndarray], value_space: int, mesh=None
) -> dict[str, Any]:
    """The ``check`` op under the device lock, in three host stages:
    ``place`` (uploads), ``kernel`` (dispatch until the verdict tensors
    are ready), ``render`` (device-to-host reads, the result maps)."""
    import jax
    import jax.numpy as jnp

    from jepsen_tpu.checkers.queue_lin import queue_lin_tensors_to_results
    from jepsen_tpu.checkers.total_queue import _tensors_to_results

    missing = [k for k in REQUIRED_ARRAYS if k not in arrays]
    if missing:
        raise ProtocolError(f"missing arrays: {missing}")
    with _check_stage("place"):
        f = jnp.asarray(arrays["f"], jnp.int32)
        type_ = jnp.asarray(arrays["type"], jnp.int32)
        value = jnp.asarray(arrays["value"], jnp.int32)
        mask = jnp.asarray(arrays["mask"].astype(bool))
        B = f.shape[0]
        if mesh is not None:
            # mesh-wide check: the sharded programs of parallel/mesh.py
            from jax.sharding import NamedSharding

            from jepsen_tpu.parallel.mesh import HIST_AXIS, SEQ_AXIS, _row_spec

            (f, type_, value, mask), B = _pad_batch_axis(
                (f, type_, value, mask), mesh.shape[HIST_AXIS]
            )
            # the op axis must divide the seq shards too: pad with masked
            # rows (appended at the end, so real row positions are unchanged)
            pad_l = (-f.shape[1]) % mesh.shape[SEQ_AXIS]
            if pad_l:
                widths = ((0, 0), (0, pad_l))
                f = jnp.pad(f, widths)
                type_ = jnp.pad(type_, widths)
                value = jnp.pad(value, widths)
                mask = jnp.pad(mask, widths)
            # place once; both programs then consume the committed arrays
            sh = NamedSharding(mesh, _row_spec())
            f, type_, value, mask = (
                jax.device_put(x, sh) for x in (f, type_, value, mask)
            )

    with _check_stage("kernel"):
        if mesh is not None:
            from jepsen_tpu.parallel.mesh import (
                _queue_lin_program,
                _total_queue_program,
            )

            tq = _total_queue_program(mesh, value_space)(f, type_, value, mask)
            ql = _queue_lin_program(mesh, value_space)(f, type_, value, mask)
        else:
            from jepsen_tpu.checkers.fused import _combined_batch

            # the canonical single-program combined check (checkers/fused.py)
            tq, ql = _combined_batch(f, type_, value, mask, value_space)
        jax.block_until_ready((tq, ql))

    with _check_stage("render"):
        tq_results = _tensors_to_results(tq)[:B]
        ql_results = queue_lin_tensors_to_results(ql)[:B]
        out = []
        for q, l in zip(tq_results, ql_results):
            out.append(
                {
                    "queue": _jsonable(q),
                    "linear": _jsonable(l),
                    "valid?": bool(q["valid?"] and l["valid?"]),
                }
            )
    return {"op": "result", "results": out}


def _jsonable(d: dict[str, Any]) -> dict[str, Any]:
    """Result maps hold value sets; the wire header is JSON."""
    return {
        k: sorted(v) if isinstance(v, (set, frozenset)) else v
        for k, v in d.items()
    }


def _prepare_stream_batch(arrays: dict[str, np.ndarray], space: int):
    """Host-side reconstruction of a StreamBatch (no device lock needed)."""
    import jax.numpy as jnp

    from jepsen_tpu.checkers.stream_lin import STREAM_ARRAYS, StreamBatch

    missing = [k for k in STREAM_ARRAYS if k not in arrays]
    if missing:
        raise ProtocolError(f"missing arrays: {missing}")
    full_read = arrays["full_read"].astype(bool)
    batch = StreamBatch(
        type=jnp.asarray(arrays["type"], jnp.int32),
        f=jnp.asarray(arrays["f"], jnp.int32),
        value=jnp.asarray(arrays["value"], jnp.int32),
        offset=jnp.asarray(arrays["offset"], jnp.int32),
        pos=jnp.asarray(arrays["pos"], jnp.int32),
        mask=jnp.asarray(arrays["mask"].astype(bool)),
        first=jnp.asarray(arrays["first"].astype(bool)),
        full_read=jnp.asarray(full_read),
        space=space,
    )
    return batch, full_read


def _stream_results(t, full_read) -> dict[str, Any]:
    from jepsen_tpu.checkers.stream_lin import stream_lin_tensors_to_results

    results = stream_lin_tensors_to_results(t, full_read.tolist())
    return {
        "op": "result",
        "results": [
            {"stream": _jsonable(r), "valid?": bool(r["valid?"])}
            for r in results
        ],
    }


def _prepare_elle_batch(histories_json: list):
    """Host-side parse + edge inference + packing (the O(total ops) part —
    runs outside the device lock)."""
    from jepsen_tpu.checkers.elle import infer_txn_graph, pack_txn_graphs
    from jepsen_tpu.history.ops import Op

    if not isinstance(histories_json, list) or not histories_json:
        raise ProtocolError("histories must be a non-empty list")
    graphs = [
        infer_txn_graph([Op.from_json(d) for d in history])
        for history in histories_json
    ]
    return graphs, pack_txn_graphs(graphs)


def _elle_results(graphs, t) -> dict[str, Any]:
    from jepsen_tpu.checkers.elle import _classify

    g0 = np.asarray(t.g0)
    g1c = np.asarray(t.g1c)
    g2 = np.asarray(t.g2)
    results = [
        _classify(
            g,
            set(np.nonzero(g0[b])[0].tolist()),
            set(np.nonzero(g1c[b])[0].tolist()),
            set(np.nonzero(g2[b])[0].tolist()),
        )
        for b, g in enumerate(graphs)
    ]
    return {
        "op": "result",
        "results": [
            {"elle": _jsonable(r), "valid?": bool(r["valid?"])}
            for r in results
        ],
    }


class _Handler(socketserver.BaseRequestHandler):
    def handle(self):
        server: CheckerServer = self.server  # type: ignore[assignment]
        while True:
            try:
                # wait for the client's next request outside the frame
                # stage: it times decoding once the first bytes are here
                if not self.request.recv(1, socket.MSG_PEEK):
                    return
                t0 = time.perf_counter()
                with obs_trace.span("service.frame"):
                    header, arrays = recv_frame(self.request)
                decode_s = time.perf_counter() - t0
            except TornPayloadError as e:
                # the frame was fully consumed (connection still in
                # sync): quarantine exactly the poisoned stream, reply,
                # keep serving this connection
                try:
                    send_frame(self.request, server.torn_reply(e))
                except (ProtocolError, ConnectionError, OSError):
                    return
                continue
            except (ProtocolError, ConnectionError, OSError):
                return
            if header.get("op") == "stream-subscribe":
                # push mode: the reply rhythm inverts — the server sends
                # verdict-window frames as segments close, until the
                # terminal window (or the chaos tear) ends the loop
                try:
                    if not self._handle_subscribe(server, header):
                        return
                    continue
                except (ProtocolError, ConnectionError, OSError):
                    return
            try:
                reply = server.dispatch(header, arrays)
                t0 = time.perf_counter()
                with obs_trace.span("service.frame"):
                    send_frame(self.request, reply)
                if header.get("op") == "check":
                    _check_stage_s("frame").add(
                        decode_s + time.perf_counter() - t0
                    )
            except ProtocolError as e:
                send_frame(self.request, {"op": "error", "error": str(e)})
            except Exception as e:  # noqa: BLE001 — report, keep serving
                logger.exception("check failed")
                send_frame(self.request, {"op": "error", "error": repr(e)})

    def _handle_subscribe(self, server: "CheckerServer", header) -> bool:
        """Run one subscription push loop.  Returns True to keep the
        connection (back to the request rhythm after the terminal
        window), False to close it (chaos tear / dead subscriber)."""
        import queue as queue_mod

        server.metrics.counter(
            "service.requests", op="stream-subscribe"
        ).inc()
        svc = server.ingest_service()
        sid = str(header.get("stream"))
        if header.get("stream") is None:
            raise ProtocolError("stream-subscribe requires stream")
        from_window = int(header.get("from_window", 0))
        ack, replay, q = svc.subscribe(sid, from_window)
        if ack.get("op") != "subscribed":
            send_frame(self.request, ack)
            return True
        drop_after = server.take_sub_drop()
        pushed = 0
        final_seen = False
        try:
            send_frame(self.request, ack)
            for w in replay:
                send_frame(self.request, w)
                pushed += 1
                final_seen = final_seen or bool(w.get("final"))
                if drop_after is not None and pushed >= drop_after:
                    logger.error(
                        "%s hook: tearing subscription on %s after %d "
                        "window(s)", SUB_DROP_ENV, sid, pushed,
                    )
                    return False
            if final_seen or q is None:
                if not final_seen:
                    # stream already done but the terminal window fell
                    # outside the replay range: say so, never hang
                    send_frame(self.request, {
                        "op": "subscribe-done", "stream": sid,
                        "pushed": pushed,
                    })
                return True
            deadline = None
            while True:
                try:
                    w = q.get(timeout=0.5)
                except queue_mod.Empty:
                    if deadline is None:
                        deadline = time.monotonic() + SUBSCRIBE_IDLE_TIMEOUT_S
                    elif time.monotonic() > deadline:
                        send_frame(self.request, {
                            "op": "subscribe-timeout", "stream": sid,
                            "idle_s": SUBSCRIBE_IDLE_TIMEOUT_S,
                            "pushed": pushed,
                        })
                        return True
                    continue
                deadline = None
                send_frame(self.request, w)
                pushed += 1
                if drop_after is not None and pushed >= drop_after:
                    logger.error(
                        "%s hook: tearing subscription on %s after %d "
                        "window(s)", SUB_DROP_ENV, sid, pushed,
                    )
                    return False
                if w.get("final"):
                    return True
        finally:
            svc.unsubscribe(sid, q)


class CheckerServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True

    def __init__(
        self,
        host: str = "0.0.0.0",
        port: int = 8640,
        mesh=None,
        metrics_registry=None,
        ingest_opts: dict | None = None,
        cache_capacity: int = 4096,
        store: str | None = None,
    ):
        super().__init__((host, port), _Handler)
        # streaming ingestion (stream-open/feed/finish, submit/collect):
        # built lazily on first streaming op so batch-only deployments
        # never pay the worker pool; constructor knobs flow through
        self._ingest = None
        self._ingest_lock = threading.Lock()
        self._ingest_opts = dict(ingest_opts or {})
        self._cache_capacity = cache_capacity
        self._store = store
        # one device-compute at a time: connections multiplex onto the
        # accelerator serially, which is also the fastest way to use it
        self._device_lock = threading.Lock()
        # optional (hist, seq) mesh: batches shard across every device the
        # runtime can see (a slice, or a pod via jax.distributed)
        self._mesh = mesh
        # the shared obs metrics registry (default: the process-global
        # one): every check op lands its wall latency in a mergeable
        # quantile sketch, which the /metrics endpoint renders as
        # p50/p90/p99 — the ROADMAP direction-1 latency-SLO substrate
        from jepsen_tpu.obs import metrics as obs_metrics

        self.metrics = (
            obs_metrics.REGISTRY
            if metrics_registry is None
            else metrics_registry
        )
        self._metrics_srv = None
        # chaos: arm the one-shot subscription tear from the env
        self._sub_drop: int | None = None
        spec = os.environ.get(SUB_DROP_ENV)
        if spec:
            try:
                self._sub_drop = int(spec)
            except ValueError:
                logger.error("%s=%r malformed (want int); ignoring",
                             SUB_DROP_ENV, spec)

    def take_sub_drop(self) -> int | None:
        """Consume the one-shot torn-subscription chaos hook (the first
        subscriber gets torn; its reconnect must find a healthy loop)."""
        with self._ingest_lock:
            n, self._sub_drop = self._sub_drop, None
            return n

    @property
    def port(self) -> int:
        return self.server_address[1]

    def start_metrics(
        self,
        host: str = "0.0.0.0",
        port: int = 9640,
        store: str | None = None,
    ):
        """Serve the shared registry as Prometheus text on
        ``GET http://host:port/metrics`` — and, when ``store`` is
        given, per-run reports on ``GET /report/<run>`` (rendered on
        demand from the store tree) plus ``GET /report/by-key/<key>``
        (content-addressed verdict-cache lookup, 302 to the recorded
        run); returns the HTTP server (``.server_address[1]`` carries
        the bound port)."""
        from jepsen_tpu.obs import metrics as obs_metrics

        self._metrics_srv = obs_metrics.serve_metrics(
            host, port, self.metrics, store=store,
            # lazy: the ingest core (and with it the cache) may not be
            # built yet when the metrics endpoint comes up
            cache=lambda: (
                self._ingest.cache if self._ingest is not None else None
            ),
        )
        self._metrics_srv.start_background()
        return self._metrics_srv

    def server_close(self):
        if self._metrics_srv is not None:
            self._metrics_srv.shutdown()
            self._metrics_srv.server_close()
            self._metrics_srv = None
        if self._ingest is not None:
            self._ingest.close()
            self._ingest = None
        super().server_close()

    def ingest_service(self):
        """The lazily-built streaming ingestion core (thread-safe)."""
        if self._ingest is None:
            with self._ingest_lock:
                if self._ingest is None:
                    from jepsen_tpu.service.cache import VerdictCache
                    from jepsen_tpu.service.stream import IngestService

                    cache = VerdictCache(
                        capacity=self._cache_capacity,
                        registry=self.metrics,
                    )
                    if self._store:
                        try:
                            n = cache.seed_from_store(self._store)
                            if n:
                                logger.info(
                                    "verdict cache seeded with %d "
                                    "recorded run(s) from %s",
                                    n, self._store,
                                )
                        except Exception:  # noqa: BLE001 — serve anyway
                            logger.exception(
                                "cache seed from %s failed", self._store
                            )
                        self._export_fleet_gauges()
                    self._ingest = IngestService(
                        cache=cache,
                        registry=self.metrics,
                        **self._ingest_opts,
                    )
        return self._ingest

    def _export_fleet_gauges(self) -> None:
        """Fleet-memory state of the backing store, as gauges on the
        service registry (visible on ``/metrics``): CAS dedup ratio,
        prefix-checkpoint index size, and per-config regression flags
        (``jepsen_tpu/report/baselines.py``).  Pure telemetry — any
        failure here costs a gauge, never the service."""
        try:
            from jepsen_tpu.history.cas import dedup_stats

            ds = dedup_stats(self._store)
            self.metrics.gauge("fleet.cas_dedup_ratio").set(ds["ratio"])
            self.metrics.gauge("fleet.cas_objects").set(
                ds.get("unique_objects", 0)
            )
        except Exception:  # noqa: BLE001 — telemetry only
            logger.debug("cas dedup gauge skipped", exc_info=True)
        try:
            import os as _os

            from jepsen_tpu.history.prefix_index import (
                DEFAULT_INDEX_DIR,
                PrefixCheckpointIndex,
            )

            st = PrefixCheckpointIndex(
                _os.path.join(self._store, DEFAULT_INDEX_DIR)
            ).stats()
            self.metrics.gauge("fleet.prefix_index_entries").set(
                st["entries"]
            )
        except Exception:  # noqa: BLE001 — telemetry only
            logger.debug("prefix index gauge skipped", exc_info=True)
        try:
            from jepsen_tpu.report.baselines import collect_baselines

            collect_baselines(self._store, registry=self.metrics)
        except Exception:  # noqa: BLE001 — telemetry only
            logger.debug("baseline gauges skipped", exc_info=True)

    def torn_reply(self, e: TornPayloadError) -> dict[str, Any]:
        """Map a torn frame to its stream: poison evidence quarantines
        exactly that stream (never folded into a verdict); torn frames
        outside a stream are a plain error reply."""
        hdr = e.header
        sid = hdr.get("stream")
        if hdr.get("op") == "stream-feed" and sid is not None:
            self.metrics.counter(
                "service.torn_blocks", op="stream-feed"
            ).inc()
            return self.ingest_service().quarantine_stream(
                str(sid),
                f"torn block on the wire (seq {hdr.get('seq')}): {e}",
            )
        return {"op": "error", "error": str(e), "torn": e.torn}

    def dispatch(
        self, header: dict[str, Any], arrays: dict[str, np.ndarray]
    ) -> dict[str, Any]:
        op = header.get("op")
        if op in ("check", "check-stream", "check-elle"):
            # per-thread track (the handler thread's name), NOT one
            # shared "service" track: concurrent requests overlap in
            # time (t0 is taken before the device lock), and overlapping
            # spans on one tid would render as bogus nesting
            with obs_trace.span(f"service.{op}"):
                t0 = time.perf_counter()
                try:
                    reply = self._dispatch(op, header, arrays)
                except Exception:
                    self.metrics.counter("service.errors", op=op).inc()
                    raise
                dt = time.perf_counter() - t0
            self.metrics.counter("service.requests", op=op).inc()
            self.metrics.counter("service.histories", op=op).inc(
                len(reply.get("results", ()))
            )
            self.metrics.sketch("service.check_latency_s", op=op).add(dt)
            return reply
        if op in _STREAM_OPS:
            self.metrics.counter("service.requests", op=op).inc()
        return self._dispatch(op, header, arrays)

    def _dispatch(
        self, op, header: dict[str, Any], arrays: dict[str, np.ndarray]
    ) -> dict[str, Any]:
        if op in _STREAM_OPS:
            return self._dispatch_stream(op, header, arrays)
        if op == "ping":
            import jax

            return {
                "op": "pong",
                "backend": jax.default_backend(),
                "device_count": jax.device_count(),
            }
        if op == "check":
            value_space = int(header.get("value_space", 0))
            if value_space <= 0:
                raise ProtocolError("value_space must be positive")
            with _check_stage("lock"):
                self._device_lock.acquire()
            try:
                return _check_arrays(arrays, value_space, mesh=self._mesh)
            finally:
                self._device_lock.release()
        if op == "check-stream":
            space = int(header.get("space", 0))
            if space <= 0:
                raise ProtocolError("space must be positive")
            append_fail = header.get("append-fail", "definite")
            if append_fail not in ("definite", "indeterminate"):
                raise ProtocolError(f"unknown append-fail {append_fail!r}")
            batch, full_read = _prepare_stream_batch(arrays, space)
            with self._device_lock:
                if self._mesh is not None:
                    from jepsen_tpu.parallel.mesh import (
                        HIST_AXIS,
                        sharded_stream_lin,
                    )

                    batch, nb = _pad_batch_axis(
                        batch, self._mesh.shape[HIST_AXIS]
                    )
                    t = sharded_stream_lin(
                        batch, self._mesh, append_fail=append_fail
                    )
                    full_read = np.pad(full_read, (0, batch.batch - nb))
                else:
                    from jepsen_tpu.checkers.stream_lin import (
                        stream_lin_tensor_check,
                    )

                    nb = len(full_read)
                    t = stream_lin_tensor_check(
                        batch, append_fail=append_fail
                    )
            reply = _stream_results(t, full_read)
            for r in reply["results"]:
                r["stream"]["append-fail"] = append_fail
            reply["results"] = reply["results"][:nb]
            return reply
        if op == "check-elle":
            graphs, batch = _prepare_elle_batch(header.get("histories"))
            with self._device_lock:
                if self._mesh is not None:
                    from jepsen_tpu.parallel.mesh import (
                        HIST_AXIS,
                        sharded_elle,
                    )

                    batch, _nb = _pad_batch_axis(
                        batch, self._mesh.shape[HIST_AXIS]
                    )
                    t = sharded_elle(batch, self._mesh)
                else:
                    from jepsen_tpu.checkers.elle import elle_tensor_check

                    t = elle_tensor_check(batch)
            # _elle_results iterates the (unpadded) graphs, so padded rows
            # drop out naturally
            return _elle_results(graphs, t)
        raise ProtocolError(f"unknown op {op!r}")

    def _dispatch_stream(
        self, op, header: dict[str, Any], arrays: dict[str, np.ndarray]
    ) -> dict[str, Any]:
        """The always-on streaming surface: every reply is a plain
        machine-readable dict (``opened`` / ``accepted`` / ``rejected``
        with ``SATURATED`` / ``quarantined`` / a verdict) — admission
        decisions are data, not exceptions."""
        svc = self.ingest_service()
        if op == "stream-open":
            workload = header.get("workload")
            if not workload:
                raise ProtocolError("stream-open requires workload")
            return svc.open(
                str(workload),
                opts=header.get("opts") or {},
                content_key=header.get("content_key"),
                deadline_s=header.get("deadline_s"),
            )
        if op == "stream-feed":
            sid = header.get("stream")
            seq = header.get("seq")
            if sid is None or seq is None:
                raise ProtocolError("stream-feed requires stream and seq")
            if "rows" in arrays:
                payload = arrays["rows"]
                bkind = "rows"
                n_ops = int(header.get("n_ops", payload.shape[0]))
            elif "ops_block" in header:
                payload = header["ops_block"]
                bkind = "ops"
                n_ops = int(header.get("n_ops", len(payload)))
            else:
                raise ProtocolError(
                    "stream-feed requires a rows array or an ops_block"
                )
            return svc.feed(str(sid), int(seq), bkind, payload, n_ops)
        if op == "stream-finish":
            sid = header.get("stream")
            if sid is None:
                raise ProtocolError("stream-finish requires stream")
            verdict = svc.finish(str(sid), timeout=header.get("timeout"))
            if "op" not in verdict:
                verdict = dict(verdict)
                verdict["op"] = "verdict"
            return verdict
        if op == "stream-abort":
            sid = header.get("stream")
            if sid is None:
                raise ProtocolError("stream-abort requires stream")
            return svc.abort(str(sid))
        if op == "submit-batch":
            # the fleet path: one frame = many histories (concatenated
            # rows + offsets), one admission decision each
            workload = header.get("workload")
            if not workload:
                raise ProtocolError("submit-batch requires workload")
            if "rows" not in arrays or "offsets" not in arrays:
                raise ProtocolError(
                    "submit-batch requires rows and offsets arrays"
                )
            rows = arrays["rows"]
            offsets = np.asarray(arrays["offsets"], np.int64)
            n_ops = header.get("n_ops") or []
            keys = header.get("content_keys") or []
            opts = header.get("opts") or {}
            replies = []
            for i in range(len(offsets) - 1):
                blk = rows[int(offsets[i]) : int(offsets[i + 1])]
                replies.append(svc.submit(
                    str(workload), opts, "rows", blk,
                    int(n_ops[i]) if i < len(n_ops) else blk.shape[0],
                    content_key=keys[i] if i < len(keys) else None,
                ))
            return {"op": "submitted", "replies": replies}
        if op == "collect":
            ids = header.get("ids") or []
            return svc.collect(
                [str(i) for i in ids],
                timeout=float(header.get("timeout", 0.0)),
            )
        if op == "cache-get":
            key = header.get("content_key")
            if not key:
                raise ProtocolError("cache-get requires content_key")
            if svc.cache is None:
                return {"op": "miss"}
            from jepsen_tpu.service.cache import cache_key

            entry = svc.cache.get(cache_key(
                str(key), str(header.get("workload", "queue")),
                header.get("opts") or {},
            ))
            if entry is None:
                return {"op": "miss"}
            out = {"op": "cached", "verdict": entry["verdict"]}
            if "report_ref" in entry:
                out["report_ref"] = entry["report_ref"]
            return out
        if op == "service-stats":
            stats = svc.stats()
            stats["op"] = "stats"
            return stats
        raise ProtocolError(f"unknown stream op {op!r}")

    def start_background(self) -> threading.Thread:
        t = threading.Thread(target=self.serve_forever, daemon=True)
        t.start()
        return t


def serve_forever(
    host: str = "0.0.0.0",
    port: int = 8640,
    seq: int = 1,
    store: str = "store",
    metrics_port: int = 9640,
    workers: int = 2,
    max_streams: int = 256,
    ingress_cap: int = 1024,
    stream_deadline_s: float = 120.0,
    batch: bool = False,
    target_batch: int = 32,
    max_batch_wait_ms: float = 25.0,
    warmup: bool = False,
    warmup_buckets=((128, 128), (256, 256)),
) -> None:
    import jax

    from jepsen_tpu.utils.jaxenv import (
        device_info,
        enable_compilation_cache,
        ensure_backend,
    )

    enable_compilation_cache(ensure_backend())
    dev = device_info()
    mesh = None
    if jax.device_count() > 1:
        from jepsen_tpu.parallel.distributed import global_checker_mesh

        mesh = global_checker_mesh(seq=seq)
    srv = CheckerServer(
        host, port, mesh=mesh, store=store,
        ingest_opts={
            "workers": workers,
            "max_streams": max_streams,
            "ingress_cap": ingress_cap,
            "stream_deadline_s": stream_deadline_s,
            # continuous batching (ISSUE 20): cross-stream coalescing
            # with AOT bucket warmup off the latency path
            "batch": batch,
            "target_batch": target_batch,
            "max_batch_wait_ms": max_batch_wait_ms,
            "warmup": warmup,
            "warmup_buckets": tuple(warmup_buckets),
        },
    )
    if batch and warmup:
        # the batcher (and its AOT warmup) is built lazily with the
        # ingest core — force it NOW so the compile happens at service
        # start, not on the first admitted stream's latency path
        srv.ingest_service()
    metrics_note = "off"
    if metrics_port >= 0:
        try:
            msrv = srv.start_metrics(host, metrics_port, store=store)
            metrics_note = (
                f"http://{host}:{msrv.server_address[1]}/metrics "
                f"(+ /report/<run> over {store})"
            )
        except OSError as e:
            # a busy metrics port must not take the checker down — the
            # sidecar's job is verdicts; scraping is best-effort
            print(f"warning: /metrics endpoint unavailable ({e}); "
                  f"serving checks without it")
    print(
        f"checker sidecar on {host}:{srv.port} (device: "
        f"platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}, "
        f"mesh={dict(mesh.shape) if mesh else None}, "
        f"metrics={metrics_note})"
    )
    try:
        srv.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        srv.server_close()
