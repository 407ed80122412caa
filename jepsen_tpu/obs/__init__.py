"""Flight recorder: the unified observability subsystem (OBSERVABILITY.md).

Three layers, threaded through every hot path of the bytes→verdict
pipeline instead of the per-module ad-hoc timers they replace:

- :mod:`jepsen_tpu.obs.trace` — a low-overhead thread-safe ring-buffer
  span tracer (monotonic-clock spans with lane/thread/device track ids,
  nesting, instant events).  Off by default; the disabled path costs one
  global read and zero allocations per span.
- :mod:`jepsen_tpu.obs.metrics` — a registry of counters, gauges, and
  mergeable log-bucketed quantile sketches (p50/p99 without storing
  every sample), with Prometheus text rendering for the service
  sidecar's ``/metrics`` endpoint.
- :mod:`jepsen_tpu.obs.export` — Chrome-trace/Perfetto JSON emission of
  the recorded ring.

While a ``jax.profiler`` session records, every span also lands in the
profiler's capture on the device trace's clock (the tracer's second
sink).
"""
