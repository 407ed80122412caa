"""The tracer's second sink, the JAX profiler, and the spans and counters
at the host layer boundaries (OBSERVABILITY.md, "Program spans on the
profiler's clock").

On the CPU: a ``span()`` under a profiler session lands on the capture's
host plane, nested in the annotation around it; the pipeline's and the
sidecar's stages appear there and advance the global registry's
counters and sketches by exactly the work done."""

from __future__ import annotations

import gzip
import json
import time
from pathlib import Path

import jax
import pytest

from jepsen_tpu.history.store import write_history_jsonl
from jepsen_tpu.history.synth import SynthSpec, synth_batch
from jepsen_tpu.obs import export as obs_export
from jepsen_tpu.obs import trace as obs_trace
from jepsen_tpu.obs.metrics import REGISTRY

PIPELINE_SPANS = {
    "pipeline.produce", "pipeline.place", "pipeline.collect",
    "pipeline.assemble", "pipeline.wait",
}
CHECK_STAGES = ("lock", "place", "kernel", "render", "frame")


@pytest.fixture(autouse=True)
def _tracer_off_after():
    yield
    obs_trace.disable()


def _options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def _host_events(logdir: Path) -> list[tuple[int, str, int, int]]:
    """``(line, name, start_ns, end_ns)`` of every event on the capture's
    host planes."""
    from jax.profiler import ProfileData

    path = sorted(logdir.rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                out.extend((k, ev.name, ev.start_ns, ev.end_ns)
                           for ev in line.events)
    return out


def _corpus(tmp_path: Path, n: int) -> list[Path]:
    files = []
    for i, sh in enumerate(synth_batch(n, SynthSpec(n_ops=40), lost=1)):
        p = tmp_path / f"h{i:03d}.jsonl"
        write_history_jsonl(p, sh.ops)
        files.append(p)
    return files


class TestProfilerSink:
    @pytest.mark.parametrize("ring", [False, True])
    def test_span_lands_nested_on_the_host_plane(self, tmp_path, ring):
        if ring:
            obs_trace.enable(capacity=256)
        with jax.profiler.trace(str(tmp_path), profiler_options=_options()):
            with jax.profiler.TraceAnnotation("outer"):
                with obs_trace.span("obs.inner"):
                    time.sleep(0.002)
        evs = _host_events(tmp_path)
        (outer,) = [e for e in evs if e[1] == "outer"]
        (inner,) = [e for e in evs if e[1] == "obs.inner"]
        assert inner[0] == outer[0]  # one thread, one line
        assert outer[2] <= inner[2] and inner[3] <= outer[3]
        assert inner[3] - inner[2] >= 1_000_000
        # the ring records the span once, as it would with no session
        recs = [r for r in obs_trace.snapshot() if r[1] == "obs.inner"]
        assert len(recs) == (1 if ring else 0)

    def test_after_the_session_span_is_the_shared_noop(self, tmp_path):
        with jax.profiler.trace(str(tmp_path), profiler_options=_options()):
            assert obs_trace.span("a") is not obs_trace.span("a")
        assert obs_trace.span("a") is obs_trace.span("b")

    def test_complete_stays_ring_only(self, tmp_path):
        obs_trace.enable(capacity=256)
        with jax.profiler.trace(str(tmp_path), profiler_options=_options()):
            t0 = time.perf_counter()
            obs_trace.complete("obs.after", t0, t0 + 0.001)
        assert not [e for e in _host_events(tmp_path) if e[1] == "obs.after"]
        assert [r[1] for r in obs_trace.snapshot()] == ["obs.after"]


class TestPipelineBoundaries:
    @pytest.mark.parametrize("mode", ["per_history", "reduce_mesh4", "lanes"])
    def test_capture_and_counters(self, tmp_path, cpu_devices, mode):
        from jepsen_tpu.parallel.pipeline import check_sources

        files = _corpus(tmp_path, 10)
        opts: dict = {"chunk": 4}
        if mode == "reduce_mesh4":
            from jepsen_tpu.parallel.mesh import checker_mesh

            opts.update(mesh=checker_mesh(cpu_devices[:4], hist=4),
                        reduce=True, fail_fast=True)
        elif mode == "lanes":
            opts.update(lanes=2)
        check_sources("queue", files, **opts)  # compile outside the capture

        def read():
            return (REGISTRY.value("pipeline.histories"),
                    REGISTRY.value("pipeline.stage_busy_s", stage="assemble"),
                    REGISTRY.value("pipeline.wait_s", on="produce"))

        h0, a0, w0 = read()
        with jax.profiler.trace(str(tmp_path / "cap"),
                                profiler_options=_options()):
            out, stats = check_sources("queue", files, **opts)
        h1, a1, w1 = read()
        assert stats.histories == len(files)
        assert out["histories"] == len(files) if mode == "reduce_mesh4" \
            else len(out) == len(files)
        assert h1 - h0 == len(files)
        assert a1 > a0
        names = {e[1] for e in _host_events(tmp_path / "cap")}
        if mode == "lanes":
            # lanes claim pre-queued units: no thread waits on a producer
            assert w1 == w0
            assert PIPELINE_SPANS - {"pipeline.wait"} <= names
        else:
            assert w1 > w0
            assert PIPELINE_SPANS <= names
        assert "encode.pack" in names

    def test_assemble_stays_out_of_the_executor_fractions(self):
        from jepsen_tpu.parallel.pipeline import PipelineStats

        stats = PipelineStats()
        stats.add_busy("produce", 0.0, 0.5)
        stats.add_busy("assemble", 0.0, 10.0)
        stats.wall_s = 1.0
        stats.finalize()
        assert stats.stage_overlap_frac == 0.0
        assert stats.device_idle_frac == 1.0
        assert stats.metrics.value(
            "pipeline.stage_busy_s", stage="assemble") == 10.0

    @pytest.mark.parametrize("to_device", [True, False])
    def test_pack_sketch(self, to_device):
        from jepsen_tpu.history.encode import pack_histories

        ops = synth_batch(1, SynthSpec(n_ops=20))[0].ops
        label = "true" if to_device else "false"
        sk = REGISTRY.sketch("encode.pack_s", to_device=label)
        n0 = sk.count
        pack_histories([ops], to_device=to_device)
        assert sk.count == n0 + 1 and sk.sum > 0


class TestServiceStages:
    def test_one_check_advances_each_stage_by_one(self):
        from jepsen_tpu.obs.metrics import Registry
        from jepsen_tpu.service import CheckerClient, CheckerServer

        sketches = {s: REGISTRY.sketch("service.stage_s", op="check",
                                       stage=s) for s in CHECK_STAGES}
        before = {s: sk.count for s, sk in sketches.items()}
        reg = Registry()
        srv = CheckerServer(host="127.0.0.1", port=0, metrics_registry=reg)
        srv.start_background()
        try:
            shs = synth_batch(2, SynthSpec(n_ops=40))
            with CheckerClient(port=srv.port) as client:
                client.ping()  # no check op: no stage moves
                results = client.check_histories([s.ops for s in shs])
                assert len(results) == 2
                # the reply is on its way before the frame stage is added
                deadline = time.monotonic() + 10
                while (sketches["frame"].count == before["frame"]
                       and time.monotonic() < deadline):
                    time.sleep(0.01)
        finally:
            srv.shutdown()
            srv.server_close()
        assert {s: sk.count - before[s] for s, sk in sketches.items()} == {
            s: 1 for s in CHECK_STAGES}
        assert reg.sketch("service.check_latency_s", op="check").count == 1


class TestExport:
    def test_write_trace_takes_no_merge_directory(self, tmp_path):
        obs_trace.enable(capacity=256)
        with obs_trace.span("a"):
            pass
        with pytest.raises(TypeError):
            obs_export.write_trace(tmp_path / "t.json",
                                   merge_jax_profile_dir=tmp_path)
        summary = obs_export.write_trace(tmp_path / "t.json")
        assert set(summary) == {"path", "events", "tracks", "dropped"}
        assert not hasattr(obs_export, "merge_jax_profile")

    def test_trace_command_writes_a_perfetto_capture(self, tmp_path):
        from jepsen_tpu.cli.main import main
        from jepsen_tpu.history.store import Store

        st = Store(tmp_path / "store")
        d = st.run_dir("t", "r0")
        st.save_history(d, synth_batch(1, SynthSpec(n_ops=60))[0].ops)
        prof = tmp_path / "prof"
        rc = main(["trace", "--out", str(tmp_path / "t.json"),
                   "--jax-profile", str(prof), "--",
                   "check", str(d), "--checker", "tpu"])
        assert rc == 0
        (path,) = prof.rglob("perfetto_trace.json.gz")
        doc = json.loads(gzip.decompress(path.read_bytes()))
        names = {ev.get("name") for ev in doc["traceEvents"]}
        assert {"pipeline.produce", "pipeline.assemble"} <= names
