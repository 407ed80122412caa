"""Differential contract of the pipelined bytes-to-verdict executor.

``parallel/pipeline.py`` must produce verdicts IDENTICAL to the serial
checker paths for every family — queue (both sub-verdicts), stream
(short and 10k-op), elle (including degenerate-history host-fallback
splices) — from history FILES, pipelined and strictly serial, warm and
cold caches.  Plus both crash contracts: under ``fail_fast=True`` a
stage failure aborts the whole run with ``PipelineError`` and NO
verdict escapes for any batch (preserved verbatim from PR 4); under
the elastic default a failing chunk is retried then isolated per
history, the crasher quarantines as an explicit ``unknown`` with
evidence, and every other verdict survives (PR 13; the deeper proofs
live in ``tests/test_elastic.py``).
"""

from __future__ import annotations

import numpy as np
import pytest

from jepsen_tpu.checkers.elle import check_elle_cpu
from jepsen_tpu.checkers.queue_lin import check_queue_lin_cpu
from jepsen_tpu.checkers.stream_lin import check_stream_lin_cpu
from jepsen_tpu.checkers.total_queue import check_total_queue_cpu
from jepsen_tpu.history.store import write_history_jsonl
from jepsen_tpu.history.synth import (
    ElleSynthSpec,
    StreamSynthSpec,
    SynthSpec,
    synth_batch,
    synth_elle_batch,
    synth_stream_batch,
)
from jepsen_tpu.parallel.pipeline import (
    PipelineError,
    PipelineStats,
    check_sources,
    run_pipeline,
)


def _write(tmp_path, base):
    files = []
    for i, sh in enumerate(base):
        p = tmp_path / f"h{i:03d}.jsonl"
        write_history_jsonl(p, sh.ops)
        files.append(p)
    return files


@pytest.fixture(scope="module")
def stream_corpus(tmp_path_factory):
    base = synth_stream_batch(
        14,
        StreamSynthSpec(n_ops=40),
        lost=2,
        duplicated=1,
        divergent=1,
        reorder=1,
        recovered=1,
    )
    td = tmp_path_factory.mktemp("stream")
    return base, _write(td, base)


@pytest.fixture(scope="module")
def queue_corpus(tmp_path_factory):
    base = synth_batch(
        12, SynthSpec(n_ops=50), lost=1, duplicated=1, unexpected=1
    )
    td = tmp_path_factory.mktemp("queue")
    return base, _write(td, base)


class TestStreamDifferential:
    def test_pipeline_equals_serial_equals_cpu(self, stream_corpus):
        base, files = stream_corpus
        piped, _ = check_sources("stream", files, chunk=4, depth=2)
        serial, _ = check_sources("stream", files, chunk=4, serial=True)
        assert piped == serial, "pipelined verdicts diverged from serial"
        for r, sh in zip(piped, base):
            cpu = check_stream_lin_cpu(sh.ops)
            assert r["stream"]["valid?"] == cpu["valid?"]
            for k in ("lost", "duplicate", "phantom", "divergent"):
                assert r["stream"][k] == cpu[k], k

    def test_warm_cache_run_identical(self, stream_corpus):
        """Second run hits the digest-guarded columnar substrate (the
        ``.jtc`` that replaced ``stream_rows.npz``); verdicts must be
        byte-identical to the cold run."""
        _base, files = stream_corpus
        cold, _ = check_sources("stream", files, chunk=8, use_cache=True)
        warm, _ = check_sources("stream", files, chunk=8, use_cache=True)
        assert cold == warm
        from jepsen_tpu.history.columnar import jtc_path_for

        assert jtc_path_for(files[0]).exists()

    def test_long_histories_chunked(self, tmp_path):
        """The stream_10k shape (longer rows, several chunks, tail chunk
        shorter than the pad) through the executor."""
        base = synth_stream_batch(5, StreamSynthSpec(n_ops=400), lost=1)
        files = _write(tmp_path, base)
        piped, stats = check_sources("stream", files, chunk=2)
        assert stats.histories == 5 and stats.batches == 3
        for r, sh in zip(piped, base):
            assert (
                r["stream"]["valid?"]
                == check_stream_lin_cpu(sh.ops)["valid?"]
            )


MB = 1_000_000


@pytest.mark.parametrize(
    "n, sizes, chunk, hist, want",
    [
        # the stream campaign: 64 x ~2.9 MB asks for 8 chunks by bytes
        pytest.param(64, [2_930_000] * 8, 64, 1, (8, "bytes"), id="stream"),
        # the queue campaign: 1000 x ~86 KB keeps 64-history chunks
        pytest.param(1000, [86_000] * 8, 64, 1, (64, "count"), id="queue"),
        # a caller's smaller chunk already cuts finer than the bytes ask
        pytest.param(64, [2_930_000] * 8, 4, 1, (4, "count"),
                     id="small-chunk"),
        # ... and a chunk between the two still caps what the bytes give
        pytest.param(64, [2_930_000] * 8, 32, 1, (8, "bytes"),
                     id="mid-chunk"),
        # 64 histories / 8 chunks = 8, rounded up to the hist extent 3
        pytest.param(64, [2_930_000] * 8, 64, 3, (9, "bytes"), id="mesh"),
        # rounding to the extent that reaches the cap keeps the count rule
        pytest.param(64, [2_930_000] * 8, 16, 16, (16, "count"),
                     id="mesh-cap"),
        # the sample's mean stands for every source
        pytest.param(64, [2_000_000, 3_860_000], 64, 1, (8, "bytes"),
                     id="sample-mean"),
        pytest.param(1, [2_930_000], 64, 1, (64, "count"), id="one-source"),
        pytest.param(1, [200 * MB], 64, 1, (1, "bytes"),
                     id="one-huge-source"),
        # substrates, not paths: the count rule
        pytest.param(64, None, 64, 1, (64, "count"), id="not-paths"),
    ],
)
def test_plan_chunk(n, sizes, chunk, hist, want):
    from jepsen_tpu.parallel.pipeline import _chunks, _pad_for, _plan_chunk

    assert _plan_chunk(n, sizes, chunk, hist) == want
    if n == 1000:
        # today's chunks: the 40-history tail is padded to the 64
        tail = _chunks(list(range(n)), want[0])[-1]
        assert len(tail) == 40 and _pad_for(want[0], {}) == 64


class TestChunkByBytes:
    """A call whose files hold more than ``CHUNK_BYTES`` a chunk is cut
    into several chunks: the verdicts are those of one chunk, in order,
    and those of the serial path."""

    @staticmethod
    def _split(monkeypatch, files, parts):
        """Set ``CHUNK_BYTES`` so that the call asks for ``parts``
        chunks by its sampled bytes."""
        from jepsen_tpu.parallel import pipeline

        sample = pipeline._sample_sizes(files)
        monkeypatch.setattr(pipeline, "CHUNK_BYTES", -(
            -sum(sample) * len(files) // (len(sample) * parts)))

    @staticmethod
    def _by_bytes():
        from jepsen_tpu.obs import metrics as obs_metrics

        return obs_metrics.REGISTRY.value("pipeline.chunk_plans", by="bytes")

    def test_sample_sizes(self, stream_corpus, tmp_path):
        from jepsen_tpu.parallel.pipeline import SIZE_SAMPLE, _sample_sizes

        _base, files = stream_corpus
        assert _sample_sizes(files) == [
            p.stat().st_size for p in files[:SIZE_SAMPLE]
        ]
        # a file that cannot be stat-ed weighs nothing; the producer
        # reports it
        assert _sample_sizes([tmp_path / "gone.jsonl", files[0]]) == [
            0, files[0].stat().st_size
        ]
        assert _sample_sizes([np.zeros((1, 6), np.int32)]) is None

    def test_split_equals_one_chunk_and_serial(
        self, stream_corpus, monkeypatch
    ):
        _base, files = stream_corpus
        one, one_stats = check_sources("stream", files)
        assert one_stats.batches == 1
        self._split(monkeypatch, files, 3)
        before = self._by_bytes()
        split, stats = check_sources("stream", files)
        assert self._by_bytes() == before + 1
        assert stats.batches == 3  # 14 histories: 5, 5, 4
        serial, _ = check_sources("stream", files, serial=True)
        assert split == one == serial

    def test_split_reduced_on_mesh(self, tmp_path, cpu_devices,
                                   monkeypatch):
        """Reduce mode: the chunks' gids are offsets by the planned
        length, so ``first_invalid`` names the same source."""
        from jepsen_tpu.parallel.mesh import checker_mesh

        # valid histories first: the first invalid one lies past the
        # first chunk
        files = _write(tmp_path, synth_stream_batch(
            7, StreamSynthSpec(n_ops=40)) + synth_stream_batch(
            7, StreamSynthSpec(n_ops=40, seed=100), lost=1))
        per = [r["stream"]["valid?"]
               for r in check_sources("stream", files)[0]]
        bad = [i for i, v in enumerate(per) if v is False]
        assert bad and bad[0] >= 6
        want = {"histories": len(files), "invalid": len(bad),
                "first_invalid": bad[0], "quarantined": 0, "dropped": 0}
        mesh = checker_mesh(cpu_devices[:2], hist=2)
        one, _ = check_sources("stream", files, mesh=mesh, reduce=True)
        self._split(monkeypatch, files, 3)
        before = self._by_bytes()
        # 14 / 3 -> 5, rounded up to the hist extent: chunks of 6, 6, 2
        split, stats = check_sources("stream", files, mesh=mesh,
                                     reduce=True)
        assert self._by_bytes() == before + 1
        assert stats.batches == 3
        assert split == one == want


class TestQueueDifferential:
    def test_pipeline_equals_serial_equals_cpu(self, queue_corpus):
        base, files = queue_corpus
        piped, _ = check_sources("queue", files, chunk=5)
        serial, _ = check_sources("queue", files, chunk=5, serial=True)
        assert piped == serial
        for r, sh in zip(piped, base):
            tq = check_total_queue_cpu(sh.ops)
            ql = check_queue_lin_cpu(sh.ops)
            assert r["queue"]["valid?"] == tq["valid?"]
            assert r["queue"]["lost"] == tq["lost"]
            assert r["linear"]["valid?"] == ql["valid?"]

    def test_result_keys_match_serial_class_path(self, queue_corpus):
        """Byte-identical to the SERIAL checker classes, including the
        recorded contract level: `linear.delivery` feeds a later bare
        re-check's no-silent-tightening inheritance (cmd_check)."""
        from jepsen_tpu.checkers.queue_lin import check_queue_lin_batch
        from jepsen_tpu.checkers.total_queue import check_total_queue_cpu

        base, files = queue_corpus
        piped, _ = check_sources("queue", files, chunk=4)
        ql = check_queue_lin_batch([sh.ops for sh in base])
        for r, serial_lin, sh in zip(piped, ql, base):
            assert r["linear"] == serial_lin
            tq = check_total_queue_cpu(sh.ops)
            for k in ("valid?", "lost", "duplicated", "unexpected"):
                assert r["queue"][k] == tq[k], k

    def test_delivery_contract_threads_through(self, queue_corpus):
        base, files = queue_corpus
        alo, _ = check_sources(
            "queue", files, chunk=6, delivery="at-least-once"
        )
        for r, sh in zip(alo, base):
            assert (
                r["linear"]["valid?"]
                == check_queue_lin_cpu(sh.ops, delivery="at-least-once")[
                    "valid?"
                ]
            )


class TestElleDifferential:
    def test_pipeline_equals_serial_equals_cpu_with_degenerates(
        self, tmp_path
    ):
        """Corpus splicing tensor-checkable and DEGENERATE histories
        (cross-key phantom collisions — the host-fallback class from the
        elle device-inference fuzz) through one pipelined run."""
        from test_fuzz_elle_device import fuzz_history

        from jepsen_tpu.checkers.elle import elle_mops_for

        class _SH:  # _write expects .ops
            def __init__(self, ops):
                self.ops = ops

        base = [_SH(fuzz_history(seed, n_txns=12)) for seed in range(8)]
        degen = [
            elle_mops_for(sh.ops)[1].degenerate for sh in base
        ]
        assert any(degen), "corpus must exercise the degenerate fallback"
        assert not all(degen), "corpus must exercise the device path too"
        files = _write(tmp_path, base)
        piped, _ = check_sources("elle", files, chunk=3)
        serial, _ = check_sources("elle", files, chunk=3, serial=True)
        assert piped == serial
        for r, sh in zip(piped, base):
            cpu = check_elle_cpu(sh.ops)
            assert r["elle"]["valid?"] == cpu["valid?"]
            for k in ("G0", "G1c", "G2", "G1a", "G1b",
                      "incompatible-order"):
                assert r["elle"][k] == cpu[k], k

    def test_synthetic_anomalies(self, tmp_path):
        base = synth_elle_batch(
            8, ElleSynthSpec(n_txns=10), g1a=1, g1b=1, g2_cycle=1
        )
        files = _write(tmp_path, base)
        piped, _ = check_sources("elle", files, chunk=4)
        for r, sh in zip(piped, base):
            assert r["elle"]["valid?"] == check_elle_cpu(sh.ops)["valid?"]


class TestCrashContract:
    """``fail_fast=True``: the PR-4 abort-all contract, preserved
    verbatim.  The elastic default's quarantine contract lives in
    :class:`TestElasticQuarantine` and ``tests/test_elastic.py``."""

    def test_produce_crash_emits_no_verdicts(self):
        """A crash in the host stage of batch k aborts the run with NO
        results for any batch — earlier chunks' verdicts never escape."""
        produced = []

        def produce(i):
            if i == 2:
                raise RuntimeError("packer exploded")
            produced.append(i)
            return np.full((4,), i, np.int32)

        import jax.numpy as jnp

        with pytest.raises(PipelineError, match="produce stage crashed"):
            run_pipeline(
                list(range(5)), produce, lambda x: jnp.asarray(x) + 1,
                fail_fast=True,
            )
        assert produced == [0, 1]

    def test_check_crash_emits_no_verdicts(self):
        def check(x):
            if int(np.asarray(x)[0]) == 1:
                raise ValueError("bad batch on device")
            import jax.numpy as jnp

            return jnp.asarray(x) + 1

        with pytest.raises(PipelineError, match="check stage crashed"):
            run_pipeline(
                list(range(4)),
                lambda i: np.full((2,), i, np.int32),
                check,
                fail_fast=True,
            )

    def test_unpacked_batch_never_reaches_check(self, tmp_path):
        """check_sources --fail-fast: a corrupt history file mid-corpus
        aborts the whole run (no partial verdict list escapes)."""
        base = synth_stream_batch(4, StreamSynthSpec(n_ops=20))
        files = _write(tmp_path, base)
        bad = tmp_path / "h999.jsonl"
        bad.write_text('{"type": "not a real op"\n')  # torn JSON line
        with pytest.raises((PipelineError, Exception)):
            check_sources(
                "stream", files[:2] + [bad] + files[2:], chunk=2,
                fail_fast=True,
            )

    def test_crashed_producer_does_not_wedge(self):
        """The bounded queue must not deadlock the producer thread when
        the consumer dies first (abort flag re-checked on full puts)."""
        import jax.numpy as jnp

        def check(x):
            raise ValueError("dies immediately")

        with pytest.raises(PipelineError):
            run_pipeline(
                list(range(64)),
                lambda i: np.full((1,), i, np.int32),
                check,
                depth=1,
                fail_fast=True,
            )


class TestElasticQuarantine:
    """The default (PR 13) contract: work-unit isolation — a crashing
    chunk is retried, then isolated per history; only the crasher
    quarantines (explicit ``unknown`` with the exception as evidence)
    and every other verdict survives ≡ serial."""

    def test_produce_crash_quarantines_only_its_item(self):
        from jepsen_tpu.parallel.pipeline import Quarantined

        def produce(i):
            if i == 2:
                raise RuntimeError("packer exploded")
            return np.full((4,), i, np.int32)

        import jax.numpy as jnp

        res, stats = run_pipeline(
            list(range(5)), produce, lambda x: jnp.asarray(x) + 1
        )
        assert isinstance(res[2], Quarantined)
        assert res[2].stage == "produce"
        assert "packer exploded" in res[2].evidence()["errors"][-1]
        for i in (0, 1, 3, 4):
            assert not isinstance(res[i], Quarantined)
            assert int(np.asarray(res[i])[0]) == i + 1
        # the retry is counted — requeues are evidence, not log lines
        assert stats.unit_retries >= 1

    def test_corrupt_history_mid_corpus_quarantines_one(self, tmp_path):
        """A torn-JSON history inside a chunk quarantines exactly ITSELF
        (per-history isolation inside the failed chunk), the other
        members' verdicts equal the serial oracle, and the composed
        verdict can never be valid."""
        from jepsen_tpu.checkers.protocol import merge_valid

        base = synth_stream_batch(6, StreamSynthSpec(n_ops=20), lost=1)
        files = _write(tmp_path, base)
        bad = tmp_path / "h999.jsonl"
        bad.write_text('{"type": "not a real op"\n')  # torn JSON line
        mix = files[:2] + [bad] + files[2:]
        res, stats = check_sources("stream", mix, chunk=4)
        assert len(res) == 7
        assert res[2]["stream"]["valid?"] == "unknown"
        ev = res[2]["stream"]["quarantined"]
        assert ev["errors"], "quarantine must carry the exception"
        serial, _ = check_sources("stream", files, chunk=4, serial=True)
        assert [r for i, r in enumerate(res) if i != 2] == serial
        assert stats.quarantined == 1
        # precedence: unknown can never fold into valid; the seeded
        # lost-write invalid still trumps it
        assert merge_valid(r["stream"]["valid?"] for r in res) is False
        clean = [r["stream"]["valid?"] for i, r in enumerate(res)
                 if i == 2 or r["stream"]["valid?"] is True]
        assert merge_valid(clean) == "unknown"


class TestStatsAndMesh:
    def test_stats_schema(self, stream_corpus):
        _base, files = stream_corpus
        _res, stats = check_sources("stream", files, chunk=4)
        assert isinstance(stats, PipelineStats)
        assert stats.histories == len(files)
        assert 0.0 <= stats.stage_overlap_frac <= 1.0
        assert 0.0 <= stats.device_idle_frac <= 1.0
        assert stats.wall_s > 0

    @pytest.mark.parametrize("workload", ["stream", "queue", "elle"])
    def test_mesh_dispatch_matches_single_device(
        self, cpu_devices, tmp_path, workload
    ):
        """The pipeline's mesh placement (parallel/mesh.py sharded
        dispatch) yields the same verdicts as the default placement."""
        from jepsen_tpu.parallel.mesh import checker_mesh

        if workload == "stream":
            base = synth_stream_batch(6, StreamSynthSpec(n_ops=30), lost=1)
        elif workload == "queue":
            base = synth_batch(6, SynthSpec(n_ops=40), lost=1)
        else:
            base = synth_elle_batch(6, ElleSynthSpec(n_txns=8), g1a=1)
        files = _write(tmp_path, base)
        mesh = checker_mesh(cpu_devices)
        meshed, _ = check_sources(workload, files, chunk=3, mesh=mesh)
        plain, _ = check_sources(workload, files, chunk=3)
        assert meshed == plain

    def test_mesh_elle_with_degenerate_splice(self, cpu_devices, tmp_path):
        """A degenerate history shrinks a chunk's LIVE batch below the
        mesh's hist divisibility: the producer must re-pad, not crash."""
        from test_fuzz_elle_device import fuzz_history

        from jepsen_tpu.checkers.elle import elle_mops_for
        from jepsen_tpu.parallel.mesh import checker_mesh

        class _SH:
            def __init__(self, ops):
                self.ops = ops

        base = [_SH(fuzz_history(seed, n_txns=10)) for seed in range(8)]
        assert any(
            elle_mops_for(sh.ops)[1].degenerate for sh in base
        ), "corpus lost its degenerate member"
        files = _write(tmp_path, base)
        mesh = checker_mesh(cpu_devices)
        meshed, _ = check_sources("elle", files, chunk=4, mesh=mesh)
        plain, _ = check_sources("elle", files, chunk=4)
        assert meshed == plain


class TestNativeMultiFile:
    """Thread-pool multi-file native entry points == per-file calls."""

    @pytest.fixture(autouse=True)
    def _lib(self):
        from jepsen_tpu.history import fastpack

        if fastpack._load() is None:
            pytest.skip("native packer unavailable")

    def test_stream_rows_files(self, stream_corpus):
        from jepsen_tpu.history.fastpack import (
            stream_rows_file,
            stream_rows_files,
        )

        _base, files = stream_corpus
        multi = stream_rows_files(files, threads=3)
        assert multi is not None
        for p, got in zip(files, multi):
            one = stream_rows_file(p)
            assert (got[0] == one[0]).all() and got[1] == one[1]

    def test_pack_files(self, queue_corpus):
        from jepsen_tpu.history.fastpack import pack_file, pack_files

        _base, files = queue_corpus
        multi = pack_files(files, threads=2)
        assert multi is not None
        for p, got in zip(files, multi):
            kind, rows = pack_file(p)
            assert got[0] == kind and (got[1] == rows).all()

    def test_elle_mops_files(self, tmp_path):
        from jepsen_tpu.history.fastpack import (
            elle_mops_file,
            elle_mops_files,
        )

        base = synth_elle_batch(5, ElleSynthSpec(n_txns=8))
        files = _write(tmp_path, base)
        multi = elle_mops_files(files, threads=2)
        assert multi is not None
        for p, got in zip(files, multi):
            mat, meta = elle_mops_file(p)
            gmat, gmeta = got
            assert (gmat == mat).all()
            assert gmeta == meta

    def test_edn_files_fall_back(self, tmp_path):
        """.edn paths are excluded from the native call (per-slot None →
        Python twin), not crashed on."""
        from jepsen_tpu.history.fastpack import stream_rows_files

        base = synth_stream_batch(2, StreamSynthSpec(n_ops=10))
        files = _write(tmp_path, base)
        edn = tmp_path / "history.edn"
        edn.write_text("[]")
        got = stream_rows_files([files[0], edn, files[1]], threads=2)
        assert got is not None
        assert got[0] is not None and got[2] is not None
        assert got[1] is None


class TestStreamRowsCache:
    def test_round_trip_and_staleness(self, tmp_path):
        from jepsen_tpu.checkers.stream_lin import _stream_rows
        from jepsen_tpu.history.store import read_history
        from jepsen_tpu.history.storecache import (
            load_stream_rows_cache,
            save_stream_rows_cache,
            stream_rows_with_cache,
        )

        base = synth_stream_batch(1, StreamSynthSpec(n_ops=25), lost=1)
        (p,) = _write(tmp_path, base)
        cols, full, hit = stream_rows_with_cache(p)
        assert not hit
        ref_cols, ref_full = _stream_rows(read_history(p))
        assert (cols == ref_cols).all() and full == ref_full
        cols2, full2, hit2 = stream_rows_with_cache(p)
        assert hit2 and (cols2 == cols).all() and full2 == full
        # rewriting the history invalidates the cache
        write_history_jsonl(p, base[0].ops[:10])
        got = load_stream_rows_cache(p)
        if got is not None:  # same-mtime-ns race: digest must catch it
            fresh = _stream_rows(read_history(p))
            assert (got[0] == fresh[0]).all()
        _c3, _f3, hit3 = stream_rows_with_cache(p)
        cols4, full4, hit4 = stream_rows_with_cache(p)
        assert hit4
        assert (cols4 == _stream_rows(read_history(p))[0]).all()

    def test_corrupt_cache_ignored(self, tmp_path, caplog):
        """Corruption in EITHER backing store (the ``.jtc`` substrate or
        a legacy npz) must never serve wrong data: the jtc corruption is
        LOGGED (never a silent fallback), COUNTED in the obs registry
        (``jtc.fallback{reason=corrupt}`` — the after-the-run record the
        scrolled-away log line never was, ISSUE 10), and the load
        reports a miss."""
        import logging

        from jepsen_tpu.history.columnar import jtc_path_for
        from jepsen_tpu.history.storecache import (
            load_stream_rows_cache,
            save_stream_rows_cache,
            stream_rows_cache_path,
        )
        from jepsen_tpu.obs.metrics import REGISTRY

        base = synth_stream_batch(1, StreamSynthSpec(n_ops=10))
        (p,) = _write(tmp_path, base)
        save_stream_rows_cache(
            p, np.zeros((1, 6), np.int32), False
        )
        raw = bytearray(jtc_path_for(p).read_bytes())
        raw[-1] ^= 0xFF
        jtc_path_for(p).write_bytes(raw)
        stream_rows_cache_path(p).write_bytes(b"not an npz")
        before = REGISTRY.value("jtc.fallback", reason="corrupt")
        with caplog.at_level(logging.WARNING, "jepsen_tpu.history.columnar"):
            assert load_stream_rows_cache(p) is None
        assert any(
            "corrupt columnar substrate" in r.message for r in caplog.records
        )
        # the counter, not just the log line: triage after the run can
        # ask the registry how many fallbacks happened and why
        assert REGISTRY.value("jtc.fallback", reason="corrupt") >= before + 1
