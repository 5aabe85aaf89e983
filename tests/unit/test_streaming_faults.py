"""Fault-tolerant streaming executor (ISSUE 2 tentpole): watchdog,
transient-IO retry, guaranteed join/drain, atomic output commit, and
chunk-journal resume — each proven against injected faults
(variantcalling_tpu/utils/faults.py), not hand-waved.

ISSUE 10 extends this with the SUPERVISED RECOVERY LADDER
(docs/robustness.md): chunk re-dispatch, watchdog v2 (stack dump + one
wedged-chunk retry), device-OOM megabatch-shrink -> dp=1 degradation,
opt-in poison-chunk quarantine, commit-ENOSPC resume, and journal v2
(fsync knob, full-prefix resume verification)."""

import argparse
import json
import os
import pickle
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from tests.conftest import assert_no_stream_leaks
from variantcalling_tpu.parallel.pipeline import (StagePipeline,
                                                  StageTimeoutError,
                                                  on_final_attempt,
                                                  retry_chunk,
                                                  retry_transient)
from variantcalling_tpu.utils import faults

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _partials(out: str) -> list[str]:
    """Every partial next to ``out`` — legacy fixed name plus the
    unique-suffix partials (ISSUE 14: ``<out>.partial.<pid>-<hex>``)."""
    from variantcalling_tpu.io.journal import list_partials

    return list_partials(out)

#: directories the leak sentinel sweeps after every test (the chaos
#: invariant enforced on the regular suite — ISSUE 10 satellite)
_WATCHED_DIRS: list[str] = []


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(autouse=True)
def _leak_sentinel():
    """No ``vctpu-*``/``pipe-*`` thread and no stray
    ``.partial``/``.journal``/``.quarantine`` sidecar survives any test
    in this module."""
    yield
    assert_no_stream_leaks(_WATCHED_DIRS)


# ---------------------------------------------------------------------------
# faults registry mechanics
# ---------------------------------------------------------------------------


def test_unknown_point_rejected():
    with pytest.raises(KeyError):
        faults.arm("no.such.point")


def test_fault_fires_exactly_n_times():
    faults.arm("io.chunk_read", times=2)
    for _ in range(2):
        with pytest.raises(OSError):
            faults.check("io.chunk_read")
    faults.check("io.chunk_read")  # budget spent: no-op
    assert faults.fired("io.chunk_read") == 2


def test_disarmed_check_is_noop():
    faults.check("io.writeback")
    assert faults.fired("io.writeback") == 0


def test_env_arming(monkeypatch):
    monkeypatch.setenv("VCTPU_FAULTS", "io.chunk_read:3,pipeline.stage_hang@7.5")
    faults.reset()
    faults._arm_from_env()
    assert faults._ARMED["io.chunk_read"].times == 3
    assert faults._ARMED["pipeline.stage_hang"].seconds == 7.5
    faults.reset()


def test_injected_hang_is_cancellable():
    faults.arm("pipeline.stage_hang", times=1, seconds=60)
    t0 = time.monotonic()
    t = threading.Thread(target=lambda: faults.check("pipeline.stage_hang"))
    t.start()
    time.sleep(0.1)
    faults.cancel_hangs()
    t.join(timeout=5)
    assert not t.is_alive()
    assert time.monotonic() - t0 < 10


# ---------------------------------------------------------------------------
# retry_transient
# ---------------------------------------------------------------------------


def test_retry_transient_succeeds_after_transient_failures():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] <= 2:
            raise OSError("transient")
        return "ok"

    assert retry_transient(flaky, "test", attempts=3, backoff_s=0.0) == "ok"
    assert calls["n"] == 3


def test_retry_transient_raises_after_budget():
    def always():
        raise OSError("persistent")

    with pytest.raises(OSError, match="persistent"):
        retry_transient(always, "test", attempts=3, backoff_s=0.0)


def test_retry_transient_does_not_retry_foreign_exceptions():
    calls = {"n": 0}

    def typed():
        calls["n"] += 1
        raise ValueError("not IO")

    with pytest.raises(ValueError):
        retry_transient(typed, "test", attempts=5, backoff_s=0.0)
    assert calls["n"] == 1


# ---------------------------------------------------------------------------
# StagePipeline watchdog + teardown
# ---------------------------------------------------------------------------


def test_watchdog_trips_on_hung_stage_and_joins_threads():
    """Acceptance: a hung stage trips the watchdog with a clean error —
    no deadlock, every worker joined."""
    faults.arm("pipeline.stage_hang", times=1, seconds=120)
    pipe = StagePipeline([lambda x: x, lambda x: x], threads=4, timeout=0.5)
    t0 = time.monotonic()
    with pytest.raises(StageTimeoutError, match="no progress"):
        list(pipe.run(range(10)))
    assert time.monotonic() - t0 < 30  # no deadlock-until-timeout-of-CI
    assert pipe.unjoined == []  # every worker joined on the way out
    assert not [t for t in threading.enumerate() if t.name.startswith("pipe-")]


def test_watchdog_names_the_stuck_stage():
    def score_stage(x):
        return x

    faults.arm("pipeline.stage_hang", times=1, seconds=120)
    pipe = StagePipeline([score_stage], threads=2, timeout=0.4)
    # the hang fires via the executor's own injection point; the error
    # names the stage that was busy when the deadline passed
    with pytest.raises(StageTimeoutError, match=r"stage 0 \(score_stage\)"):
        list(pipe.run(range(4)))


def test_injected_stage_exception_propagates_cleanly():
    faults.arm("pipeline.stage", times=1)
    pipe = StagePipeline([lambda x: x], threads=2, timeout=30)
    with pytest.raises(RuntimeError, match="injected fault"):
        list(pipe.run(range(8)))
    assert pipe.unjoined == []


def test_watchdog_disabled_with_zero_timeout():
    pipe = StagePipeline([lambda x: x], threads=2, timeout=0)
    assert list(pipe.run(range(5))) == list(range(5))


# ---------------------------------------------------------------------------
# streaming pipeline end-to-end under injected faults
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def stream_fault_world(tmp_path_factory):
    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.synthetic import make_fixtures, synthetic_forest

    d = str(tmp_path_factory.mktemp("faults"))
    make_fixtures(d, n=4000, genome_len=200_000)
    model = synthetic_forest(np.random.default_rng(0), n_trees=8, depth=4)
    with open(f"{d}/model.pkl", "wb") as fh:
        pickle.dump({"m": model}, fh)
    _WATCHED_DIRS.append(d)  # leak sentinel sweeps this dir per test
    return {"dir": d, "model": model,
            "fasta": FastaReader(f"{d}/ref.fa"), "n": 4000}


def _stream_args(w, out):
    return argparse.Namespace(
        input_file=f"{w['dir']}/calls.vcf", output_file=out, runs_file=None,
        hpol_filter_length_dist=[10, 10], blacklist=None,
        blacklist_cg_insertions=False, annotate_intervals=[],
        flow_order="TGCA", is_mutect=False, limit_to_contig=None)


def _run_stream(w, out, monkeypatch, chunk_bytes=1 << 15):
    from variantcalling_tpu.io import vcf as vcf_mod
    from variantcalling_tpu.pipelines.filter_variants import run_streaming

    monkeypatch.setattr(vcf_mod, "STREAM_CHUNK_BYTES", chunk_bytes)
    monkeypatch.setenv("VCTPU_IO_BACKOFF_S", "0.01")
    return run_streaming(_stream_args(w, out), w["model"], w["fasta"], {}, None)


@pytest.fixture(scope="module")
def clean_bytes(stream_fault_world, tmp_path_factory):
    """One fault-free streaming run — the byte oracle for every fault leg."""
    from variantcalling_tpu.io import vcf as vcf_mod
    from variantcalling_tpu.pipelines.filter_variants import run_streaming

    w = stream_fault_world
    out = f"{w['dir']}/clean.vcf"
    old = vcf_mod.STREAM_CHUNK_BYTES
    vcf_mod.STREAM_CHUNK_BYTES = 1 << 15
    try:
        stats = run_streaming(_stream_args(w, out), w["model"], w["fasta"], {}, None)
    finally:
        vcf_mod.STREAM_CHUNK_BYTES = old
    assert stats is not None and stats["chunks"] > 3
    return open(out, "rb").read()


def test_transient_chunk_read_error_retried(stream_fault_world, clean_bytes, monkeypatch):
    """Acceptance: a transient ingest IO error is retried and the run
    succeeds with byte-identical output."""
    w = stream_fault_world
    out = f"{w['dir']}/retry_read.vcf"
    faults.arm("io.chunk_read", times=2)
    stats = _run_stream(w, out, monkeypatch)
    assert stats is not None and stats["n"] == w["n"]
    assert faults.fired("io.chunk_read") == 2
    assert open(out, "rb").read() == clean_bytes


def test_fused_chunk_body_survives_retry_redispatch(stream_fault_world,
                                                    clean_bytes, monkeypatch):
    """ISSUE 12 acceptance: the fused zero-wait chunk body (parse ->
    fused native featurize+score -> render as ONE pooled task over a raw
    buffer) is a pure retry-safe function of the held buffer — a
    ``retry_chunk`` re-dispatch after a transient mid-body fault
    re-parses and re-scores the chunk and the output stays
    byte-identical to the clean run."""
    w = stream_fault_world
    out = f"{w['dir']}/retry_fused.vcf"
    monkeypatch.setenv("VCTPU_ENGINE", "native")
    monkeypatch.setenv("VCTPU_NATIVE_FUSED", "1")
    monkeypatch.setenv("VCTPU_IO_THREADS", "4")  # the pooled raw layout
    faults.arm("pipeline.chunk", times=1)
    stats = _run_stream(w, out, monkeypatch)
    assert stats is not None and stats["n"] == w["n"]
    assert faults.fired("pipeline.chunk") == 1
    # records + non-configuration header (the clean oracle may have
    # resolved a different engine than this pinned fault leg)
    from tests.fixtures import strip_vctpu_header

    assert strip_vctpu_header(open(out, "rb").read()) == \
        strip_vctpu_header(clean_bytes)


def test_transient_writeback_enospc_retried(stream_fault_world, clean_bytes, monkeypatch):
    w = stream_fault_world
    out = f"{w['dir']}/retry_write.vcf"
    faults.arm("io.writeback", times=1)
    stats = _run_stream(w, out, monkeypatch)
    assert stats is not None
    assert faults.fired("io.writeback") == 1
    assert open(out, "rb").read() == clean_bytes


def test_persistent_writeback_failure_is_atomic(stream_fault_world, monkeypatch):
    """A failed run never leaves ANY file at the destination path; the
    partial file + journal stay behind for resume, and the rerun heals."""
    w = stream_fault_world
    out = f"{w['dir']}/enospc.vcf"
    faults.arm("io.writeback", times=None)  # every attempt fails
    with pytest.raises(OSError):
        _run_stream(w, out, monkeypatch)
    assert not os.path.exists(out)
    assert _partials(out) and os.path.exists(out + ".journal")
    faults.reset()
    stats = _run_stream(w, out, monkeypatch)
    assert stats is not None and stats["n"] == w["n"]
    assert not _partials(out) and not os.path.exists(out + ".journal")


def test_hung_score_stage_recovers_via_watchdog_v2(
        stream_fault_world, clean_bytes, monkeypatch):
    """Watchdog v2 (recovery ladder): a CANCELLABLE hang (the injected
    kind — a wait the teardown can release) no longer kills the run. The
    first deadline expiry dumps every thread's stack into the obs
    stream, releases the hang, re-dispatches the wedged chunk once, and
    the run completes byte-identically. The abort path is still proven
    by test_watchdog_v2_aborts_when_truly_wedged below."""
    w = stream_fault_world
    out = f"{w['dir']}/hung.vcf"
    monkeypatch.setenv("VCTPU_STAGE_TIMEOUT_S", "1.0")
    monkeypatch.setenv("VCTPU_OBS", "1")
    faults.arm("pipeline.stage_hang", times=1, seconds=120)
    stats = _run_stream(w, out, monkeypatch)
    assert stats is not None and stats["n"] == w["n"]
    assert open(out, "rb").read() == clean_bytes
    events = [json.loads(ln) for ln in open(out + ".obs.jsonl")]
    retries = [e for e in events
               if e["kind"] == "recovery" and e["name"] == "watchdog_retry"]
    assert retries, "watchdog v2 never fired"
    assert "Thread" in retries[0]["stacks"]  # the faulthandler dump
    assert not [t for t in threading.enumerate() if t.name.startswith("pipe-")]


def test_resume_after_midstream_failure_is_byte_identical(
        stream_fault_world, clean_bytes, monkeypatch):
    """Fail AFTER some chunks committed, then resume: the journaled chunks
    are skipped (resumed_chunks > 0) and the final bytes are identical."""
    w = stream_fault_world
    out = f"{w['dir']}/resume.vcf"
    # first writes (header + 2 chunks) succeed, then every attempt fails
    faults.arm("io.writeback", times=None, after=3)
    with pytest.raises(OSError):
        _run_stream(w, out, monkeypatch)
    assert not os.path.exists(out)
    journal_lines = open(out + ".journal").read().splitlines()
    committed = len(journal_lines) - 1
    assert committed >= 1
    faults.reset()
    stats = _run_stream(w, out, monkeypatch)
    assert stats is not None
    assert stats["resumed_chunks"] == committed
    assert stats["n"] == w["n"]
    assert open(out, "rb").read() == clean_bytes


def test_resume_rejects_stale_journal(stream_fault_world, clean_bytes, monkeypatch):
    """A journal whose identity does not match this run (different chunk
    size) is ignored — fresh run, correct output."""
    from variantcalling_tpu.io import journal as journal_mod

    w = stream_fault_world
    out = f"{w['dir']}/stale.vcf"
    faults.arm("io.writeback", times=None, after=3)
    with pytest.raises(OSError):
        _run_stream(w, out, monkeypatch)
    faults.reset()
    # different chunking invalidates the journal identity
    stats = _run_stream(w, out, monkeypatch, chunk_bytes=1 << 14)
    assert stats is not None and stats["resumed_chunks"] == 0
    assert stats["n"] == w["n"]
    # chunking does not change output bytes
    assert open(out, "rb").read() == clean_bytes
    assert journal_mod.ChunkJournal.load(out) is None


def test_resume_rejects_forest_strategy_change(stream_fault_world, clean_bytes,
                                               monkeypatch):
    """The resume identity pins the FULL scoring configuration: a run
    interrupted under one VCTPU_FOREST_STRATEGY and resumed under another
    RESTARTS (resumed_chunks == 0) instead of splicing — and since every
    strategy is byte-parity-locked, the fresh run's bytes still match the
    clean oracle (which doubles as strategy parity through the whole
    streaming pipeline)."""
    w = stream_fault_world
    out = f"{w['dir']}/strat_change.vcf"
    faults.arm("io.writeback", times=None, after=3)
    with pytest.raises(OSError):
        _run_stream(w, out, monkeypatch)
    assert len(open(out + ".journal").read().splitlines()) - 1 >= 1
    faults.reset()
    monkeypatch.setenv("VCTPU_FOREST_STRATEGY", "gemm")
    stats = _run_stream(w, out, monkeypatch)
    assert stats is not None and stats["resumed_chunks"] == 0
    assert stats["n"] == w["n"]
    assert open(out, "rb").read().replace(
        b"##vctpu_forest_strategy=gemm", b"##vctpu_forest_strategy=gather") \
        == clean_bytes


def test_resume_accepts_same_forest_strategy(stream_fault_world, clean_bytes,
                                             monkeypatch):
    """Control for the identity test: the SAME strategy resumes."""
    w = stream_fault_world
    out = f"{w['dir']}/strat_same.vcf"
    monkeypatch.setenv("VCTPU_FOREST_STRATEGY", "wide")
    faults.arm("io.writeback", times=None, after=3)
    with pytest.raises(OSError):
        _run_stream(w, out, monkeypatch)
    committed = len(open(out + ".journal").read().splitlines()) - 1
    assert committed >= 1
    faults.reset()
    stats = _run_stream(w, out, monkeypatch)
    assert stats is not None and stats["resumed_chunks"] == committed
    assert open(out, "rb").read().replace(
        b"##vctpu_forest_strategy=wide", b"##vctpu_forest_strategy=gather") \
        == clean_bytes


def test_resume_rejects_mesh_device_count_change(stream_fault_world,
                                                 clean_bytes, monkeypatch):
    """The mesh layout is part of the resume identity (the design the
    tentpole pins): record bytes are device-count-invariant, but the
    HEADER names the layout (##vctpu_mesh=dp=N when N > 1), so a run
    interrupted on a 2-device scoring mesh and resumed single-device
    RESTARTS cleanly (resumed_chunks == 0) instead of splicing two
    headers. The fresh run's records still match the native oracle —
    device-count parity through the whole streaming pipeline."""
    from variantcalling_tpu import engine as engine_mod

    w = stream_fault_world
    out = f"{w['dir']}/mesh_change.vcf"
    monkeypatch.setenv("VCTPU_ENGINE", "jit")
    monkeypatch.setenv("VCTPU_MESH_DEVICES", "2")
    engine_mod.reset_for_tests()
    try:
        faults.arm("io.writeback", times=None, after=3)
        with pytest.raises(OSError):
            _run_stream(w, out, monkeypatch)
        assert len(open(out + ".journal").read().splitlines()) - 1 >= 1
        faults.reset()
        monkeypatch.setenv("VCTPU_MESH_DEVICES", "1")
        stats = _run_stream(w, out, monkeypatch)
        assert stats is not None and stats["resumed_chunks"] == 0
        assert stats["n"] == w["n"]
        # the single-device restart emits no mesh line, so its bytes equal
        # the oracle exactly (the 8-forced-device test env auto-resolves
        # the oracle's engine to jit/gather, same as the explicit pin)
        assert open(out, "rb").read() == clean_bytes
    finally:
        engine_mod.reset_for_tests()


def test_resume_accepts_same_mesh_device_count(stream_fault_world,
                                               clean_bytes, monkeypatch):
    """Control for the identity test: the SAME 2-device mesh resumes
    (resumed_chunks == committed) and the continuation is byte-identical
    to the oracle modulo the configuration header lines."""
    from variantcalling_tpu import engine as engine_mod

    w = stream_fault_world
    out = f"{w['dir']}/mesh_same.vcf"
    monkeypatch.setenv("VCTPU_ENGINE", "jit")
    monkeypatch.setenv("VCTPU_MESH_DEVICES", "2")
    engine_mod.reset_for_tests()
    try:
        faults.arm("io.writeback", times=None, after=3)
        with pytest.raises(OSError):
            _run_stream(w, out, monkeypatch)
        committed = len(open(out + ".journal").read().splitlines()) - 1
        assert committed >= 1
        faults.reset()
        stats = _run_stream(w, out, monkeypatch)
        assert stats is not None and stats["resumed_chunks"] == committed
        assert open(out, "rb").read().replace(
            b"##vctpu_mesh=dp=2\n", b"") == clean_bytes
    finally:
        engine_mod.reset_for_tests()


def test_resume_survives_io_thread_count_change(stream_fault_world, clean_bytes,
                                                monkeypatch):
    """Chunk boundaries are identical at every VCTPU_IO_THREADS setting,
    so a run interrupted under one worker count RESUMES under another
    (the journal identity does not — and must not — pin the pool size)."""
    w = stream_fault_world
    out = f"{w['dir']}/io_change.vcf"
    monkeypatch.setenv("VCTPU_IO_THREADS", "4")
    faults.arm("io.writeback", times=None, after=3)
    with pytest.raises(OSError):
        _run_stream(w, out, monkeypatch)
    committed = len(open(out + ".journal").read().splitlines()) - 1
    assert committed >= 1
    faults.reset()
    monkeypatch.setenv("VCTPU_IO_THREADS", "1")
    stats = _run_stream(w, out, monkeypatch)
    assert stats is not None and stats["resumed_chunks"] == committed
    assert open(out, "rb").read() == clean_bytes


# ---------------------------------------------------------------------------
# parallel host IO: worker death mid-decompress / mid-compress
# ---------------------------------------------------------------------------


def _bgzf_input(w) -> str:
    from variantcalling_tpu.io.bgzf import BgzfWriter

    path = f"{w['dir']}/calls.vcf.gz"
    if not os.path.exists(path):
        with open(f"{w['dir']}/calls.vcf", "rb") as fh, \
                BgzfWriter(path) as out:
            out.write(fh.read())
    return path


def test_transient_shard_decompress_retried(stream_fault_world, clean_bytes,
                                            monkeypatch):
    """A transient IO error inside a parallel BGZF inflate worker is
    retried (inflate is a pure function of the mapped bytes) and the run
    completes byte-identically."""
    from variantcalling_tpu.pipelines.filter_variants import run_streaming

    w = stream_fault_world
    inp = _bgzf_input(w)
    out = f"{w['dir']}/shard_retry.vcf"
    monkeypatch.setenv("VCTPU_IO_THREADS", "4")
    monkeypatch.setenv("VCTPU_IO_BACKOFF_S", "0.01")
    monkeypatch.setenv("VCTPU_STREAM_CHUNK_BYTES", str(1 << 15))
    faults.arm("io.shard_decompress", times=2)
    args = _stream_args(w, out)
    args.input_file = inp
    stats = run_streaming(args, w["model"], w["fasta"], {}, None)
    assert stats is not None and stats["n"] == w["n"]
    assert faults.fired("io.shard_decompress") == 2
    assert open(out, "rb").read() == clean_bytes


def test_persistent_shard_decompress_death_fails_clean(stream_fault_world,
                                                       monkeypatch):
    """An IO worker dying on every inflate attempt fails the run cleanly:
    the real error surfaces, nothing lands at the destination, and no
    pipeline threads leak."""
    from variantcalling_tpu.pipelines.filter_variants import run_streaming

    w = stream_fault_world
    inp = _bgzf_input(w)
    out = f"{w['dir']}/shard_dead.vcf"
    monkeypatch.setenv("VCTPU_IO_THREADS", "4")
    monkeypatch.setenv("VCTPU_IO_BACKOFF_S", "0.01")
    monkeypatch.setenv("VCTPU_STREAM_CHUNK_BYTES", str(1 << 15))
    faults.arm("io.shard_decompress", times=None)
    args = _stream_args(w, out)
    args.input_file = inp
    with pytest.raises(OSError, match="shard inflate"):
        run_streaming(args, w["model"], w["fasta"], {}, None)
    assert not os.path.exists(out)
    assert not [t for t in threading.enumerate() if t.name.startswith("pipe-")]
    # the error surfaced from the reader CONSTRUCTOR (the header scan is
    # the first shard read): its pool workers must be released too — a
    # long-lived process retrying runs must not accumulate idle daemons
    time.sleep(0.2)  # bounded pool joins finish
    assert not [t for t in threading.enumerate()
                if t.name.startswith("vctpu-io-")]


def test_compress_worker_death_is_atomic(stream_fault_world, monkeypatch):
    """A worker death mid-BGZF-compress on the writeback side fails the
    run with the torn .partial discarded — the destination is never
    touched (gz outputs: atomic, non-resumable)."""
    w = stream_fault_world
    out = f"{w['dir']}/compress_dead.vcf.gz"
    monkeypatch.setenv("VCTPU_IO_THREADS", "2")
    faults.arm("io.shard_compress", times=1)
    with pytest.raises(OSError, match="shard compress"):
        _run_stream(w, out, monkeypatch)
    assert not os.path.exists(out)
    assert not _partials(out)
    faults.reset()
    stats = _run_stream(w, out, monkeypatch)  # rerun heals
    assert stats is not None and stats["n"] == w["n"]


def test_malformed_journal_degrades_to_fresh_run(tmp_path):
    """A journal whose lines parse as JSON but lack fields must not crash
    resume — it degrades to a fresh run (docs/robustness.md contract)."""
    from variantcalling_tpu.io import journal as journal_mod

    out = str(tmp_path / "x.vcf")
    meta = {"input": "i", "input_sig": [1, 2], "chunk_bytes": 3,
            "header_len": 4, "header_crc": 5}
    with open(out + ".journal", "w") as fh:
        fh.write(__import__("json").dumps(dict(meta, version=1)) + "\n")
        fh.write('{"seq": 0}\n')  # parses, but has no body_len/crc
    open(out + ".partial", "wb").write(b"x" * 100)
    assert journal_mod.try_resume(out, meta) is None


def test_journal_tolerates_torn_tail_line(tmp_path):
    from variantcalling_tpu.io import journal as journal_mod

    out = str(tmp_path / "x.vcf")
    j = journal_mod.ChunkJournal(out)
    j.begin({"input": "i", "input_sig": [1, 2], "chunk_bytes": 3,
             "header_len": 4, "header_crc": 5})
    j.append(0, 10, 5, 100, 123)
    j.close()
    with open(out + ".journal", "a") as fh:
        fh.write('{"seq": 1, "records": 7')  # killed mid-append
    loaded = journal_mod.ChunkJournal.load(out)
    assert loaded is not None
    meta, entries = loaded
    assert len(entries) == 1 and entries[0]["seq"] == 0


def test_sigkill_midstream_then_resume_byte_identical(stream_fault_world, tmp_path):
    """Acceptance: SIGKILL mid-stream leaves no partial output at the
    destination; the resumed run skips committed chunks and produces
    byte-identical output."""
    w = stream_fault_world
    d = str(tmp_path)
    out = f"{d}/out.vcf"
    child = (
        "from variantcalling_tpu.pipelines.filter_variants import run\n"
        f"raise SystemExit(run(['--input_file', {w['dir'] + '/calls.vcf'!r},\n"
        f" '--model_file', {w['dir'] + '/model.pkl'!r}, '--model_name', 'm',\n"
        f" '--reference_file', {w['dir'] + '/ref.fa'!r},\n"
        f" '--output_file', {out!r}, '--backend', 'cpu']))\n")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(PYTHONPATH=_REPO, JAX_PLATFORMS="cpu",
               VCTPU_STREAM_CHUNK_BYTES=str(1 << 15),
               # the kill must land while the PARALLEL host-IO machinery
               # is live (pool workers mid-chunk) — resume then proves
               # the journal contract under parallel writeback
               VCTPU_IO_THREADS="4",
               # slow each chunk so the kill lands mid-stream
               VCTPU_FAULTS="pipeline.stage_hang:999@0.3")
    env.pop("XLA_FLAGS", None)
    p = subprocess.Popen([sys.executable, "-c", child], env=env, cwd=_REPO,
                         stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    jpath = out + ".journal"
    deadline = time.time() + 120
    committed = 0
    try:
        while time.time() < deadline:
            if os.path.exists(jpath):
                committed = max(0, len(open(jpath).read().splitlines()) - 1)
                if committed >= 2:
                    break
            time.sleep(0.05)
    finally:
        os.kill(p.pid, signal.SIGKILL)
        p.wait()
    assert committed >= 2, "child never journaled 2 chunks before the deadline"
    assert not os.path.exists(out)  # SIGKILL left nothing at the destination

    env2 = dict(env)
    env2.pop("VCTPU_FAULTS")
    p2 = subprocess.run([sys.executable, "-c", child], env=env2, cwd=_REPO,
                        capture_output=True, text=True, timeout=300)
    assert p2.returncode == 0, p2.stderr[-2000:]
    assert "streaming resume" in p2.stderr
    resumed = open(out, "rb").read()

    out2 = f"{d}/uninterrupted.vcf"
    p3 = subprocess.run([sys.executable, "-c", child.replace(repr(out), repr(out2))],
                        env=env2, cwd=_REPO, capture_output=True, text=True,
                        timeout=300)
    assert p3.returncode == 0, p3.stderr[-2000:]
    assert resumed == open(out2, "rb").read()
    assert not os.path.exists(jpath)


# ---------------------------------------------------------------------------
# supervised recovery ladder (ISSUE 10 tentpole)
# ---------------------------------------------------------------------------


def _obs_events(path):
    return [json.loads(ln) for ln in open(path, encoding="utf-8")]


def test_env_arming_after_grammar(monkeypatch):
    """VCTPU_FAULTS grows `+after` free passes so subprocess harnesses
    (tools/chaoshunt) can schedule mid-stream failures."""
    monkeypatch.setenv("VCTPU_FAULTS", "io.writeback:0+3,pipeline.chunk:2+1")
    faults.reset()
    faults._arm_from_env()
    assert faults._ARMED["io.writeback"].times is None
    assert faults._ARMED["io.writeback"].after == 3
    assert faults._ARMED["pipeline.chunk"].times == 2
    assert faults._ARMED["pipeline.chunk"].after == 1
    faults.reset()


def test_retry_delay_deterministic_per_worker_jitter():
    from variantcalling_tpu.parallel.pipeline import _retry_delay

    d0 = _retry_delay(1, 0.05, "vctpu-io-w0")
    assert d0 == _retry_delay(1, 0.05, "vctpu-io-w0")  # deterministic
    fleet = {_retry_delay(1, 0.05, f"vctpu-io-w{i}") for i in range(8)}
    assert len(fleet) > 1  # workers do NOT stampede in lockstep
    base = 0.05 * 2
    assert all(base <= d < 1.5 * base for d in fleet)  # bounded
    assert _retry_delay(0, 0.0, "x") == 0.0  # zero backoff stays zero


def test_retry_chunk_recovers_then_respects_budget(monkeypatch):
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("boom")
        return "ok"

    assert retry_chunk(flaky, "t") == "ok"
    assert calls["n"] == 2
    monkeypatch.setenv("VCTPU_CHUNK_RETRIES", "0")
    calls["n"] = 0
    with pytest.raises(RuntimeError, match="boom"):
        retry_chunk(flaky, "t")
    assert calls["n"] == 1  # zero retries == first-strike failure


def test_retry_chunk_passes_contract_errors_through():
    from variantcalling_tpu.engine import EngineError

    calls = {"n": 0}

    def config_error():
        calls["n"] += 1
        raise EngineError("bad knob")

    with pytest.raises(EngineError):
        retry_chunk(config_error, "t")
    assert calls["n"] == 1  # configuration errors are never re-dispatched

    calls["n"] = 0

    def watchdog():
        calls["n"] += 1
        raise StageTimeoutError("wedged")

    with pytest.raises(StageTimeoutError):
        retry_chunk(watchdog, "t")
    assert calls["n"] == 1


def test_on_final_attempt_visible_to_chunk_bodies():
    """The quarantine guard diverts only once the re-dispatch budget is
    spent — it learns the attempt through pipeline.on_final_attempt."""
    seen = []

    def body():
        seen.append(on_final_attempt())
        raise RuntimeError("poison")

    with pytest.raises(RuntimeError):
        retry_chunk(body, "t")  # default budget: 1 retry
    assert seen == [False, True]
    assert on_final_attempt()  # restored outside the ladder


def test_supervised_pipeline_retries_stage_fault_threaded():
    faults.arm("pipeline.stage", times=1)
    pipe = StagePipeline([lambda x: x + 1], threads=2, timeout=30,
                         recover=True)
    assert list(pipe.run(range(8))) == list(range(1, 9))
    assert faults.fired("pipeline.stage") == 1
    assert pipe.unjoined == []


def test_supervised_pipeline_retries_stage_fault_serial():
    faults.arm("pipeline.stage", times=1)
    pipe = StagePipeline([lambda x: x + 1], threads=1, recover=True)
    assert list(pipe.run(range(8))) == list(range(1, 9))
    assert faults.fired("pipeline.stage") == 1


def test_supervised_pipeline_persistent_fault_still_fails_loud():
    faults.arm("pipeline.stage", times=None)
    pipe = StagePipeline([lambda x: x], threads=2, timeout=30, recover=True)
    with pytest.raises(RuntimeError, match="injected fault"):
        list(pipe.run(range(8)))
    assert pipe.unjoined == []


def test_supervised_pipeline_never_redispatches_stateful_stage():
    """A stage marked ``retry_safe = False`` (the BGZF compressor's
    block carry — re-running it would absorb the same bytes twice) is
    excluded from the ladder: its failure stays first-strike fail-loud
    even in supervised mode, threaded AND serial."""
    calls = {"n": 0}

    def stateful(x):
        calls["n"] += 1
        raise OSError("carry torn")

    stateful.retry_safe = False
    pipe = StagePipeline([stateful], threads=2, timeout=30, recover=True)
    with pytest.raises(OSError, match="carry torn"):
        list(pipe.run(range(8)))
    assert calls["n"] == 1  # exactly one strike, no re-dispatch
    calls["n"] = 0
    pipe = StagePipeline([lambda x: x, stateful], threads=1, recover=True)
    with pytest.raises(OSError, match="carry torn"):
        list(pipe.run(range(8)))
    # serial path: the stateful stage alone is excluded (per-stage, like
    # the threaded path) — its first strike is final
    assert calls["n"] == 1


def test_serial_supervised_retries_pure_stage_despite_stateful_neighbor():
    """Serial mode must keep the retry budget for PURE stages even when a
    stateful stage sits later in the chain (single-thread .gz layout):
    only the stateful stage itself is excluded from re-dispatch."""
    flaky_calls = {"n": 0}

    def flaky(x):
        flaky_calls["n"] += 1
        if flaky_calls["n"] == 1:
            raise RuntimeError("transient")
        return x

    stateful_seen = []

    def stateful(x):
        stateful_seen.append(x)
        return x

    stateful.retry_safe = False
    pipe = StagePipeline([flaky, stateful], threads=1, recover=True)
    assert list(pipe.run(range(4))) == list(range(4))
    assert flaky_calls["n"] == 5  # item 0 retried once, 1-3 clean
    assert stateful_seen == list(range(4))  # exactly once per item


def test_watchdog_redispatch_duplicates_drop_before_downstream_stage():
    """A watchdog re-dispatch can deliver the wedged chunk TWICE (the
    one-shot retry plus the woken worker). Downstream stages must see
    each sequence number exactly once — a stateful stage after the
    wedged one would otherwise absorb the chunk's bytes twice."""
    seen: list[int] = []

    def downstream(x):
        seen.append(x)
        return x

    faults.arm("pipeline.stage_hang", times=1, seconds=120)
    pipe = StagePipeline([lambda x: x, downstream], threads=3, timeout=0.4,
                         recover=True)
    out = list(pipe.run(range(4)))
    assert out == list(range(4))
    assert pipe.watchdog_retried
    assert sorted(seen) == list(range(4))  # no duplicate ever reached it


def test_watchdog_v2_recovers_cancellable_hang():
    """First deadline expiry: stacks dumped, hangs cancelled, wedged
    chunk re-dispatched — the run COMPLETES instead of aborting."""
    faults.arm("pipeline.stage_hang", times=1, seconds=120)
    pipe = StagePipeline([lambda x: x], threads=2, timeout=0.4, recover=True)
    t0 = time.monotonic()
    assert list(pipe.run(range(4))) == list(range(4))
    assert pipe.watchdog_retried
    assert time.monotonic() - t0 < 20
    assert pipe.unjoined == []


def test_watchdog_v2_aborts_when_truly_wedged():
    """A stage wedged in an UNcancellable call (bare sleep — the stand-in
    for a dead native call) still aborts: the single watchdog retry
    re-dispatches the chunk, no progress follows, the second deadline
    raises StageTimeoutError with every joinable worker joined."""
    def wedge(x):
        time.sleep(2.5)
        return x

    pipe = StagePipeline([wedge], threads=2, timeout=0.3, recover=True)
    t0 = time.monotonic()
    with pytest.raises(StageTimeoutError, match="no progress"):
        list(pipe.run(range(4)))
    assert pipe.watchdog_retried
    assert time.monotonic() - t0 < 30


def test_streaming_transient_stage_fault_recovers(stream_fault_world,
                                                  clean_bytes, monkeypatch):
    """Acceptance (ISSUE 10): a transient chunk failure recovers WITHOUT
    a run abort, with a recorded `recovery` event, on the pooled layout."""
    w = stream_fault_world
    out = f"{w['dir']}/chunk_retry.vcf"
    monkeypatch.setenv("VCTPU_IO_THREADS", "4")
    monkeypatch.setenv("VCTPU_OBS", "1")
    faults.arm("pipeline.stage", times=1)
    stats = _run_stream(w, out, monkeypatch)
    assert stats is not None and stats["n"] == w["n"]
    assert faults.fired("pipeline.stage") == 1
    assert open(out, "rb").read() == clean_bytes
    retries = [e for e in _obs_events(out + ".obs.jsonl")
               if e["kind"] == "recovery" and e["name"] == "chunk_retry"]
    assert len(retries) == 1 and retries[0]["attempt"] == 1


def test_streaming_zero_chunk_retries_fails_first_strike(
        stream_fault_world, monkeypatch):
    w = stream_fault_world
    out = f"{w['dir']}/no_retry.vcf"
    monkeypatch.setenv("VCTPU_IO_THREADS", "4")
    monkeypatch.setenv("VCTPU_CHUNK_RETRIES", "0")
    faults.arm("pipeline.stage", times=1)
    with pytest.raises(RuntimeError, match="injected fault"):
        _run_stream(w, out, monkeypatch)
    assert not os.path.exists(out)
    # failed resumable run keeps the journal+partial pair: clean it so
    # the leak sentinel's "no strays" invariant holds for this module
    from variantcalling_tpu.io import journal as journal_mod

    journal_mod.discard(out)


def test_quarantine_default_off_poison_chunk_fails_loud(
        stream_fault_world, monkeypatch):
    """Byte parity stays untouchable by default: a deterministic chunk
    failure kills the run even after the re-dispatch budget."""
    from variantcalling_tpu.io import journal as journal_mod

    w = stream_fault_world
    out = f"{w['dir']}/poison_loud.vcf"
    monkeypatch.setenv("VCTPU_IO_THREADS", "1")
    faults.arm("pipeline.chunk", times=None)
    with pytest.raises(RuntimeError, match="chunk scoring failure"):
        _run_stream(w, out, monkeypatch)
    assert not os.path.exists(out)
    assert not os.path.exists(out + ".quarantine")
    journal_mod.discard(out)


def test_quarantine_diverts_poison_chunk(stream_fault_world, clean_bytes,
                                         monkeypatch):
    """VCTPU_QUARANTINE=1: a chunk that fails deterministically through
    the whole re-dispatch budget (N strikes) diverts its ORIGINAL records
    to <out>.quarantine; the main output holds exactly the clean bytes
    minus that chunk, and the diversion is loud (degrade + recovery
    event + stats)."""
    from variantcalling_tpu.utils import degrade

    w = stream_fault_world
    out = f"{w['dir']}/poison_quar.vcf"
    monkeypatch.setenv("VCTPU_IO_THREADS", "1")  # deterministic chunk order
    monkeypatch.setenv("VCTPU_QUARANTINE", "1")
    monkeypatch.setenv("VCTPU_OBS", "1")
    degrade.clear_for_tests()
    # 2 strikes == 1 attempt + 1 re-dispatch of chunk 0, then quarantine
    faults.arm("pipeline.chunk", times=2)
    stats = _run_stream(w, out, monkeypatch)
    assert stats is not None
    assert stats["quarantined_chunks"] == 1
    assert stats["n"] == w["n"]  # quarantined records still counted
    out_bytes = open(out, "rb").read()
    q_bytes = open(out + ".quarantine", "rb").read()
    clean_recs = [ln for ln in clean_bytes.split(b"\n")
                  if ln and not ln.startswith(b"#")]
    out_recs = [ln for ln in out_bytes.split(b"\n")
                if ln and not ln.startswith(b"#")]
    q_recs = [ln for ln in q_bytes.split(b"\n") if ln]
    assert len(q_recs) == stats["quarantined_records"] > 0
    # main output == clean minus the quarantined (first) chunk's records
    assert out_recs == clean_recs[len(q_recs):]
    # quarantined records are the ORIGINAL lines (no TREE_SCORE added)
    assert not any(b"TREE_SCORE" in ln for ln in q_recs)
    assert degrade.events_for("stream.quarantine")
    quar = [e for e in _obs_events(out + ".obs.jsonl")
            if e["kind"] == "recovery" and e["name"] == "quarantine"]
    assert len(quar) == 1 and quar[0]["records"] == len(q_recs)
    os.remove(out + ".quarantine")  # sentinel: no stray sidecars


def test_mesh_oom_megabatch_shrink_recovers(stream_fault_world, clean_bytes,
                                            monkeypatch):
    """Device OOM on a mesh megabatch dispatch: the ladder shrinks the
    megabatch and re-dispatches chunk by chunk — the run completes
    byte-identically (modulo the mesh header line) with the recovery
    recorded."""
    from variantcalling_tpu import engine as engine_mod

    w = stream_fault_world
    out = f"{w['dir']}/oom_shrink.vcf"
    monkeypatch.setenv("VCTPU_ENGINE", "jit")
    monkeypatch.setenv("VCTPU_MESH_DEVICES", "2")
    monkeypatch.setenv("VCTPU_OBS", "1")
    engine_mod.reset_for_tests()
    try:
        faults.arm("xla.dispatch_oom", times=1)
        stats = _run_stream(w, out, monkeypatch)
        assert stats is not None and stats["n"] == w["n"]
        assert open(out, "rb").read().replace(
            b"##vctpu_mesh=dp=2\n", b"") == clean_bytes
        events = _obs_events(out + ".obs.jsonl")
        assert [e for e in events if e["kind"] == "recovery"
                and e["name"] == "megabatch_shrink"]
        assert not [e for e in events if e["name"] == "dp_degrade"]
    finally:
        engine_mod.reset_for_tests()


def test_mesh_oom_persistent_degrades_to_dp1(stream_fault_world, clean_bytes,
                                             monkeypatch):
    """Acceptance (ISSUE 10): persistent device OOM degrades the run to
    dp=1 with a recorded `recovery` event and a clean journal restart —
    the completed output carries NO mesh header line and matches the
    oracle exactly."""
    from variantcalling_tpu import engine as engine_mod
    from variantcalling_tpu.utils import degrade

    w = stream_fault_world
    out = f"{w['dir']}/oom_degrade.vcf"
    monkeypatch.setenv("VCTPU_ENGINE", "jit")
    monkeypatch.setenv("VCTPU_MESH_DEVICES", "2")
    monkeypatch.setenv("VCTPU_OBS", "1")
    engine_mod.reset_for_tests()
    degrade.clear_for_tests()
    try:
        faults.arm("xla.dispatch_oom", times=None)
        stats = _run_stream(w, out, monkeypatch)
        assert stats is not None and stats["n"] == w["n"]
        data = open(out, "rb").read()
        assert b"##vctpu_mesh" not in data  # the dp=1 restart's header
        assert data == clean_bytes
        assert not os.path.exists(out + ".journal")
        assert degrade.events_for("shard_score.device_oom")
        events = _obs_events(out + ".obs.jsonl")
        dg = [e for e in events if e["kind"] == "recovery"
              and e["name"] == "dp_degrade"]
        assert len(dg) == 1 and dg[0]["devices_from"] == 2 \
            and dg[0]["devices_to"] == 1
    finally:
        engine_mod.reset_for_tests()


def test_commit_enospc_keeps_journal_then_resume_completes(
        stream_fault_world, clean_bytes, monkeypatch):
    """ISSUE 10 satellite: ENOSPC at the atomic commit (os.replace). The
    destination stays untouched, the JOURNAL is retained (finish() now
    runs only after the rename landed), and the next run resumes —
    skipping every chunk — to byte-identical output."""
    w = stream_fault_world
    out = f"{w['dir']}/commit_enospc.vcf"
    faults.arm("io.commit", times=None)
    with pytest.raises(OSError):
        _run_stream(w, out, monkeypatch)
    assert not os.path.exists(out)
    assert _partials(out)
    assert os.path.exists(out + ".journal")
    faults.reset()
    stats = _run_stream(w, out, monkeypatch)
    assert stats is not None
    assert stats["resumed_chunks"] == stats["chunks"]  # nothing recomputed
    assert open(out, "rb").read() == clean_bytes
    assert not _partials(out)
    assert not os.path.exists(out + ".journal")


def test_commit_enospc_transient_retried_in_run(stream_fault_world,
                                                clean_bytes, monkeypatch):
    w = stream_fault_world
    out = f"{w['dir']}/commit_retry.vcf"
    faults.arm("io.commit", times=1)
    stats = _run_stream(w, out, monkeypatch)
    assert stats is not None
    assert faults.fired("io.commit") == 1
    assert open(out, "rb").read() == clean_bytes


def test_full_resume_verify_catches_early_corruption(stream_fault_world,
                                                     clean_bytes,
                                                     monkeypatch):
    """Journal v2 (VCTPU_RESUME_VERIFY=full): a flipped byte in an EARLY
    committed chunk — invisible to the default last-chunk spot check —
    fails the full-prefix verification, so the run restarts fresh and
    still produces correct bytes."""
    from variantcalling_tpu.io import journal as journal_mod

    w = stream_fault_world
    out = f"{w['dir']}/verify_full.vcf"
    faults.arm("io.writeback", times=None, after=4)
    with pytest.raises(OSError):
        _run_stream(w, out, monkeypatch)
    faults.reset()
    jmeta = json.loads(open(out + ".journal", encoding="utf-8").readline())
    assert len(open(out + ".journal").read().splitlines()) - 1 >= 2
    # flip one byte INSIDE the FIRST chunk's region of the partial file
    # (unique-suffix partial: the journal header names the token). The
    # token is journal-internal state — drop it from the identity meta
    # these direct try_resume calls pass, like the production caller's
    # meta (try_resume RE-TOKENS the partial on success, so a stale
    # token in expect would mismatch for the wrong reason).
    from variantcalling_tpu.io import journal as _j

    token = jmeta.pop("partial", None)
    with open(_j.partial_path(out, token), "r+b") as fh:
        fh.seek(int(jmeta["header_len"]) + 5)
        b = fh.read(1)
        fh.seek(int(jmeta["header_len"]) + 5)
        fh.write(bytes([b[0] ^ 1]))
    # the default last-chunk spot check MISSES the early corruption ...
    assert journal_mod.try_resume(out, jmeta) is not None
    # ... full-prefix verification catches it and degrades to fresh
    monkeypatch.setenv("VCTPU_RESUME_VERIFY", "full")
    assert journal_mod.try_resume(out, jmeta) is None
    stats = _run_stream(w, out, monkeypatch)
    assert stats is not None and stats["resumed_chunks"] == 0
    assert open(out, "rb").read() == clean_bytes


def test_full_resume_verify_accepts_intact_prefix(stream_fault_world,
                                                  clean_bytes, monkeypatch):
    """Control: with an intact partial file, full verification RESUMES
    (same chunks skipped as the default mode) byte-identically."""
    w = stream_fault_world
    out = f"{w['dir']}/verify_ok.vcf"
    monkeypatch.setenv("VCTPU_RESUME_VERIFY", "full")
    faults.arm("io.writeback", times=None, after=4)
    with pytest.raises(OSError):
        _run_stream(w, out, monkeypatch)
    committed = len(open(out + ".journal").read().splitlines()) - 1
    assert committed >= 1
    faults.reset()
    stats = _run_stream(w, out, monkeypatch)
    assert stats is not None and stats["resumed_chunks"] == committed
    assert open(out, "rb").read() == clean_bytes


def test_journal_fsync_knob_is_byte_neutral(stream_fault_world, clean_bytes,
                                            monkeypatch):
    w = stream_fault_world
    out = f"{w['dir']}/fsync.vcf"
    monkeypatch.setenv("VCTPU_JOURNAL_FSYNC", "1")
    stats = _run_stream(w, out, monkeypatch)
    assert stats is not None
    assert open(out, "rb").read() == clean_bytes


def test_dist_rank_timeout_point_is_wired():
    """Single-process: the dist.rank_timeout delay point fires inside
    allgather_concat and the gather still completes correctly."""
    from variantcalling_tpu.parallel import distributed as dist

    faults.arm("dist.rank_timeout", times=1, seconds=0.2)
    t0 = time.monotonic()
    out = dist.allgather_concat(np.asarray([1, 2, 3], dtype=np.int64))
    assert time.monotonic() - t0 >= 0.15
    np.testing.assert_array_equal(out, [1, 2, 3])
    assert faults.fired("dist.rank_timeout") == 1


# ---------------------------------------------------------------------------
# causal trace linkage under faults (ISSUE 11): every recovery-ladder
# action names the trace of the chunk it recovers, and the trace id
# resolves to that chunk's span DAG
# ---------------------------------------------------------------------------


def _trace_spans_by_id(events):
    out = {}
    for e in events:
        if e["kind"] != "trace":
            continue
        for tid in (e.get("traces") or [e.get("trace_id")]):
            out.setdefault(tid, []).append(e)
    return out


def test_streaming_chunk_traces_form_complete_dags(
        stream_fault_world, clean_bytes, monkeypatch):
    """A clean streaming run: every chunk's trace walks from the
    sequenced-commit terminal span back to its ingest root, and the
    critical-path engine reconstructs one path per chunk."""
    from variantcalling_tpu.obs import critical as critical_mod

    w = stream_fault_world
    out = f"{w['dir']}/traced.vcf"
    monkeypatch.setenv("VCTPU_OBS", "1")
    stats = _run_stream(w, out, monkeypatch)
    assert stats is not None and stats["n"] == w["n"]
    assert open(out, "rb").read() == clean_bytes
    events = _obs_events(out + ".obs.jsonl")
    by_trace = _trace_spans_by_id(events)
    assert len(by_trace) == stats["chunks"]
    for tid, spans in by_trace.items():
        names = [s["name"] for s in spans if s.get("trace_id") == tid
                 or tid in (s.get("traces") or ())]
        assert "ingest" in names and "writeback" in names, (tid, names)
    cp = critical_mod.critical_path(events)
    assert cp["chunks"] == stats["chunks"]
    # each path must span ingest -> writeback (root chosen correctly)
    for p in critical_mod.chunk_paths(events):
        assert p["edges"][0]["edge"] == "ingest.work"
        assert p["edges"][-1]["edge"] == "writeback.work"


def test_chunk_retry_event_links_to_chunk_trace(
        stream_fault_world, clean_bytes, monkeypatch):
    """Acceptance (trace linkage): a transient chunk failure's
    `recovery`/`chunk_retry` event carries the original chunk's
    trace_id, and that id resolves to the chunk's spans."""
    w = stream_fault_world
    out = f"{w['dir']}/trace_retry.vcf"
    monkeypatch.setenv("VCTPU_OBS", "1")
    monkeypatch.setenv("VCTPU_IO_THREADS", "1")
    faults.arm("pipeline.chunk", times=1)  # one strike, then recovered
    stats = _run_stream(w, out, monkeypatch)
    assert stats is not None and stats["n"] == w["n"]
    assert open(out, "rb").read() == clean_bytes
    events = _obs_events(out + ".obs.jsonl")
    retries = [e for e in events
               if e["kind"] == "recovery" and e["name"] == "chunk_retry"]
    assert retries, "no chunk_retry event"
    by_trace = _trace_spans_by_id(events)
    for e in retries:
        assert "trace_id" in e, e
        spans = by_trace.get(e["trace_id"])
        assert spans, f"retry trace {e['trace_id']} resolves to no spans"
        # the recovered chunk still completed: its DAG has the terminal
        assert "writeback" in {s["name"] for s in spans}


def test_quarantine_event_links_to_chunk_trace(
        stream_fault_world, monkeypatch):
    """Acceptance (trace linkage): the quarantine diversion names the
    poisoned chunk's trace, which resolves to its ingest root."""
    w = stream_fault_world
    out = f"{w['dir']}/trace_quar.vcf"
    monkeypatch.setenv("VCTPU_OBS", "1")
    monkeypatch.setenv("VCTPU_IO_THREADS", "1")
    monkeypatch.setenv("VCTPU_QUARANTINE", "1")
    faults.arm("pipeline.chunk", times=2)  # through the whole budget
    stats = _run_stream(w, out, monkeypatch)
    assert stats is not None and stats["quarantined_chunks"] == 1
    events = _obs_events(out + ".obs.jsonl")
    quar = [e for e in events
            if e["kind"] == "recovery" and e["name"] == "quarantine"]
    assert len(quar) == 1 and "trace_id" in quar[0]
    spans = _trace_spans_by_id(events).get(quar[0]["trace_id"])
    assert spans and "ingest" in {s["name"] for s in spans}
    os.remove(out + ".quarantine")


def test_mesh_fanin_spans_list_every_member_chunk(
        stream_fault_world, clean_bytes, monkeypatch):
    """Acceptance: a megabatch dispatch span is a FAN-IN — it lists
    every member chunk's trace in `traces` and parents each member's
    preceding span, so every chunk's DAG walks through the shared
    dispatch."""
    from variantcalling_tpu import engine as engine_mod

    w = stream_fault_world
    out = f"{w['dir']}/trace_mesh.vcf"
    monkeypatch.setenv("VCTPU_ENGINE", "jit")
    monkeypatch.setenv("VCTPU_MESH_DEVICES", "2")
    monkeypatch.setenv("VCTPU_OBS", "1")
    engine_mod.reset_for_tests()
    try:
        stats = _run_stream(w, out, monkeypatch)
        assert stats is not None and stats["n"] == w["n"]
        events = _obs_events(out + ".obs.jsonl")
        fanin = [e for e in events if e["kind"] == "trace"
                 and e["name"] == "score_stage" and e.get("traces")]
        assert fanin, "no fan-in dispatch span"
        # every chunk trace appears in exactly one dispatch's fan-in
        member_tids = [t for e in fanin for t in e["traces"]]
        assert sorted(member_tids) == sorted(set(member_tids))
        assert len(member_tids) == stats["chunks"]
        # each fan-in parents every member's preceding span
        spans_by_id = {e["span_id"]: e for e in events
                       if e["kind"] == "trace"}
        for e in fanin:
            assert len(e.get("parents", [])) == len(e["traces"]), e
            parent_traces = {spans_by_id[p]["trace_id"]
                             for p in e["parents"]}
            assert parent_traces == set(e["traces"])
        # and a multi-chunk megabatch actually happened in this layout
        assert any(len(e["traces"]) > 1 for e in fanin)
    finally:
        engine_mod.reset_for_tests()


def test_mesh_oom_shrink_event_links_member_traces(
        stream_fault_world, clean_bytes, monkeypatch):
    """Acceptance (trace linkage): the OOM shrink rung's recovery event
    lists the member chunks' trace_ids, each resolving to real spans,
    and the per-chunk re-dispatches link their retries too."""
    from variantcalling_tpu import engine as engine_mod

    w = stream_fault_world
    out = f"{w['dir']}/trace_oom.vcf"
    monkeypatch.setenv("VCTPU_ENGINE", "jit")
    monkeypatch.setenv("VCTPU_MESH_DEVICES", "2")
    monkeypatch.setenv("VCTPU_OBS", "1")
    engine_mod.reset_for_tests()
    try:
        faults.arm("xla.dispatch_oom", times=1)
        stats = _run_stream(w, out, monkeypatch)
        assert stats is not None and stats["n"] == w["n"]
        events = _obs_events(out + ".obs.jsonl")
        shrink = [e for e in events if e["kind"] == "recovery"
                  and e["name"] == "megabatch_shrink"]
        assert len(shrink) == 1
        tids = shrink[0].get("trace_ids")
        assert tids, "shrink event carries no member traces"
        by_trace = _trace_spans_by_id(events)
        for tid in tids:
            spans = by_trace.get(tid)
            assert spans, f"shrink member {tid} resolves to no spans"
            assert "ingest" in {s["name"] for s in spans}
    finally:
        engine_mod.reset_for_tests()


def test_megabatch_split_links_traces_unit():
    """The non-OOM SPLIT rung (driven directly): the group failure event
    lists every member's trace, and the poison chunk's per-chunk retry
    links its own trace via the bound scope."""
    import tempfile

    from variantcalling_tpu import obs
    from variantcalling_tpu.parallel import shard_score

    class _Tab:
        def __init__(self, n):
            self._n = n

        def __len__(self):
            return self._n

    class _Plan:
        devices = 2

    class _Ctx:
        mesh_plan = _Plan()

        def __init__(self):
            self.calls = 0

        def score_packed(self, group):
            self.calls += 1
            if len(group) > 1:
                raise RuntimeError("poison in the group")  # non-OOM
            return [(t, "score", "filters") for t, _ in group]

    d = tempfile.mkdtemp()
    run = obs.start_run("split_unit", force_path=f"{d}/r.jsonl")
    assert run is not None
    try:
        ctx = _Ctx()
        pairs = []
        for i in range(3):
            t = _Tab(12000)  # 3 x 12000 crosses the 32768-row target: ONE group
            t._obs_trace = obs.new_trace()
            obs.trace_span(t._obs_trace, "ingest", 0.001)
            pairs.append((t, f"hf{i}"))
        out = list(shard_score.megabatch_stream(iter(pairs), ctx))
        assert len(out) == 3  # split re-dispatched chunk by chunk
    finally:
        obs.end_run(run, "ok")
    events = _obs_events(f"{d}/r.jsonl")
    split = [e for e in events if e["kind"] == "recovery"
             and e["name"] == "megabatch_split"]
    assert len(split) == 1
    tids = split[0]["trace_ids"]
    assert len(tids) == 3
    by_trace = _trace_spans_by_id(events)
    assert all(tid in by_trace for tid in tids)
    # the per-chunk fan-in spans after the split: one per chunk
    fanin = [e for e in events if e["kind"] == "trace"
             and e["name"] == "score_stage"]
    assert len(fanin) == 3
    assert [e["traces"] for e in fanin] == [[t] for t in tids]
