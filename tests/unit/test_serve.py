"""``vctpu serve`` — the fault-isolated resident daemon (ISSUE 14).

Covers the tentpole and its satellites: request/thread-scoped knob
overrides (``knobs.scope``) that cannot leak across concurrent
contexts (including through the executor's worker pools), scoped fault
injection, cooperative cancellation, the unique-suffix atomic-commit
partials (collision regression + stale sweep), the admission
controller's shed/deadline decisions, the in-process daemon round trip
(byte parity vs the batch path, per-request fault isolation, shed
responses, per-endpoint metrics with Prometheus endpoint labels), and
the graceful SIGTERM drain as a subprocess test (in-flight completes
byte-identically, new requests refused with a distinct status, obs
``run_end`` flushes with status ``drain``, no thread leaks)."""

import json
import os
import pickle
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from tests.conftest import assert_no_stream_leaks
from variantcalling_tpu import knobs
from variantcalling_tpu.engine import EngineError
from variantcalling_tpu.utils import cancellation, faults

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: directories the leak sentinel sweeps after every test in this module
_WATCHED_DIRS: list[str] = []


@pytest.fixture(autouse=True)
def _leak_sentinel():
    yield
    assert_no_stream_leaks(_WATCHED_DIRS)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


# ---------------------------------------------------------------------------
# knobs.scope — request/thread-scoped overrides
# ---------------------------------------------------------------------------


def test_knob_scope_overrides_and_restores(monkeypatch):
    monkeypatch.setenv("VCTPU_CHUNK_RETRIES", "3")
    assert knobs.get_int("VCTPU_CHUNK_RETRIES") == 3
    with knobs.scope({"VCTPU_CHUNK_RETRIES": "0"}):
        assert knobs.get_int("VCTPU_CHUNK_RETRIES") == 0
        assert knobs.source("VCTPU_CHUNK_RETRIES") == "scope"
    assert knobs.get_int("VCTPU_CHUNK_RETRIES") == 3
    assert knobs.source("VCTPU_CHUNK_RETRIES") == "env"


def test_knob_scope_none_masks_env(monkeypatch):
    monkeypatch.setenv("VCTPU_IO_THREADS", "7")
    with knobs.scope({"VCTPU_IO_THREADS": None}):
        # masked back to the declared default (None -> cpu count path)
        assert knobs.raw("VCTPU_IO_THREADS") is None
        assert knobs.source("VCTPU_IO_THREADS") == "scope"
    assert knobs.get_int("VCTPU_IO_THREADS") == 7


def test_knob_scope_nests_and_layers():
    with knobs.scope({"VCTPU_CHUNK_RETRIES": "5"}):
        with knobs.scope({"VCTPU_IO_RETRIES": "9"}):
            # inner layer merges over outer: both visible
            assert knobs.get_int("VCTPU_CHUNK_RETRIES") == 5
            assert knobs.get_int("VCTPU_IO_RETRIES") == 9
        assert knobs.source("VCTPU_IO_RETRIES") == "default"


def test_knob_scope_unknown_name_raises_at_entry():
    with pytest.raises(KeyError):
        knobs.scope({"VCTPU_NO_SUCH_KNOB": "1"})


def test_knob_scope_malformed_value_raises_at_read():
    with knobs.scope({"VCTPU_CHUNK_RETRIES": "banana"}), \
            pytest.raises(EngineError):
        knobs.get_int("VCTPU_CHUNK_RETRIES")


def test_knob_scope_isolated_between_threads():
    """The serve isolation contract: a scope bound in one thread is
    invisible to a sibling thread's reads."""
    seen = {}
    gate = threading.Barrier(2, timeout=10)

    def reader():
        gate.wait()  # scope is active in the main thread now
        seen["sibling"] = knobs.get_int("VCTPU_CHUNK_RETRIES")

    t = threading.Thread(target=reader)
    t.start()
    with knobs.scope({"VCTPU_CHUNK_RETRIES": "0"}):
        gate.wait()
        t.join(timeout=10)
        assert knobs.get_int("VCTPU_CHUNK_RETRIES") == 0
    assert seen["sibling"] == 1  # registry default, not the scope's 0


def test_knob_scope_propagates_into_io_pool():
    """IoPool tasks run in the SUBMITTER's context (the executor-side
    half of the no-leak contract): a pooled chunk body sees its
    request's scoped knobs."""
    from variantcalling_tpu.parallel.pipeline import IoPool

    pool = IoPool(2, name="vctpu-io-scopetest")
    try:
        with knobs.scope({"VCTPU_CHUNK_RETRIES": "7"}):
            inside = pool.submit(
                lambda: knobs.get_int("VCTPU_CHUNK_RETRIES")).result(10)
        outside = pool.submit(
            lambda: knobs.get_int("VCTPU_CHUNK_RETRIES")).result(10)
    finally:
        pool.shutdown()
    assert inside == 7
    assert outside == 1


def test_knob_scope_propagates_into_stage_pipeline():
    from variantcalling_tpu.parallel.pipeline import StagePipeline

    seen = []

    def stage(item):
        seen.append(knobs.get_int("VCTPU_CHUNK_RETRIES"))
        return item

    with knobs.scope({"VCTPU_CHUNK_RETRIES": "9"}):
        pipe = StagePipeline([stage], threads=2, timeout=30)
        assert list(pipe.run(iter(range(3)))) == [0, 1, 2]
    assert seen == [9, 9, 9]


# ---------------------------------------------------------------------------
# faults.scope — request-scoped injection
# ---------------------------------------------------------------------------


def test_fault_scope_fires_only_in_scope():
    with faults.scope("pipeline.chunk:1"):
        with pytest.raises(RuntimeError, match="chunk scoring"):
            faults.check("pipeline.chunk")
        faults.check("pipeline.chunk")  # budget spent
    faults.check("pipeline.chunk")  # outside: disarmed


def test_fault_scope_invisible_to_sibling_thread():
    results = {}
    gate = threading.Barrier(2, timeout=10)

    def sibling():
        gate.wait()
        try:
            faults.check("pipeline.chunk")
            results["sibling"] = "clean"
        except RuntimeError:
            results["sibling"] = "fired"

    t = threading.Thread(target=sibling)
    t.start()
    with faults.scope("pipeline.chunk:0"):  # unlimited, this scope only
        gate.wait()
        t.join(timeout=10)
        with pytest.raises(RuntimeError):
            faults.check("pipeline.chunk")
    assert results["sibling"] == "clean"


def test_fault_scope_propagates_into_io_pool():
    from variantcalling_tpu.parallel.pipeline import IoPool

    def body():
        faults.check("pipeline.chunk")
        return "clean"

    pool = IoPool(1, name="vctpu-io-faultscope")
    try:
        with faults.scope("pipeline.chunk:0"):
            with pytest.raises(RuntimeError, match="chunk scoring"):
                pool.submit(body).result(10)
        assert pool.submit(body).result(10) == "clean"
    finally:
        pool.shutdown()


def test_fault_scope_empty_spec_noop():
    with faults.scope(""):
        faults.check("pipeline.chunk")


# ---------------------------------------------------------------------------
# cancellation
# ---------------------------------------------------------------------------


def test_cancellation_token_scope_and_check():
    token = cancellation.CancelToken()
    with cancellation.scope(token):
        cancellation.check("t")  # not yet tripped
        token.cancel("deadline expired")
        with pytest.raises(cancellation.CancelledError, match="deadline"):
            cancellation.check("t")
    cancellation.check("t")  # outside the scope: no token, no raise


# ---------------------------------------------------------------------------
# streaming fixtures (filter world) for collision/cancel/daemon tests
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serve_world(tmp_path_factory):
    from variantcalling_tpu.synthetic import make_fixtures, synthetic_forest

    d = tmp_path_factory.mktemp("serve_world")
    _WATCHED_DIRS.append(str(d))
    make_fixtures(str(d), n=1500, genome_len=120_000)
    model = synthetic_forest(np.random.default_rng(0), n_trees=8, depth=4)
    model_pkl = str(d / "model.pkl")
    with open(model_pkl, "wb") as fh:
        pickle.dump({"m": model}, fh)
    # the cold reference (direct pipeline run)
    from variantcalling_tpu.pipelines.filter_variants import run as frun

    ref_out = str(d / "reference.vcf")
    assert frun(["--input_file", str(d / "calls.vcf"),
                 "--model_file", model_pkl, "--model_name", "m",
                 "--reference_file", str(d / "ref.fa"),
                 "--output_file", ref_out, "--backend", "cpu"]) == 0
    return {"dir": str(d), "input": str(d / "calls.vcf"),
            "model": model_pkl, "ref": str(d / "ref.fa"),
            "reference_bytes": open(ref_out, "rb").read()}


def _filter_argv(w, out, extra=()):
    return ["--input_file", w["input"], "--model_file", w["model"],
            "--model_name", "m", "--reference_file", w["ref"],
            "--output_file", out, "--backend", "cpu", *extra]


def _strip_prov(data: bytes) -> bytes:
    from tools.chaoshunt.harness import normalize_output

    return normalize_output(data)


# ---------------------------------------------------------------------------
# unique-suffix partials (the atomic-commit collision fix)
# ---------------------------------------------------------------------------


def test_concurrent_runs_same_output_do_not_clobber(serve_world,
                                                    monkeypatch):
    """The ISSUE 14 collision regression: two concurrent streaming runs
    targeting the SAME output each accumulate their own unique-suffix
    partial; both commit atomically; the destination holds one COMPLETE
    output and no partial survives. (Journaling off: a shared journal
    path is a separate, documented non-goal for same-output concurrency;
    the partial clobber was the silent byte-corruption bug.)"""
    from variantcalling_tpu.pipelines.filter_variants import run as frun

    w = serve_world
    out = os.path.join(w["dir"], "collide.vcf")
    monkeypatch.setenv("VCTPU_RESUME", "0")
    monkeypatch.setenv("VCTPU_STREAM_CHUNK_BYTES", str(1 << 14))
    rcs = []
    gate = threading.Barrier(2, timeout=30)

    def one():
        gate.wait()
        rcs.append(frun(_filter_argv(w, out)))

    ts = [threading.Thread(target=one) for _ in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    assert rcs == [0, 0]
    assert open(out, "rb").read() == w["reference_bytes"]
    from variantcalling_tpu.io.journal import list_partials

    assert not list_partials(out)
    os.remove(out)


def test_concurrent_journaled_runs_same_output_bytes_safe(serve_world,
                                                          monkeypatch):
    """The DEFAULT path (journaling ON): two concurrent runs to one
    output must both complete with the destination holding one COMPLETE
    reference-equal file — the in-use partial of the live peer is never
    discarded/truncated (token_in_use), only the shared journal
    bookkeeping is superseded (documented: bytes safe, the loser's
    resume degrades to fresh)."""
    from variantcalling_tpu.pipelines.filter_variants import run as frun

    w = serve_world
    out = os.path.join(w["dir"], "collide_journaled.vcf")
    monkeypatch.setenv("VCTPU_STREAM_CHUNK_BYTES", str(1 << 14))
    rcs = []
    gate = threading.Barrier(2, timeout=30)

    def one():
        gate.wait()
        rcs.append(frun(_filter_argv(w, out)))

    ts = [threading.Thread(target=one) for _ in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    assert rcs == [0, 0]
    assert open(out, "rb").read() == w["reference_bytes"]
    import glob

    from variantcalling_tpu.io.journal import list_partials

    assert not list_partials(out)
    for p in glob.glob(glob.escape(out) + "*"):
        os.remove(p)


def test_resume_refused_while_partial_in_use_then_retokened(tmp_path):
    """try_resume refuses a journal whose partial a RUNNING request owns
    (claimed token, our pid); once released it resumes — renaming the
    partial onto a fresh token owned by the resumer's pid."""
    import zlib

    from variantcalling_tpu.io import journal as journal_mod

    out = str(tmp_path / "x.vcf")
    header, body = b"HEAD", b"x" * 100
    token = journal_mod.new_partial_token()
    meta = {"input": "i", "input_sig": [1, 2], "chunk_bytes": 3,
            "header_len": len(header), "header_crc": zlib.crc32(header)}
    j = journal_mod.ChunkJournal(out)
    j.begin(dict(meta, partial=token))
    j.append(0, 10, 5, len(body), zlib.crc32(body))
    j.close()
    with open(journal_mod.partial_path(out, token), "wb") as fh:
        fh.write(header + body)
    journal_mod.claim_token(token)
    try:
        assert journal_mod.try_resume(out, meta) is None  # live owner
    finally:
        journal_mod.release_token(token)
    rs = journal_mod.try_resume(out, meta)
    assert rs is not None and rs.chunks == 1
    assert rs.partial_token != token  # re-tokened to the resumer
    assert rs.partial_token.split("-")[0] == str(os.getpid())
    new_part = journal_mod.partial_path(out, rs.partial_token)
    assert os.path.exists(new_part)
    assert not os.path.exists(journal_mod.partial_path(out, token))
    # the healed journal names the new token
    jmeta = json.loads(open(out + ".journal", encoding="utf-8").readline())
    assert jmeta["partial"] == rs.partial_token
    journal_mod.discard(out)


def test_discard_spares_in_use_partial(tmp_path):
    from variantcalling_tpu.io import journal as journal_mod

    out = str(tmp_path / "y.vcf")
    token = journal_mod.new_partial_token()
    j = journal_mod.ChunkJournal(out)
    j.begin({"input": "i", "partial": token})
    j.close()
    part = journal_mod.partial_path(out, token)
    open(part, "wb").write(b"live bytes")
    journal_mod.claim_token(token)
    try:
        journal_mod.discard(out)
        assert os.path.exists(part)  # the live writer's file survives
        assert not os.path.exists(out + ".journal")
    finally:
        journal_mod.release_token(token)
    journal_mod.discard(out)  # released: now it goes
    assert not os.path.exists(part)


def test_stale_partial_cleanup_sweeps_unowned_only(tmp_path):
    from variantcalling_tpu.io import journal as journal_mod

    out = str(tmp_path / "x.vcf")
    dead = out + ".partial.999999999-cafe0000"
    claimed_tok = f"{os.getpid()}-beef0000"
    claimed = out + f".partial.{claimed_tok}"
    orphan = out + f".partial.{os.getpid()}-dead0000"  # own pid, no claim
    foreign = out + ".partial.not-a-pid"
    for p in (dead, claimed, orphan, foreign):
        open(p, "wb").write(b"z")
    journal_mod.claim_token(claimed_tok)
    try:
        journal_mod.cleanup_stale_partials(out)
        assert not os.path.exists(dead)  # owner pid gone: swept
        assert not os.path.exists(orphan)  # own pid, unclaimed: swept
        assert os.path.exists(claimed)  # an open sink owns it: untouched
        assert os.path.exists(foreign)  # not our scheme: untouched
    finally:
        journal_mod.release_token(claimed_tok)
    for p in (claimed, foreign):
        os.remove(p)


def test_resume_finds_unique_partial_token(serve_world, monkeypatch):
    """A failed journaled run leaves <out>.partial.<token> + journal;
    the rerun resumes through the token the journal recorded."""
    from variantcalling_tpu.pipelines.filter_variants import run as frun

    w = serve_world
    out = os.path.join(w["dir"], "resume_tok.vcf")
    monkeypatch.setenv("VCTPU_STREAM_CHUNK_BYTES", str(1 << 14))
    faults.arm("io.writeback", times=None, after=2)
    with pytest.raises(OSError):
        from variantcalling_tpu.pipelines.filter_variants import \
            run_streaming
        from variantcalling_tpu.io.fasta import FastaReader
        from variantcalling_tpu.models.registry import load_model

        run_streaming(
            __import__("argparse").Namespace(
                input_file=w["input"], model_file=w["model"],
                model_name="m", reference_file=w["ref"], output_file=out,
                runs_file=None, blacklist=None,
                blacklist_cg_insertions=False,
                hpol_filter_length_dist=[10, 10], flow_order="TGCA",
                is_mutect=False, annotate_intervals=[],
                limit_to_contig=None),
            load_model(w["model"], "m"), FastaReader(w["ref"]), {}, None)
    faults.reset()
    jmeta = json.loads(open(out + ".journal", encoding="utf-8").readline())
    token = jmeta.get("partial")
    assert token and str(os.getpid()) == token.split("-")[0]
    from variantcalling_tpu.io import journal as journal_mod

    assert os.path.exists(journal_mod.partial_path(out, token))
    assert frun(_filter_argv(w, out)) == 0
    assert open(out, "rb").read() == w["reference_bytes"]
    os.remove(out)


# ---------------------------------------------------------------------------
# admission controller
# ---------------------------------------------------------------------------


def test_admission_sheds_beyond_queue_depth(monkeypatch):
    from variantcalling_tpu.serve.admission import (AdmissionController,
                                                    ShedError)

    monkeypatch.setenv("VCTPU_SERVE_MAX_INFLIGHT", "1")
    monkeypatch.setenv("VCTPU_SERVE_QUEUE_DEPTH", "0")
    ac = AdmissionController()
    release = ac.admit("filter", None)  # takes the one slot
    with pytest.raises(ShedError) as ei:
        ac.admit("filter", None)  # queue depth 0: immediate shed
    assert ei.value.reason == "queue_full"
    release()
    ac.admit("filter", None)()  # slot free again


def test_admission_queue_deadline(monkeypatch):
    from variantcalling_tpu.serve.admission import (AdmissionController,
                                                    QueueDeadlineError)

    monkeypatch.setenv("VCTPU_SERVE_MAX_INFLIGHT", "1")
    monkeypatch.setenv("VCTPU_SERVE_QUEUE_DEPTH", "4")
    ac = AdmissionController()
    release = ac.admit("filter", None)
    t0 = time.monotonic()
    with pytest.raises(QueueDeadlineError):
        ac.admit("filter", 0.3)
    assert 0.2 < time.monotonic() - t0 < 5.0
    release()


def test_admission_slo_early_shed(monkeypatch):
    """The closed loop: a rolling-p50 latency estimate that already
    blows the deadline sheds at arrival (reason 'slo')."""
    from variantcalling_tpu.serve.admission import (AdmissionController,
                                                    ShedError)

    monkeypatch.setenv("VCTPU_SERVE_MAX_INFLIGHT", "1")
    monkeypatch.setenv("VCTPU_SERVE_QUEUE_DEPTH", "8")
    ac = AdmissionController(latency_p50=lambda ep: 10.0)
    release = ac.admit("filter", 60.0)  # in-flight: est wait 10s < 60s
    with pytest.raises(ShedError) as ei:
        ac.admit("filter", 5.0)  # est wait 10s > 5s deadline
    assert ei.value.reason == "slo"
    assert ei.value.retry_after_s >= 10.0
    release()


def test_admission_draining_refuses(monkeypatch):
    from variantcalling_tpu.serve.admission import (AdmissionController,
                                                    ShedError)

    ac = AdmissionController()
    ac.draining = True
    with pytest.raises(ShedError) as ei:
        ac.admit("filter", None)
    assert ei.value.reason == "draining"


# ---------------------------------------------------------------------------
# the in-process daemon
# ---------------------------------------------------------------------------


@pytest.fixture()
def daemon(serve_world, monkeypatch):
    from variantcalling_tpu.serve.daemon import Server

    monkeypatch.setenv("VCTPU_STREAM_CHUNK_BYTES", str(1 << 14))
    monkeypatch.setenv("VCTPU_SERVE_MAX_INFLIGHT", "2")
    monkeypatch.setenv("VCTPU_SERVE_QUEUE_DEPTH", "2")
    s = Server(port=0)
    s.start()
    yield s
    if not s.draining.is_set():
        s.drain("test")


def _post(address, path, body, timeout=120):
    req = urllib.request.Request(
        address + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _get(address, path, timeout=30):
    with urllib.request.urlopen(address + path, timeout=timeout) as r:
        return r.status, r.read()


def _filter_body(w, out, **kw):
    return {"input": w["input"], "model": w["model"], "model_name": "m",
            "reference": w["ref"], "output": out, **kw}


def test_serve_filter_byte_parity(daemon, serve_world):
    w = serve_world
    out = os.path.join(w["dir"], "served.vcf")
    code, payload = _post(daemon.address, "/v1/filter", _filter_body(w, out))
    assert code == 200 and payload["status"] == "ok"
    assert open(out, "rb").read() == w["reference_bytes"]
    os.remove(out)


def test_serve_score_and_coverage(daemon, serve_world):
    w = serve_world
    code, payload = _post(daemon.address, "/v1/score",
                          {"input": w["input"], "model": w["model"],
                           "model_name": "m", "reference": w["ref"]})
    assert code == 200 and payload["n"] == 1500
    assert 0.0 < payload["score_mean"] < 1.0
    code, payload = _post(daemon.address, "/v1/coverage",
                          {"depth": list(range(400)), "window": 40})
    assert code == 200 and payload["windows"] == 10
    assert payload["percentiles"]["p50"] == 199


def test_serve_poisoned_request_isolated(daemon, serve_world):
    """The headline: a poisoned request fails with a DISTINCT per-request
    error while a concurrent request completes byte-identically, and the
    daemon keeps serving."""
    w = serve_world
    out_bad = os.path.join(w["dir"], "poison.vcf")
    out_good = os.path.join(w["dir"], "good.vcf")
    res = {}

    def call(name, body):
        res[name] = _post(daemon.address, "/v1/filter", body)

    ts = [threading.Thread(target=call, args=(
        "bad", _filter_body(w, out_bad, faults="pipeline.chunk:0",
                            knobs={"VCTPU_CHUNK_RETRIES": "0"}))),
        threading.Thread(target=call, args=(
            "good", _filter_body(w, out_good)))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    code, payload = res["bad"]
    assert code == 500 and payload["status"] == "error"
    assert payload["kind"] == "RuntimeError"
    assert not os.path.exists(out_bad)
    code, payload = res["good"]
    assert code == 200 and payload["status"] == "ok"
    assert open(out_good, "rb").read() == w["reference_bytes"]
    # the daemon is still healthy
    code, body = _get(daemon.address, "/healthz")
    assert code == 200 and json.loads(body)["status"] == "ok"
    os.remove(out_good)
    from tools.loadhunt.harness import _sidecars

    for flag, present in _sidecars(out_bad).items():
        if present:  # failed request keeps paired resume state at most
            assert flag in ("partial", "journal")
    import glob

    for p in glob.glob(glob.escape(out_bad) + "*"):
        os.remove(p)


def test_serve_scoped_knob_error_is_per_request(daemon, serve_world):
    w = serve_world
    out = os.path.join(w["dir"], "cfg.vcf")
    code, payload = _post(daemon.address, "/v1/filter",
                          _filter_body(w, out,
                                       knobs={"VCTPU_CHUNK_RETRIES": "nan!"}))
    assert code == 400 and payload["status"] == "config_error"
    code, payload = _post(daemon.address, "/v1/filter",
                          _filter_body(w, out,
                                       knobs={"VCTPU_TYPO_KNOB": "1"}))
    assert code == 400 and payload["status"] == "config_error"
    code, payload = _post(daemon.address, "/v1/filter",
                          _filter_body(w, out,
                                       knobs={"VCTPU_SERVE_PORT": "1"}))
    assert code == 400 and "cannot be scoped" in payload["error"]
    assert not os.path.exists(out)


def test_serve_sheds_beyond_capacity(daemon, serve_world):
    """Overload: capacity is max_inflight(2)+queue(2)=4; 8 concurrent
    slow requests must produce explicit sheds, never a hang."""
    w = serve_world
    results = []
    lock = threading.Lock()

    def call(i):
        out = os.path.join(w["dir"], f"flood{i}.vcf")
        body = _filter_body(w, out, faults="pipeline.stage_hang:0@0.1")
        r = _post(daemon.address, "/v1/filter", body, timeout=120)
        with lock:
            results.append(r)

    ts = [threading.Thread(target=call, args=(i,)) for i in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    assert len(results) == 8
    statuses = [p.get("status") for _, p in results]
    assert all(s in ("ok", "shed") for s in statuses), statuses
    assert statuses.count("shed") >= 8 - 4
    for _, p in results:
        if p.get("status") == "shed":
            assert p["reason"] in ("queue_full", "slo")
    import glob

    for i in range(8):
        for p in glob.glob(os.path.join(w["dir"], f"flood{i}.vcf*")):
            os.remove(p)


def test_serve_request_deadline_cancels(daemon, serve_world):
    """A request whose deadline expires mid-run is cancelled at a chunk
    boundary: 504 deadline status, destination untouched, daemon alive.

    The injected hang outlasts the deadline by construction: EVERY chunk
    body waits 1.5 s before it parses, so no chunk can reach the committer
    (whose per-chunk poll is where the cancellation lands) before the 0.5 s
    deadline has expired, whatever the machine's speed."""
    w = serve_world
    out = os.path.join(w["dir"], "late.vcf")
    code, payload = _post(
        daemon.address, "/v1/filter",
        _filter_body(w, out, deadline_s=0.5,
                     faults="pipeline.stage_hang:0@1.5"))
    assert code == 504 and payload["status"] == "deadline"
    assert not os.path.exists(out)
    code, _ = _get(daemon.address, "/healthz")
    assert code == 200
    import glob

    for p in glob.glob(glob.escape(out) + "*"):
        os.remove(p)


def test_serve_status_and_prom_metrics(daemon, serve_world):
    w = serve_world
    out = os.path.join(w["dir"], "metrics_run.vcf")
    assert _post(daemon.address, "/v1/filter",
                 _filter_body(w, out))[0] == 200
    os.remove(out)
    code, body = _get(daemon.address, "/v1/status")
    st = json.loads(body)
    assert code == 200 and st["status"] == "ok"
    assert st["in_flight"] == 0 and "filter" in st["endpoints"]
    assert st["endpoints"]["filter"]["rolling_p99_s"] > 0
    assert st["resident"]["models"]["entries"] >= 1
    code, body = _get(daemon.address, "/v1/metrics")
    text = body.decode()
    assert 'vctpu_serve_requests_ok_total{endpoint="filter"}' in text
    assert 'vctpu_serve_request_s_rolling{endpoint="filter",quantile="0.99"' \
        in text
    # one TYPE line per family even with several endpoint labels
    assert text.count("# TYPE vctpu_serve_requests_ok_total counter") == 1


def test_serve_unknown_path_and_malformed_body(daemon):
    code, payload = _post(daemon.address, "/v1/nope", {})
    assert code == 404
    req = urllib.request.Request(
        daemon.address + "/v1/filter", data=b"not json{",
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            code, payload = r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        code, payload = e.code, json.loads(e.read())
    assert code == 400 and payload["status"] == "bad_request"


# ---------------------------------------------------------------------------
# graceful drain (subprocess — the satellite's SIGTERM test)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sig,signame", [(signal.SIGTERM, "sigterm"),
                                         (signal.SIGINT, "sigint")])
def test_serve_signal_graceful_drain(serve_world, tmp_path, sig, signame):
    """SIGTERM/SIGINT mid-request: the in-flight request COMPLETES
    byte-identically, new requests get a distinct refused status, the
    obs stream flushes run_end with status 'drain', the daemon exits 0
    and self-reports zero leaked threads."""
    w = serve_world
    d = str(tmp_path)
    ready, status_f = os.path.join(d, "ready.json"), os.path.join(d, "st.json")
    obs_log = os.path.join(d, "serve_obs.jsonl")
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("VCTPU_")}
    env.update(PYTHONPATH=_REPO, JAX_PLATFORMS="cpu",
               VCTPU_STREAM_CHUNK_BYTES=str(1 << 14),
               VCTPU_SERVE_DRAIN_S="60")
    proc = subprocess.Popen(  # noqa: S603
        [sys.executable, "-m", "variantcalling_tpu", "serve", "--port", "0",
         "--backend", "cpu", "--ready-file", ready,
         "--status-file", status_f, "--obs-log", obs_log],
        env=env, cwd=_REPO, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    try:
        deadline = time.time() + 120
        while time.time() < deadline and not os.path.exists(ready):
            assert proc.poll() is None, "daemon died before listening"
            time.sleep(0.05)
        address = json.load(open(ready))["address"]
        out = os.path.join(d, "inflight.vcf")
        result = {}

        def slow_request():
            # per-chunk injected delays stretch the run so the SIGTERM
            # lands mid-request
            result["r"] = _post(
                address, "/v1/filter",
                _filter_body(w, out, faults="pipeline.stage_hang:0@0.25"),
                timeout=120)

        t = threading.Thread(target=slow_request)
        t.start()
        # wait until the request is actually in flight
        for _ in range(600):
            st = json.loads(_get(address, "/v1/status")[1])
            if st["in_flight"] > 0:
                break
            time.sleep(0.05)
        assert st["in_flight"] > 0, "request never started"
        proc.send_signal(sig)
        time.sleep(0.2)
        # new work is refused with a DISTINCT status while draining
        code, payload = _post(address, "/v1/filter",
                              _filter_body(w, os.path.join(d, "new.vcf")),
                              timeout=30)
        assert code == 503 and payload["status"] == "draining"
        t.join(timeout=120)
        code, payload = result["r"]
        assert code == 200 and payload["status"] == "ok"
        assert open(out, "rb").read() == w["reference_bytes"]
        assert proc.wait(timeout=90) == 0
        status = json.load(open(status_f))
        assert status["status"] == "drained"
        assert status["reason"] == signame
        assert status["leaked"] == []
        run_end = [json.loads(ln) for ln in open(obs_log)
                   if '"run_end"' in ln][-1]
        assert run_end["status"] == "drain"
        assert not os.path.exists(os.path.join(d, "new.vcf"))
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
