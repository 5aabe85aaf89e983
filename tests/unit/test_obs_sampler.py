"""obs v3 (ISSUE 13 tentpole): the continuous in-process sampling
profiler — sampler lifecycle + thread-family/category attribution, the
native-span overlay, flame exports (speedscope/collapsed/diff), the
measured cpu-budget ledger, wait-edge reconciliation in the
critical-path engine, CLI exit codes, coexistence with the recovery
ladder under injected faults (byte identity + no thread leaks), and the
``VCTPU_OBS_TAIL_POLL_S`` knob."""

from __future__ import annotations

import argparse
import json
import os
import pickle
import threading
import time
import zlib

import numpy as np
import pytest

from tests.conftest import assert_no_stream_leaks
from variantcalling_tpu import obs
from variantcalling_tpu.obs import cli as obs_cli
from variantcalling_tpu.obs import critical as critical_mod
from variantcalling_tpu.obs import export as export_mod
from variantcalling_tpu.obs import sampler as sampler_mod
from variantcalling_tpu.utils import faults

_WATCHED_DIRS: list[str] = []


@pytest.fixture(autouse=True)
def _isolated():
    yield
    run = obs.current()
    if run is not None:
        obs.end_run(run, "test-teardown")
    faults.reset()
    assert_no_stream_leaks(_WATCHED_DIRS)


def _open_run(tmp_path, name="run.jsonl", **kw):
    path = str(tmp_path / name)
    run = obs.start_run("test_tool", force_path=path, **kw)
    assert run is not None
    return run, path


def _events(path):
    return [json.loads(ln) for ln in open(path, encoding="utf-8")
            if ln.strip()]


# ---------------------------------------------------------------------------
# sampler lifecycle + attribution
# ---------------------------------------------------------------------------


def _gil_releasing_burn(stop, span=None):
    """CPU work that RELEASES the GIL (zlib, like the real native
    engine) so the sampler thread can actually sample mid-call."""
    payload = os.urandom(1 << 18)
    while not stop.is_set():
        if span is not None:
            with sampler_mod.native_span(span):
                zlib.compress(payload, 6)
        else:
            zlib.compress(payload, 6)


def test_sampler_records_samples_families_and_summary(tmp_path):
    run, path = _open_run(tmp_path)
    cs = sampler_mod.CpuSampler(run, hz=200.0)
    cs.start()
    stop = threading.Event()
    t = threading.Thread(target=_gil_releasing_burn, args=(stop,),
                         name="vctpu-io-w0", daemon=True)
    t.start()
    deadline = time.time() + 5.0
    while cs.cpu_samples == 0 and time.time() < deadline:
        time.sleep(0.01)
    stop.set()
    t.join()
    cs.stop()
    obs.end_run(run, "ok")
    evs = _events(path)
    samples = [e for e in evs if e["kind"] == "sample"]
    assert samples, "no sample events recorded"
    # every sample carries the schema'd fields + a window start
    for e in samples:
        assert isinstance(e["stack"], str) and isinstance(e["n"], int)
        assert e["cat"] in ("gil", "native", "runnable", "wait")
        assert isinstance(e["family"], str)
        assert e["win_t0"] <= e["t"]
    fams = {e["family"] for e in samples}
    assert "io" in fams  # name-classified vctpu-io-w0 worker
    cats = {e["cat"] for e in samples}
    assert cats & {"gil", "native"}, f"no on-CPU category in {cats}"
    summary = [e for e in evs
               if e["kind"] == "profile" and e["name"] == "cpuprof"]
    assert len(summary) == 1
    assert summary[0]["samples"] >= summary[0]["cpu_samples"] > 0
    assert summary[0]["hz"] == 200.0
    # summary precedes the final metrics snapshot (end_run ordering)
    kinds = [e["kind"] for e in evs]
    assert kinds.index("metrics") > [i for i, e in enumerate(evs)
                                     if e["kind"] == "profile"
                                     and e["name"] == "cpuprof"][0]


def test_native_span_overlay_and_category(tmp_path):
    run, path = _open_run(tmp_path)
    cs = sampler_mod.CpuSampler(run, hz=200.0)
    cs.start()
    stop = threading.Event()
    t = threading.Thread(target=_gil_releasing_burn,
                         args=(stop, "fused_chunk_score"),
                         name="vctpu-io-w0", daemon=True)
    t.start()
    # wait for several on-CPU samples — a single one could belong to an
    # unrelated thread (the obs-sampler resource thread) without the
    # overlay; the burn thread is the only sustained CPU consumer
    deadline = time.time() + 5.0
    while cs.cpu_samples < 5 and time.time() < deadline:
        time.sleep(0.01)
    stop.set()
    t.join()
    cs.stop()
    obs.end_run(run, "ok")
    samples = [e for e in _events(path) if e["kind"] == "sample"]
    overlaid = [e for e in samples
                if e["stack"].endswith("[native:fused_chunk_score]")]
    assert overlaid, "no sample carried the native-span overlay"
    # an on-CPU sample inside a native span classifies as off-GIL native
    assert any(e["cat"] == "native" for e in overlaid)


def test_sampler_off_by_default_and_started_by_knob(tmp_path, monkeypatch):
    run, path = _open_run(tmp_path, name="off.jsonl")
    assert run.cpu_sampler is None  # VCTPU_OBS_CPUPROF defaults off
    obs.end_run(run, "ok")
    assert not any(e["kind"] == "sample" for e in _events(path))
    monkeypatch.setenv("VCTPU_OBS_CPUPROF", "1")
    monkeypatch.setenv("VCTPU_OBS_CPUPROF_HZ", "100")
    run, path = _open_run(tmp_path, name="on.jsonl")
    assert run.cpu_sampler is not None
    assert run.cpu_sampler.hz == 100.0
    obs.end_run(run, "ok")
    # end_run stopped and joined the sampler thread (leak sentinel
    # re-checks in teardown)
    assert not [t for t in threading.enumerate()
                if t.name == "vctpu-sampler"]


def test_thread_family_classification():
    assert sampler_mod.classify("vctpu-io-w3") == "io"
    assert sampler_mod.classify("vctpu-mesh-dispatch-w0") == "mesh"
    assert sampler_mod.classify("pipe-src") == "pipe.src"
    assert sampler_mod.classify("pipe-stage2") == "pipe.stage"
    assert sampler_mod.classify("genome-prefetch") == "prefetch"
    assert sampler_mod.classify("MainThread") == "main"
    assert sampler_mod.classify("obs-sampler") == "obs"
    assert sampler_mod.classify("whatever") == "other"


# ---------------------------------------------------------------------------
# exporters on a synthetic log (deterministic goldens)
# ---------------------------------------------------------------------------


def _env(seq, t, kind, name, **fields):
    return dict(fields, v=1, seq=seq, ts=1000.0 + t, t=t, kind=kind,
                name=name, pid=1, tid=1)


def _synthetic_sampled_log(records=1_000_000):
    """A hand-built log: 100 Hz, known per-stage sample counts — the
    ledger golden. 40 score + 30 parse + 20 render + 10 commit CPU
    samples => 1.0 cpu-s total at 100 Hz => exactly 1.0 cpu-s/1M."""
    evs = [
        _env(0, 0.0, "manifest", "t", tool="t", version="0",
             knobs={}, topology={}),
        _env(1, 1.0, "sample", "io",
             stack="io.vcf:parse_chunk;native:fused_chunk_score", n=40,
             cat="native", family="io", win_t0=0.0),
        _env(2, 1.0, "sample", "io",
             stack="io.vcf:parse_chunk;native:vcf_parse", n=30,
             cat="gil", family="io", win_t0=0.0),
        _env(3, 1.0, "sample", "io",
             stack="pipelines.filter_variants:render_stage", n=20,
             cat="gil", family="io", win_t0=0.0),
        _env(4, 1.0, "sample", "committer",
             stack="pipelines.filter_variants:_sink_write", n=10,
             cat="gil", family="committer", win_t0=0.0),
        # wait samples never enter the CPU ledger
        _env(5, 1.0, "sample", "main",
             stack="threading:wait", n=500, cat="wait", family="main",
             win_t0=0.0),
        _env(6, 1.5, "profile", "cpuprof", hz=100.0, interval_s=0.01,
             samples=600, cpu_samples=100, threads=3, cpu_s_total=1.0,
             families={"io": 0.9, "committer": 0.1}),
        _env(7, 2.0, "heartbeat", "stream", chunks=1, records=records),
        _env(8, 2.5, "metrics", "final", counters={"records": records},
             gauges={}, histograms={}),
        _env(9, 3.0, "run_end", "t", status="ok", dur=3.0),
    ]
    return evs


def _write_log(tmp_path, evs, name="synth.jsonl"):
    path = str(tmp_path / name)
    with open(path, "w", encoding="utf-8") as fh:
        for e in evs:
            fh.write(json.dumps(e) + "\n")
    return path


def test_cpuledger_golden_per_stage_per_1m(tmp_path):
    evs = _synthetic_sampled_log()
    ledger = sampler_mod.cpuledger(evs)
    assert ledger["hz"] == 100.0
    assert ledger["cpu_samples"] == 100
    assert ledger["records"] == 1_000_000
    assert ledger["total_cpu_s"] == pytest.approx(1.0)
    assert ledger["total_cpu_s_per_1m"] == pytest.approx(1.0)
    assert ledger["stages"] == {
        "score": pytest.approx(0.4),   # [native:...]-free frame marker
        "parse": pytest.approx(0.3),
        "render": pytest.approx(0.2),
        "commit": pytest.approx(0.1),
    }
    # the wait samples contributed nothing
    assert sum(ledger["stages_cpu_s"].values()) == pytest.approx(1.0)
    text = sampler_mod.render_cpuledger(ledger)
    assert "cpu-s/1M" in text and "score" in text and "TOTAL" in text


def test_cpuledger_without_records_reports_cpu_seconds_only():
    evs = [e for e in _synthetic_sampled_log()
           if e["kind"] not in ("heartbeat", "metrics")]
    ledger = sampler_mod.cpuledger(evs)
    assert "stages" not in ledger and "total_cpu_s_per_1m" not in ledger
    assert ledger["total_cpu_s"] == pytest.approx(1.0)
    assert "per-1M column" in sampler_mod.render_cpuledger(ledger)


def test_speedscope_and_collapsed_exports(tmp_path):
    evs = _synthetic_sampled_log()
    scope = sampler_mod.to_speedscope(evs, name="synth")
    n_frames = len(scope["shared"]["frames"])
    cats = {p["name"] for p in scope["profiles"]}
    assert any("[native]" in c or "native" in c for c in cats)
    for prof in scope["profiles"]:
        assert len(prof["samples"]) == len(prof["weights"])
        assert prof["endValue"] == sum(prof["weights"])
        for stack in prof["samples"]:
            assert all(0 <= i < n_frames for i in stack)
    lines = sampler_mod.collapsed_lines(evs)
    assert lines[0].endswith(" 500")  # heaviest first (the wait stack)
    assert any(line.startswith("io;native;io.vcf:parse_chunk;"
                               "native:fused_chunk_score 40")
               for line in lines)


def test_flame_diff_ranks_frame_deltas():
    base = _synthetic_sampled_log()
    # candidate: score samples doubled — its share rises, every other
    # frame's share falls; the diff must rank by |delta| with signs
    cand = [dict(e) for e in _synthetic_sampled_log()]
    for e in cand:
        if "fused_chunk_score" in e.get("stack", ""):
            e["n"] = 80
    report = sampler_mod.diff_folds(cand, base)
    assert report["frames"], "empty diff report"
    by_frame = {r["frame"]: r for r in report["frames"]}
    score = by_frame["native:fused_chunk_score"]
    render = by_frame["pipelines.filter_variants:render_stage"]
    assert score["delta_pct"] > 0 and render["delta_pct"] < 0
    # ranked by |delta|
    deltas = [abs(r["delta_pct"]) for r in report["frames"]]
    assert deltas == sorted(deltas, reverse=True)
    text = sampler_mod.render_diff(report)
    assert "fused_chunk_score" in text


# ---------------------------------------------------------------------------
# wait-edge reconciliation (critical-path join)
# ---------------------------------------------------------------------------


def test_critical_path_names_frames_running_during_wait_edge(tmp_path):
    """A chunk waits 1s on its writeback edge; CPU samples inside that
    window name the frame the cores were running — the r13
    ``writeback.wait`` question, on synthetic geometry."""
    run, path = _open_run(tmp_path, name="waitcpu.jsonl")
    tid = obs.new_trace()
    obs.trace_span(tid, "ingest", 0.01)
    # synthesize the wait by emitting the writeback span after a gap —
    # spans derive start = t_emit - dur, so the ~0.2s gap IS the wait
    time.sleep(0.22)
    obs.trace_span(tid, "writeback", 0.01, chunk=0)
    obs.end_trace(tid)
    # CPU samples whose window covers the whole run: overlap-weighted
    # against the ~0.2s wait — enough whole samples to report
    t_now = time.perf_counter() - run._t0_mono
    obs.event("sample", "io",
              stack="io.vcf:parse_chunk;native:fused_chunk_score", n=100,
              cat="native", family="io", win_t0=0.0)
    obs.event("profile", "cpuprof", hz=100.0, interval_s=0.01,
              samples=100, cpu_samples=100, threads=1, cpu_s_total=1.0,
              families={"io": 1.0})
    obs.end_run(run, "ok")
    cp = critical_mod.critical_path(export_mod.read_run(path))
    assert cp["dominant_p95_edge"] == "writeback.wait"
    wait_cpu = cp.get("wait_cpu")
    assert wait_cpu and "writeback.wait" in wait_cpu
    frames = wait_cpu["writeback.wait"]["frames"]
    assert frames[0]["frame"] == "native:fused_chunk_score"
    assert frames[0]["share_pct"] == pytest.approx(100.0)
    # and the renderer names it
    assert "cores were running" in critical_mod.render(cp)


# ---------------------------------------------------------------------------
# CLI: flame / cpuledger exit codes + outputs
# ---------------------------------------------------------------------------


def test_cli_flame_writes_speedscope_and_collapsed(tmp_path, capsys):
    path = _write_log(tmp_path, _synthetic_sampled_log())
    out = str(tmp_path / "prof.speedscope.json")
    rc = obs_cli.run(["flame", path, "-o", out])
    assert rc == 0
    assert "wrote" in capsys.readouterr().out
    scope = json.load(open(out, encoding="utf-8"))
    assert scope["$schema"].startswith("https://www.speedscope.app")
    collapsed = path + ".collapsed.txt"
    assert os.path.exists(collapsed)
    assert os.path.getsize(collapsed) > 0


def test_cli_flame_exits_2_without_samples(tmp_path, capsys):
    evs = [e for e in _synthetic_sampled_log() if e["kind"] != "sample"]
    for i, e in enumerate(evs):
        e["seq"] = i  # keep the stream contract after the filter
    path = _write_log(tmp_path, evs, name="nosamples.jsonl")
    rc = obs_cli.run(["flame", path])
    assert rc == 2
    assert "no sample events" in capsys.readouterr().err


def test_cli_flame_diff_report_and_json(tmp_path, capsys):
    base = _write_log(tmp_path, _synthetic_sampled_log(), name="a.jsonl")
    cand_evs = [dict(e) for e in _synthetic_sampled_log()]
    for e in cand_evs:
        if "fused_chunk_score" in e.get("stack", ""):
            e["n"] = 80
    cand = _write_log(tmp_path, cand_evs, name="b.jsonl")
    rc = obs_cli.run(["flame", "--diff", cand, base])
    assert rc == 0
    out = capsys.readouterr().out
    assert "flame diff" in out and "fused_chunk_score" in out
    rc = obs_cli.run(["flame", "--diff", cand, base, "--json"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["frames"][0]["delta_pct"] != 0
    # usage errors exit 2
    assert obs_cli.run(["flame", "--diff", cand]) == 2


def test_cli_cpuledger_text_and_json_and_exit_codes(tmp_path, capsys):
    path = _write_log(tmp_path, _synthetic_sampled_log())
    rc = obs_cli.run(["cpuledger", path])
    assert rc == 0
    out = capsys.readouterr().out
    assert "cpu-budget ledger" in out and "score" in out
    rc = obs_cli.run(["cpuledger", path, "--json"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["total_cpu_s_per_1m"] == pytest.approx(1.0)
    evs = [e for e in _synthetic_sampled_log() if e["kind"] != "sample"]
    for i, e in enumerate(evs):
        e["seq"] = i
    bare = _write_log(tmp_path, evs, name="bare.jsonl")
    assert obs_cli.run(["cpuledger", bare]) == 2


# ---------------------------------------------------------------------------
# tail --follow poll knob + multi-segment rotation
# ---------------------------------------------------------------------------


def test_tail_poll_knob_registered_and_used(monkeypatch):
    from variantcalling_tpu import knobs

    assert knobs.get_float("VCTPU_OBS_TAIL_POLL_S") == 1.0
    monkeypatch.setenv("VCTPU_OBS_TAIL_POLL_S", "0.05")
    assert knobs.get_float("VCTPU_OBS_TAIL_POLL_S") == 0.05
    # a malformed value is a configuration error like every knob
    monkeypatch.setenv("VCTPU_OBS_TAIL_POLL_S", "0.001")
    from variantcalling_tpu.engine import EngineError

    with pytest.raises(EngineError):
        knobs.get_float("VCTPU_OBS_TAIL_POLL_S")


def test_tail_follow_traverses_segments_appearing_between_polls(
        tmp_path, capsys, monkeypatch):
    """Rotation segments that appear while --follow is parked at the
    previous file's EOF are picked up in order — base -> .seg1 -> .seg2
    — without re-reading anything, until run_end (in .seg2) lands. The
    poll cadence comes from VCTPU_OBS_TAIL_POLL_S."""
    monkeypatch.setenv("VCTPU_OBS_TAIL_POLL_S", "0.02")
    path = str(tmp_path / "rot.jsonl")
    with open(path, "w", encoding="utf-8") as fh:
        fh.write(json.dumps(_env(0, 0.0, "manifest", "m", tool="m",
                                 version="0", knobs={}, topology={}))
                 + "\n")
        fh.write(json.dumps(_env(1, 0.1, "heartbeat", "stream", chunks=1,
                                 records=10, vps=100)) + "\n")

    def rotate_later():
        time.sleep(0.1)
        with open(path + ".seg1", "w", encoding="utf-8") as fh:
            fh.write(json.dumps(_env(2, 0.5, "heartbeat", "stream",
                                     chunks=2, records=20, vps=100))
                     + "\n")
        time.sleep(0.1)
        with open(path + ".seg2", "w", encoding="utf-8") as fh:
            fh.write(json.dumps(_env(3, 1.0, "run_end", "m", status="ok",
                                     dur=1.0)) + "\n")

    t = threading.Thread(target=rotate_later)
    t.start()
    rc = obs_cli.run(["tail", path, "--follow"])
    t.join()
    assert rc == 0
    out = capsys.readouterr().out
    assert out.count("heartbeat:") == 2
    assert "run_end: ok" in out


# ---------------------------------------------------------------------------
# coexistence: profiled streaming run under injected faults
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def prof_world(tmp_path_factory):
    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.synthetic import make_fixtures, synthetic_forest

    d = str(tmp_path_factory.mktemp("profworld"))
    make_fixtures(d, n=4000, genome_len=200_000)
    model = synthetic_forest(np.random.default_rng(0), n_trees=8, depth=4)
    with open(f"{d}/model.pkl", "wb") as fh:
        pickle.dump({"m": model}, fh)
    _WATCHED_DIRS.append(d)
    return {"dir": d, "model": model,
            "fasta": FastaReader(f"{d}/ref.fa"), "n": 4000}


def _stream_args(w, out):
    return argparse.Namespace(
        input_file=f"{w['dir']}/calls.vcf", output_file=out,
        runs_file=None, hpol_filter_length_dist=[10, 10], blacklist=None,
        blacklist_cg_insertions=False, annotate_intervals=[],
        flow_order="TGCA", is_mutect=False, limit_to_contig=None)


def _run_stream(w, out, monkeypatch, profiled):
    from variantcalling_tpu.io import vcf as vcf_mod
    from variantcalling_tpu.pipelines.filter_variants import run_streaming

    monkeypatch.setattr(vcf_mod, "STREAM_CHUNK_BYTES", 1 << 15)
    monkeypatch.setenv("VCTPU_IO_BACKOFF_S", "0.01")
    if profiled:
        monkeypatch.setenv("VCTPU_OBS", "1")
        monkeypatch.setenv("VCTPU_OBS_CPUPROF", "1")
    else:
        monkeypatch.delenv("VCTPU_OBS", raising=False)
    return run_streaming(_stream_args(w, out), w["model"], w["fasta"],
                         {}, None)


def test_profiled_run_with_faults_stays_byte_identical_no_leaks(
        prof_world, monkeypatch):
    """ISSUE 13 satellite: the sampler coexists with the chunk-retry
    ladder AND the watchdog faulthandler stack dump — a profiled run
    under injected faults (a transient chunk-body strike + a released
    stage hang that trips the watchdog's stack-dump path) produces
    byte-identical output, and no ``vctpu-sampler`` thread survives
    (the module leak sentinel re-checks after every test)."""
    w = prof_world
    clean = f"{w['dir']}/clean.vcf"
    stats = _run_stream(w, clean, monkeypatch, profiled=False)
    assert stats is not None and stats["n"] == w["n"]
    clean_bytes = open(clean, "rb").read()

    out = f"{w['dir']}/prof_faults.vcf"
    faults.arm("pipeline.stage", times=1)  # chunk-retry rung
    stats = _run_stream(w, out, monkeypatch, profiled=True)
    assert stats is not None and stats["n"] == w["n"]
    assert open(out, "rb").read() == clean_bytes
    log = out + ".obs.jsonl"
    evs = export_mod.read_run(log)
    # the recovery ladder fired AND the profiler sampled the same run
    assert any(e["kind"] == "recovery" for e in evs)
    assert any(e["kind"] == "profile" and e["name"] == "cpuprof"
               for e in evs)
    assert not [t for t in threading.enumerate()
                if t.name == "vctpu-sampler"]


def test_profiled_run_survives_watchdog_stack_dump(prof_world,
                                                   monkeypatch):
    """The watchdog v2 first-expiry path dumps EVERY thread's stack via
    faulthandler while the sampler is concurrently walking the same
    frames — the run must complete byte-identically (the injected hang
    is released by the watchdog) with the sampler alive throughout."""
    w = prof_world
    clean_bytes = open(f"{w['dir']}/clean.vcf", "rb").read()
    out = f"{w['dir']}/prof_watchdog.vcf"
    monkeypatch.setenv("VCTPU_STAGE_TIMEOUT_S", "1.0")
    faults.arm("pipeline.stage_hang", times=1, seconds=30)
    stats = _run_stream(w, out, monkeypatch, profiled=True)
    assert stats is not None and stats["n"] == w["n"]
    assert open(out, "rb").read() == clean_bytes
    evs = export_mod.read_run(out + ".obs.jsonl")
    assert any(e["kind"] == "recovery" and e["name"] == "watchdog_retry"
               for e in evs)
    assert any(e["kind"] == "sample" for e in evs)


def test_profiled_run_ledger_covers_real_stages(prof_world, monkeypatch):
    """On a real (tiny) streaming run the ledger attributes CPU to the
    known stage rows and the flame CLI round-trips the log."""
    w = prof_world
    out = f"{w['dir']}/prof_ledger.vcf"
    monkeypatch.setenv("VCTPU_OBS_CPUPROF_HZ", "200")
    stats = _run_stream(w, out, monkeypatch, profiled=True)
    assert stats is not None
    log = out + ".obs.jsonl"
    evs = export_mod.read_run(log)
    ledger = sampler_mod.cpuledger(evs)
    # a 4k-record run may be too brief for an on-CPU tick on a slow
    # box: the ledger may be None then — but the sample stream and the
    # summary must exist regardless
    assert any(e["kind"] == "profile" and e["name"] == "cpuprof"
               for e in evs)
    assert any(e["kind"] == "sample" for e in evs)
    if ledger is not None and "stages" in ledger:
        assert ledger["records"] == w["n"]
        assert all(v >= 0 for v in ledger["stages"].values())
