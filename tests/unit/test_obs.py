"""obs/ runtime telemetry (ISSUE 5 tentpole): run manifest, metrics
registry, unified JSONL event stream, Perfetto export, CLI exit codes,
thread-aware tracing, and the output-neutrality (byte-parity)
acceptance criterion."""

from __future__ import annotations

import json
import os
import pickle
import subprocess
import sys
import threading

import numpy as np
import pytest

from variantcalling_tpu import engine as engine_mod
from variantcalling_tpu import knobs, obs
from variantcalling_tpu.obs import cli as obs_cli
from variantcalling_tpu.obs import export as export_mod
from variantcalling_tpu.obs import schema as schema_mod
from variantcalling_tpu.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from variantcalling_tpu.utils import degrade, faults, trace

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _obs_isolated():
    """No test leaks an open stream (or armed faults) into the next."""
    yield
    run = obs.current()
    if run is not None:
        obs.end_run(run, "test-teardown")
    faults.reset()


def _open_run(tmp_path, name="run.jsonl", **kw):
    path = str(tmp_path / name)
    run = obs.start_run("test_tool", force_path=path, **kw)
    assert run is not None
    return run, path


def _events(path):
    return [json.loads(ln) for ln in open(path, encoding="utf-8")
            if ln.strip()]


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_is_exact_across_threads():
    c = Counter("records")
    n_threads, per = 8, 5000

    def work():
        for _ in range(per):
            c.add(1)

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    # per-thread cells make increments lock-free AND lossless — a shared
    # `value += 1` would drop increments under this contention
    assert c.value == n_threads * per


def test_gauge_tracks_peak_and_histogram_merges_threads():
    g = Gauge("depth")
    g.set(3)
    g.set(1)
    assert g.snapshot() == {"value": 1, "peak": 3}

    h = Histogram("chunk")

    def observe(vals):
        for v in vals:
            h.observe(v)

    t = threading.Thread(target=observe, args=([10.0] * 100,))
    t.start()
    observe([30.0, 50.0])
    t.join()
    snap = h.snapshot()
    assert snap["count"] == 102
    assert snap["min"] == 10.0 and snap["max"] == 50.0
    assert snap["sum"] == 100 * 10.0 + 80.0


def test_registry_snapshot_shape():
    r = MetricsRegistry()
    r.counter("a").add(2)
    r.gauge("b").set(7)
    r.histogram("c").observe(1.5)
    snap = r.snapshot()
    assert snap["counters"] == {"a": 2}
    assert snap["gauges"]["b"]["value"] == 7
    assert snap["histograms"]["c"]["count"] == 1


# ---------------------------------------------------------------------------
# run lifecycle, manifest, ordered stream
# ---------------------------------------------------------------------------


def test_inactive_is_noop_and_writes_nothing(tmp_path):
    assert not obs.active()
    obs.event("stage", "ignored")
    obs.span("ignored", 0.1, "MainThread")
    obs.counter("x").add(1)  # the shared no-op metric
    obs.gauge("x").set(1)
    obs.histogram("x").observe(1)
    assert list(tmp_path.iterdir()) == []


def test_start_run_gated_on_knob(tmp_path, monkeypatch):
    # VCTPU_OBS unset -> no stream, even with a default path
    assert obs.start_run("t", default_path=str(tmp_path / "x.jsonl")) is None
    monkeypatch.setenv("VCTPU_OBS", "1")
    run = obs.start_run("t", default_path=str(tmp_path / "x.jsonl"))
    assert run is not None and obs.active()
    # a second starter JOINS (None) instead of nesting a second stream
    assert obs.start_run("t2", default_path=str(tmp_path / "y.jsonl")) is None
    obs.end_run(run)
    assert not obs.active() and not (tmp_path / "y.jsonl").exists()


def test_obs_path_env_overrides_default(tmp_path, monkeypatch):
    monkeypatch.setenv("VCTPU_OBS", "1")
    override = str(tmp_path / "override.jsonl")
    monkeypatch.setenv("VCTPU_OBS_PATH", override)
    run = obs.start_run("t", default_path=str(tmp_path / "default.jsonl"))
    obs.end_run(run)
    assert os.path.exists(override)
    assert not (tmp_path / "default.jsonl").exists()


def test_manifest_opens_stream_with_knobs_topology_inputs(tmp_path, monkeypatch):
    monkeypatch.setenv("VCTPU_THREADS", "3")
    run, path = _open_run(tmp_path, argv=["--input_file", "x.vcf"],
                          inputs={"input": __file__})
    obs.end_run(run)
    events = _events(path)
    m = events[0]
    assert m["kind"] == "manifest" and m["seq"] == 0
    assert m["tool"] == "test_tool" and m["argv"] == ["--input_file", "x.vcf"]
    from variantcalling_tpu import __version__

    assert m["version"] == __version__
    # the WHOLE resolved knob registry with value + source
    assert set(m["knobs"]) == set(knobs.REGISTRY)
    assert m["knobs"]["VCTPU_THREADS"] == {"value": 3, "source": "env"}
    assert m["knobs"]["VCTPU_ENGINE"]["source"] == "default"
    assert m["topology"]["backend"] == "cpu"
    assert m["topology"]["local_devices"] >= 1
    # input identity: same signature the resume journal binds to
    st = os.stat(__file__)
    assert m["inputs"]["input"]["size"] == st.st_size
    assert m["inputs"]["input"]["mtime_ns"] == st.st_mtime_ns


def test_stream_is_ordered_and_schema_valid_from_threads(tmp_path):
    run, path = _open_run(tmp_path)

    def spam(k):
        for i in range(200):
            obs.event("stage", f"t{k}", i=i)

    ts = [threading.Thread(target=spam, args=(k,)) for k in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    obs.end_run(run)
    lines = open(path, encoding="utf-8").read().splitlines()
    assert schema_mod.validate_lines(lines) == []  # seq/ts order included
    events = _events(path)
    assert [e["seq"] for e in events] == list(range(len(events)))
    # manifest + spam + sampler watermark (obs v2) + metrics/run_end + the
    # stream's own two spans (obs_open, obs_close)
    assert len(events) == 1 + 4 * 200 + 3 + 2


def test_end_run_snapshots_metrics(tmp_path):
    run, path = _open_run(tmp_path)
    obs.counter("records").add(42)
    obs.gauge("queue.stage0.depth").set(2)
    obs.histogram("chunk.records").observe(42)
    obs.end_run(run, "ok")
    events = _events(path)
    metrics = [e for e in events if e["kind"] == "metrics"][-1]
    assert metrics["counters"]["records"] == 42
    assert metrics["gauges"]["queue.stage0.depth"]["peak"] == 2
    assert metrics["histograms"]["chunk.records"]["count"] == 1
    assert events[-1]["kind"] == "run_end" and events[-1]["status"] == "ok"


def test_schema_validator_rejects_drift():
    ok = {"v": 1, "seq": 0, "ts": 1.0, "t": 0.0, "kind": "span",
          "name": "x", "pid": 1, "tid": 1, "dur": 0.5, "thread": "MainThread"}
    assert schema_mod.validate_event(ok) == []
    assert schema_mod.validate_event({**ok, "v": 99})  # wrong version
    bad = dict(ok)
    del bad["dur"]
    assert any("dur" in e for e in schema_mod.validate_event(bad))
    bad2 = dict(ok, ts="yesterday")
    assert any("ts" in e for e in schema_mod.validate_event(bad2))


# ---------------------------------------------------------------------------
# thread-aware tracer (satellite: the process-global _depth corruption)
# ---------------------------------------------------------------------------


def test_trace_depth_is_per_thread_regression(tmp_path):
    """Spans recorded from a worker thread while the main thread is
    nested must NOT inherit the main thread's depth (the old process-
    global ``_depth`` interleaved and corrupted both). The table they
    land in is the RUN's (``trace.spans()``), not a process global."""
    run, _ = _open_run(tmp_path)
    start = threading.Barrier(2, timeout=30)
    mid = threading.Barrier(2, timeout=30)

    def worker():
        start.wait()
        with trace.stage("w-outer"):
            with trace.stage("w-inner"):
                mid.wait()

    t = threading.Thread(target=worker, name="obs-test-worker")
    t.start()
    with trace.stage("m-outer"):
        start.wait()  # worker opens its spans INSIDE m-outer's window
        mid.wait()
    t.join(timeout=30)
    assert not t.is_alive()
    spans = {s.name: s for s in trace.spans()}
    assert spans["m-outer"].depth == 0
    # old code: w-outer closed at depth >= 1 (main held the shared depth)
    assert spans["w-outer"].depth == 0
    assert spans["w-inner"].depth == 1
    assert spans["w-inner"].thread == "obs-test-worker"
    assert spans["m-outer"].thread == "MainThread"
    assert spans["w-inner"].parent == "w-outer"
    assert spans["w-outer"].parent is None  # never the main thread's span
    rep = trace.report()
    assert "[thread obs-test-worker]" in rep
    obs.end_run(run, "ok")
    # the table went with the run: nothing outlives it
    assert trace.spans() == [] and trace.report() == "stage timings:"


def test_trace_many_threads_never_negative_depth(tmp_path):
    run, _ = _open_run(tmp_path)

    def churn():
        for _ in range(50):
            with trace.stage("a"):
                with trace.stage("b"):
                    pass

    ts = [threading.Thread(target=churn) for _ in range(6)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    spans = trace.spans()
    obs.end_run(run, "ok")
    assert len(spans) == 6 * 50 * 2
    assert all(s.depth in (0, 1) for s in spans)
    assert all(s.seconds >= 0 for s in spans)


# ---------------------------------------------------------------------------
# unified stream: spans + degrade + faults + journal in ONE run log
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def stream_world(tmp_path_factory):
    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.synthetic import make_fixtures, synthetic_forest

    d = str(tmp_path_factory.mktemp("obs_stream"))
    make_fixtures(d, n=4000, genome_len=200_000)
    model = synthetic_forest(np.random.default_rng(0), n_trees=8, depth=4)
    with open(f"{d}/model.pkl", "wb") as fh:
        pickle.dump({"m": model}, fh)
    return {"dir": d, "model": model,
            "fasta": FastaReader(f"{d}/ref.fa"), "n": 4000}


def _stream_args(w, out):
    import argparse

    return argparse.Namespace(
        input_file=f"{w['dir']}/calls.vcf", output_file=out, runs_file=None,
        hpol_filter_length_dist=[10, 10], blacklist=None,
        blacklist_cg_insertions=False, annotate_intervals=[],
        flow_order="TGCA", is_mutect=False, limit_to_contig=None)


def test_streaming_run_unifies_all_event_classes(stream_world, tmp_path,
                                                 monkeypatch):
    """Acceptance: a streaming filter run's JSONL contains the manifest,
    every stage span, the injected-fault events, and the degrade.record
    events — one schema-versioned, ordered stream."""
    from variantcalling_tpu.io import vcf as vcf_mod
    from variantcalling_tpu.pipelines.filter_variants import run_streaming

    w = stream_world
    if not pytest.importorskip("variantcalling_tpu.native").available():
        pytest.skip("streaming needs the native engine")
    monkeypatch.setattr(vcf_mod, "STREAM_CHUNK_BYTES", 1 << 15)
    monkeypatch.setenv("VCTPU_IO_BACKOFF_S", "0.0")
    run, path = _open_run(tmp_path, name="stream.jsonl")
    degrade.record("obs.test_probe", ValueError("pre-run"), fallback="continue")
    faults.arm("io.chunk_read", times=2)  # retried transparently mid-run
    out = str(tmp_path / "out.vcf")
    stats = run_streaming(_stream_args(w, out), w["model"], w["fasta"], {}, None)
    assert stats is not None and stats["n"] == w["n"]
    assert faults.fired("io.chunk_read") == 2
    obs.end_run(run, "ok")

    lines = open(path, encoding="utf-8").read().splitlines()
    assert schema_mod.validate_lines(lines) == []  # ONE valid ordered stream
    events = _events(path)
    kinds = {e["kind"] for e in events}
    assert {"manifest", "span", "degrade", "fault", "retry", "journal",
            "stage", "heartbeat", "metrics", "run_end"} <= kinds

    # every chunk produced a span per pipeline stage
    span_names = [e["name"] for e in events if e["kind"] == "span"]
    assert span_names.count("score_stage") == stats["chunks"]
    assert span_names.count("render_stage") == stats["chunks"]
    # both injected firings and the degradation are in the stream
    assert len([e for e in events
                if e["kind"] == "fault" and e["name"] == "io.chunk_read"]) == 2
    assert [e for e in events
            if e["kind"] == "degrade" and e["name"] == "obs.test_probe"]
    # executor lifecycle + journal decision + heartbeats with ETA fields
    stage_names = {e["name"] for e in events if e["kind"] == "stage"}
    assert {"pipeline_start", "pipeline_end"} <= stage_names
    resume = [e for e in events if e["kind"] == "journal"
              and e["name"] == "resume_decision"]
    assert resume and resume[0]["outcome"] == "fresh"
    hb = [e for e in events if e["kind"] == "heartbeat"]
    assert len(hb) == stats["chunks"]
    assert hb[-1]["records"] == w["n"] and "eta_s" in hb[0] and "vps" in hb[0]
    # metrics snapshot saw the counters the hot path recorded
    metrics = [e for e in events if e["kind"] == "metrics"][-1]
    assert metrics["counters"]["records"] == w["n"]
    assert metrics["counters"]["faults.fired"] == 2
    # queue pressure gauge: per-stage queues in the serial-IO layout,
    # the head queue in the pooled parallel layout
    assert any(k.startswith("queue.") for k in metrics["gauges"])


# ---------------------------------------------------------------------------
# byte parity (acceptance): VCTPU_OBS=1 vs 0, both engines, both executors
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ["native", "jit"])
@pytest.mark.parametrize("threads", [None, "1"])  # streaming vs serial
def test_filter_output_byte_identical_with_obs(stream_world, tmp_path,
                                               monkeypatch, engine, threads):
    from variantcalling_tpu.pipelines.filter_variants import run as fvp_run

    w = stream_world
    if engine == "native":
        import variantcalling_tpu.native as native

        if not native.available():
            pytest.skip("native engine unavailable")

    def cli_run(out, obs_on):
        saved = engine_mod._RESOLVED
        engine_mod.reset_for_tests()
        monkeypatch.setenv("VCTPU_ENGINE", engine)
        if threads is not None:
            monkeypatch.setenv("VCTPU_THREADS", threads)
        else:
            monkeypatch.delenv("VCTPU_THREADS", raising=False)
        monkeypatch.setenv("VCTPU_OBS", "1" if obs_on else "0")
        # the acceptance criterion covers obs v2: byte parity holds with
        # the attribution profiler ON (per-stage stats, sampler)
        monkeypatch.setenv("VCTPU_OBS_PROFILE", "1")
        try:
            rc = fvp_run([
                "--input_file", f"{w['dir']}/calls.vcf",
                "--model_file", f"{w['dir']}/model.pkl", "--model_name", "m",
                "--reference_file", f"{w['dir']}/ref.fa",
                "--output_file", out])
        finally:
            engine_mod._RESOLVED = saved
        assert rc == 0
        return open(out, "rb").read()

    off = cli_run(str(tmp_path / "off.vcf"), obs_on=False)
    on = cli_run(str(tmp_path / "on.vcf"), obs_on=True)
    assert on == off  # output-neutrality: obs can NEVER change output bytes
    assert not os.path.exists(str(tmp_path / "off.vcf") + ".obs.jsonl")
    sidecar = str(tmp_path / "on.vcf") + ".obs.jsonl"
    assert os.path.exists(sidecar)
    lines = open(sidecar, encoding="utf-8").read().splitlines()
    assert schema_mod.validate_lines(lines) == []
    # the run recorded its resolved engine in the stream
    events = [json.loads(ln) for ln in lines]
    resolves = [e for e in events if e["kind"] == "resolve"]
    values = {e["name"]: e["value"] for e in resolves}
    assert values.get("engine", engine) == engine
    # obs v2: profiling was enabled, so the attribution landed too —
    # per-stage profile events on the streaming executor and the
    # resource watermark on every run
    profile_names = {e["name"] for e in events if e["kind"] == "profile"}
    assert "resources" in profile_names
    if threads is None:  # streaming: the executor fed the profiler
        assert {"stage", "pipeline"} <= profile_names


# ---------------------------------------------------------------------------
# Perfetto export + summary + CLI exit codes
# ---------------------------------------------------------------------------


@pytest.fixture()
def sample_log(tmp_path):
    run, path = _open_run(tmp_path, name="sample.jsonl")
    with trace.stage("ingest"):
        pass
    with trace.stage("score"):
        with trace.stage("featurize"):
            pass
    degrade.record("obs.export_probe", None, fallback="x")
    obs.counter("records").add(10)
    obs.event("heartbeat", "stream", chunks=2, records=10, vps=100)
    obs.span("score_stage", 0.25, "pipe-stage0", chunk=0)
    obs.span("score_stage", 0.5, "pipe-stage0", chunk=1)
    obs.end_run(run, "ok")
    return path


def test_chrome_trace_schema(sample_log):
    events = export_mod.read_events(sample_log)
    trace_json = export_mod.to_chrome_trace(events)
    te = trace_json["traceEvents"]
    assert te, "no trace events"
    ts = [e["ts"] for e in te]
    assert ts == sorted(ts)  # monotonically consistent timeline
    for e in te:
        assert {"ph", "pid", "tid", "ts"} <= set(e)
        assert e["ts"] >= 0
    phs = {e["ph"] for e in te}
    assert {"M", "X", "i", "C"} <= phs  # metadata, spans, instants, counters
    spans = [e for e in te if e["ph"] == "X"]
    assert all("dur" in e and e["dur"] >= 0 for e in spans)
    assert {e["name"] for e in spans} >= {"ingest", "score", "featurize"}
    # the whole object is valid JSON for Perfetto's loader
    json.loads(json.dumps(trace_json))


def test_summary_rolls_up(sample_log):
    s = export_mod.summarize(export_mod.read_events(sample_log))
    assert s["run"]["tool"] == "test_tool" and s["run"]["status"] == "ok"
    assert s["stages"]["score_stage"]["count"] == 2
    assert s["degradations"] == {"obs.export_probe": 1}
    assert s["slowest_chunks"][0]["chunk"] == 1  # 0.5s beats 0.25s
    assert s["throughput"]["records"] == 10
    text = export_mod.render_summary(s)
    assert "score_stage" in text and "degradations" in text


def test_obs_cli_exit_codes(sample_log, tmp_path, capsys):
    assert obs_cli.run(["summary", sample_log]) == 0
    assert obs_cli.run(["summary", "--json", sample_log]) == 0
    capsys.readouterr()  # drain
    assert obs_cli.run(["export", "--format=perfetto", sample_log]) == 0
    trace_path = sample_log + ".trace.json"
    assert os.path.exists(trace_path)
    loaded = json.load(open(trace_path, encoding="utf-8"))
    assert "traceEvents" in loaded
    out2 = str(tmp_path / "custom.json")
    assert obs_cli.run(["export", sample_log, "-o", out2]) == 0
    assert os.path.exists(out2)
    # unreadable / malformed logs exit 2 (usage contract)
    assert obs_cli.run(["summary", str(tmp_path / "missing.jsonl")]) == 2
    garbage = tmp_path / "garbage.jsonl"
    garbage.write_text("not json\n")
    assert obs_cli.run(["summary", str(garbage)]) == 2
    with pytest.raises(SystemExit) as exc:
        obs_cli.run(["no-such-command"])
    assert exc.value.code == 2


def test_knobs_and_obs_summary_share_json_emitter(sample_log, tmp_path,
                                                  monkeypatch, capsys):
    """Satellite: both CLIs emit through utils.jsonio — same contract,
    and both exit codes covered (0 on success, 2 on config error)."""
    assert knobs.run(["--json"]) == 0
    knobs_out = capsys.readouterr().out
    json.loads(knobs_out)  # parses
    assert knobs_out.endswith("}\n") and '  "' in knobs_out  # 2-space indent
    assert obs_cli.run(["summary", "--json", sample_log]) == 0
    summary_out = capsys.readouterr().out
    json.loads(summary_out)
    assert summary_out.endswith("}\n") and '  "' in summary_out
    # knobs exits 2 on a malformed knob, same contract as obs's bad file
    monkeypatch.setenv("VCTPU_THREADS", "zebra")
    assert knobs.run([]) == 2


def test_obs_tool_registered_in_cli_dispatch():
    from variantcalling_tpu.__main__ import TOOLS

    assert TOOLS["obs"] == "variantcalling_tpu.obs.cli"


@pytest.mark.slow
def test_obs_cli_subprocess_end_to_end(stream_world, tmp_path):
    """Whole loop through the real CLI: filter with VCTPU_OBS=1, then
    `vctpu obs summary` and `vctpu obs export` on the sidecar."""
    w = stream_world
    out = str(tmp_path / "out.vcf")
    env = {k: v for k, v in os.environ.items() if not k.startswith("VCTPU_")}
    env.update(PYTHONPATH="", JAX_PLATFORMS="cpu", VCTPU_OBS="1")
    r = subprocess.run(
        [sys.executable, "-m", "variantcalling_tpu", "filter_variants_pipeline",
         "--input_file", f"{w['dir']}/calls.vcf",
         "--model_file", f"{w['dir']}/model.pkl", "--model_name", "m",
         "--reference_file", f"{w['dir']}/ref.fa", "--output_file", out],
        env=env, cwd=_REPO, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    sidecar = out + ".obs.jsonl"
    assert os.path.exists(sidecar)
    for sub in (["obs", "summary", sidecar],
                ["obs", "export", "--format=perfetto", sidecar]):
        r2 = subprocess.run([sys.executable, "-m", "variantcalling_tpu", *sub],
                            env=env, cwd=_REPO, capture_output=True,
                            text=True, timeout=120)
        assert r2.returncode == 0, r2.stderr[-2000:]
    assert os.path.exists(sidecar + ".trace.json")
