"""obs v2 (ISSUE 6 tentpole): performance-attribution profiler —
fixed-bucket histogram percentiles vs numpy, per-stage work/wait
attribution on a real streaming run, the `vctpu obs bottleneck` roll-up,
the resource-watermark sampler, multi-rank log
merging, the atexit/SIGTERM flush, and the `vctpu obs diff` sentry."""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from variantcalling_tpu import obs
from variantcalling_tpu.obs import cli as obs_cli
from variantcalling_tpu.obs import export as export_mod
from variantcalling_tpu.obs import metrics as metrics_mod
from variantcalling_tpu.obs import profile as profile_mod
from variantcalling_tpu.obs import schema as schema_mod
from variantcalling_tpu.utils import faults

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _obs_isolated():
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
# fixed-bucket histogram: percentile correctness vs numpy quantiles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dist", ["uniform", "lognormal", "exponential"])
def test_histogram_percentiles_match_numpy_within_bucket_error(dist):
    rng = np.random.default_rng(7)
    vals = {
        "uniform": rng.uniform(1e-4, 2.0, 20_000),
        "lognormal": rng.lognormal(-3, 2, 20_000),
        "exponential": rng.exponential(0.05, 20_000),
    }[dist]
    h = metrics_mod.Histogram("lat")
    for v in vals:
        h.observe(float(v))
    # geometric-midpoint reporting: worst case half a bucket, i.e. a
    # relative error of sqrt(HIST_FACTOR) - 1 (~4.4%); assert with slack
    rtol = metrics_mod.HIST_FACTOR ** 0.5 - 1 + 0.01
    for q in (0.5, 0.9, 0.95, 0.99):
        est = h.quantile(q)
        true = float(np.quantile(vals, q))
        assert est == pytest.approx(true, rel=rtol), (q, est, true)


def test_histogram_snapshot_carries_slo_percentiles_and_merges_threads():
    import threading

    h = metrics_mod.Histogram("lat")

    def observe(vals):
        for v in vals:
            h.observe(v)

    t = threading.Thread(target=observe, args=([0.010] * 900,))
    t.start()
    observe([1.0] * 100)
    t.join()
    snap = h.snapshot()
    assert snap["count"] == 1000
    assert snap["p50"] == pytest.approx(0.010, rel=0.06)
    # p95 straddles the jump: 90% of mass at 10ms, 10% at 1s
    assert snap["p95"] == pytest.approx(1.0, rel=0.06)
    assert snap["p99"] == pytest.approx(1.0, rel=0.06)


def test_histogram_bucket_geometry_edges():
    # under/overflow clamp, zero/negative land in bucket 0
    assert metrics_mod.bucket_index(0.0) == 0
    assert metrics_mod.bucket_index(-5.0) == 0
    assert metrics_mod.bucket_index(1e300) == metrics_mod.N_BUCKETS - 1
    # empty histogram: percentiles are None, never a crash
    h = metrics_mod.Histogram("empty")
    snap = h.snapshot()
    assert snap["p50"] is None and snap["p99"] is None
    assert h.quantile(0.5) is None


# ---------------------------------------------------------------------------
# StageProfiler accumulators + emitted profile events
# ---------------------------------------------------------------------------


def test_stage_profiler_emit_shapes(tmp_path):
    run, path = _open_run(tmp_path)
    prof = profile_mod.StageProfiler()
    s = prof.stage("score")
    s.add_work(0.5, bytes_in=100)
    s.add_work(0.25, bytes_out=50)
    s.add_wait_in(0.1)
    s.add_wait_out(0.05)
    prof.stage("ingest").add_work(0.2)
    prof.emit(wall_s=1.0, records=1000)
    obs.end_run(run, "ok")
    events = _events(path)
    assert schema_mod.validate_lines(
        open(path, encoding="utf-8").read().splitlines()) == []
    stages = {e["stage"]: e for e in events
              if e["kind"] == "profile" and e["name"] == "stage"}
    assert stages["score"]["work_s"] == 0.75
    assert stages["score"]["wait_in_s"] == 0.1
    assert stages["score"]["wait_out_s"] == 0.05
    assert stages["score"]["items"] == 2
    assert stages["score"]["records"] == 1000
    assert stages["score"]["vps"] == round(1000 / 0.75)
    assert stages["score"]["bytes_in"] == 100
    assert stages["score"]["bytes_out"] == 50
    pipe = next(e for e in events
                if e["kind"] == "profile" and e["name"] == "pipeline")
    assert pipe["wall_s"] == 1.0 and pipe["records"] == 1000
    assert pipe["stages"] == ["ingest", "score"]


def test_set_records_skips_worker_stages(tmp_path):
    """Byte-only worker rows (``inflate.wN``) keep their accumulated
    records — even zero. Assigning the run total to each of k workers
    would inflate the merged family's records (and its standalone v/s)
    k-fold in the bottleneck roll-up."""
    run, path = _open_run(tmp_path)
    prof = profile_mod.StageProfiler()
    prof.stage("ingest").add_work(0.2)
    for w in range(4):
        prof.stage(f"inflate.w{w}").add_work(0.1, bytes_in=1000)
    prof.stage("parse.w0").add_work(0.1, records=600)
    prof.emit(wall_s=1.0, records=1000)
    obs.end_run(run, "ok")
    stages = {e["stage"]: e for e in _events(path)
              if e["kind"] == "profile" and e["name"] == "stage"}
    assert stages["ingest"]["records"] == 1000  # linear stage: run total
    assert all("records" not in stages[f"inflate.w{w}"] for w in range(4))
    assert stages["parse.w0"]["records"] == 600  # its own share, untouched
    b = export_mod.bottleneck(export_mod.read_run(path))
    assert b["stages"]["inflate"]["workers"] == 4
    # the roll-up falls back to the run total ONCE for the whole family
    # (all records' bytes passed through inflate): 1000/(0.4/4) — the
    # pre-fix per-worker clobber summed 4x1000 and reported 40000
    assert b["stages"]["inflate"]["vps"] == 10_000


def test_bottleneck_merges_score_device_family(tmp_path):
    """Mesh-sharded scoring profiles one row PER DEVICE (``score.dN``,
    parallel/shard_score.megabatch_stream); the roll-up merges the
    family exactly like the ``.wN`` worker families — lane count in
    ``workers`` (plus the ``devices`` marker), capacity normalized to
    lanes x wall so fractions still read against wall-clock, records
    summed across device shares."""
    run, path = _open_run(tmp_path)
    prof = profile_mod.StageProfiler()
    prof.stage("ingest").add_work(0.2)
    # 2 devices in lockstep: each carries the 4.0s dispatch wall and its
    # half of the records (megabatch shards are same-shape)
    for dev in range(2):
        prof.stage(f"score.d{dev}").add_work(4.0, records=5_000)
    prof.stage("writeback").add_work(0.5)
    prof.emit(wall_s=10.0, records=10_000)
    obs.end_run(run, "ok")
    stages = {e["stage"]: e for e in _events(path)
              if e["kind"] == "profile" and e["name"] == "stage"}
    # set_records must not clobber per-device shares (the .wN rule)
    assert stages["score.d0"]["records"] == 5_000
    assert stages["ingest"]["records"] == 10_000
    b = export_mod.bottleneck(export_mod.read_run(path))
    fam = b["stages"]["score"]
    assert fam["workers"] == 2 and fam["devices"] == 2
    assert "devices" not in b["stages"]["ingest"]
    # capacity = 2 x 10s wall; each lane worked 4s -> 40% of capacity
    assert fam["work_pct"] == 40.0
    # standalone v/s: all 10k records over the 4s lockstep dispatch wall
    assert fam["vps"] == 2_500
    assert b["limiting_stage"] == "score"
    assert "score x2" in export_mod.render_bottleneck(b)


def test_profiler_disabled_by_knob(tmp_path, monkeypatch):
    monkeypatch.setenv("VCTPU_OBS_PROFILE", "0")
    run, path = _open_run(tmp_path)
    assert not profile_mod.enabled()
    assert run.sampler is None  # no watermark thread either
    obs.end_run(run, "ok")
    assert all(e["kind"] != "profile" for e in _events(path))


# ---------------------------------------------------------------------------
# bottleneck roll-up: synthetic skewed-stage log names the right stage
# ---------------------------------------------------------------------------


def _skewed_log(tmp_path, name="skew.jsonl"):
    """10s wall: ingest works 9s (the hog), score 2s, writeback 0.5s."""
    run, path = _open_run(tmp_path, name=name)
    obs.event("profile", "stage", stage="ingest", work_s=9.0, wait_in_s=0.0,
              wait_out_s=0.5, items=10, records=10_000, bytes_in=4096)
    obs.event("profile", "stage", stage="score", work_s=2.0, wait_in_s=7.0,
              wait_out_s=0.5, items=10, records=10_000)
    obs.event("profile", "stage", stage="writeback", work_s=0.5,
              wait_in_s=9.0, wait_out_s=0.0, items=10, records=10_000,
              bytes_out=8192)
    obs.event("profile", "pipeline", wall_s=10.0, records=10_000,
              stages=["ingest", "score", "writeback"],
              bytes_in=4096, bytes_out=8192)
    obs.end_run(run, "ok")
    return path


def test_bottleneck_names_limiting_stage_and_fractions_sum(tmp_path):
    path = _skewed_log(tmp_path)
    b = export_mod.bottleneck(export_mod.read_run(path))
    assert b["source"] == "profile"
    assert b["limiting_stage"] == "ingest"
    assert b["limiting_work_pct"] == 90.0
    assert b["wall_s"] == 10.0
    assert b["records"] == 10_000
    assert b["e2e_vps"] == 1000
    # acceptance: per-stage work/wait fractions sum to ~100% of wall
    for name, s in b["stages"].items():
        total = s["work_pct"] + s["wait_in_pct"] + s["wait_out_pct"] \
            + s["other_pct"]
        assert total == pytest.approx(100.0, abs=0.5), (name, s)
    assert b["stages"]["ingest"]["vps"] == round(10_000 / 9.0)
    # the human rendering names the stage and the wait columns
    text = export_mod.render_bottleneck(b)
    assert "limiting stage: ingest" in text
    assert "wait-in%" in text and "90.0" in text


def test_bottleneck_cli_and_span_fallback(tmp_path, capsys):
    path = _skewed_log(tmp_path)
    assert obs_cli.run(["bottleneck", str(path)]) == 0
    assert "limiting stage: ingest" in capsys.readouterr().out
    assert obs_cli.run(["bottleneck", "--json", str(path)]) == 0
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["limiting_stage"] == "ingest"
    assert obs_cli.run(["bottleneck", str(tmp_path / "missing.jsonl")]) == 2

    # a log with only spans (profiling off / serial run) falls back to
    # work-only attribution instead of claiming waits it cannot know
    run, path2 = _open_run(tmp_path, name="spans.jsonl")
    obs.span("ingest", 4.0, "MainThread", depth=0)
    obs.span("featurize+score", 1.0, "MainThread", depth=0)
    obs.end_run(run, "ok")
    b = export_mod.bottleneck(export_mod.read_run(path2))
    assert b["source"] == "spans"
    assert b["limiting_stage"] == "ingest"


def test_bottleneck_prints_a_familys_on_cpu_share_beside_its_work(tmp_path,
                                                                 capsys):
    """The operator's reading of the spans' ``cpu_s``: of a family's work,
    the share its threads spent on a CPU. A family no span fed has none."""
    run, path = _open_run(tmp_path, name="oncpu.jsonl")
    prof = profile_mod.StageProfiler()
    prof.stage("parse.w0", layer="ingest").add_work(2.0, cpu=0.5, records=50)
    prof.stage("parse.w1", layer="ingest").add_work(2.0, cpu=0.7, records=50)
    prof.stage("score_stage.w0", layer="wait").add_work(3.0, cpu=0.3, records=100)
    prof.stage("dispatch_wait.w0", parent="score_stage",
               layer="wait").add_work(2.0, cpu=0.01)
    prof.stage("generic").add_work(1.0)  # the executor's: a wall only
    prof.emit(wall_s=4.0, records=100)
    obs.end_run(run, "ok")
    b = export_mod.bottleneck(export_mod.read_run(path))
    parse, score = b["stages"]["parse"], b["stages"]["score_stage"]
    assert (parse["work_pct"], parse["on_cpu_pct"], parse["cpu_s"]) == (50.0, 30.0, 1.2)
    assert score["on_cpu_pct"] == 10.0
    assert score["children"]["dispatch_wait"]["on_cpu_pct"] == 0.5
    assert "on_cpu_pct" not in b["stages"]["generic"]
    assert obs_cli.run(["bottleneck", str(path)]) == 0
    text = capsys.readouterr().out
    header, = [ln for ln in text.splitlines() if "work%" in ln]
    assert header.split()[:3] == ["stage", "work%", "on-cpu%"]
    row, = [ln for ln in text.splitlines() if ln.strip().startswith("parse x2")]
    assert row.split()[2:4] == ["50.0", "30.0"]
    generic, = [ln for ln in text.splitlines() if ln.strip().startswith("generic")]
    assert generic.split()[2] == "-"
    assert "(66.7% of score_stage's work, 0.5% of it on CPU)" in text


# ---------------------------------------------------------------------------
# the real streaming executor feeds the profiler
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def stream_world(tmp_path_factory):
    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.synthetic import make_fixtures, synthetic_forest

    d = str(tmp_path_factory.mktemp("obs_profile"))
    make_fixtures(d, n=4000, genome_len=200_000)
    model = synthetic_forest(np.random.default_rng(0), n_trees=8, depth=4)
    return {"dir": d, "model": model,
            "fasta": FastaReader(f"{d}/ref.fa"), "n": 4000}


def _stream_args(w, out):
    return argparse.Namespace(
        input_file=f"{w['dir']}/calls.vcf", output_file=out, runs_file=None,
        hpol_filter_length_dist=[10, 10], blacklist=None,
        blacklist_cg_insertions=False, annotate_intervals=[],
        flow_order="TGCA", is_mutect=False, limit_to_contig=None)


def test_streaming_run_emits_stage_attribution(stream_world, tmp_path,
                                               monkeypatch):
    from variantcalling_tpu.io import vcf as vcf_mod
    from variantcalling_tpu.pipelines.filter_variants import run_streaming

    w = stream_world
    if not pytest.importorskip("variantcalling_tpu.native").available():
        pytest.skip("streaming needs the native engine")
    monkeypatch.setattr(vcf_mod, "STREAM_CHUNK_BYTES", 1 << 15)
    run, path = _open_run(tmp_path, name="stream.jsonl")
    out = str(tmp_path / "out.vcf")
    stats = run_streaming(_stream_args(w, out), w["model"], w["fasta"], {}, None)
    assert stats is not None and stats["n"] == w["n"]
    obs.end_run(run, "ok")

    events = _events(path)
    stages = {e["stage"]: e for e in events
              if e["kind"] == "profile" and e["name"] == "stage"}
    # the attribution stages of the filter pipeline, by name: ingest and
    # writeback always; scoring/render either as dedicated stage rows
    # (serial-IO layout) or as per-worker families (parallel layout,
    # VCTPU_IO_THREADS > 1 — parse.wN / score_stage.wN / render_stage.wN)
    assert {"ingest", "writeback"} <= set(stages)
    for base in ("score_stage", "render_stage"):
        family = [s for n, s in stages.items()
                  if n == base or re.match(rf"{base}\.w\d+$", n)]
        assert family, base
        assert sum(s["items"] for s in family) == stats["chunks"]
        assert sum(s.get("records", 0) for s in family) == w["n"]
    parse = [s for n, s in stages.items() if re.match(r"parse\.w\d+$", n)]
    if parse:  # parallel-IO layout: workers cover every chunk and record
        assert sum(s["items"] for s in parse) == stats["chunks"]
        assert sum(s.get("records", 0) for s in parse) == w["n"]
    assert stages["ingest"]["items"] == stats["chunks"]
    assert stages["ingest"]["bytes_in"] > 0
    assert stages["writeback"]["items"] == stats["chunks"]
    assert stages["writeback"]["records"] == w["n"]
    assert stages["writeback"]["bytes_out"] > 0
    pipe = next(e for e in events
                if e["kind"] == "profile" and e["name"] == "pipeline")
    assert pipe["records"] == w["n"] and pipe["wall_s"] > 0
    # per-stage latency histograms (the serve-SLO substrate) snapshot
    # with percentiles
    metrics = [e for e in events if e["kind"] == "metrics"][-1]
    hist = metrics["histograms"]["stage.score_stage.s"]
    assert hist["count"] == stats["chunks"] and hist["p50"] is not None
    # the roll-up attributes the run and fractions close to 100% —
    # worker families merge into one row normalized by worker count
    b = export_mod.bottleneck(events)
    assert not any(re.match(r".*\.w\d+$", n) for n in b["stages"])
    assert b["limiting_stage"] in b["stages"]
    for name, s in b["stages"].items():
        total = s["work_pct"] + s["wait_in_pct"] + s["wait_out_pct"] \
            + s["other_pct"]
        assert total == pytest.approx(100.0, abs=5.0), (name, s)
    # resource watermarks landed (daemon sampler)
    res = [e for e in events
           if e["kind"] == "profile" and e["name"] == "resources"]
    assert res and res[-1]["rss_peak_mb"] > 0


def test_serial_pipeline_also_profiles(stream_world, tmp_path, monkeypatch):
    """VCTPU_THREADS=1 (serial loop) still attributes work per stage —
    waits are zero by construction."""
    from variantcalling_tpu.pipelines.filter_variants import run as fvp_run
    import pickle

    w = stream_world
    model_pkl = os.path.join(w["dir"], "model_serial.pkl")
    with open(model_pkl, "wb") as fh:
        pickle.dump({"m": w["model"]}, fh)
    monkeypatch.setenv("VCTPU_THREADS", "1")
    monkeypatch.setenv("VCTPU_OBS", "1")
    out = str(tmp_path / "serial.vcf")
    rc = fvp_run([
        "--input_file", f"{w['dir']}/calls.vcf",
        "--model_file", model_pkl, "--model_name", "m",
        "--reference_file", f"{w['dir']}/ref.fa", "--output_file", out])
    assert rc == 0
    events = _events(out + ".obs.jsonl")
    b = export_mod.bottleneck(events)
    # serial whole-table path: no StagePipeline ran, so the roll-up
    # falls back to the depth-0 spans (ingest/featurize+score/writeback)
    assert b["limiting_stage"] is not None
    assert b["source"] in ("profile", "spans")


def test_jaxprof_hook_captures_device_trace(tmp_path, monkeypatch):
    """VCTPU_OBS_JAXPROF=1: a jax.profiler trace lands next to the run
    log with start/stop markers in the stream (Perfetto side-by-side)."""
    monkeypatch.setenv("VCTPU_OBS_JAXPROF", "1")
    run, path = _open_run(tmp_path, name="jp.jsonl")
    import jax.numpy as jnp

    (jnp.ones((8, 8)) @ jnp.ones((8, 8))).block_until_ready()
    obs.end_run(run, "ok")
    events = _events(path)
    names = {e["name"] for e in events if e["kind"] == "profile"}
    if "jaxprof_start" not in names:
        pytest.skip("jax.profiler unavailable on this backend/build "
                    "(recorded as a degradation)")
    assert "jaxprof_stop" in names
    assert os.path.isdir(path + ".jaxprof")


# ---------------------------------------------------------------------------
# multi-rank merge (satellite): .rankN siblings -> one timeline
# ---------------------------------------------------------------------------


def _write_rank_log(tmp_path, name, tool="rank_tool", records=100):
    path = str(tmp_path / name)
    run = obs.start_run(tool, force_path=path)
    assert run is not None
    obs.span("score", 0.5, "MainThread")
    obs.event("heartbeat", "stream", chunks=1, records=records)
    obs.end_run(run, "ok")
    return path


def test_rank_siblings_merge_into_one_timeline(tmp_path, capsys):
    base = _write_rank_log(tmp_path, "run.jsonl", records=100)
    _write_rank_log(tmp_path, "run.jsonl.rank1", records=150)

    events = export_mod.read_run(base)
    ranks = {e.get("rank") for e in events}
    assert ranks == {0, 1}
    # rank becomes the Perfetto pid: one process track per rank
    assert {e["pid"] for e in events} == {0, 1}
    trace = export_mod.to_chrome_trace(events)
    names = {e["args"]["name"] for e in trace["traceEvents"]
             if e["name"] == "process_name"}
    assert names == {"rank_tool (rank 0)", "rank_tool (rank 1)"}
    ts = [e["ts"] for e in trace["traceEvents"]]
    assert ts == sorted(ts)

    # summary merges: both ranks' spans counted, records summed
    s = export_mod.summarize(events)
    assert s["run"]["ranks"] == 2
    assert s["stages"]["score"]["count"] == 2
    assert s["throughput"]["records"] == 250
    # the CLI reads the merged run transparently
    assert obs_cli.run(["summary", "--json", base]) == 0
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["run"]["ranks"] == 2


def test_single_rank_log_unchanged_by_merge(tmp_path):
    base = _write_rank_log(tmp_path, "solo.jsonl")
    events = export_mod.read_run(base)
    assert all("rank" not in e for e in events)
    assert events == export_mod.read_events(base)


def test_fabric_backend_siblings_merge_into_one_timeline(tmp_path, capsys):
    """The serving-fabric spelling of the sibling merge (ISSUE 20): the
    router's log is the base path, each backend H wrote ``.backendH``
    next to it (tools/podrun --fabric); ``vctpu obs tail``/``summary``/
    ``prom`` read them as ONE timeline with the tiers labeled apart."""
    base = _write_rank_log(tmp_path, "fabric.jsonl", tool="fabric",
                           records=100)
    _write_rank_log(tmp_path, "fabric.jsonl.backend1", tool="fabric",
                    records=60)
    _write_rank_log(tmp_path, "fabric.jsonl.backend2", tool="fabric",
                    records=40)

    events = export_mod.read_run(base)
    assert {e.get("backend") for e in events} == {0, 1, 2}
    assert {e["pid"] for e in events} == {0, 1, 2}
    trace = export_mod.to_chrome_trace(events)
    names = {e["args"]["name"] for e in trace["traceEvents"]
             if e["name"] == "process_name"}
    assert names == {"fabric (router)", "fabric (backend 1)",
                     "fabric (backend 2)"}
    ts = [e["ts"] for e in trace["traceEvents"]]
    assert ts == sorted(ts)

    # every tier's work lands in one summary, and the CLI reads the
    # merged run transparently (tail/summary/prom share this loader)
    s = export_mod.summarize(events)
    assert s["stages"]["score"]["count"] == 3
    assert s["throughput"]["records"] == 200
    assert obs_cli.run(["summary", "--json", base]) == 0
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["throughput"]["records"] == 200


# ---------------------------------------------------------------------------
# atexit / SIGTERM flush (satellite): no silently truncated streams
# ---------------------------------------------------------------------------

_FLUSH_SCRIPT = textwrap.dedent("""
    import sys, time
    from variantcalling_tpu import obs
    run = obs.start_run("flush_test", force_path=sys.argv[1])
    obs.counter("records").add(7)
    print("READY", flush=True)
    if "--exit" in sys.argv:
        sys.exit(0)          # NO end_run: atexit must flush
    time.sleep(30)           # parent SIGTERMs us here
""")


def _flush_env():
    env = {k: v for k, v in os.environ.items() if not k.startswith("VCTPU_")}
    env.update(PYTHONPATH="", JAX_PLATFORMS="cpu")
    return env


def test_atexit_flush_writes_run_end(tmp_path):
    log = str(tmp_path / "atexit.jsonl")
    r = subprocess.run([sys.executable, "-c", _FLUSH_SCRIPT, log, "--exit"],
                       env=_flush_env(), cwd=_REPO, capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    events = _events(tmp_path / "atexit.jsonl")
    assert events[-1]["kind"] == "run_end"
    assert events[-1]["status"] == "atexit"
    metrics = [e for e in events if e["kind"] == "metrics"][-1]
    assert metrics["counters"]["records"] == 7
    assert schema_mod.validate_lines(
        open(log, encoding="utf-8").read().splitlines()) == []


def test_sigterm_flush_writes_run_end_and_still_dies_by_signal(tmp_path):
    log = str(tmp_path / "sigterm.jsonl")
    proc = subprocess.Popen([sys.executable, "-c", _FLUSH_SCRIPT, log],
                            env=_flush_env(), cwd=_REPO,
                            stdout=subprocess.PIPE, text=True)
    try:
        assert proc.stdout.readline().strip() == "READY"
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    # the handler re-delivers SIGTERM after flushing: killed-by-signal
    assert rc == -signal.SIGTERM
    events = _events(tmp_path / "sigterm.jsonl")
    assert events[-1]["kind"] == "run_end"
    assert events[-1]["status"] == "sigterm"


def test_sigint_flush_writes_run_end_and_still_dies_by_signal(tmp_path):
    """ISSUE 10 satellite: Ctrl-C previously exited without flushing
    metrics/run_end (Python's default SIGINT handler raises
    KeyboardInterrupt wherever the main thread happens to be). The
    first start_run now registers a SIGINT flush with the same
    re-deliver-default-handler pattern as SIGTERM."""
    log = str(tmp_path / "sigint.jsonl")
    proc = subprocess.Popen([sys.executable, "-c", _FLUSH_SCRIPT, log],
                            env=_flush_env(), cwd=_REPO,
                            stdout=subprocess.PIPE, text=True)
    try:
        assert proc.stdout.readline().strip() == "READY"
        proc.send_signal(signal.SIGINT)
        rc = proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    # re-delivered with the default disposition: killed-by-SIGINT
    assert rc == -signal.SIGINT
    events = _events(tmp_path / "sigint.jsonl")
    assert events[-1]["kind"] == "run_end"
    assert events[-1]["status"] == "sigint"
    metrics = [e for e in events if e["kind"] == "metrics"][-1]
    assert metrics["counters"]["records"] == 7
    assert schema_mod.validate_lines(
        open(log, encoding="utf-8").read().splitlines()) == []


# ---------------------------------------------------------------------------
# `vctpu obs diff` sentry: noise bands, exit codes
# ---------------------------------------------------------------------------


def _profiled_log(tmp_path, name, work_s):
    run, path = _open_run(tmp_path, name=name)
    obs.event("profile", "stage", stage="score", work_s=work_s,
              wait_in_s=0.1, wait_out_s=0.0, items=4, records=1000)
    obs.event("profile", "pipeline", wall_s=work_s + 0.2, records=1000,
              stages=["score"])
    obs.end_run(run, "ok")
    return path


def test_obs_diff_detects_regression_and_passes_identical(tmp_path, capsys):
    base = _profiled_log(tmp_path, "base.jsonl", work_s=1.0)
    slow = _profiled_log(tmp_path, "slow.jsonl", work_s=1.5)  # 50% slower
    # identical comparison: inside any band
    assert obs_cli.run(["diff", base, base]) == 0
    out = capsys.readouterr().out
    assert "within the noise band" in out
    # 50% regression beyond the default 8% band: exit 1
    assert obs_cli.run(["diff", slow, base]) == 1
    out = capsys.readouterr().out
    assert "REGRESSED" in out
    # a wide band waves the same diff through
    assert obs_cli.run(["diff", slow, base, "--tolerance-pct", "80"]) == 0
    capsys.readouterr()
    # --json emits the machine-readable report
    assert obs_cli.run(["diff", "--json", slow, base]) == 1
    report = json.loads(capsys.readouterr().out)
    assert report["regressed"] is True
    assert any(c["metric"] == "stage.score.work_s" and c["regressed"]
               for c in report["checks"])
    # unreadable logs exit 2 (usage contract)
    assert obs_cli.run(["diff", base, str(tmp_path / "nope.jsonl")]) == 2


def test_diff_improvements_are_never_fatal(tmp_path):
    base = _profiled_log(tmp_path, "b2.jsonl", work_s=1.0)
    fast = _profiled_log(tmp_path, "f2.jsonl", work_s=0.5)
    events_f = export_mod.read_run(fast)
    events_b = export_mod.read_run(base)
    report = export_mod.diff_runs(events_f, events_b)
    assert report["regressed"] is False
