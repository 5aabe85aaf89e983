"""The ONE span primitive (``utils.trace.stage``) and what hangs on it:
off it is a bool check; on it is one ``span`` event with ``start`` /
``parent`` / ``trace_id`` / ``cpu`` / ``layer``, one attribution row with
its parent, its layer and its on-CPU seconds, a ``vctpu:<name>`` annotation
on the profiler trace's clock; the score stage's parts are its children and
add up to no more than it; every span name has a layer in ONE table; a
run's head and tail are under spans; the predictor cache and JAX's compiles
are counted at their boundaries."""

from __future__ import annotations

import argparse
import ast
import glob
import json
import os
import threading
import time

import numpy as np
import pytest

from variantcalling_tpu import obs
from variantcalling_tpu.obs import export as export_mod
from variantcalling_tpu.obs import layers as layers_mod
from variantcalling_tpu.obs import profile as profile_mod
from variantcalling_tpu.utils import trace

#: the spans of ISSUE 26's table: the score stage's parts
SCORE_PARTS = ("host_featurize", "prepare_inputs", "fused_program",
               "dispatch_feed", "dispatch_enqueue", "dispatch_wait",
               "score_finalize")
STAGE_LEVEL = ("parse", "score_stage", "render_stage", "writeback")


def _open_run(tmp_path, name="run.jsonl"):
    path = str(tmp_path / name)
    run = obs.start_run("test_spans", force_path=path)
    assert run is not None
    return run, path


def _events(path):
    with open(path, encoding="utf-8") as fh:
        return [json.loads(ln) for ln in fh if ln.strip()]


def _spans(events):
    """The program's span events: all but the stream's own open and close
    (``obs_open`` / ``obs_close``, which every stream carries)."""
    return [e for e in events if e["kind"] == "span"
            and e.get("layer") != "tracing"]


class _CountingAnnotation:
    made = 0

    def __init__(self, *a, **kw):
        type(self).made += 1

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


# ---------------------------------------------------------------------------
# the primitive, off and on
# ---------------------------------------------------------------------------


def test_stage_off_is_one_shared_noop(monkeypatch):
    import jax

    assert not obs.active()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    _CountingAnnotation.made = 0
    a = trace.stage("score_stage", records=7, causal=True)
    b = trace.stage("other")
    assert a is b  # no object per call
    with a as sp:
        sp.set(records=9)
        trace.note(built=True)
        assert trace.current_span() is None  # no state while off
    assert _CountingAnnotation.made == 0
    assert trace.spans() == [] and trace.report() == "stage timings:"
    assert not hasattr(trace, "TRACER")  # no process-global table


def test_stage_on_emits_one_span_with_start_parent_trace(tmp_path):
    run, path = _open_run(tmp_path)
    prof = profile_mod.StageProfiler()

    def worker():
        with obs.bind_profiler(prof), obs.trace_scope("t7"):
            with trace.stage("score_stage", records=100):
                with trace.stage("dispatch_wait") as sp:
                    sp.set(rows=128)

    t = threading.Thread(target=worker, name="vctpu-io-w3")
    t.start()
    t.join(timeout=30)
    prof.emit(wall_s=1.0, records=100)
    obs.end_run(run, "ok")
    ev = _events(path)
    spans = _spans(ev)
    assert [e["name"] for e in spans] == ["dispatch_wait", "score_stage"]
    inner, outer = spans
    for e in spans:
        assert e["thread"] == "vctpu-io-w3" and e["trace_id"] == "t7"
        assert e["start"] >= 0 and e["dur"] >= 0
        assert e["start"] + e["dur"] <= e["t"] + 1e-3  # closed before the stamp
    assert inner["parent"] == "score_stage" and inner["depth"] == 1
    assert "parent" not in outer and outer["depth"] == 0
    assert inner["rows"] == 128 and outer["records"] == 100
    assert outer["start"] <= inner["start"]
    assert inner["start"] + inner["dur"] <= outer["start"] + outer["dur"] + 1e-6
    rows = {e["stage"]: e for e in ev
            if e["kind"] == "profile" and e["name"] == "stage"}
    assert set(rows) == {"score_stage.w3", "dispatch_wait.w3"}
    assert rows["dispatch_wait.w3"]["parent"] == "score_stage"
    assert "parent" not in rows["score_stage.w3"]
    assert rows["score_stage.w3"]["records"] == 100
    hist = [e for e in ev if e["kind"] == "metrics"][-1]["histograms"]
    assert hist["stage.score_stage.s"]["count"] == 1
    assert hist["stage.dispatch_wait.s"]["count"] == 1


def test_stage_causal_feeds_the_trace_span_from_the_same_measurement(tmp_path):
    run, path = _open_run(tmp_path)
    tid = obs.new_trace()
    with trace.stage("writeback", trace=tid, causal=True, chunk=0,
                     bytes_out=10) as sp:
        pass
    obs.end_run(run, "ok")
    ev = _events(path)
    span, = _spans(ev)
    causal = next(e for e in ev if e["kind"] == "trace")
    assert causal["name"] == "writeback" and causal["trace_id"] == tid
    assert causal["dur"] == span["dur"] == round(sp.seconds, 6)
    assert causal["bytes_out"] == 10 and span["trace_id"] == tid


def test_failed_body_records_nothing_and_unwinds(tmp_path):
    run, path = _open_run(tmp_path)
    with pytest.raises(ValueError):
        with trace.stage("score_stage"):
            raise ValueError("poison")
    assert trace.current_span() is None
    with trace.stage("score_stage"):
        pass
    obs.end_run(run, "ok")
    spans = _spans(_events(path))
    assert len(spans) == 1 and "parent" not in spans[0]


def _sleep():
    time.sleep(0.15)


def _spin():
    """A tenth of a second of this thread's OWN CPU time (however long the
    machine takes to grant it: the suite runs beside five other workers)."""
    t_end = time.thread_time() + 0.1
    while time.thread_time() < t_end:
        pass


def test_a_sleeping_span_has_its_cpu_far_under_its_wall(tmp_path):
    run, path = _open_run(tmp_path)
    with trace.stage("parse"):
        _sleep()
    obs.end_run(run, "ok")
    span, = _spans(_events(path))
    assert span["dur"] >= 0.15 and 0 <= span["cpu"] <= 0.2 * span["dur"]


def test_a_spinning_span_has_its_cpu_within_a_fifth_of_its_wall(tmp_path):
    """``cpu`` is the thread's own CPU clock around the body. On a machine
    that grants the thread a core, a body that spins reads within a fifth of
    its wall; one of five tries finds such a moment."""
    run, path = _open_run(tmp_path)
    for _ in range(5):
        with trace.stage("parse") as sp:
            _spin()
        if sp.seconds <= 0.12:
            break  # the machine gave the thread its core
    obs.end_run(run, "ok")
    spans = _spans(_events(path))
    for e in spans:  # what the body burnt, and not a hundredth more
        assert 0.1 <= e["cpu"] <= 0.11 and e["cpu"] <= e["dur"] + 1e-3
    assert max(e["cpu"] / e["dur"] for e in spans) >= 0.8


def test_a_rows_cpu_s_is_the_sum_of_its_spans(tmp_path):
    run, path = _open_run(tmp_path)
    prof = profile_mod.StageProfiler()
    with obs.bind_profiler(prof):
        for body in (_sleep, _spin, _spin):
            with trace.stage("render_stage"):
                body()
        with pytest.raises(ValueError):  # a failed body records neither
            with trace.stage("render_stage"):
                _spin()
                raise ValueError("poison")
        with pytest.raises(ValueError):
            with trace.stage("compress_stage"):
                raise ValueError("poison")
    prof.emit(wall_s=1.0)
    obs.end_run(run, "ok")
    ev = _events(path)
    spans = _spans(ev)
    assert [e["name"] for e in spans] == ["render_stage"] * 3
    rows = {e["stage"]: e for e in ev
            if e["kind"] == "profile" and e["name"] == "stage"}
    assert set(rows) == {"render_stage"}  # the failed compress left no row
    row = rows["render_stage"]
    assert row["items"] == 3 and row["layer"] == "render"
    assert row["work_s"] == pytest.approx(sum(e["dur"] for e in spans), abs=1e-5)
    assert row["cpu_s"] == pytest.approx(sum(e["cpu"] for e in spans), abs=1e-5)
    # two spun a tenth of a second of CPU each, one slept 0.15 s of wall
    assert 0.2 <= row["cpu_s"] <= row["work_s"] - 0.14


def test_a_row_no_span_fed_says_nothing_of_cpu(tmp_path):
    """The executor's generic stages measure a wall only: their rows carry
    no ``cpu_s`` (0 would read as "all of it waiting")."""
    run, path = _open_run(tmp_path)
    prof = profile_mod.StageProfiler()
    prof.stage("generic").add_work(0.5)
    # rows the executor made, fed by a span later: one that reads the CPU
    # clock and one that does not (``obs.layers.CPU_SPANS``)
    prof.stage("compress_stage")
    prof.stage("writeback")
    with obs.bind_profiler(prof):
        for name in ("compress_stage", "writeback"):
            with trace.stage(name):
                pass
    prof.emit(wall_s=1.0)
    obs.end_run(run, "ok")
    rows = {e["stage"]: e for e in _events(path)
            if e["kind"] == "profile" and e["name"] == "stage"}
    assert "cpu_s" not in rows["generic"] and "layer" not in rows["generic"]
    assert rows["writeback"]["layer"] == "commit" and "cpu_s" not in rows["writeback"]
    assert rows["compress_stage"]["layer"] == "commit" \
        and rows["compress_stage"]["cpu_s"] >= 0


def test_span_table_is_the_runs_and_bounded(tmp_path, monkeypatch):
    monkeypatch.setattr(obs, "SPAN_TABLE_MAX", 8)
    run, _ = _open_run(tmp_path)
    for _ in range(20):
        with trace.stage("a"):
            pass
    assert len(trace.spans()) == 8  # a daemon-long run keeps the newest
    obs.end_run(run, "ok")
    assert trace.spans() == []
    run2, _ = _open_run(tmp_path, name="second.jsonl")
    assert trace.spans() == []  # nothing carried over into the next run
    obs.end_run(run2, "ok")


def test_bottleneck_lists_children_under_parents_and_ranks_neither_twice():
    def row(stage, work, **kw):
        return dict(kind="profile", name="stage", stage=stage, work_s=work,
                    wait_in_s=0.0, wait_out_s=0.0, items=1, **kw)

    events = [
        row("score_stage.w0", 4.0, records=50), row("score_stage.w1", 4.0, records=50),
        row("dispatch_wait.w0", 3.0, parent="score_stage"),
        row("dispatch_wait.w1", 3.5, parent="score_stage"),
        row("host_featurize.w0", 0.5, parent="score_stage"),
        row("render_stage.w0", 1.0, records=50),
        row("orphan_part", 9.0, parent="not_a_row"),
        dict(kind="profile", name="pipeline", wall_s=5.0, records=100),
    ]
    b = export_mod.bottleneck(events)
    assert set(b["stages"]) == {"score_stage", "render_stage", "orphan_part"}
    kids = b["stages"]["score_stage"]["children"]
    assert list(kids) == ["dispatch_wait", "host_featurize"]  # by work
    assert kids["dispatch_wait"]["work_s"] == 6.5
    assert kids["dispatch_wait"]["workers"] == 2
    assert b["stages"]["score_stage"]["work_s"] == 8.0  # children not added
    text = export_mod.render_bottleneck(b)
    assert "- dispatch_wait: 6.500s (81.2% of score_stage's work)" in text
    assert "cost_analysis" not in text and "cost_analysis" not in b


# ---------------------------------------------------------------------------
# one table of layers, in the program
# ---------------------------------------------------------------------------


def _span_names_in_the_package():
    """Every literal name handed to ``stage(...)`` / ``timed(name=...)`` (and
    to obs's own ``_SelfSpan``) under ``variantcalling_tpu/``: {name: where}."""
    import variantcalling_tpu

    root = os.path.dirname(variantcalling_tpu.__file__)
    found: dict = {}
    for path in glob.glob(os.path.join(root, "**", "*.py"), recursive=True):
        with open(path, encoding="utf-8") as fh:
            tree = ast.parse(fh.read(), path)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            called = fn.attr if isinstance(fn, ast.Attribute) else \
                fn.id if isinstance(fn, ast.Name) else None
            arg = None
            if called in ("stage", "_SelfSpan") and node.args:
                arg = node.args[0]
            elif called == "timed":
                arg = next((k.value for k in node.keywords if k.arg == "name"),
                           None)
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                found.setdefault(arg.value, f"{os.path.relpath(path, root)}:"
                                            f"{node.lineno}")
    return found


def test_every_span_name_in_the_package_has_a_layer():
    found = _span_names_in_the_package()
    # the walk sees what it should: spans of every kind of call site
    assert {"parse", "host_featurize", "stream_open", "commit", "obs_open",
            "serve_request", "genome_upload"} <= set(found)
    missing = {n: at for n, at in found.items() if n not in trace.LAYER_OF}
    assert not missing, f"span names with no layer in trace.LAYER_OF: {missing}"
    # and the table names nothing that is gone
    assert set(trace.LAYER_OF) <= set(found), set(trace.LAYER_OF) - set(found)
    assert trace.LAYER_OF["dispatch_wait"] == trace.LAYER_OF["stream"] == "wait"


# ---------------------------------------------------------------------------
# a small streaming run (VCTPU_ENGINE=jit, pooled workers)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.synthetic import make_fixtures, synthetic_forest

    d = str(tmp_path_factory.mktemp("obs_spans"))
    make_fixtures(d, n=4000, genome_len=200_000)
    return {"dir": d, "n": 4000, "fasta": FastaReader(f"{d}/ref.fa"),
            "model": synthetic_forest(np.random.default_rng(0), n_trees=8,
                                      depth=4)}


def _args(w, out):
    return argparse.Namespace(
        input_file=f"{w['dir']}/calls.vcf", output_file=out, runs_file=None,
        hpol_filter_length_dist=[10, 10], blacklist=None,
        blacklist_cg_insertions=False, annotate_intervals=[],
        flow_order="TGCA", is_mutect=False, limit_to_contig=None)


@pytest.fixture()
def jit_engine(monkeypatch):
    from variantcalling_tpu import engine as engine_mod
    from variantcalling_tpu.io import vcf as vcf_mod

    if not pytest.importorskip("variantcalling_tpu.native").available():
        pytest.skip("streaming (chunked ingest) needs the native library")
    saved = engine_mod._RESOLVED
    engine_mod.reset_for_tests()
    monkeypatch.setenv("VCTPU_ENGINE", "jit")
    monkeypatch.setenv("VCTPU_IO_THREADS", "3")
    monkeypatch.setattr(vcf_mod, "STREAM_CHUNK_BYTES", 1 << 15)
    yield
    engine_mod._RESOLVED = saved


@pytest.fixture()
def streamed(world, jit_engine, fresh_predictor_cache, tmp_path):
    """One obs-on streaming run under a ``jax.profiler`` trace, from an
    empty predictor cache (so that it builds)."""
    import jax

    from variantcalling_tpu.pipelines.filter_variants import run_streaming

    trace_dir = str(tmp_path / "jaxtrace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    run, path = _open_run(tmp_path, name="stream.jsonl")
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        out = str(tmp_path / "on.vcf")
        stats = run_streaming(_args(world, out), world["model"],
                              world["fasta"], {}, None)
    finally:
        jax.profiler.stop_trace()
    obs.end_run(run, "ok")
    assert stats is not None and stats["n"] == world["n"]
    return {"events": _events(path), "out": out, "trace_dir": trace_dir,
            "chunks": stats["chunks"]}


def test_streaming_run_emits_every_span_once_a_chunk(streamed):
    ev, chunks = streamed["events"], streamed["chunks"]
    assert chunks >= 3
    spans = [e for e in ev if e["kind"] == "span"]
    by_name: dict = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    for name in STAGE_LEVEL + SCORE_PARTS:
        assert name in by_name, f"no {name} span"
        if name == "score_finalize":  # the margins, then the FILTER column
            assert len(by_name[name]) == 2 * chunks
        else:
            assert len(by_name[name]) == chunks, name
    # every part is a child of score_stage, on its thread, under its trace
    stages = {e["trace_id"]: e for e in by_name["score_stage"]}
    assert len(stages) == chunks
    inside = {tid: 0.0 for tid in stages}
    # (a process's first wave also probes the backend, once a platform)
    for name in SCORE_PARTS + ("backend_probe",):
        for e in by_name.get(name, ()):
            st = stages[e["trace_id"]]
            assert e["parent"] == "score_stage" and e["thread"] == st["thread"]
            assert e["start"] >= st["start"] - 1e-6
            assert e["start"] + e["dur"] <= st["start"] + st["dur"] + 1e-6
            inside[e["trace_id"]] += e["dur"]
    for tid, st in stages.items():
        assert inside[tid] <= st["dur"] + 1e-5  # the parts overlap nothing
    assert sum(inside.values()) >= 0.9 * sum(s["dur"] for s in stages.values())
    # pooled workers: one row per worker and name, children name the parent
    rows = {e["stage"]: e for e in ev
            if e["kind"] == "profile" and e["name"] == "stage"}
    assert any(n.startswith("score_stage.w") for n in rows)
    for n, r in rows.items():
        base = n.split(".")[0]
        if base in SCORE_PARTS:
            assert r["parent"] == "score_stage", n
        elif base in STAGE_LEVEL:
            assert "parent" not in r, n
    # the causal DAG is fed from the same measurement
    causal = {(e["trace_id"], e["name"]): e["dur"] for e in ev
              if e["kind"] == "trace"}
    for st in by_name["score_stage"] + by_name["render_stage"] \
            + by_name["writeback"]:
        assert causal[st["trace_id"], st["name"]] == st["dur"]
    # the bottleneck table ranks the stages and lists the parts under them
    b = export_mod.bottleneck(ev)
    assert not set(SCORE_PARTS) & set(b["stages"])
    assert set(SCORE_PARTS) <= set(b["stages"]["score_stage"]["children"])
    # and the fused_program span says whether it built
    built = [e["built"] for e in by_name["fused_program"]]
    assert built.count(True) >= 1 and len(built) == chunks
    # ... or waited for another worker's build, or first call at a bucket:
    # a lookup is one of build, wait or reuse, never a build AND a wait
    for e in by_name["fused_program"] + by_name["dispatch_enqueue"]:
        assert isinstance(e["waited"], bool)
        assert not (e.get("built") and e["waited"])
    assert built.count(True) == 1  # single flight: the workers share one


def test_every_span_event_and_row_of_a_streamed_run_carries_its_layer(streamed):
    ev = streamed["events"]
    spans = [e for e in ev if e["kind"] == "span"]
    assert len(spans) > 20
    for e in spans:
        assert e.get("layer") == trace.LAYER_OF[e["name"]], e
    # a span named in CPU_SPANS (the families a metric or the bottleneck
    # column reads on-CPU shares of) carries its thread's on-CPU seconds,
    # within its wall (the clocks differ: a hundredth of slack); no other
    # span reads that clock
    assert layers_mod.CPU_SPANS <= set(trace.LAYER_OF)
    for e in spans:
        assert ("cpu" in e) == (e["name"] in layers_mod.CPU_SPANS), e
        if "cpu" in e:
            assert 0 <= e["cpu"] <= e["dur"] + 0.01, e
    assert {e["name"] for e in spans if "cpu" in e} == {"parse", "render_stage"}
    for r in (e for e in ev if e["kind"] == "profile" and e["name"] == "stage"):
        base = r["stage"].split(".")[0]
        if base in trace.LAYER_OF and "cpu_s" in r:
            assert r["layer"] == trace.LAYER_OF[base], r
            assert 0 <= r["cpu_s"] <= r["work_s"] + 0.01 * max(1, r["items"]), r
    families = {r["stage"].split(".")[0] for r in ev
                if r["kind"] == "profile" and r["name"] == "stage" and "cpu_s" in r}
    assert families == {"parse", "render_stage"}


def test_profiler_trace_holds_a_vctpu_event_for_each_span(streamed):
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(streamed["trace_dir"], "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert paths
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    found: dict = {}
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(trace.ANNOTATION_PREFIX):
                    found.setdefault(e.name[len(trace.ANNOTATION_PREFIX):],
                                     []).append(dict(e.stats))
    traces = {e["trace_id"] for e in streamed["events"]
              if e["kind"] == "span" and e["name"] == "score_stage"}
    for name in STAGE_LEVEL + SCORE_PARTS:
        assert name in found, f"no vctpu:{name} event in the profiler trace"
        for stats in found[name]:
            assert set(stats) >= {"trace", "thread"}
        assert {s["trace"] for s in found[name]} <= traces
    assert {s["trace"] for s in found["dispatch_wait"]} == traces
    assert all(s["thread"].startswith("vctpu-io-w")
               for s in found["score_stage"])


def test_output_bytes_equal_with_obs_on_and_off(streamed, world, tmp_path):
    from variantcalling_tpu.pipelines.filter_variants import run_streaming

    assert not obs.active()
    off = str(tmp_path / "off.vcf")
    stats = run_streaming(_args(world, off), world["model"], world["fasta"],
                          {}, None)
    assert stats is not None
    with open(off, "rb") as a, open(streamed["out"], "rb") as b:
        assert a.read() == b.read()
    assert not os.path.exists(off + ".obs.jsonl")


# ---------------------------------------------------------------------------
# a run's head and tail are under spans (the CLI entry, plain and .vcf.gz)
# ---------------------------------------------------------------------------

HEAD_AND_TAIL = ("obs_open", "run_open", "stream_open", "stream_close",
                 "commit", "obs_close")


@pytest.fixture(scope="module")
def cli_world(tmp_path_factory):
    import pickle

    from variantcalling_tpu.io import bgzf as bgzf_mod
    from variantcalling_tpu.synthetic import make_fixtures_fast, synthetic_forest

    d = str(tmp_path_factory.mktemp("obs_head_tail"))
    make_fixtures_fast(d, n=6000, genome_len=400_000, n_contigs=4)
    with open(f"{d}/calls.vcf", "rb") as fh, \
            bgzf_mod.BgzfWriter(f"{d}/calls.vcf.gz") as w:
        w.write(fh.read())
    with open(f"{d}/model.pkl", "wb") as fh:
        pickle.dump({"m": synthetic_forest(np.random.default_rng(0),
                                           n_trees=8, depth=4)}, fh)
    return d


def _cli(cli_world, monkeypatch, suffix, out, obs_on):
    from variantcalling_tpu import engine as engine_mod
    from variantcalling_tpu.io import vcf as vcf_mod
    from variantcalling_tpu.pipelines.filter_variants import run as fvp_run

    if not pytest.importorskip("variantcalling_tpu.native").available():
        pytest.skip("streaming (chunked ingest) needs the native library")
    monkeypatch.setattr(vcf_mod, "STREAM_CHUNK_BYTES", 1 << 16)
    monkeypatch.setenv("VCTPU_IO_THREADS", "2")
    monkeypatch.setenv("VCTPU_IO_SHARD_BYTES", str(1 << 17))
    monkeypatch.setenv("VCTPU_OBS", "1" if obs_on else "0")
    try:
        rc = fvp_run(["--input_file", f"{cli_world}/calls{suffix}",
                      "--model_file", f"{cli_world}/model.pkl",
                      "--model_name", "m",
                      "--reference_file", f"{cli_world}/ref.fa",
                      "--output_file", out])
    finally:
        engine_mod.reset_for_tests()
    assert rc == 0
    return _events(out + ".obs.jsonl") if obs_on else None


@pytest.mark.parametrize("suffix", [".vcf", ".vcf.gz"], ids=["plain", "vcfgz"])
def test_a_cli_run_puts_its_head_and_tail_under_spans(cli_world, monkeypatch,
                                                      tmp_path, suffix):
    out = str(tmp_path / ("on" + suffix))
    ev = _cli(cli_world, monkeypatch, suffix, out, obs_on=True)
    spans = [e for e in ev if e["kind"] == "span"]
    mine = [e for e in spans if e["name"] in HEAD_AND_TAIL]
    # once each, in this order (file order is close order), on one thread
    assert [e["name"] for e in mine] == list(HEAD_AND_TAIL)
    assert {e["thread"] for e in mine} == {"MainThread"}
    eps = 1e-4

    def end(e):
        return e["start"] + e["dur"]

    for a, b in zip(mine, mine[1:]):
        assert end(a) <= b["start"] + eps, (a, b)  # none overlaps the next
    by = {e["name"]: e for e in mine}
    writes = [e for e in spans if e["name"] == "writeback"]
    assert writes
    for w in writes:  # nor a writeback: they lie between the open and the close
        assert end(by["stream_open"]) <= w["start"] + eps
        assert end(w) <= by["stream_close"]["start"] + eps
    # the committer's own work between two writebacks has a name, once a
    # journaled chunk (a .gz output keeps no journal)
    appends = [e for e in spans if e["name"] == "journal_append"]
    assert len(appends) == (len(writes) if suffix == ".vcf" else 0)
    for w, j in zip(writes, appends):
        assert end(w) <= j["start"] + eps and j["layer"] == "commit"
    # and so has what obs itself adds to the file
    assert [e["name"] for e in spans if e["layer"] == "tracing"] == [
        "obs_open", "profile_emit", "obs_close"]
    stream, = [e for e in spans if e["name"] == "stream"]
    assert by["stream_open"]["parent"] == by["commit"]["parent"] == "stream"
    assert 0 <= by["stream_open"]["start"] - stream["start"] < 0.05
    assert end(by["commit"]) <= end(stream) + eps
    assert end(by["run_open"]) <= stream["start"] + eps
    assert by["commit"]["records"] == 6000 and by["commit"]["chunks"] >= 2
    # the stream's own close is its last event before the end
    assert [e["name"] for e in ev[-3:]] == ["final", "obs_close", ev[-1]["name"]]
    assert ev[-1]["kind"] == "run_end"
    if suffix == ".vcf.gz":  # the commit ends before the index is written
        index, = [e for e in spans if e["name"] == "tabix_index" and "bytes" in e]
        assert end(by["commit"]) <= index["start"] + eps
        assert os.path.exists(out + ".tbi")
    # the pipeline's wall holds the tail: the rows are on the profile
    rows = {e["stage"] for e in ev if e["kind"] == "profile" and e["name"] == "stage"}
    assert {"stream_open", "stream_close", "commit"} <= rows
    # and the bytes are the bytes of a run with obs off
    off = str(tmp_path / ("off" + suffix))
    assert _cli(cli_world, monkeypatch, suffix, off, obs_on=False) is None
    assert not os.path.exists(off + ".obs.jsonl")
    with open(out, "rb") as a, open(off, "rb") as b:
        assert a.read() == b.read()


def test_a_request_through_run_loaded_emits_the_middle_three_only(
        world, jit_engine, tmp_path):
    """A daemon's request enters at ``run_loaded`` with its model and genome
    resident, inside the daemon's one obs run: no ``run_open``, no stream
    opened or closed for it."""
    from variantcalling_tpu.pipelines.filter_variants import run_loaded

    run, path = _open_run(tmp_path)
    rc = run_loaded(_args(world, str(tmp_path / "req.vcf")), world["model"],
                    world["fasta"], {}, None)
    assert rc == 0
    obs.end_run(run, "ok")
    spans = _spans(_events(path))  # all but the test's own stream's two
    mine = [e["name"] for e in spans if e["name"] in HEAD_AND_TAIL]
    assert mine == ["stream_open", "stream_close", "commit"]
    stream, = [e for e in spans if e["name"] == "stream"]
    for e in spans:
        if e["name"] in mine:
            assert e["parent"] == "stream"
            assert stream["start"] - 1e-6 <= e["start"]
            assert e["start"] + e["dur"] <= stream["start"] + stream["dur"] + 1e-6


# ---------------------------------------------------------------------------
# counters at the same boundaries
# ---------------------------------------------------------------------------


def test_predictor_builds_moves_on_a_miss_and_not_on_a_hit(
        fresh_predictor_cache, tmp_path):
    from variantcalling_tpu.pipelines import filter_variants as fv
    from variantcalling_tpu.synthetic import synthetic_forest

    from variantcalling_tpu.featurize import BASE_FEATURES

    model = synthetic_forest(np.random.default_rng(3), n_trees=4, depth=3)
    names = list(BASE_FEATURES)
    run, path = _open_run(tmp_path)

    def counts():
        snap = run.metrics.snapshot()["counters"]
        return snap.get("predictor.builds", 0), snap.get("predictor.reuses", 0)

    with trace.stage("fused_program", built=False):
        first = fv._predictor_for(model, names)
    assert counts() == (1, 0)
    with trace.stage("fused_program", built=False):
        again = fv._predictor_for(model, names)
    assert again is first and counts() == (1, 1)
    fused = fv._fused_program(model, names, "TGCA")
    assert fv._fused_program(model, names, "TGCA") is fused
    assert counts() == (2, 2)
    obs.end_run(run, "ok")
    built = [e["built"] for e in _events(path)
             if e["kind"] == "span" and e["name"] == "fused_program"]
    assert built == [True, False]


def test_fresh_jit_moves_backend_compiles_by_one_and_names_its_span(
        tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a cache directory of its own, and a threshold no toy compile reaches:
    # the compile happens, the cache keeps nothing, JAX's own miss counter
    # stays silent — and the listener still counts it
    cache_dir = str(tmp_path / "jax_cache")
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 3600.0)
    cc.reset_cache()
    run, path = _open_run(tmp_path)
    try:
        salt = float(np.random.default_rng().integers(1 << 30))
        fn = jax.jit(lambda x: (x * salt + 3.0).sum())
        x = jnp.ones((33, 7), dtype=jnp.float32)
        x.block_until_ready()
        before = dict(run.metrics.snapshot()["counters"])
        with trace.stage("dispatch_enqueue"):
            fn(x).block_until_ready()
        with trace.stage("dispatch_enqueue"):
            fn(x).block_until_ready()  # warm: no compile request at all
        after = run.metrics.snapshot()["counters"]
    finally:
        obs.end_run(run, "ok")
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_min)
        cc.reset_cache()
    assert after["jax.backend_compiles"] - before.get("jax.backend_compiles", 0) == 1
    assert after["jax.backend_compile_s"] > before.get("jax.backend_compile_s", 0)
    assert after.get("jax.trace_s", 0) > before.get("jax.trace_s", 0)
    assert after.get("jax.cache_loads", 0) == before.get("jax.cache_loads", 0)
    compiles = [e for e in _events(path)
                if e["kind"] == "profile" and e["name"] == "backend_compile"]
    mine = [e for e in compiles if e["span"] == "dispatch_enqueue"]
    assert len(mine) == 1
    assert mine[0]["thread"] == "MainThread" and mine[0]["dur"] > 0


def test_cache_hit_counts_a_load_and_no_compile(tmp_path):
    """The backend-compile event wraps the cache lookup too: a retrieval
    on the same thread marks it a load."""
    run, _ = _open_run(tmp_path)
    obs._on_jax_duration(obs.JAX_CACHE_RETRIEVAL_EVENT, 0.125)
    obs._on_jax_duration(obs.JAX_BACKEND_COMPILE_EVENT, 0.130, fun_name="f")
    obs._on_jax_duration(obs.JAX_BACKEND_COMPILE_EVENT, 0.5, fun_name="g")
    snap = run.metrics.snapshot()["counters"]
    obs.end_run(run, "ok")
    assert snap["jax.cache_loads"] == 1 and snap["jax.cache_load_s"] == 0.125
    assert snap["jax.backend_compiles"] == 1
    assert snap["jax.backend_compile_s"] == 0.5
    obs._on_jax_duration(obs.JAX_BACKEND_COMPILE_EVENT, 0.5)  # off: nothing


def test_fused_program_parts_carry_their_scope_names():
    """The three named scopes reach the REAL fused program's lowered
    metadata (the audit's builder: ``tools/jaxpr_audit``), genome-resident
    layout, and a host-window program has no gather scope."""
    from tools import jaxpr_audit
    from variantcalling_tpu.pipelines import filter_variants as fv

    programs = {name: (fn, avals) for name, fn, avals, _ in
                jaxpr_audit.build_fused_programs(jaxpr_audit.load_contract())}
    fn, avals = programs["fused/genome/dp=1"]
    text = fn.lower(*avals).as_text(debug_info=True)
    for scope in (fv.SCOPE_WINDOW_GATHER, fv.SCOPE_WINDOW_FEATURES,
                  fv.SCOPE_MODEL):
        assert scope in text, scope
    fn, avals = programs["fused/windows/dp=1"]
    text = fn.lower(*avals).as_text(debug_info=True)
    assert fv.SCOPE_WINDOW_GATHER not in text
    assert fv.SCOPE_WINDOW_FEATURES in text and fv.SCOPE_MODEL in text
    assert {fv.SCOPE_WINDOW_GATHER, fv.SCOPE_WINDOW_FEATURES, fv.SCOPE_MODEL} \
        == {"vctpu_window_gather", "vctpu_window_features", "vctpu_model"}
