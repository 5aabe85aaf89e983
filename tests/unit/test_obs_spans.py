"""The ONE span primitive (``utils.trace.stage``) and what hangs on it:
off it is a bool check; on it is one ``span`` event with ``start`` /
``parent`` / ``trace_id``, one attribution row with its parent, a
``vctpu:<name>`` annotation on the profiler trace's clock; the score
stage's parts are its children and add up to no more than it; the
predictor cache and JAX's compiles are counted at their boundaries."""

from __future__ import annotations

import argparse
import glob
import json
import os
import threading

import numpy as np
import pytest

from variantcalling_tpu import obs
from variantcalling_tpu.obs import export as export_mod
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
    spans = [e for e in ev if e["kind"] == "span"]
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
    span = next(e for e in ev if e["kind"] == "span")
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
    spans = [e for e in _events(path) if e["kind"] == "span"]
    assert len(spans) == 1 and "parent" not in spans[0]


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
    for name in SCORE_PARTS:
        for e in by_name[name]:
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
