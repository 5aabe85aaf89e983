"""Mesh-sharded scoring (ISSUE 8 tentpole): device-data-parallel filter
hot path over ``shard_map`` with device-count byte parity.

Locks the contracts the mesh dispatch must keep:

- **Byte parity**: streaming CLI output records are byte-identical at
  forced device counts {1, 2, 4} x {native, jit} engines x {gather,
  wide} strategies — only the ``##vctpu_*`` header lines name the
  configuration (the PR 2 invariant extended to the mesh layout).
- **Canonical unpack**: megabatch packing across chunks changes WHO
  scores, never the bits — packed scores equal per-chunk scores exactly,
  in chunk order.
- **Plan resolution**: explicit ``VCTPU_MESH_DEVICES`` is honored or
  fails loudly; auto keeps 1 device on cpu; the native engine always
  resolves 1 (host walk, nothing to shard).
- **Forced-host route**: ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
  in a fresh subprocess produces the same record bytes as the in-process
  mesh (the container-visible path to multi-device testing).
- **The four-chip host** (ISSUE 34, the benchmark's
  ``forest-t40d6-hg38x2-host4`` at a small size): whole ``main()`` runs at
  dp=4 over a resident, replicated genome and the native wire fill agree
  with the benchmark's plain numpy reference and equal the dp=1 run's bytes
  modulo the one ``##vctpu_mesh=`` line; the ``mesh.*`` counters add up, and
  ``megabatch_pack`` / ``genome_upload`` / the dispatch worker's feed spans
  are where the per-layer metrics read them.
"""

from __future__ import annotations

import json
import os
import pickle
import subprocess
import sys

import numpy as np
import pytest

native = pytest.importorskip("variantcalling_tpu.native")

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _engine_cache_isolated():
    yield
    from variantcalling_tpu import engine as engine_mod

    engine_mod.reset_for_tests()


@pytest.fixture(scope="module")
def mesh_world(tmp_path_factory):
    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.synthetic import make_fixtures, synthetic_forest

    d = str(tmp_path_factory.mktemp("meshscore"))
    make_fixtures(d, n=5000, genome_len=250_000)
    model = synthetic_forest(np.random.default_rng(0), n_trees=8, depth=4)
    with open(f"{d}/model.pkl", "wb") as fh:
        pickle.dump({"m": model}, fh)
    return {"dir": d, "n": 5000, "model": model,
            "fasta": FastaReader(f"{d}/ref.fa")}


def _stream(w, out, monkeypatch, engine, devices, strategy=None,
            io_threads=2):
    import argparse

    from variantcalling_tpu import engine as engine_mod
    from variantcalling_tpu.io import vcf as vcf_mod
    from variantcalling_tpu.pipelines.filter_variants import run_streaming

    monkeypatch.setattr(vcf_mod, "STREAM_CHUNK_BYTES", 1 << 15)
    monkeypatch.setenv("VCTPU_ENGINE", engine)
    monkeypatch.setenv("VCTPU_MESH_DEVICES", str(devices))
    monkeypatch.setenv("VCTPU_IO_THREADS", str(io_threads))
    if strategy is None:
        monkeypatch.delenv("VCTPU_FOREST_STRATEGY", raising=False)
    else:
        monkeypatch.setenv("VCTPU_FOREST_STRATEGY", strategy)
    engine_mod.reset_for_tests()
    args = argparse.Namespace(
        input_file=f"{w['dir']}/calls.vcf", output_file=out, runs_file=None,
        hpol_filter_length_dist=[10, 10], blacklist=None,
        blacklist_cg_insertions=False, annotate_intervals=[],
        flow_order="TGCA", is_mutect=False, limit_to_contig=None)
    return run_streaming(args, w["model"], w["fasta"], {}, None)


from tests.fixtures import strip_vctpu_header as _modulo_header  # noqa: E402


# ---------------------------------------------------------------------------
# plan resolution
# ---------------------------------------------------------------------------


def test_resolve_plan_auto_cpu_is_single_device(monkeypatch):
    from variantcalling_tpu.parallel import shard_score

    monkeypatch.delenv("VCTPU_MESH_DEVICES", raising=False)
    plan = shard_score.resolve_plan("jit")
    assert plan.devices == 1 and plan.requested == "auto"
    assert shard_score.mesh_for(plan) is None


def test_resolve_plan_explicit_honored_and_meshed(monkeypatch):
    from variantcalling_tpu.parallel import shard_score
    from variantcalling_tpu.parallel.mesh import DATA_AXIS

    monkeypatch.setenv("VCTPU_MESH_DEVICES", "4")
    plan = shard_score.resolve_plan("jit")
    assert plan.devices == 4 and plan.requested == "4"
    mesh = shard_score.mesh_for(plan)
    assert mesh.shape[DATA_AXIS] == 4
    # one Mesh object per size per process (jit caches key on identity)
    assert shard_score.mesh_for(plan) is mesh
    assert plan.header_line() == "##vctpu_mesh=dp=4"


def test_resolve_plan_native_engine_has_no_mesh(monkeypatch):
    from variantcalling_tpu.parallel import shard_score

    monkeypatch.setenv("VCTPU_MESH_DEVICES", "4")
    plan = shard_score.resolve_plan("native")
    assert plan.devices == 1
    assert "native" in plan.reason


def test_resolve_plan_overcommit_fails_loudly(monkeypatch):
    from variantcalling_tpu.engine import EngineError
    from variantcalling_tpu.parallel import shard_score

    monkeypatch.setenv("VCTPU_MESH_DEVICES", "99")
    with pytest.raises(EngineError, match="VCTPU_MESH_DEVICES=99"):
        shard_score.resolve_plan("jit")


def test_megabatch_rows_default_and_override(monkeypatch):
    from variantcalling_tpu.parallel import shard_score

    monkeypatch.delenv("VCTPU_MESH_MEGABATCH_ROWS", raising=False)
    assert shard_score.resolve_megabatch_rows(2) == \
        2 * shard_score.MEGABATCH_ROWS_PER_DEVICE
    monkeypatch.setenv("VCTPU_MESH_MEGABATCH_ROWS", "777")
    assert shard_score.resolve_megabatch_rows(2) == 777


def test_unpack_scores_slices_in_canonical_order():
    from variantcalling_tpu.parallel import shard_score

    packed = np.arange(10, dtype=np.float32)
    parts = shard_score.unpack_scores(packed, [3, 0, 7])
    assert [len(p) for p in parts] == [3, 0, 7]
    assert np.array_equal(np.concatenate(parts), packed)
    with pytest.raises(ValueError):
        shard_score.unpack_scores(packed, [3, 3])


# ---------------------------------------------------------------------------
# packed megabatch == per-chunk scoring, bit for bit
# ---------------------------------------------------------------------------


def _filter_context(w, monkeypatch, devices, strategy="gather"):
    from variantcalling_tpu import engine as engine_mod
    from variantcalling_tpu.pipelines.filter_variants import FilterContext

    monkeypatch.setenv("VCTPU_ENGINE", "jit")
    monkeypatch.setenv("VCTPU_MESH_DEVICES", str(devices))
    monkeypatch.setenv("VCTPU_FOREST_STRATEGY", strategy)
    engine_mod.reset_for_tests()
    return FilterContext(w["model"], w["fasta"])


def test_score_packed_matches_per_chunk_bitwise(mesh_world, monkeypatch):
    from variantcalling_tpu.io.vcf import VcfChunkReader

    w = mesh_world
    ctx = _filter_context(w, monkeypatch, devices=2)
    assert ctx.mesh_plan.devices == 2
    tables = list(VcfChunkReader(f"{w['dir']}/calls.vcf",
                                 chunk_bytes=1 << 15, io_threads=1))
    assert len(tables) > 2
    pairs = [(t, ctx.host_features(t)) for t in tables]
    packed = ctx.score_packed(pairs)
    assert [len(t) for t, _, _ in packed] == [len(t) for t in tables]
    for (table, score, filters), (t0, hf) in zip(packed, pairs):
        ref_score, ref_filters = ctx.score_table(t0)
        assert np.array_equal(score, ref_score)  # bitwise
        assert np.array_equal(filters.codes, ref_filters.codes)


def test_megabatch_stream_groups_and_attributes_devices(mesh_world,
                                                        monkeypatch):
    from variantcalling_tpu.io.vcf import VcfChunkReader
    from variantcalling_tpu.obs import profile as profile_mod
    from variantcalling_tpu.parallel import shard_score

    w = mesh_world
    ctx = _filter_context(w, monkeypatch, devices=2)
    tables = list(VcfChunkReader(f"{w['dir']}/calls.vcf",
                                 chunk_bytes=1 << 15, io_threads=1))
    prof = profile_mod.StageProfiler()
    prepped = ((t, ctx.host_features(t)) for t in tables)
    scored = list(shard_score.megabatch_stream(prepped, ctx, profiler=prof))
    assert [len(t) for t, _, _ in scored] == [len(t) for t in tables]
    assert sum(len(t) for t, _, _ in scored) == w["n"]
    # per-device attribution rows exist and carry the record shares
    rows = {name: s for name, s in prof._stages.items()
            if name.startswith("score.d")}
    assert set(rows) == {"score.d0", "score.d1"}
    assert sum(s.records for s in rows.values()) == w["n"]
    # tiny megabatch target: every chunk becomes its own dispatch, and
    # the bits STILL match the single-group run (packing is bit-neutral)
    monkeypatch.setenv("VCTPU_MESH_MEGABATCH_ROWS", "1")
    scored_tiny = list(shard_score.megabatch_stream(
        ((t, ctx.host_features(t)) for t in tables), ctx))
    for (_, s_a, f_a), (_, s_b, f_b) in zip(scored, scored_tiny):
        assert np.array_equal(s_a, s_b)
        assert np.array_equal(f_a.codes, f_b.codes)


def test_serial_io_mesh_layout_attribution_not_double_counted(mesh_world,
                                                              monkeypatch,
                                                              tmp_path):
    """VCTPU_IO_THREADS=1 with a >1-device mesh: the megabatch dispatch
    runs inside the executor feed's next(), so the pipeline must book
    its feed-blocked time as ingest QUEUE-WAIT (the pooled-source rule)
    — the featurize/score walls already belong to the featurize/score.dN
    rows recorded inside the source chain. Before the fix the whole
    scoring wall was double-counted as ingest WORK, misnaming the
    limiting stage."""
    import json

    from variantcalling_tpu import obs
    from variantcalling_tpu.obs import export as export_mod

    w = mesh_world
    path = str(tmp_path / "mesh_serial.jsonl")
    run = obs.start_run("test_tool", force_path=path)
    assert run is not None
    try:
        out = str(tmp_path / "mesh_serial.vcf")
        stats = _stream(w, out, monkeypatch, "jit", 2, io_threads=1)
        assert stats is not None and stats["n"] == w["n"]
    finally:
        obs.end_run(run, "ok")
    events = [json.loads(ln) for ln in open(path, encoding="utf-8")
              if ln.strip()]
    b = export_mod.bottleneck(events)
    stages = b["stages"]
    # the dispatch wall is ONE row (score_stage, the dispatching thread's
    # trace.stage span); the score.dN family, merged at device capacity,
    # and the dispatch's own parts are listed under it, never beside it
    assert "score" not in stages and "dispatch_wait" not in stages
    parts = stages["score_stage"]["children"]
    assert parts["score"]["devices"] == 2
    assert parts["score"]["work_s"] > 0
    assert parts["score"]["work_s"] == pytest.approx(
        2 * stages["score_stage"]["work_s"], rel=1e-3)
    assert {"dispatch_feed", "dispatch_enqueue", "dispatch_wait"} <= set(parts)
    # ingest carries the reader's own parse work plus feed QUEUE-WAIT on
    # the scoring chain — wait_in (and its per-item count) only exist on
    # the pooled-source rule, so these are the regression tripwires: the
    # old non-pooled branch booked the whole megabatch wall as ingest
    # work with zero wait and zero items
    assert stages["ingest"]["wait_in_s"] > 0
    assert stages["ingest"]["items"] == stats["chunks"]


def test_a_runs_tail_chunk_takes_the_layout_of_the_chunks_before_it(
        mesh_world, monkeypatch):
    """The mesh layout featurizes a file's chunks ahead of the dispatch that
    uploads the genome: asked alone, a tail chunk under the threshold would
    gather on the host in a reference's first file and on the device ever
    after (a second program, compiled in the second file). The feed notes
    each chunk in order, and the tail follows the chunks before it."""
    from variantcalling_tpu import featurize
    from variantcalling_tpu.io.vcf import VcfChunkReader

    w = mesh_world
    tables = list(VcfChunkReader(f"{w['dir']}/calls.vcf",
                                 chunk_bytes=1 << 15, io_threads=1))
    head, tail = tables[0], tables[-1]
    assert len(tail) < len(head)
    monkeypatch.setattr(featurize, "GENOME_RESIDENT_MIN_VARIANTS", len(head))
    featurize._DEVICE_GENOME_CACHE.clear()
    ctx = _filter_context(w, monkeypatch, devices=4)
    assert ctx.host_features(tail).windows is not None  # alone: host gather
    ctx.note_chunk(tail)
    assert not ctx.genome_wanted
    ctx.note_chunk(head)
    assert ctx.genome_wanted
    assert ctx.host_features(head).windows is None
    assert ctx.host_features(tail).windows is None  # after a large chunk: the device
    assert featurize.device_genome_stats()["entries"] == 0  # nothing uploaded yet


@pytest.mark.parametrize("suffix", ["", ".gz"])
def test_the_raw_feed_notes_its_chunks_as_the_mesh_feed_does(
        mesh_world, monkeypatch, tmp_path, suffix):
    """The pooled layout's feed holds raw text, so the same rule is asked of
    each chunk's lines, in canonical order: a tail under the threshold
    follows the chunks before it, whatever the pool's timing (a ``.vcf.gz``
    input's chunks all start side by side: ISSUE 36). Nothing is counted
    once the answer is yes, nor while the genome is on the device."""
    from variantcalling_tpu import featurize
    from variantcalling_tpu.io import bgzf as bgzf_mod
    from variantcalling_tpu.io.vcf import VcfChunkReader

    w = mesh_world
    path = f"{w['dir']}/calls.vcf"
    if suffix:
        path = str(tmp_path / "calls.vcf.gz")
        with open(f"{w['dir']}/calls.vcf", "rb") as fh, bgzf_mod.BgzfWriter(path) as out:
            out.write(fh.read())
    raws = [buf for buf, _ in VcfChunkReader(path, chunk_bytes=1 << 15,
                                             io_threads=1).iter_raw()]
    lines = [int(np.count_nonzero(b == 0x0A)) for b in raws]
    assert len(raws) > 2 and lines[-1] < min(lines[:-1])
    monkeypatch.setattr(featurize, "GENOME_RESIDENT_MIN_VARIANTS", min(lines[:-1]))
    featurize._DEVICE_GENOME_CACHE.clear()
    ctx = _filter_context(w, monkeypatch, devices=1)
    ctx.note_raw_chunk(raws[-1])
    assert not ctx.genome_wanted  # alone, the tail is too short for the upload
    ctx.note_raw_chunk(raws[0])
    assert ctx.genome_wanted

    def never(*a, **k):
        raise AssertionError("counted a chunk's lines with the answer known")

    monkeypatch.setattr(np, "count_nonzero", never)
    ctx.note_raw_chunk(raws[-1])  # the answer is yes already
    ctx.genome_wanted = False
    monkeypatch.setattr(featurize, "_genome_resident_worthwhile", lambda *a, **k: True)
    ctx.note_raw_chunk(raws[0])  # the genome is on the device: nothing to decide
    assert not ctx.genome_wanted


# ---------------------------------------------------------------------------
# acceptance: byte parity at forced device counts x engine x strategy
# ---------------------------------------------------------------------------


@pytest.mark.flakehunt
@pytest.mark.parametrize("engine", ["native", "jit"])
def test_streaming_byte_parity_device_count_matrix(mesh_world, monkeypatch,
                                                   engine):
    """Acceptance: CLI output records byte-identical at forced device
    counts {1,2,4}, per engine, across two forest strategies (jit; the
    native engine has no XLA strategy) — modulo the ``##vctpu_*`` header
    lines naming the configuration. Ordering-sensitive under the pooled
    layouts: flakehunt repeats it."""
    w = mesh_world
    d = w["dir"]
    strategies = ("gather", "wide") if engine == "jit" else (None,)
    oracle = None
    for strategy in strategies:
        for devices in (1, 2, 4):
            out = f"{d}/mesh_{engine}_{strategy}_{devices}.vcf"
            stats = _stream(w, out, monkeypatch, engine, devices,
                            strategy=strategy)
            assert stats is not None and stats["n"] == w["n"], \
                (engine, strategy, devices)
            data = open(out, "rb").read()
            mesh_lines = [ln for ln in data.split(b"\n")
                          if ln.startswith(b"##vctpu_mesh=")]
            if engine == "jit" and devices > 1:
                # >1-device runs name their layout exactly once
                assert mesh_lines == [b"##vctpu_mesh=dp=%d" % devices]
            else:
                # single-device plans (and every native run — nothing to
                # shard) emit NO mesh line
                assert mesh_lines == []
            body = _modulo_header(data)
            if oracle is None:
                oracle = body
            else:
                assert body == oracle, (engine, strategy, devices)


@pytest.mark.flakehunt
def test_streaming_parity_native_vs_meshed_jit_modulo_header(mesh_world,
                                                             monkeypatch):
    """Cross-engine x cross-mesh: the native host walk and a 4-device
    shard_map jit run produce identical records."""
    w = mesh_world
    d = w["dir"]
    outs = {}
    for name, engine, devices in (("native", "native", 1),
                                  ("jit4", "jit", 4)):
        out = f"{d}/cross_{name}.vcf"
        assert _stream(w, out, monkeypatch, engine, devices) is not None
        outs[name] = open(out, "rb").read()
    assert _modulo_header(outs["native"]) == _modulo_header(outs["jit4"])


def test_forced_host_device_count_subprocess_parity(mesh_world, monkeypatch,
                                                    tmp_path):
    """The documented container route: a FRESH process forced to 4 host
    devices (XLA_FLAGS) scoring on a 4-device mesh emits the same record
    bytes as the in-process single-device run — proving the env route
    end to end, not just the in-process mesh slicing."""
    w = mesh_world
    out_ref = f"{w['dir']}/sub_ref.vcf"
    assert _stream(w, out_ref, monkeypatch, "jit", 1) is not None

    out = str(tmp_path / "sub_mesh.vcf")
    child = (
        "from variantcalling_tpu.pipelines.filter_variants import run\n"
        f"raise SystemExit(run(['--input_file', {w['dir'] + '/calls.vcf'!r},\n"
        f" '--model_file', {w['dir'] + '/model.pkl'!r}, '--model_name', 'm',\n"
        f" '--reference_file', {w['dir'] + '/ref.fa'!r},\n"
        f" '--output_file', {out!r}, '--backend', 'cpu']))\n")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(PYTHONPATH=_REPO, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               VCTPU_ENGINE="jit", VCTPU_MESH_DEVICES="4",
               VCTPU_STREAM_CHUNK_BYTES=str(1 << 15))
    p = subprocess.run([sys.executable, "-c", child], env=env, cwd=_REPO,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    data = open(out, "rb").read()
    assert b"##vctpu_mesh=dp=4" in data
    assert _modulo_header(data) == _modulo_header(open(out_ref, "rb").read())


# ---------------------------------------------------------------------------
# the four-chip host as the benchmark's configuration states it, small
# ---------------------------------------------------------------------------

_BENCH = os.path.join(_REPO, "benchmarks")
_HOST4_GENOME, _HOST4_N, _HOST4_SEED = 240_000, 6000, 34
_FEED_SPANS = ("dispatch_feed", "dispatch_enqueue", "dispatch_wait",
               "score_finalize")


def _obs_events(out):
    with open(out + ".obs.jsonl", encoding="utf-8") as fh:
        return [json.loads(ln) for ln in fh if ln.strip()]


def _final(events):
    (snap,) = [e for e in events
               if e.get("kind") == "metrics" and e.get("name") == "final"]
    return snap


def _spans(events, name):
    return [e for e in events if e.get("kind") == "span" and e.get("name") == name]


@pytest.fixture(scope="module")
def host4(tmp_path_factory):
    """Both references of ``forest-t40d6-hg38x2-host4`` at 240 kbp, a callset
    each, the configuration's forest from its ``weights_seed``; every file
    through ``main()`` as the cell's driver calls it, under obs: at dp=4
    (each reference, the first again), then at dp=1. Chunks of about 1,500
    rows count as large enough for the resident genome, two to a megabatch."""
    from variantcalling_tpu import engine as engine_mod
    from variantcalling_tpu import featurize
    from variantcalling_tpu.__main__ import main
    from variantcalling_tpu.models import registry

    mp = pytest.MonkeyPatch()
    mp.syspath_prepend(_BENCH)
    import fixtures
    import lookup

    with open(os.path.join(_BENCH, "configs", "forest-t40d6-hg38x2-host4.json"),
              encoding="utf-8") as fh:
        config = json.load(fh)
    for k, v in {"VCTPU_ENGINE": "jit", "VCTPU_OBS": "1",
                 "VCTPU_STREAM_CHUNK_BYTES": str(1 << 17),
                 "VCTPU_MESH_MEGABATCH_ROWS": "2500"}.items():
        mp.setenv(k, v)
    mp.setattr(featurize, "GENOME_RESIDENT_MIN_VARIANTS", 1000)
    saved = engine_mod._RESOLVED
    engine_mod.reset_for_tests()
    featurize._DEVICE_GENOME_CACHE.clear()
    d = str(tmp_path_factory.mktemp("host4"))
    family = lookup.load("families", config["family"])
    weights = family.arrays(config["weights_seed"], config)
    models = os.path.join(d, "models.pkl")
    registry.save_models(models, {config["model_name"]:
                                  family.to_program(config, weights)})
    w = {"config": config, "weights": weights, "refs": []}
    for r in config["references"]:
        seed, nc = r["reference_seed"], r["n_contigs"]
        ref = {"seed": seed, "n_contigs": nc, "path": f"{d}/ref_{seed}.fa",
               "calls": f"{d}/calls_{seed}.vcf"}
        fixtures.write_reference(ref["path"], seed, _HOST4_GENOME, nc)
        fixtures.write_callset(ref["calls"], seed, _HOST4_GENOME, nc,
                               _HOST4_N, _HOST4_SEED)
        w["refs"].append(ref)

    def run(ref, tag, devices):
        mp.setenv("VCTPU_MESH_DEVICES", str(devices))
        out = f"{d}/{tag}_{ref['seed']}.vcf"
        assert main(["filter_variants_pipeline", "--input_file", ref["calls"],
                     "--model_file", models, "--model_name",
                     config["model_name"], "--reference_file", ref["path"],
                     "--output_file", out]) == 0
        return out

    for ref in w["refs"]:
        ref["dp4"] = run(ref, "dp4", 4)
    w["refs"][0]["dp4_again"] = run(w["refs"][0], "dp4again", 4)
    for ref in w["refs"]:
        ref["dp1"] = run(ref, "dp1", 1)
    yield w
    featurize._DEVICE_GENOME_CACHE.clear()
    engine_mod._RESOLVED = saved
    mp.undo()


@pytest.mark.parametrize("which", [0, 1])
def test_host4_main_agrees_with_the_plain_reference(host4, which):
    import fixtures
    import reference
    import run_cell

    ref, config = host4["refs"][which], host4["config"]
    with open(ref["calls"], "rb") as fh:
        lines_in = run_cell.body_lines(fh.read())
    with open(ref["dp4"], "rb") as fh:
        lines_out = run_cell.body_lines(fh.read())
    got = reference.compare(
        lines_in, lines_out, family=config["family"], weights=host4["weights"],
        body=fixtures.contig_body(ref["seed"], _HOST4_GENOME // ref["n_contigs"]),
        n_contigs=ref["n_contigs"], score_limit=config["limits"]["score_gap_max"])
    assert got["records"] == _HOST4_N
    assert all(got[k] <= v for k, v in config["limits"].items()), got


@pytest.mark.parametrize("which", [0, 1])
def test_host4_bytes_equal_one_device_modulo_the_mesh_line(host4, which):
    ref = host4["refs"][which]
    with open(ref["dp4"], "rb") as fh:
        dp4 = fh.read().split(b"\n")
    with open(ref["dp1"], "rb") as fh:
        dp1 = fh.read().split(b"\n")
    assert dp4.count(b"##vctpu_mesh=dp=4") == 1
    assert not any(ln.startswith(b"##vctpu_mesh=") for ln in dp1)
    assert [ln for ln in dp4 if ln != b"##vctpu_mesh=dp=4"] == dp1


@pytest.mark.parametrize("which", [0, 1])
def test_mesh_counters_add_up_on_four_devices(host4, which):
    events = _obs_events(host4["refs"][which]["dp4"])
    snap = _final(events)
    c = snap["counters"]
    chunks = snap["histograms"]["chunk.records"]["count"]
    assert chunks >= 3  # two groups: a chunk at the target alone, then two chunks
    assert c["mesh.devices"] == 4
    assert c["mesh.rows"] == c["records"] == _HOST4_N
    assert c["mesh.chunks"] == chunks
    assert c["mesh.padded_rows"] % 4 == 0 and c["mesh.padded_rows"] >= c["mesh.rows"]
    assert c["mesh.dispatches"] == c["feed.dispatches"] < chunks
    assert (c["feed.rows"], c["feed.padded_rows"]) == (c["mesh.rows"], c["mesh.padded_rows"])
    assert c["feed.native_fills"] == c["feed.dispatches"]
    # the tail chunk (under the resident threshold) gathered from the
    # device like the chunks before it, in a reference's first file too:
    # one array a dispatch, two with host windows
    assert c["feed.h2d_arrays"] == c["feed.dispatches"]
    assert [e["value"] for e in events
            if e.get("kind") == "resolve" and e.get("name") == "mesh"] == ["4"]
    assert c.get("recovery.dp_degrades", 0) == 0


@pytest.mark.parametrize("which", [0, 1])
def test_one_device_run_declares_the_mesh_counters_zero(host4, which):
    c = _final(_obs_events(host4["refs"][which]["dp1"]))["counters"]
    assert [c[f"mesh.{k}"] for k in ("dispatches", "chunks", "rows", "padded_rows")] \
        == [0, 0, 0, 0]
    assert c["mesh.devices"] == 1 and c["feed.dispatches"] >= 1
    assert c["feed.padded_rows"] >= c["feed.rows"] == c["records"] == _HOST4_N


@pytest.mark.parametrize("which", [0, 1])
def test_one_pack_span_a_group_and_one_upload_a_genome(host4, which):
    from variantcalling_tpu import featurize

    events = _obs_events(host4["refs"][which]["dp4"])
    packs, groups = _spans(events, "megabatch_pack"), _spans(events, "score_stage")
    assert len(packs) == len(groups) >= 2
    assert sorted(p["chunks"] for p in packs) == sorted(g["chunks"] for g in groups)
    assert sum(p["rows"] for p in packs) == _HOST4_N
    assert {p["thread"] for p in packs}.isdisjoint({g["thread"] for g in groups})
    (up,) = _spans(events, "genome_upload")
    n_rows = -(-(_HOST4_GENOME + 40 * (host4["refs"][which]["n_contigs"] + 1))
               // featurize.GENOME_ROW_BYTES) + 1
    assert up["devices"] == 4 and up["bytes"] == n_rows * featurize.GENOME_ROW_BYTES
    if which == 0:  # the same reference again: the genome is on the chips
        assert not _spans(_obs_events(host4["refs"][0]["dp4_again"]), "genome_upload")


@pytest.mark.parametrize("span", _FEED_SPANS)
def test_feed_spans_hang_under_the_dispatch_workers_score_stage(host4, span):
    events = _obs_events(host4["refs"][0]["dp4"])
    got = _spans(events, span)
    assert got and {e.get("parent") for e in got} == {"score_stage"}
    assert {e["thread"] for e in got} == {"vctpu-mesh-dispatch-w0"}
    rows = [e for e in events if e.get("kind") == "profile"
            and e.get("name") == "stage" and e.get("stage") == span + ".w0"]
    assert rows and all(r.get("parent") == "score_stage" for r in rows)
