"""DAN scoring engine (ISSUE 18 tentpole): the GEMM-native second model
family on the streaming hot path, under the EXACT contract the forest
strategies obey.

Layers proven here:

- predictor: name-keyed column selection, f32 end-to-end determinism
  (bit-identical across batch buckets/padding), loud failure on a
  missing feature;
- run-level family resolution: ``VCTPU_MODEL_FAMILY`` resolved ONCE on
  FilterContext — auto follows the loaded model, an explicit mismatch
  fails loudly (EngineError, exit 2) — and the ``##vctpu_model_family=``
  provenance header is emitted for DAN and STRIPPED for forest (so
  forest outputs stay byte-identical to every prior release);
- byte parity: streaming/serial × io threads × mesh device counts are
  identical modulo the ``##vctpu_*`` provenance headers;
- resume identity: a family change — or a same-family WEIGHTS change —
  restarts cleanly (resumed_chunks == 0); the same DAN resumes;
- cache identity: cross-family (and cross-digest) runs can never share
  chunk-cache entries (io/identity.py);
- registry: dan is a first-class family (name mapping, pickle
  round-trip, family-named load error);
- jaxpr census: the DAN scoring programs trace clean under
  tools/jaxpr_audit's contract at every committed device count;
- chaoshunt: the recovery ladder's invariants hold unchanged when the
  campaign fixtures score through the DAN family.
"""

import argparse
import os
import pickle

import numpy as np
import pytest

from tests.conftest import assert_no_stream_leaks
from variantcalling_tpu.utils import faults

_WATCHED_DIRS: list[str] = []


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(autouse=True)
def _leak_sentinel():
    yield
    assert_no_stream_leaks(_WATCHED_DIRS)


# ---------------------------------------------------------------------------
# shared world: one synthetic input set + a DAN and a forest over it
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def dan_world(tmp_path_factory):
    from variantcalling_tpu.featurize import BASE_FEATURES
    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.synthetic import make_fixtures, synthetic_dan, synthetic_forest

    d = str(tmp_path_factory.mktemp("dan"))
    make_fixtures(d, n=3000, genome_len=150_000)
    model = synthetic_dan(np.random.default_rng(0), BASE_FEATURES)
    forest = synthetic_forest(np.random.default_rng(1), n_trees=8, depth=4)
    _WATCHED_DIRS.append(d)
    return {"dir": d, "model": model, "forest": forest,
            "fasta": FastaReader(f"{d}/ref.fa"), "n": 3000}


def _args(w, out):
    return argparse.Namespace(
        input_file=f"{w['dir']}/calls.vcf", output_file=out, runs_file=None,
        hpol_filter_length_dist=[10, 10], blacklist=None,
        blacklist_cg_insertions=False, annotate_intervals=[],
        flow_order="TGCA", is_mutect=False, limit_to_contig=None)


def _run_stream(w, out, monkeypatch, model=None, chunk_bytes=1 << 15):
    from variantcalling_tpu.io import vcf as vcf_mod
    from variantcalling_tpu.pipelines.filter_variants import run_streaming

    monkeypatch.setattr(vcf_mod, "STREAM_CHUNK_BYTES", chunk_bytes)
    monkeypatch.setenv("VCTPU_IO_BACKOFF_S", "0.01")
    # streaming eligibility must not depend on the host's core count
    # (a 1-CPU runner would silently divert every leg onto the serial
    # path) — same pin the chaoshunt harness applies to its children
    monkeypatch.setenv("VCTPU_THREADS", "2")
    return run_streaming(_args(w, out), model if model is not None
                         else w["model"], w["fasta"], {}, None)


def _norm(data: bytes) -> bytes:
    from tools.chaoshunt.harness import normalize_output

    return normalize_output(data)


@pytest.fixture(scope="module")
def clean_bytes(dan_world, tmp_path_factory):
    """One fault-free streaming DAN run — the byte oracle."""
    from variantcalling_tpu.io import vcf as vcf_mod
    from variantcalling_tpu.pipelines.filter_variants import run_streaming

    w = dan_world
    out = f"{w['dir']}/clean.vcf"
    old = vcf_mod.STREAM_CHUNK_BYTES
    vcf_mod.STREAM_CHUNK_BYTES = 1 << 15
    saved = {k: os.environ.get(k)
             for k in ("VCTPU_IO_BACKOFF_S", "VCTPU_THREADS")}
    os.environ.update(VCTPU_IO_BACKOFF_S="0.01", VCTPU_THREADS="2")
    try:
        stats = run_streaming(_args(w, out), w["model"], w["fasta"], {}, None)
    finally:
        vcf_mod.STREAM_CHUNK_BYTES = old
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    assert stats is not None and stats["chunks"] > 3
    # a synthetic DAN must produce VARYING scores — a constant-output
    # model would make every parity/digest check below pass trivially
    scores = {ln.rsplit(b"TREE_SCORE=", 1)[1].split(b";", 1)[0].split(b"\t", 1)[0]
              for ln in open(out, "rb").read().splitlines()
              if b"TREE_SCORE=" in ln}
    assert len(scores) > 10
    return open(out, "rb").read()


# ---------------------------------------------------------------------------
# predictor: column selection by name + f32 bucket/pad determinism
# ---------------------------------------------------------------------------


def _tiny_dan(numeric_features, seed=0):
    import jax

    from variantcalling_tpu.models import dan as dan_mod

    cfg = dan_mod.DanConfig(n_numeric=len(numeric_features), embed_dim=4,
                            hidden=16, n_layers=2)
    params = dan_mod.init_params(cfg, jax.random.PRNGKey(seed))
    params["w_out"] = jax.random.normal(
        jax.random.PRNGKey(seed + 1), params["w_out"].shape) * 0.25
    return dan_mod.DanModel.from_params(
        cfg, params, feature_names=[*numeric_features,
                                    "left_motif", "right_motif"],
        numeric_features=list(numeric_features))


def _feature_matrix(layout, columns, n=257, seed=3):
    from variantcalling_tpu.models.dan import MOTIF_VOCAB

    rng = np.random.default_rng(seed)
    x = np.zeros((n, len(layout)), np.float32)
    for name, col in columns.items():
        x[:, layout.index(name)] = col
    for m in ("left_motif", "right_motif"):
        if m not in columns:
            x[:, layout.index(m)] = rng.integers(
                0, MOTIF_VOCAB, n).astype(np.float32)
    return x


def test_predictor_selects_columns_by_name():
    """The SAME logical rows score identically under two run layouts that
    permute the physical column order — selection is by name, never
    positional."""
    from variantcalling_tpu.models.dan import MOTIF_VOCAB, make_score_predictor

    model = _tiny_dan(["qual", "dp"])
    rng = np.random.default_rng(5)
    cols = {"qual": rng.uniform(0, 90, 257).astype(np.float32),
            "dp": rng.uniform(1, 60, 257).astype(np.float32),
            "left_motif": rng.integers(0, MOTIF_VOCAB, 257).astype(np.float32),
            "right_motif": rng.integers(0, MOTIF_VOCAB, 257).astype(np.float32),
            "sor": rng.uniform(0, 4, 257).astype(np.float32)}
    layout_a = ["qual", "dp", "sor", "left_motif", "right_motif"]
    layout_b = ["right_motif", "sor", "dp", "left_motif", "qual"]
    sa = np.asarray(make_score_predictor(model, layout_a)(
        _feature_matrix(layout_a, cols)))
    sb = np.asarray(make_score_predictor(model, layout_b)(
        _feature_matrix(layout_b, cols)))
    assert np.array_equal(sa, sb)
    assert len(np.unique(np.round(sa, 6))) > 10


def test_predictor_bit_identical_across_pad_buckets():
    """f32 end-to-end determinism through the dispatch ladder: a chunk
    zero-padded to ANY bucket (``_dispatch_fused`` pads every batch to a
    rung of ``featurize._bucket``: a power of two up to 32,768 rows, four
    rungs an octave above) scores its real rows bit-identically — the
    bucket choice and the padding rows never perturb a score, under
    both the eager and the jitted program."""
    import jax

    from variantcalling_tpu.models.dan import make_score_predictor

    model = _tiny_dan(["qual", "dp"])
    layout = ["qual", "dp", "left_motif", "right_motif"]
    x = _feature_matrix(layout, {}, n=1000, seed=7)
    rng = np.random.default_rng(8)
    x[:, 0] = rng.uniform(0, 90, 1000)
    x[:, 1] = rng.uniform(1, 60, 1000)
    program = make_score_predictor(model, layout)
    full = np.asarray(program(x))
    assert full.dtype == np.float32
    # zero-padding extra rows must not perturb the real rows' bits
    padded = np.asarray(program(np.pad(x, ((0, 24), (0, 0)))))[:1000]
    assert np.array_equal(padded, full)
    # a 37-row chunk in a 64-row bucket == the same chunk in a 128-row or
    # an 80-row one (5 * 2**4: a rung that is no power of two), eager and
    # jitted (the ladder may pick either depending on history)
    chunk = x[:37]
    for fn in (program, jax.jit(program)):
        b64 = np.asarray(fn(np.pad(chunk, ((0, 27), (0, 0)))))[:37]
        b128 = np.asarray(fn(np.pad(chunk, ((0, 91), (0, 0)))))[:37]
        b80 = np.asarray(fn(np.pad(chunk, ((0, 43), (0, 0)))))[:37]
        assert np.array_equal(b64, b128) and np.array_equal(b64, b80)
        assert len(np.unique(b64)) > 5  # varying, not trivially equal


def test_predictor_missing_feature_fails_loudly():
    from variantcalling_tpu.engine import EngineError
    from variantcalling_tpu.models.dan import make_score_predictor

    model = _tiny_dan(["qual", "dp"])
    with pytest.raises(EngineError, match="dp"):
        make_score_predictor(model, ["qual", "left_motif", "right_motif"])


def test_untrained_dan_scores_exactly_half():
    """init_params zeroes the output head, so an UNTRAINED model scores
    sigmoid(0) == 0.5 exactly — the training-friendly init contract."""
    import jax

    from variantcalling_tpu.models import dan as dan_mod

    cfg = dan_mod.DanConfig(n_numeric=2, embed_dim=4, hidden=16)
    params = dan_mod.init_params(cfg, jax.random.PRNGKey(0))
    model = dan_mod.DanModel.from_params(
        cfg, params, feature_names=["qual", "dp", "left_motif", "right_motif"],
        numeric_features=["qual", "dp"])
    layout = ["qual", "dp", "left_motif", "right_motif"]
    s = np.asarray(dan_mod.make_score_predictor(model, layout)(
        _feature_matrix(layout, {}, n=33)))
    assert np.array_equal(s, np.full(33, 0.5, np.float32))


# ---------------------------------------------------------------------------
# run-level family resolution + provenance header
# ---------------------------------------------------------------------------


def _ctx(w, model, engine=None):
    from variantcalling_tpu.pipelines.filter_variants import FilterContext

    return FilterContext(model, w["fasta"], engine=engine)


def test_family_auto_resolves_from_loaded_model(dan_world, monkeypatch):
    from variantcalling_tpu.models import dan as dan_mod

    w = dan_world
    monkeypatch.setenv("VCTPU_MODEL_FAMILY", "auto")
    ctx = _ctx(w, w["model"])
    assert ctx.model_family == "dan"
    assert ctx.model_digest == dan_mod.weights_digest(w["model"])
    ctx = _ctx(w, w["forest"])
    assert ctx.model_family == "forest"
    assert ctx.model_digest is None


def test_explicit_family_match_accepted(dan_world, monkeypatch):
    w = dan_world
    monkeypatch.setenv("VCTPU_MODEL_FAMILY", "dan")
    assert _ctx(w, w["model"]).model_family == "dan"
    monkeypatch.setenv("VCTPU_MODEL_FAMILY", "forest")
    assert _ctx(w, w["forest"]).model_family == "forest"


def test_explicit_family_mismatch_fails_loudly_both_ways(dan_world,
                                                         monkeypatch):
    from variantcalling_tpu.engine import EngineError

    w = dan_world
    monkeypatch.setenv("VCTPU_MODEL_FAMILY", "forest")
    with pytest.raises(EngineError, match="family 'dan'"):
        _ctx(w, w["model"])
    monkeypatch.setenv("VCTPU_MODEL_FAMILY", "dan")
    with pytest.raises(EngineError, match="family 'forest'"):
        _ctx(w, w["forest"])


def test_family_mismatch_exits_2_through_the_pipeline(dan_world, monkeypatch,
                                                      tmp_path):
    """The CLI contract: a family mismatch is a CONFIGURATION error —
    exit 2 on both the streaming and the serial path, destination
    untouched."""
    from variantcalling_tpu.pipelines.filter_variants import run_loaded

    w = dan_world
    monkeypatch.setenv("VCTPU_MODEL_FAMILY", "dan")
    monkeypatch.setenv("VCTPU_THREADS", "2")  # streaming-eligible leg
    out = str(tmp_path / "mismatch.vcf")
    assert run_loaded(_args(w, out), w["forest"], w["fasta"], {}, None) == 2
    assert not os.path.exists(out)
    monkeypatch.setenv("VCTPU_THREADS", "1")  # force the serial path
    assert run_loaded(_args(w, out), w["forest"], w["fasta"], {}, None) == 2
    assert not os.path.exists(out)


def test_dan_header_emitted_forest_header_absent(dan_world, clean_bytes,
                                                 monkeypatch, tmp_path):
    """##vctpu_model_family=dan is in every DAN output; a forest run
    emits NO family line (forest outputs stay byte-identical to every
    pre-family release)."""
    w = dan_world
    assert b"##vctpu_model_family=dan\n" in clean_bytes
    out = str(tmp_path / "forest.vcf")
    stats = _run_stream(w, out, monkeypatch, model=w["forest"])
    assert stats is not None and stats["n"] == w["n"]
    assert b"##vctpu_model_family" not in open(out, "rb").read()


def test_resolve_event_records_family(dan_world, monkeypatch, tmp_path):
    import json

    w = dan_world
    out = str(tmp_path / "obs.vcf")
    monkeypatch.setenv("VCTPU_OBS", "1")
    try:
        stats = _run_stream(w, out, monkeypatch)
        assert stats is not None
        events = [json.loads(ln) for ln in open(out + ".obs.jsonl")]
    finally:
        for side in (out + ".obs.jsonl",):
            if os.path.exists(side):
                os.remove(side)
    fam = [e for e in events
           if e["kind"] == "resolve" and e["name"] == "model_family"]
    assert fam and fam[0]["value"] == "dan"
    assert fam[0]["requested"] == "auto"


# ---------------------------------------------------------------------------
# byte-parity matrix: io threads x mesh devices x streaming/serial
# ---------------------------------------------------------------------------


def test_dan_byte_parity_matrix(dan_world, clean_bytes, monkeypatch,
                                tmp_path):
    """The flakehunt matrix, in-process: IO_THREADS {1,4} x MESH_DEVICES
    {1,2} streaming legs plus the serial whole-table path all produce
    identical bytes modulo the ``##vctpu_*`` provenance headers (the
    mesh header is the ONE byte naming the layout)."""
    from variantcalling_tpu import engine as engine_mod
    from variantcalling_tpu.pipelines.filter_variants import run_loaded

    w = dan_world
    oracle = _norm(clean_bytes)
    legs = {}
    for io_threads in ("1", "4"):
        for mesh in ("1", "2"):
            out = str(tmp_path / f"io{io_threads}_dp{mesh}.vcf")
            monkeypatch.setenv("VCTPU_IO_THREADS", io_threads)
            monkeypatch.setenv("VCTPU_ENGINE", "jit")
            monkeypatch.setenv("VCTPU_MESH_DEVICES", mesh)
            engine_mod.reset_for_tests()
            try:
                stats = _run_stream(w, out, monkeypatch)
            finally:
                monkeypatch.delenv("VCTPU_IO_THREADS")
                monkeypatch.delenv("VCTPU_ENGINE")
                monkeypatch.delenv("VCTPU_MESH_DEVICES")
                engine_mod.reset_for_tests()
            assert stats is not None and stats["n"] == w["n"], \
                (io_threads, mesh)
            data = open(out, "rb").read()
            if mesh == "2":
                assert b"##vctpu_mesh=dp=2\n" in data
            assert b"##vctpu_model_family=dan\n" in data
            legs[f"io{io_threads}_dp{mesh}"] = _norm(data)
    out = str(tmp_path / "serial.vcf")
    monkeypatch.setenv("VCTPU_THREADS", "1")
    try:
        rc = run_loaded(_args(w, out), w["model"], w["fasta"], {}, None)
    finally:
        monkeypatch.delenv("VCTPU_THREADS")
    assert rc == 0
    legs["serial"] = _norm(open(out, "rb").read())
    for name, data in legs.items():
        assert data == oracle, f"leg {name} diverged from the oracle"


# ---------------------------------------------------------------------------
# resume identity: the family and the weights digest pin the journal
# ---------------------------------------------------------------------------


def test_resume_rejects_model_family_change(dan_world, monkeypatch, tmp_path):
    """A run interrupted under DAN and resumed with a FOREST model
    RESTARTS (resumed_chunks == 0) instead of splicing two families into
    one output — and the restarted output equals a clean forest run."""
    w = dan_world
    out = str(tmp_path / "fam_change.vcf")
    faults.arm("io.writeback", times=None, after=3)
    with pytest.raises(OSError):
        _run_stream(w, out, monkeypatch)
    assert len(open(out + ".journal").read().splitlines()) - 1 >= 1
    faults.reset()
    stats = _run_stream(w, out, monkeypatch, model=w["forest"])
    assert stats is not None and stats["resumed_chunks"] == 0
    assert stats["n"] == w["n"]
    clean_forest = str(tmp_path / "forest_oracle.vcf")
    stats = _run_stream(w, clean_forest, monkeypatch, model=w["forest"])
    assert stats is not None
    assert open(out, "rb").read() == open(clean_forest, "rb").read()


def test_resume_rejects_dan_weights_change(dan_world, monkeypatch, tmp_path):
    """Same family, different WEIGHTS: the model-file signature alone
    cannot tell two DANs in one pickle apart, so the weights digest in
    the scoring identity must force the restart."""
    from variantcalling_tpu.featurize import BASE_FEATURES
    from variantcalling_tpu.synthetic import synthetic_dan

    w = dan_world
    out = str(tmp_path / "weights_change.vcf")
    faults.arm("io.writeback", times=None, after=3)
    with pytest.raises(OSError):
        _run_stream(w, out, monkeypatch)
    assert len(open(out + ".journal").read().splitlines()) - 1 >= 1
    faults.reset()
    other = synthetic_dan(np.random.default_rng(99), BASE_FEATURES)
    stats = _run_stream(w, out, monkeypatch, model=other)
    assert stats is not None and stats["resumed_chunks"] == 0
    assert stats["n"] == w["n"]


def test_resume_accepts_same_dan_model(dan_world, clean_bytes, monkeypatch,
                                       tmp_path):
    """Control: the SAME DAN resumes the journaled prefix and completes
    byte-identically to the clean oracle."""
    w = dan_world
    out = str(tmp_path / "fam_same.vcf")
    faults.arm("io.writeback", times=None, after=3)
    with pytest.raises(OSError):
        _run_stream(w, out, monkeypatch)
    committed = len(open(out + ".journal").read().splitlines()) - 1
    assert committed >= 1
    faults.reset()
    stats = _run_stream(w, out, monkeypatch)
    assert stats is not None and stats["resumed_chunks"] == committed
    assert stats["n"] == w["n"]
    assert open(out, "rb").read() == clean_bytes


# ---------------------------------------------------------------------------
# cache identity: cross-family / cross-digest runs can never share entries
# ---------------------------------------------------------------------------


def test_cross_family_runs_cannot_share_cache_entries(dan_world):
    from variantcalling_tpu.io import identity

    w = dan_world
    args = _args(w, "/dev/null")

    def fp(family, digest):
        cfg = identity.scoring_config(
            args, engine="jit", forest_strategy="jit", mesh_devices=1,
            rank=0, ranks=1, model_family=family, model_digest=digest)
        return identity.fingerprint(identity.cache_identity(cfg))

    dan_fp = fp("dan", "a" * 64)
    assert fp("forest", None) != dan_fp  # family change -> cache miss
    assert fp("dan", "b" * 64) != dan_fp  # weights change -> cache miss
    assert fp("dan", "a" * 64) == dan_fp  # same family+weights -> hit


def test_cache_identity_is_partition_agnostic_but_family_aware(dan_world):
    """cache_identity strips ONLY the rank/span partition layout — the
    family and digest must survive into the cache fingerprint."""
    from variantcalling_tpu.io import identity

    cfg = identity.scoring_config(
        _args(dan_world, "/dev/null"), engine="jit", forest_strategy="jit",
        mesh_devices=1, rank=1, ranks=4, span=(100, 200),
        model_family="dan", model_digest="d" * 64)
    ci = identity.cache_identity(cfg)
    assert "ranks" not in ci and "span" not in ci
    assert ci["model_family"] == "dan"
    assert ci["model_digest"] == "d" * 64


# ---------------------------------------------------------------------------
# registry: dan is a first-class family
# ---------------------------------------------------------------------------


def test_registry_family_mapping(dan_world):
    from variantcalling_tpu.models import registry
    from variantcalling_tpu.models.threshold import ThresholdModel

    assert "dan" in registry.FAMILIES
    assert registry.family_of(dan_world["model"]) == "dan"
    assert registry.family_of(dan_world["forest"]) == "forest"
    thr = ThresholdModel(feature_names=["qual"], thresholds=np.zeros(1),
                         signs=np.ones(1), scales=np.ones(1))
    assert registry.family_of(thr) == "threshold"
    assert registry.family_of_name("dan_model_ignore_gt_incl_hpol_runs") == "dan"
    assert registry.family_of_name("rf_model_ignore_gt_incl_hpol_runs") == "forest"
    assert registry.family_of_name("nonsense") is None


def test_registry_round_trips_a_mixed_family_pickle(dan_world, tmp_path):
    """One pickle holding BOTH families (the reference's multi-model
    container) loads each model under its own family, weights intact."""
    from variantcalling_tpu.models import dan as dan_mod
    from variantcalling_tpu.models import registry

    path = str(tmp_path / "mixed.pkl")
    registry.save_models(path, {"dan_model_a": dan_world["model"],
                                "rf_model_a": dan_world["forest"]})
    m = registry.load_model(path, "dan_model_a")
    assert registry.family_of(m) == "dan"
    assert dan_mod.weights_digest(m) == dan_mod.weights_digest(dan_world["model"])
    assert registry.family_of(registry.load_model(path, "rf_model_a")) == "forest"


def test_load_model_error_names_the_missing_family(dan_world, tmp_path):
    from variantcalling_tpu.models import registry

    path = str(tmp_path / "forest_only.pkl")
    registry.save_models(path, {"rf_model_a": dan_world["forest"]})
    with pytest.raises(KeyError, match="no 'dan'-family model"):
        registry.load_model(path, "dan_model_ignore_gt_incl_hpol_runs")


# ---------------------------------------------------------------------------
# jaxpr census: the DAN programs are under contract
# ---------------------------------------------------------------------------


def test_jaxpr_dan_programs_present_and_clean():
    """tools/jaxpr_audit builds the DAN scoring programs at every
    committed device count and every one traces clean — no collectives,
    no host callbacks, no f64, f32 score outputs."""
    import jax

    from tools import jaxpr_audit as ja

    contract = ja.load_contract()
    assert "dan" in contract
    programs = ja.build_dan_programs(contract)
    labels = [label for label, _, _, _ in programs]
    for dp in contract["dan"]["mesh_device_counts"]:
        assert any(f"dp={dp}" in label for label in labels), labels
    for label, fn, avals, kind in programs:
        closed = jax.make_jaxpr(fn)(*avals)
        violations = ja.audit_closed_jaxpr(closed, contract, label, kind)
        assert violations == [], (label, violations)


# ---------------------------------------------------------------------------
# chaoshunt: the recovery ladder is family-independent
# ---------------------------------------------------------------------------


def test_chaoshunt_recovery_ladder_holds_under_dan(tmp_path):
    """The ISSUE's chaos leg: campaign fixtures built with
    ``model_family='dan'`` run the SAME schedules the forest runs —
    a transient-IO retry under the io4 layout and a device-OOM
    megabatch-shrink under mesh2 — and every invariant holds (recovery
    ladder unchanged, byte-identical completion)."""
    from tools.chaoshunt import harness
    from variantcalling_tpu.models.dan import DanModel

    fx = harness.build_fixtures(str(tmp_path), records=700,
                                model_family="dan")
    with open(fx.model, "rb") as fh:
        assert isinstance(pickle.load(fh)["m"], DanModel)
    # the clean reference itself carries the DAN provenance header (it
    # is normalized away for the cross-leg compare, like every vctpu_*)
    assert b"vctpu_model_family" not in fx.reference_norm
    schedules = [
        harness.Schedule(seed=0, layout="io4",
                         faults=[harness.FaultSpec("io.chunk_read", times=2)]),
        harness.Schedule(seed=1, layout="mesh2",
                         faults=[harness.FaultSpec("xla.dispatch_oom",
                                                   times=1)]),
    ]
    for sched in schedules:
        result = harness.run_schedule(sched, fx, str(tmp_path))
        assert result["violations"] == [], (sched.describe(),
                                            result["violations"])
