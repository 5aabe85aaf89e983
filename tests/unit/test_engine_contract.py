"""Deterministic scoring-engine contract (ISSUE 2 tentpole).

The round-5 VERDICT's worst finding: output bytes depended on which
scoring engine happened to load (`_native_cpu_featurize_score` silently
fell back to jit on any native hiccup). These tests lock the contract
that replaces it: the engine is resolved once per run from
``VCTPU_ENGINE``/``VCTPU_REQUIRE_NATIVE``, recorded in the output header,
forbidden to switch mid-run — and the two engines produce byte-identical
scores and formatted output (the ≥10k-variant byte-equality acceptance
criterion)."""

import os
import pickle
import subprocess
import sys

import numpy as np
import pytest

from variantcalling_tpu import engine as engine_mod
from variantcalling_tpu.utils import faults

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture
def fresh_engine(monkeypatch):
    """Re-resolvable engine for env-patching tests; restores the cache on
    teardown so other tests keep the process-wide decision."""
    saved = engine_mod._RESOLVED
    engine_mod.reset_for_tests()
    yield monkeypatch
    engine_mod._RESOLVED = saved
    faults.reset()


# ---------------------------------------------------------------------------
# resolution semantics
# ---------------------------------------------------------------------------


def test_resolve_is_cached_and_immutable(fresh_engine):
    fresh_engine.setenv("VCTPU_ENGINE", "jit")
    first = engine_mod.resolve()
    assert first.name == "jit" and first.requested == "jit"
    # env mutation after resolution cannot flip the engine (no mid-run switch)
    fresh_engine.setenv("VCTPU_ENGINE", "native")
    assert engine_mod.resolve() is first


def test_invalid_engine_value_fails_loudly(fresh_engine):
    fresh_engine.setenv("VCTPU_ENGINE", "fastest")
    with pytest.raises(engine_mod.EngineError, match="not a valid engine"):
        engine_mod.resolve()


def test_require_native_conflicts_with_jit(fresh_engine):
    fresh_engine.setenv("VCTPU_ENGINE", "jit")
    fresh_engine.setenv("VCTPU_REQUIRE_NATIVE", "1")
    with pytest.raises(engine_mod.EngineError, match="conflicts"):
        engine_mod.resolve()


def test_require_native_with_build_failure_raises(fresh_engine):
    """VCTPU_REQUIRE_NATIVE=1 + a native build failure must fail loudly —
    the silent-jit-fallback failure mode the contract exists to kill."""
    fresh_engine.setenv("VCTPU_REQUIRE_NATIVE", "1")
    fresh_engine.delenv("VCTPU_ENGINE", raising=False)
    faults.arm("native.build", times=None)
    with pytest.raises(engine_mod.EngineError, match="required"):
        engine_mod.resolve()


def test_auto_resolves_jit_on_multi_device_harness(fresh_engine):
    """The test harness forces 8 virtual devices, so auto must pick jit
    (the mesh path stays XLA) — and say why."""
    fresh_engine.delenv("VCTPU_ENGINE", raising=False)
    fresh_engine.delenv("VCTPU_REQUIRE_NATIVE", raising=False)
    d = engine_mod.resolve()
    assert d.name == "jit" and d.requested == "auto"


def test_header_line_format(fresh_engine):
    fresh_engine.setenv("VCTPU_ENGINE", "jit")
    assert engine_mod.resolve().header_line() == "##vctpu_engine=jit"


def test_require_native_falsy_spellings_disable(fresh_engine):
    fresh_engine.setenv("VCTPU_ENGINE", "jit")
    for v in ("0", "false", "no", "off", ""):
        engine_mod.reset_for_tests()
        fresh_engine.setenv("VCTPU_REQUIRE_NATIVE", v)
        assert engine_mod.resolve().name == "jit"  # no conflict raised


def test_stale_engine_header_line_is_replaced():
    """Re-filtering a previously-filtered VCF must record THIS run's
    engine, not the inherited one (provenance contract)."""
    from variantcalling_tpu.io.vcf import VcfHeader
    from variantcalling_tpu.pipelines.filter_variants import _ensure_output_header

    header = VcfHeader()
    header.add_meta_line("##fileformat=VCFv4.2")
    header.add_meta_line("##vctpu_engine=jit")  # stale, from the input file
    _ensure_output_header(header, engine=engine_mod.EngineDecision("native", "native", "t"))
    lines = [line for line in header.lines if line.startswith("##vctpu_engine=")]
    assert lines == ["##vctpu_engine=native"]


def test_native_engine_refuses_mid_run_degradation(fresh_engine):
    """With the engine pinned native, a native hiccup mid-run raises
    EngineError instead of silently degrading to jit."""
    from variantcalling_tpu.pipelines.filter_variants import fused_featurize_score
    from variantcalling_tpu.synthetic import synthetic_forest

    fresh_engine.setenv("VCTPU_ENGINE", "native")
    eng = engine_mod.resolve()
    assert eng.name == "native"
    model = synthetic_forest(np.random.default_rng(0), n_trees=4, depth=3)

    class _HF:  # windows unavailable and no table/fasta -> native cannot serve
        names = list(model.feature_names)
        cols = {}
        windows = None
        alle = None

    with pytest.raises(engine_mod.EngineError, match="native"):
        fused_featurize_score(model, _HF(), "TGCA", engine=eng)


# ---------------------------------------------------------------------------
# byte-equality across engines (acceptance criterion, >= 10k variants)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def parity_world(tmp_path_factory):
    from variantcalling_tpu.synthetic import make_fixtures, synthetic_forest

    d = str(tmp_path_factory.mktemp("engine_parity"))
    make_fixtures(d, n=12000, genome_len=300_000)
    model = synthetic_forest(np.random.default_rng(0), n_trees=10, depth=5)
    with open(f"{d}/model.pkl", "wb") as fh:
        pickle.dump({"m": model}, fh)
    return {"dir": d, "model": model, "n": 12000}


def test_score_bytes_identical_native_vs_jit_10k(parity_world):
    """The two engines' scores are BITWISE identical on >=10k variants, and
    so are the formatted TREE_SCORE bytes (round(4) + %g rendering)."""
    from variantcalling_tpu.featurize import host_featurize
    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.io.vcf import read_vcf
    from variantcalling_tpu.pipelines.filter_variants import (
        _native_cpu_featurize_score, fused_featurize_score)

    w = parity_world
    table = read_vcf(f"{w['dir']}/calls.vcf")
    assert len(table) >= 10_000
    fasta = FastaReader(f"{w['dir']}/ref.fa")
    hf = host_featurize(table, fasta)

    native_scores = _native_cpu_featurize_score(w["model"], hf, "TGCA", table, fasta)
    assert native_scores is not None, "native engine unavailable in test image"
    jit_eng = engine_mod.EngineDecision("jit", "jit", "test")
    jit_scores = fused_featurize_score(w["model"], hf, "TGCA", engine=jit_eng)

    assert np.asarray(native_scores).tobytes() == np.asarray(jit_scores).tobytes()

    # formatted writeback bytes (what lands in the VCF) are identical too
    from variantcalling_tpu.io.vcf import _format_extra_info_bytes

    n = len(table)
    fmt_n = _format_extra_info_bytes(n, {"TREE_SCORE": np.round(native_scores, 4)})
    fmt_j = _format_extra_info_bytes(n, {"TREE_SCORE": np.round(jit_scores, 4)})
    assert fmt_n == fmt_j


def test_cli_output_byte_identical_native_vs_jit(parity_world):
    """Full CLI under VCTPU_ENGINE=native vs =jit: identical bytes except
    the ##vctpu_engine / ##vctpu_forest_strategy header lines that name
    the scoring configuration (the native engine's C++ walk records
    native-cpp; the jit engine records its resolved XLA strategy)."""
    w = parity_world
    d = w["dir"]
    env0 = {k: v for k, v in os.environ.items() if k not in ("PYTHONPATH",)}
    env0.update(PYTHONPATH=_REPO, JAX_PLATFORMS="cpu")
    env0.pop("XLA_FLAGS", None)  # single device: both engines eligible
    outs = {}
    for name in ("native", "jit"):
        env = dict(env0, VCTPU_ENGINE=name)
        p = subprocess.run(
            [sys.executable, "-m", "variantcalling_tpu", "filter_variants_pipeline",
             "--input_file", f"{d}/calls.vcf", "--model_file", f"{d}/model.pkl",
             "--model_name", "m", "--reference_file", f"{d}/ref.fa",
             "--output_file", f"{d}/out_{name}.vcf"],
            env=env, cwd=_REPO, capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-2000:]
        outs[name] = open(f"{d}/out_{name}.vcf", "rb").read()
        assert f"##vctpu_engine={name}".encode() in outs[name]
    # provenance: each output names the full scoring configuration
    assert b"##vctpu_forest_strategy=native-cpp" in outs["native"]
    assert b"##vctpu_forest_strategy=gather" in outs["jit"]  # cpu auto

    def body(b: bytes) -> bytes:
        return b"\n".join(line for line in b.split(b"\n")
                          if not line.startswith(b"##vctpu_engine=")
                          and not line.startswith(b"##vctpu_forest_strategy="))

    assert body(outs["native"]) == body(outs["jit"])
    assert outs["native"].count(b"TREE_SCORE=") == w["n"]


def test_cli_require_native_with_injected_build_failure_exits_nonzero(parity_world):
    """Acceptance: VCTPU_REQUIRE_NATIVE=1 + injected build failure ->
    non-zero exit, clear message, NO output file (no silent jit fallback)."""
    d = parity_world["dir"]
    env = {k: v for k, v in os.environ.items() if k not in ("PYTHONPATH",)}
    env.update(PYTHONPATH=_REPO, JAX_PLATFORMS="cpu",
               VCTPU_REQUIRE_NATIVE="1", VCTPU_FAULTS="native.build")
    p = subprocess.run(
        [sys.executable, "-m", "variantcalling_tpu", "filter_variants_pipeline",
         "--input_file", f"{d}/calls.vcf", "--model_file", f"{d}/model.pkl",
         "--model_name", "m", "--reference_file", f"{d}/ref.fa",
         "--output_file", f"{d}/out_req.vcf"],
        env=env, cwd=_REPO, capture_output=True, text=True, timeout=600)
    assert p.returncode != 0
    assert "native" in p.stderr and "required" in p.stderr
    assert not os.path.exists(f"{d}/out_req.vcf")
