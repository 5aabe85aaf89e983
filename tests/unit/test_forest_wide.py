"""Wide-contraction forest strategy (ISSUE 3 tentpole): all trees per MXU
pass via block-diagonal operands, strategy registry, and the determinism
contract — every strategy must emit per-tree margins reduced in canonical
sequential tree order, so scores are BYTE-identical to the scan GEMM, the
gather walk and the native C++ engine (PR-2 engine contract extended to
the strategy axis). Adversarial coverage: ragged/padded trees, NaN
missing-value routing, the GEMM_MAX_LEAVES boundary, chunked-driver and
tree-block invariance, and formatted CLI bytes on the 12k fixture."""

import os
import pickle

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from variantcalling_tpu import engine as engine_mod
from variantcalling_tpu.engine import EngineError
from variantcalling_tpu.models import forest as fmod

STRATEGIES = ("gather", "gemm", "wide", "pallas")
#: the strategies the jit engine can run on this (CPU) backend through the
#: pipeline — the pallas kernel compiles for TPUs only; here it runs through
#: the Pallas interpreter, which a test asks for by argument
#: (``interpret=True``), never the product path
CPU_PIPELINE_STRATEGIES = ("gather", "gemm", "wide")


def _margins(forest, x, n_features, strategies=STRATEGIES):
    xj = jnp.asarray(x)
    return {s: np.asarray(jax.jit(
        fmod.make_margin_predictor(forest, n_features, strategy=s,
                                   interpret=True))(xj))
        for s in strategies}


def _assert_all_bits_equal(margins: dict):
    ref_name, ref = next(iter(margins.items()))
    for name, m in margins.items():
        assert m.tobytes() == ref.tobytes(), \
            f"{name} margins differ from {ref_name} " \
            f"(max abs diff {np.abs(m - ref).max()})"


# ---------------------------------------------------------------------------
# bit-parity across strategies (the determinism hard constraint)
# ---------------------------------------------------------------------------


@pytest.mark.flakehunt
def test_wide_margin_bits_identical_ragged_sklearn_forest(rng):
    """Ragged sklearn trees: unequal node counts per tree mean PADDED
    leaves (plen=-1) in the GEMM encodings — the adversarial case where a
    padded leaf accidentally matching would corrupt one tree's margin."""
    from sklearn.ensemble import GradientBoostingClassifier, RandomForestClassifier

    x = rng.random((1500, 8)).astype(np.float32)
    y = (x[:, 0] + 0.3 * x[:, 1] + rng.normal(0, 0.2, 1500) > 0.6).astype(int)
    xq = rng.random((999, 8)).astype(np.float32)  # non-multiple of any tile
    for clf in (
        RandomForestClassifier(n_estimators=9, max_depth=7, random_state=0).fit(x, y),
        GradientBoostingClassifier(n_estimators=11, max_depth=4, random_state=0).fit(x, y),
    ):
        forest = fmod.from_sklearn(clf)
        margins = _margins(forest, xq, 8)
        _assert_all_bits_equal(margins)
        # and the finalized scores (shared host finalize) agree with sklearn
        score = fmod.finalize_margin(margins["wide"], forest)
        np.testing.assert_allclose(score, clf.predict_proba(xq)[:, 1], atol=2e-6)


@pytest.mark.flakehunt
def test_wide_margin_bits_identical_deep_synthetic(rng):
    from variantcalling_tpu.synthetic import synthetic_forest

    for depth in (3, 6, 10):
        f = synthetic_forest(rng, n_trees=5, depth=depth, n_features=12)
        x = rng.uniform(0, 50, (700, 12)).astype(np.float32)
        _assert_all_bits_equal(_margins(f, x, 12))


def test_wide_matches_native_engine_bits(rng):
    """finalized wide scores vs the native C++ walk (the other engine)."""
    from variantcalling_tpu.synthetic import synthetic_forest

    f = synthetic_forest(rng, n_trees=12, depth=6, n_features=12)
    nf = fmod.native_host_predictor(f)
    if nf is None:
        pytest.skip("native engine unavailable")
    x = rng.uniform(0, 50, (2048, 12)).astype(np.float32)
    native_scores = nf(x)
    for strat in ("wide", "pallas"):
        m = np.asarray(fmod.make_margin_predictor(
            f, 12, strategy=strat, interpret=True)(jnp.asarray(x)))
        assert fmod.finalize_margin(m, f).tobytes() == native_scores.tobytes()


def test_wide_nan_missing_routing_bits(rng):
    """NaN features route through default_left in the wide path and the
    pallas kernel exactly as in the gather walk and the scan GEMM (xgboost
    semantics)."""
    from tests.unit.test_xgb_ingest import _probe_matrix, _two_tree_model
    from variantcalling_tpu.models.xgb import from_xgboost_json

    forest = from_xgboost_json(_two_tree_model())
    assert forest.default_left is not None
    x = _probe_matrix(rng)  # exact-threshold hits + NaN rows
    _assert_all_bits_equal(_margins(forest, x, 3))


def test_wide_tree_block_invariance(rng):
    """G is a perf knob, never a semantics knob: every blocking (1, 3, T,
    oversized) produces the same bytes, including a non-divisor of T."""
    from variantcalling_tpu.synthetic import synthetic_forest

    f = synthetic_forest(rng, n_trees=7, depth=5, n_features=12)
    x = jnp.asarray(rng.uniform(0, 50, (513, 12)).astype(np.float32))
    gf = fmod.to_gemm(f, 12)
    ref = np.asarray(fmod.predict_margin(f, x))
    for g in (1, 3, 7, 50):
        wf = fmod.to_wide(gf, g)
        assert np.asarray(fmod.predict_margin_wide(wf, x)).tobytes() == ref.tobytes()
        # pallas wide-block kernel under the same blocking
        from variantcalling_tpu.models.forest_pallas import \
            make_wide_pallas_margin_predictor

        pfn = make_wide_pallas_margin_predictor(gf, tree_block=g, interpret=True)
        assert np.asarray(pfn(x)).tobytes() == ref.tobytes()


def test_wide_chunked_driver_invariance(rng, monkeypatch):
    """The N-chunked driver (VCTPU_WIDE_CHUNK) cannot change any bit —
    rows are independent — including when N is not a chunk multiple."""
    from variantcalling_tpu.synthetic import synthetic_forest

    f = synthetic_forest(rng, n_trees=6, depth=5, n_features=12)
    x = jnp.asarray(rng.uniform(0, 50, (1000, 12)).astype(np.float32))
    wf = fmod.to_wide(fmod.to_gemm(f, 12))
    ref = np.asarray(fmod.predict_margin_wide(wf, x))
    for chunk in ("64", "250", "1000", "4096"):
        monkeypatch.setenv(fmod.WIDE_CHUNK_ENV, chunk)
        assert np.asarray(fmod.predict_margin_wide(wf, x)).tobytes() == ref.tobytes()


def test_edge_batch_sizes_all_strategies(rng):
    """n=0 (empty table), n=1 and odd sizes through every strategy —
    found by end-to-end verification: reshape(-1) cannot infer the leaf
    dim on a zero-size array, and a zero-size pallas grid cannot
    dispatch."""
    from variantcalling_tpu.synthetic import synthetic_forest

    f = synthetic_forest(rng, n_trees=5, depth=4, n_features=12)
    for n in (0, 1, 17):
        x = jnp.asarray(rng.uniform(0, 50, (n, 12)).astype(np.float32))
        ref = np.asarray(fmod.predict_margin(f, x)) if n else \
            np.zeros(0, np.float32)
        for strat in STRATEGIES:
            m = np.asarray(fmod.make_margin_predictor(
                f, 12, strategy=strat, interpret=True)(x))
            assert m.shape == (n,) and m.tobytes() == ref.tobytes(), (strat, n)


def test_gemm_max_leaves_boundary(rng):
    """Trees AT the GEMM_MAX_LEAVES=512 boundary stay GEMM-eligible
    (auto), one level deeper falls back to the gather walk — and the wide
    path stays bit-exact on the boundary forest."""
    from variantcalling_tpu.synthetic import synthetic_forest

    at = synthetic_forest(rng, n_trees=2, depth=10, n_features=12)  # 512 leaves
    over = synthetic_forest(rng, n_trees=2, depth=11, n_features=12)  # 1024
    assert fmod.to_gemm(at, 12).n_leaves == fmod.GEMM_MAX_LEAVES
    # the vectorized leaf count auto-resolution uses must agree with the
    # traversal count to_gemm performs (full-binary-tree invariant)
    assert fmod.max_tree_leaves(at) == fmod.to_gemm(at, 12).n_leaves
    assert fmod.max_tree_leaves(over) == fmod.to_gemm(over, 12).n_leaves
    assert fmod.resolve_strategy(at, 12, backend="tpu") == "pallas"
    assert fmod.resolve_strategy(over, 12, backend="tpu") == "gather"
    assert fmod.resolve_strategy(at, 12, backend="cpu") == "gather"
    x = rng.uniform(0, 50, (300, 12)).astype(np.float32)
    _assert_all_bits_equal(_margins(at, x, 12, ("gather", "gemm", "wide")))


# ---------------------------------------------------------------------------
# strategy registry: explicit override, loud failure, attribution
# ---------------------------------------------------------------------------


def test_env_override_selects_strategy(rng, monkeypatch):
    """VCTPU_FOREST_STRATEGY makes every GEMM path testable on CPU (the
    old make_predictor hard-excluded CPU from GEMM strategies)."""
    from variantcalling_tpu.synthetic import synthetic_forest

    f = synthetic_forest(rng, n_trees=4, depth=4, n_features=12)
    x = jnp.asarray(rng.uniform(0, 50, (64, 12)).astype(np.float32))
    ref = np.asarray(fmod.predict_margin(f, x))
    for strat in STRATEGIES:
        monkeypatch.setenv(fmod.FOREST_STRATEGY_ENV, strat)
        fn = fmod.make_margin_predictor(f, 12, interpret=True)  # env-driven, no pin
        assert fmod.last_strategy == strat
        assert np.asarray(fn(x)).tobytes() == ref.tobytes()
    monkeypatch.delenv(fmod.FOREST_STRATEGY_ENV)
    fmod.make_margin_predictor(f, 12)
    assert fmod.last_strategy == "gather"  # auto on the CPU harness


def test_invalid_strategy_env_fails_loudly(rng, monkeypatch):
    from variantcalling_tpu.synthetic import synthetic_forest

    monkeypatch.setenv(fmod.FOREST_STRATEGY_ENV, "fastest")
    f = synthetic_forest(rng, n_trees=2, depth=3, n_features=12)
    with pytest.raises(EngineError, match="not a valid forest strategy"):
        fmod.make_margin_predictor(f, 12)


def test_malformed_wide_knobs_fail_loudly(rng, monkeypatch):
    """VCTPU_WIDE_CHUNK/VCTPU_WIDE_BLOCK follow the same config-error rule
    as the strategy name: validated up front (FilterContext calls
    validate_strategy_env), never a raw ValueError from inside a trace."""
    from variantcalling_tpu.pipelines.filter_variants import FilterContext
    from variantcalling_tpu.synthetic import synthetic_forest

    model = synthetic_forest(rng, n_trees=2, depth=3, n_features=12)
    jit_eng = engine_mod.EngineDecision("jit", "jit", "test")
    for knob, bad in ((fmod.WIDE_CHUNK_ENV, "16k"), (fmod.WIDE_BLOCK_ENV, "-4"),
                      (fmod.WIDE_CHUNK_ENV, "0")):
        monkeypatch.setenv(knob, bad)
        with pytest.raises(EngineError, match="not a positive integer"):
            FilterContext(model, fasta=None, engine=jit_eng)
        monkeypatch.delenv(knob)
    FilterContext(model, fasta=None, engine=jit_eng)  # clean env: fine


def test_pallas_serves_missing_routing_and_fails_loudly_where_it_cannot_compile(
        rng, monkeypatch):
    """The engine contract applied to make_predictor's old bare-except: an
    EXPLICITLY requested strategy that cannot build raises (exit-2 style)
    instead of silently degrading to another program. A default_left
    forest is the kernel's to serve (auto picks it on a TPU, the
    interpreter matches the gather walk); on this CPU backend Mosaic
    cannot compile it, and that is what fails loudly."""
    from tests.unit.test_xgb_ingest import _probe_matrix, _two_tree_model
    from variantcalling_tpu.models.xgb import from_xgboost_json

    forest = from_xgboost_json(_two_tree_model())
    with pytest.raises(EngineError, match="explicitly requested"):
        fmod.make_margin_predictor(forest, 3, strategy="pallas")
    monkeypatch.setenv(fmod.FOREST_STRATEGY_ENV, "pallas")
    with pytest.raises(EngineError, match="explicitly requested"):
        fmod.make_margin_predictor(forest, 3)
    monkeypatch.setenv(fmod.FOREST_STRATEGY_ENV, "auto")
    assert fmod.resolve_strategy(forest, 3, backend="tpu") == "pallas"
    fn = fmod.make_margin_predictor(forest, 3)
    assert fmod.last_strategy == "gather"  # cpu auto
    assert fn is not None
    x = jnp.asarray(_probe_matrix(rng))
    kernel = fmod.make_margin_predictor(forest, 3, strategy="pallas", interpret=True)
    assert np.asarray(kernel(x)).tobytes() == \
        np.asarray(fmod.predict_margin(forest, x)).tobytes()


def test_auto_resolved_strategy_that_cannot_build_raises(rng, monkeypatch):
    """``auto`` resolves ONCE and the resolved program builds or the call
    dies — there is no pallas -> wide -> gemm -> gather chain that would
    let a run stamped with one strategy be scored by another (and let a
    benchmark row labelled pallas quietly time wide)."""
    from variantcalling_tpu.synthetic import synthetic_forest

    f = synthetic_forest(rng, n_trees=3, depth=4, n_features=12)
    # what auto resolves to on a TPU; on this CPU backend the Mosaic
    # kernel cannot compile, which stands in for any build failure
    monkeypatch.setattr(fmod, "resolve_strategy", lambda *a, **k: "pallas")
    before = fmod.last_strategy
    with pytest.raises(EngineError, match="auto-resolved.*no fallback chain"):
        fmod.make_margin_predictor(f, 12)
    assert fmod.last_strategy == before


def test_explicit_pallas_on_cpu_backend_fails_loudly(rng):
    """The product path never selects Pallas interpret mode: without the
    test-only ``interpret=True`` argument the kernel must compile with
    Mosaic, and on a CPU backend that is a loud configuration error."""
    from variantcalling_tpu.synthetic import synthetic_forest

    f = synthetic_forest(rng, n_trees=3, depth=4, n_features=12)
    with pytest.raises(EngineError, match="explicitly requested"):
        fmod.make_margin_predictor(f, 12, strategy="pallas")


def test_invalid_strategy_env_fails_even_on_native_engine(rng, monkeypatch):
    """A malformed VCTPU_FOREST_STRATEGY is a configuration error on EVERY
    engine — the native engine ignores the strategy for scoring, but must
    not silently accept garbage config (found by end-to-end verification:
    the unvalidated value only raised on the jit path)."""
    from variantcalling_tpu.pipelines.filter_variants import FilterContext
    from variantcalling_tpu.synthetic import synthetic_forest

    monkeypatch.setenv(fmod.FOREST_STRATEGY_ENV, "warp")
    model = synthetic_forest(rng, n_trees=2, depth=3, n_features=12)
    native_eng = engine_mod.EngineDecision("native", "native", "test")
    with pytest.raises(EngineError, match="not a valid forest strategy"):
        FilterContext(model, fasta=None, engine=native_eng)


def test_auto_resolution_matrix(rng):
    from tests.unit.test_xgb_ingest import _two_tree_model
    from variantcalling_tpu.models.xgb import from_xgboost_json
    from variantcalling_tpu.synthetic import synthetic_forest

    f = synthetic_forest(rng, n_trees=3, depth=4, n_features=12)
    assert fmod.resolve_strategy(f, 12, backend="cpu") == "gather"
    assert fmod.resolve_strategy(f, 12, backend="tpu") == "pallas"
    assert fmod.resolve_strategy(f, 12, backend="gpu") == "wide"
    # missing-value routing (default_left) takes the kernel too
    dl = from_xgboost_json(_two_tree_model())
    assert fmod.resolve_strategy(dl, 3, backend="tpu") == "pallas"
    assert fmod.resolve_strategy(dl, 3, backend="gpu") == "wide"
    # VCTPU_PALLAS=0 opt-out
    os.environ["VCTPU_PALLAS"] = "0"
    try:
        assert fmod.resolve_strategy(f, 12, backend="tpu") == "wide"
        assert fmod.resolve_strategy(dl, 3, backend="tpu") == "wide"
    finally:
        del os.environ["VCTPU_PALLAS"]


# ---------------------------------------------------------------------------
# formatted CLI bytes across strategies on the 12k engine-contract fixture
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def wide_parity_world(tmp_path_factory):
    from variantcalling_tpu.synthetic import make_fixtures, synthetic_forest

    d = str(tmp_path_factory.mktemp("wide_parity"))
    make_fixtures(d, n=12000, genome_len=300_000)
    model = synthetic_forest(np.random.default_rng(0), n_trees=10, depth=5)
    with open(f"{d}/model.pkl", "wb") as fh:
        pickle.dump({"m": model}, fh)
    return {"dir": d, "model": model, "n": 12000}


@pytest.mark.flakehunt
def test_formatted_tree_score_bytes_identical_across_strategies_12k(wide_parity_world):
    """Acceptance: the 12k engine-contract fixture scored under EVERY
    strategy (and the native engine) produces byte-identical scores AND
    byte-identical formatted TREE_SCORE writeback bytes."""
    from variantcalling_tpu.featurize import host_featurize
    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.io.vcf import _format_extra_info_bytes, read_vcf
    from variantcalling_tpu.pipelines.filter_variants import (
        _native_cpu_featurize_score, fused_featurize_score)

    w = wide_parity_world
    table = read_vcf(f"{w['dir']}/calls.vcf")
    assert len(table) >= 10_000
    fasta = FastaReader(f"{w['dir']}/ref.fa")
    hf = host_featurize(table, fasta)
    jit_eng = engine_mod.EngineDecision("jit", "jit", "test")

    scores = {}
    for strat in CPU_PIPELINE_STRATEGIES:
        scores[strat] = fused_featurize_score(w["model"], hf, "TGCA",
                                              engine=jit_eng, strategy=strat)
    native = _native_cpu_featurize_score(w["model"], hf, "TGCA", table, fasta)
    if native is not None:
        scores["native-cpp"] = native

    n = len(table)
    ref_name = "gather"
    ref_scores = np.asarray(scores[ref_name])
    ref_fmt = _format_extra_info_bytes(n, {"TREE_SCORE": np.round(ref_scores, 4)})
    for name, s in scores.items():
        assert np.asarray(s).tobytes() == ref_scores.tobytes(), \
            f"{name} scores differ from {ref_name}"
        fmt = _format_extra_info_bytes(n, {"TREE_SCORE": np.round(np.asarray(s), 4)})
        assert fmt == ref_fmt, f"{name} formatted bytes differ from {ref_name}"


def test_cli_wide_strategy_header_and_bytes(wide_parity_world):
    """Full CLI under VCTPU_FOREST_STRATEGY=wide: exit 0, the header
    records ##vctpu_forest_strategy=wide, and the body bytes match the
    auto (gather) run exactly."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    w = wide_parity_world
    d = w["dir"]
    env0 = {k: v for k, v in os.environ.items() if k not in ("PYTHONPATH",)}
    env0.update(PYTHONPATH=repo, JAX_PLATFORMS="cpu", VCTPU_ENGINE="jit")
    env0.pop("XLA_FLAGS", None)
    outs = {}
    for strat in ("auto", "wide"):
        env = dict(env0, VCTPU_FOREST_STRATEGY=strat)
        p = subprocess.run(
            [sys.executable, "-m", "variantcalling_tpu", "filter_variants_pipeline",
             "--input_file", f"{d}/calls.vcf", "--model_file", f"{d}/model.pkl",
             "--model_name", "m", "--reference_file", f"{d}/ref.fa",
             "--output_file", f"{d}/out_strat_{strat}.vcf"],
            env=env, cwd=repo, capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-2000:]
        outs[strat] = open(f"{d}/out_strat_{strat}.vcf", "rb").read()
    assert b"##vctpu_forest_strategy=gather" in outs["auto"]
    assert b"##vctpu_forest_strategy=wide" in outs["wide"]

    def body(b: bytes) -> bytes:
        return b"\n".join(line for line in b.split(b"\n")
                          if not line.startswith(b"##vctpu_forest_strategy="))

    assert body(outs["auto"]) == body(outs["wide"])
    assert outs["wide"].count(b"TREE_SCORE=") == w["n"]


# ---------------------------------------------------------------------------
# bounded memory: the N-chunked wide driver at BASELINE scale (slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_wide_5m_scoring_rss_within_scan_budget(tmp_path):
    """Acceptance: peak RSS of 5M-variant scoring under the wide strategy
    stays within ~1.2x of the scan-GEMM path — the N-chunked driver keeps
    the decision tensor at O(chunk * T*I) instead of (N, T*L)."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    rss = {}
    for strat in ("gemm", "wide"):
        code = f"""
import resource, sys
import numpy as np
sys.path.insert(0, {repo!r})
import jax.numpy as jnp
from variantcalling_tpu.pipelines.filter_variants import score_variants
from variantcalling_tpu.synthetic import synthetic_forest
model = synthetic_forest(np.random.default_rng(0), n_trees=40, depth=6)
x = np.random.default_rng(1).uniform(0, 50, (5_000_000, 12)).astype(np.float32)
s = score_variants(model, x, [f"f{{i}}" for i in range(12)])
assert np.isfinite(s).all() and len(s) == 5_000_000
print("RSS_KB", resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)
"""
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
        env.update(PYTHONPATH=repo, JAX_PLATFORMS="cpu", VCTPU_ENGINE="jit",
                   VCTPU_FOREST_STRATEGY=strat)
        env.pop("XLA_FLAGS", None)
        p = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-2000:]
        rss[strat] = int(p.stdout.split("RSS_KB")[1].strip().split()[0])
    assert rss["wide"] < 1.25 * rss["gemm"], rss
