"""Reference-artifact fidelity: model pickles in the exact shape the
reference ships must load and score identically to their source engine.

The reference's production artifacts are named-model pickles —
``--model_name rf_model_ignore_gt_incl_hpol_runs`` over ``test.model.pkl``
(reference docs/howto-callset-filter.md:114), the somatic
``threshold_model_ignore_gt_incl_hpol_runs`` on TLOD/SOR (:129,139), and
train fixtures ``exact_gt.model.pkl`` / ``approximate_gt.model.pkl``
(test/resources/system/test_train_models_pipeline/). The snapshot's lfs
resources are unhydrated, so the artifacts are CONSTRUCTED TO SPEC with
the in-env sklearn (xgboost is not installed; xgboost fidelity is locked
separately by tests/unit/test_xgb_ingest.py against hand-built JSON
models) and asserted against sklearn's own predict_proba:

- every name in the {rf,threshold} x {ignore_gt,use_gt} x
  {incl,excl}_hpol_runs grid loads through the registry;
- forest scores match sklearn predict_proba to <= 1e-6 on adversarial
  matrices (exact-threshold ties, deep trees, extreme values), on BOTH
  the jitted walk and the native C++ walk;
- threshold-model scores are bit-identical across a pickle round-trip;
- the flagship CLI consumes the artifact end to end with the documented
  model-name flag.
"""

import pickle
import subprocess
import sys

import numpy as np
import pytest

from variantcalling_tpu.models import registry
from variantcalling_tpu.models.forest import FlatForest, predict_score
from variantcalling_tpu.models.threshold import ThresholdModel
from variantcalling_tpu.models.threshold import predict_score as threshold_predict

RF_FEATURES = ["qual", "dp", "sor", "af", "gq", "gc_content",
               "hmer_indel_length", "indel_length"]
GT_FEATURES = RF_FEATURES + ["is_het"]  # use_gt variants add GT-derived columns
MUTECT_FEATURES = ["tlod", "sor"]


def _grid_pickle(rng, deep: bool = False):
    """The full reference model grid as {name: fitted sklearn / threshold}."""
    from sklearn.ensemble import GradientBoostingClassifier, RandomForestClassifier

    n = 4000
    models = {}
    for gt in ("ignore_gt", "use_gt"):
        feats = RF_FEATURES if gt == "ignore_gt" else GT_FEATURES
        x = rng.random((n, len(feats))).astype(np.float32)
        y = (x[:, 0] + 0.5 * x[:, 1] + rng.normal(0, 0.3, n) > 0.8).astype(int)
        for hpol in ("incl_hpol_runs", "excl_hpol_runs"):
            import zlib

            clf = RandomForestClassifier(
                n_estimators=12, max_depth=14 if deep else 6,
                random_state=zlib.crc32(f"{gt}/{hpol}".encode())).fit(x, y)
            clf.feature_names_in_ = np.asarray(feats, dtype=object)
            models[f"rf_model_{gt}_{hpol}"] = clf
            models[f"threshold_model_{gt}_{hpol}"] = ThresholdModel(
                feature_names=MUTECT_FEATURES,
                thresholds=np.asarray([6.3, 3.0], np.float32),
                signs=np.asarray([1.0, -1.0], np.float32),
                scales=np.asarray([2.0, 1.0], np.float32),
                pass_threshold=0.25,
                all_feature_names=MUTECT_FEATURES)
    # one boosted sklearn artifact (regressor trees -> margin aggregation)
    xg = rng.random((n, len(RF_FEATURES))).astype(np.float32)
    yg = (xg[:, 0] > 0.5).astype(int)
    gb = GradientBoostingClassifier(n_estimators=8, max_depth=3,
                                    random_state=0).fit(xg, yg)
    gb.feature_names_in_ = np.asarray(RF_FEATURES, dtype=object)
    models["gbt_model_ignore_gt_incl_hpol_runs"] = gb
    return models


def _adversarial(rng, clf, n_feats: int) -> np.ndarray:
    """Probe matrix: random rows + rows pinned EXACTLY to fitted split
    thresholds (tie-routing) + extreme magnitudes."""
    x = rng.normal(0.5, 0.6, size=(512, n_feats)).astype(np.float32)
    thr = []
    for est in getattr(clf, "estimators_", [])[:4]:
        t = est[0] if isinstance(est, np.ndarray) else est
        tree = t.tree_
        for nid in range(tree.node_count):
            if tree.children_left[nid] != -1:
                thr.append((tree.feature[nid], tree.threshold[nid]))
    for i, (f, t) in enumerate(thr[:128]):
        x[i, f] = np.float32(t)  # exact tie: must route like sklearn's <=
    x[200] = 1e30
    x[201] = -1e30
    return x


@pytest.fixture(scope="module")
def grid(tmp_path_factory):
    rng = np.random.default_rng(11)
    d = tmp_path_factory.mktemp("ref_artifacts")
    exact = _grid_pickle(rng)
    approx = _grid_pickle(rng, deep=True)  # depth-14 trees (> 10)
    p_exact = d / "exact_gt.model.pkl"
    p_approx = d / "approximate_gt.model.pkl"
    for p, m in ((p_exact, exact), (p_approx, approx)):
        with open(p, "wb") as fh:
            pickle.dump(m, fh)
    return d, {"exact_gt": (p_exact, exact), "approximate_gt": (p_approx, approx)}


def test_every_documented_model_name_loads(grid):
    _d, files = grid
    for _label, (path, src) in files.items():
        loaded = registry.load_models(str(path))
        assert set(loaded) == set(src)
        for name in registry.standard_model_names():
            assert isinstance(loaded[name], (FlatForest, ThresholdModel)), name
            # loaded forests carry the fitted column order for by-name
            # reordering inside the pipeline
            if isinstance(loaded[name], FlatForest):
                assert loaded[name].feature_names == list(src[name].feature_names_in_)


@pytest.mark.parametrize("label", ["exact_gt", "approximate_gt"])
def test_rf_scores_match_sklearn(grid, label, rng):
    _d, files = grid
    path, src = files[label]
    for gt in ("ignore_gt", "use_gt"):
        feats = RF_FEATURES if gt == "ignore_gt" else GT_FEATURES
        for hpol in ("incl_hpol_runs", "excl_hpol_runs"):
            name = f"rf_model_{gt}_{hpol}"
            clf = src[name]
            x = _adversarial(rng, clf, len(feats))
            expect = clf.predict_proba(np.asarray(x, np.float64))[:, 1]
            ours = registry.load_model(str(path), name)
            got_jit = np.asarray(predict_score(ours, x))
            np.testing.assert_allclose(got_jit, expect, atol=1e-6,
                                       err_msg=f"{label}/{name} jitted walk")
            from variantcalling_tpu.models.forest import native_host_predictor

            nf = native_host_predictor(ours)
            if nf is not None:
                np.testing.assert_allclose(nf(x), expect, atol=1e-6,
                                           err_msg=f"{label}/{name} native walk")


def test_gbt_pickle_matches_sklearn(grid, rng):
    _d, files = grid
    path, src = files["exact_gt"]
    clf = src["gbt_model_ignore_gt_incl_hpol_runs"]
    x = _adversarial(rng, clf, len(RF_FEATURES))
    expect = clf.predict_proba(np.asarray(x, np.float64))[:, 1]
    ours = registry.load_model(str(path), "gbt_model_ignore_gt_incl_hpol_runs")
    np.testing.assert_allclose(np.asarray(predict_score(ours, x)), expect, atol=1e-6)


def test_threshold_model_bit_stable_roundtrip(grid, rng):
    """Mutect TLOD/SOR threshold model: pickle round-trip scores are
    BIT-identical (same float32 program, same operands)."""
    _d, files = grid
    path, src = files["exact_gt"]
    name = "threshold_model_ignore_gt_incl_hpol_runs"
    direct = src[name]
    loaded = registry.load_model(str(path), name)
    x = np.column_stack([rng.uniform(0, 40, 2048), rng.uniform(0, 8, 2048)]).astype(np.float32)
    x[0] = [6.3, 3.0]  # exactly at both thresholds -> sigmoid(0)^2 = 0.25
    a = np.asarray(threshold_predict(direct, x, MUTECT_FEATURES))
    b = np.asarray(threshold_predict(loaded, x, MUTECT_FEATURES))
    assert a.tobytes() == b.tobytes()
    np.testing.assert_allclose(a[0], 0.25, atol=1e-6)


def test_cli_consumes_reference_shaped_pickle(grid, tmp_path):
    """The documented flow: filter_variants_pipeline --model_file
    <grid pickle> --model_name rf_model_ignore_gt_incl_hpol_runs."""
    import os

    from variantcalling_tpu.synthetic import make_fixtures

    _d, files = grid
    path, _src = files["exact_gt"]
    d = str(tmp_path)
    make_fixtures(d, n=1500, genome_len=60_000)
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    out = os.path.join(d, "filtered.vcf")
    p = subprocess.run(
        [sys.executable, "-m", "variantcalling_tpu", "filter_variants_pipeline",
         "--input_file", os.path.join(d, "calls.vcf"),
         "--model_file", str(path),
         "--model_name", "rf_model_ignore_gt_incl_hpol_runs",
         "--flow_order", "TGCA", "--backend", "cpu",
         "--reference_file", os.path.join(d, "ref.fa"),
         "--output_file", out],
        cwd=repo, capture_output=True, text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": repo})
    assert p.returncode == 0, p.stderr[-2000:]
    text = open(out).read()
    assert "TREE_SCORE=" in text and text.count("\n") > 1500

    # the written TREE_SCOREs must equal sklearn predict_proba over the
    # pipeline's own feature columns, reordered BY NAME onto the model's
    # fitted order — the oracle that catches dropped feature_names_in_
    from variantcalling_tpu.featurize import host_featurize, materialize_features
    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.io.vcf import read_vcf

    clf = _src["rf_model_ignore_gt_incl_hpol_runs"]
    table = read_vcf(os.path.join(d, "calls.vcf"))
    fs = materialize_features(
        host_featurize(table, FastaReader(os.path.join(d, "ref.fa"))),
        flow_order="TGCA")
    cols = np.column_stack([np.nan_to_num(fs.columns[f].astype(np.float64))
                            for f in clf.feature_names_in_])
    expect = clf.predict_proba(cols)[:, 1]
    got = np.asarray([float(line.split("TREE_SCORE=")[1].split(";")[0].split("\t")[0])
                      for line in text.splitlines() if "TREE_SCORE=" in line])
    assert len(got) == len(expect)
    np.testing.assert_allclose(got, expect, atol=1e-3)  # output rounds to 4dp
