"""Pallas wide-block forest kernel parity (interpreter mode on the CPU
backend; that the SAME kernel compiles with Mosaic for a v5e is checked
by test_tpu_aot.py, and that it matches the gather walk on the chip by
chip_smoke.py)."""

import numpy as np
import pytest

import jax.numpy as jnp

from variantcalling_tpu.models import boosting
from variantcalling_tpu.models.forest import (finalize_margin, from_sklearn,
                                              predict_margin, to_gemm)
from variantcalling_tpu.models.forest_pallas import (
    TILE_N, make_wide_pallas_margin_predictor)


def test_pallas_matches_gather_on_boosted_forest(rng):
    x = rng.random((1000, 8)).astype(np.float32)  # non-TILE_N multiple: pad path
    y = (x[:, 0] + 0.5 * x[:, 1] > 0.8).astype(np.float32)
    cfg = boosting.BoostConfig(n_trees=12, depth=4, n_bins=32)
    forest = boosting.fit(x, y, cfg=cfg)
    got = np.asarray(make_wide_pallas_margin_predictor(
        to_gemm(forest, 8), interpret=True)(jnp.asarray(x)))
    walk = np.asarray(predict_margin(forest, jnp.asarray(x)))
    assert got.tobytes() == walk.tobytes()


def test_pallas_matches_sklearn_rf(rng):
    from sklearn.ensemble import RandomForestClassifier

    x = rng.random((TILE_N, 6)).astype(np.float32)  # exact tile: no-pad path
    y = (x[:, 0] > 0.5).astype(int)
    clf = RandomForestClassifier(n_estimators=7, max_depth=5, random_state=0).fit(x, y)
    forest = from_sklearn(clf)  # ragged trees: padded leaves must never match
    got = np.asarray(make_wide_pallas_margin_predictor(
        to_gemm(forest, 6), interpret=True)(jnp.asarray(x)))
    np.testing.assert_allclose(finalize_margin(got, forest),
                               clf.predict_proba(x)[:, 1], atol=2e-6)


def test_pallas_rejects_missing_value_forests():
    from tests.unit.test_xgb_ingest import _model_json, _xgb_tree
    from variantcalling_tpu.models.xgb import from_xgboost_json

    t0 = _xgb_tree(left=[1, -1, -1], right=[2, -1, -1],
                   cond=[0.5, -0.3, 0.4], sidx=[0, 0, 0], default_left=[1, 0, 0])
    forest = from_xgboost_json(_model_json([t0]))
    gf = to_gemm(forest, 3)
    with pytest.raises(ValueError, match="default_left"):
        make_wide_pallas_margin_predictor(gf, interpret=True)
