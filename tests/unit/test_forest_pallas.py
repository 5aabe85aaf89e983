"""Pallas wide-block forest kernel parity (interpreter mode on the CPU
backend; that the SAME kernel compiles with Mosaic for a v5e is checked
by test_tpu_aot.py, and that it matches the gather walk on the chip by
chip_smoke.py)."""

import numpy as np
import pytest

import jax
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


def _one_default_tree_forest():
    from tests.unit.test_xgb_ingest import _model_json, _xgb_tree
    from variantcalling_tpu.models.xgb import from_xgboost_json

    t0 = _xgb_tree(left=[1, -1, -1], right=[2, -1, -1],
                   cond=[0.5, -0.3, 0.4], sidx=[0, 0, 0], default_left=[1, 0, 0])
    return from_xgboost_json(_model_json([t0]))


def test_pallas_routes_missing_values_by_default_left(rng):
    """A NaN feature takes the split's default branch inside the kernel,
    bit for bit as the gather walk routes it."""
    forest = _one_default_tree_forest()
    x = rng.normal(0.5, 1.0, (600, 3)).astype(np.float32)
    x[::3, 0] = np.nan  # default LEFT at the root
    x[1] = [0.5, 0.0, 0.0]  # the threshold itself routes right (strict <)
    gf = to_gemm(forest, 3)
    assert gf.dleft is not None
    got = np.asarray(make_wide_pallas_margin_predictor(gf, interpret=True)(jnp.asarray(x)))
    walk = np.asarray(predict_margin(forest, jnp.asarray(x)))
    assert got.tobytes() == walk.tobytes()
    assert np.all(got[::3] == np.float32(-0.3))


def _kernel_call(gf):
    """The one ``pallas_call`` equation of the predictor's jaxpr."""
    fn = make_wide_pallas_margin_predictor(gf, interpret=True)
    jx = jax.make_jaxpr(fn)(jnp.zeros((700, gf.a.shape[1]), jnp.float32))
    (eqn,) = [e for e in jx.jaxpr.eqns if e.primitive.name == "pallas_call"]
    return eqn


@pytest.mark.parametrize("missing", [False, True])
def test_the_kernel_a_forest_traces_to(rng, missing):
    """A forest without ``default_left`` traces to the plain kernel (its
    six operands, its name, its three matmuls, its cost); one with it to
    ``forest_wide_block_missing``, which adds the mask and the ``dleft``
    column as operands and the mask matmul."""
    from variantcalling_tpu.synthetic import synthetic_forest

    forest = _one_default_tree_forest() if missing else \
        synthetic_forest(rng, n_trees=5, depth=4, n_features=12)
    gf = to_gemm(forest, 3 if missing else 12)
    eqn = _kernel_call(gf)
    body = eqn.params["jaxpr"]
    dots = sum(e.primitive.name == "dot_general" for e in body.eqns)
    if missing:
        assert (len(eqn.invars), eqn.params["name"], dots) == \
            (8, "forest_wide_block_missing", 4)
        return
    assert (len(eqn.invars), eqn.params["name"], dots) == (6, "forest_wide_block", 3)
    # the five trees in one block (G = min(128 // 15, 5)): tables of
    # (128, 16), (128, 128) and (8, 128) over two 512-row tiles
    n_pad, b, gip, fp, glp, gp = 1024, 1, 128, 16, 128, 8
    assert eqn.params["cost_estimate"].flops == \
        2 * n_pad * b * (gip * fp + glp * gip + gp * glp)
