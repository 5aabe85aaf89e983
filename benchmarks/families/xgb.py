"""The xgboost family: a seeded xgboost (>= 1.6) JSON model document, as
``Booster.save_model("*.json")`` writes it, its plain reference scorer
(xgboost's documented prediction), the work the algorithm needs, and the one
function that hands the document to the program, through the ingestion a
user's model takes. Everything but ``to_program`` is numpy only."""

from __future__ import annotations

import math

import numpy as np

import fixtures
import lookup
from reference import QUANTIZE

#: what a trained booster never splits on: ``n_alts`` is 1 on every record
CONSTANT_COLUMNS = ("n_alts",)
#: the size of a leaf a learning rate of 0.3 gives (xgboost's default ``eta``)
ETA = 0.3


def _tree(rng, depth: int, columns: list[int]) -> dict:
    """One complete tree of ``depth`` levels of splits in xgboost's arrays:
    node ``k``'s children are ``2k + 1`` and ``2k + 2``, the last level
    leaves, whose ``split_conditions`` hold the leaf value (eta included)."""
    n_internal, n_nodes = 2 ** depth - 1, 2 ** (depth + 1) - 1
    node = np.arange(n_nodes)
    internal = node < n_internal
    feature = np.asarray(columns)[rng.integers(0, len(columns), size=n_nodes)]
    ranges = [fixtures.FEATURE_RANGE[fixtures.RUN_FEATURES[c]] for c in feature]
    lo = np.array([r[0] for r in ranges], np.float64)
    hi = np.array([r[1] for r in ranges], np.float64)
    integral = np.array([r[2] for r in ranges])
    cont = lo + rng.random(n_nodes) * (hi - lo)
    threshold = np.where(integral, np.floor(cont) + 0.5, cont).astype(np.float32)
    leaf = (ETA * rng.normal(0.0, 0.5, size=n_nodes)).astype(np.float32)
    default_left = rng.random(n_nodes) < 0.5
    parents = np.where(node == 0, 2 ** 31 - 1, (node - 1) // 2)
    return {
        "left_children": np.where(internal, 2 * node + 1, -1).tolist(),
        "right_children": np.where(internal, 2 * node + 2, -1).tolist(),
        "parents": parents.tolist(),
        "split_indices": np.where(internal, feature, 0).tolist(),
        "split_conditions": np.where(internal, threshold, leaf).astype(np.float32).tolist(),
        "split_type": [0] * n_nodes,
        "default_left": (internal & default_left).astype(int).tolist(),
        "base_weights": np.where(internal, 0.0, leaf).astype(np.float32).tolist(),
        "loss_changes": np.where(internal, 1.0, 0.0).tolist(),
        "sum_hessian": [1.0] * n_nodes,
        "categories": [], "categories_nodes": [], "categories_segments": [],
        "categories_sizes": [],
        "tree_param": {"num_deleted": "0", "num_feature": str(len(fixtures.RUN_FEATURES)),
                       "num_nodes": str(n_nodes), "size_leaf_vector": "1"},
    }


def arrays(weights_seed: int, config: dict) -> dict:
    """The model document (``document``) and the columns the benchmark's
    callsets never carry (``missing_columns``). Trees are complete to
    ``max_depth``; splits are drawn over the columns that vary, thresholds
    inside each column's range (integral columns between two values), a
    ``default_left`` bit per split, leaf values of the size eta gives, and a
    ``base_score`` that is not 0.5, so that its logit is exercised."""
    rng = np.random.default_rng(weights_seed)
    names = list(fixtures.RUN_FEATURES)
    columns = [i for i, f in enumerate(names) if f not in CONSTANT_COLUMNS]
    trees = []
    for t in range(config["n_trees"]):
        tree = _tree(rng, config["max_depth"], columns)
        tree["id"] = t
        trees.append(tree)
    base_score = float(rng.uniform(0.55, 0.75))
    document = {
        "version": [2, 1, 2],
        "learner": {
            "attributes": {},
            "feature_names": names,
            "feature_types": ["float"] * len(names),
            "gradient_booster": {
                "name": "gbtree",
                "model": {
                    "gbtree_model_param": {"num_parallel_tree": "1",
                                           "num_trees": str(len(trees))},
                    "iteration_indptr": list(range(len(trees) + 1)),
                    "tree_info": [0] * len(trees),
                    "trees": trees,
                },
            },
            "learner_model_param": {"base_score": f"{base_score:E}", "boost_from_average": "1",
                                    "num_class": "0", "num_feature": str(len(names)),
                                    "num_target": "1"},
            "objective": {"name": "binary:logistic",
                          "reg_loss_param": {"scale_pos_weight": "1"}},
        },
    }
    return {"document": document, "missing_columns": list(config["missing_columns"])}


def to_program(config: dict, w: dict):
    """The program's model object: the document through the program's own
    xgboost ingestion (the only import of the program in this file)."""
    from variantcalling_tpu.models.xgb import from_xgboost_json

    return from_xgboost_json(w["document"])


def score(w: dict, x: np.ndarray, precision: str = "f32") -> np.ndarray:
    """xgboost's prediction for ``binary:logistic``: in every tree a NaN
    takes the node's default child, any other value goes left where it is
    ``<`` the split condition; the leaves are summed tree by tree in
    float32, the base margin (the logit of ``base_score``) added, and the
    sigmoid taken. The configuration's ``missing_columns`` are NaN first:
    the callsets never carry them, and the program keeps NaN for an absent
    value where the plain parse (``reference.parse_records``) writes 0."""
    q = QUANTIZE[precision]
    learner = w["document"]["learner"]
    x = np.array(x, np.float32)
    for name in w["missing_columns"]:
        x[:, fixtures.RUN_FEATURES.index(name)] = np.nan
    xq, rows = q(x), np.arange(len(x))
    total = np.zeros(len(x), np.float32)
    for tree in learner["gradient_booster"]["model"]["trees"]:
        left = np.asarray(tree["left_children"])
        right = np.asarray(tree["right_children"])
        column = np.asarray(tree["split_indices"])
        cond = q(np.asarray(tree["split_conditions"], np.float32))
        default_left = np.asarray(tree["default_left"]).astype(bool)
        node = np.zeros(len(x), np.int64)
        while (left[node] >= 0).any():
            v = xq[rows, column[node]]
            go_left = np.where(np.isnan(v), default_left[node], v < cond[node])
            nxt = np.where(go_left, left[node], right[node])
            node = np.where(left[node] >= 0, nxt, node)
        total = q(total + cond[node])
    p = float(learner["learner_model_param"]["base_score"])
    margin = q(total + np.float32(math.log(p / (1.0 - p))))
    return q(np.float32(1.0) / (np.float32(1.0) + np.exp(-margin)))


# -- required work: the forest family's counts, with I = 2**depth - 1 splits
# and L = 2**depth leaves a tree --------------------------------------------

_FOREST = lookup.load("families", "forest")
flops_per_variant = _FOREST.flops_per_variant
bytes_per_variant = _FOREST.bytes_per_variant
table_bytes = _FOREST.table_bytes
