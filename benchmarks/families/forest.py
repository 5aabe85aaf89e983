"""The forest family: its seeded weights, its plain reference scorer, the
work the algorithm needs, and the one function that hands the weights to the
program. Everything but ``to_program`` is numpy only."""

from __future__ import annotations

import numpy as np

import fixtures
from reference import QUANTIZE


def arrays(weights_seed: int, config: dict) -> dict:
    """Complete binary trees in the program's flat layout (``2**depth`` node
    slots a tree, the bottom level and the last slot leaves). A node's
    threshold is drawn inside the range of the column it tests, so every
    split divides the callset; integral columns split between two values."""
    n_trees, depth, feature_names = config["n_trees"], config["depth"], config["features"]
    rng = np.random.default_rng(weights_seed)
    m = 2 ** depth
    node = np.arange(m)
    is_leaf = node >= (m // 2 - 1)
    feature = rng.integers(0, len(feature_names), size=(n_trees, m)).astype(np.int32)
    ranges = [fixtures.FEATURE_RANGE[f] for f in feature_names]
    lo = np.array([r[0] for r in ranges], np.float64)[feature]
    hi = np.array([r[1] for r in ranges], np.float64)[feature]
    integral = np.array([r[2] for r in ranges])[feature]
    cont = lo + rng.random((n_trees, m)) * (hi - lo)
    threshold = np.where(integral, np.floor(cont) + 0.5, cont).astype(np.float32)
    feature[:, is_leaf] = -1
    kids = lambda a: np.broadcast_to(  # noqa: E731
        np.where(is_leaf, node, np.minimum(a, m - 1)), (n_trees, m)).astype(np.int32)
    return {
        "feature": feature, "threshold": threshold,
        "left": kids(2 * node + 1), "right": kids(2 * node + 2),
        "value": rng.uniform(0, 1, size=(n_trees, m)).astype(np.float32),
        "max_depth": depth, "feature_names": list(feature_names),
    }


def to_program(config: dict, w: dict):
    """The program's model object over the benchmark's arrays (the only
    import of the program in this file)."""
    from variantcalling_tpu.models.forest import FlatForest

    return FlatForest(aggregation=config["aggregation"], **w)


def score(fa: dict, x: np.ndarray, precision: str = "f32") -> np.ndarray:
    """Mean over the trees of the leaf each row reaches (left when the column
    is <= the threshold), summed tree by tree in float32."""
    q = QUANTIZE[precision]
    col = np.array([fixtures.RUN_FEATURES.index(f) for f in fa["feature_names"]])
    xq, thr, val = q(x), q(fa["threshold"]), q(fa["value"])
    total = np.zeros(len(x), np.float32)
    for t in range(fa["feature"].shape[0]):
        node = np.zeros(len(x), np.int64)
        for _ in range(fa["max_depth"]):
            f = fa["feature"][t, node]
            go_left = xq[np.arange(len(x)), col[np.maximum(f, 0)]] <= thr[t, node]
            nxt = np.where(go_left, fa["left"][t, node], fa["right"][t, node])
            node = np.where(f < 0, node, nxt)
        total = q(total + val[t, node])
    return q(total / np.float32(fa["feature"].shape[0]))


# -- required work, from the configuration's shapes alone ---------------------

def flops_per_variant(config: dict) -> float:
    """Per tree: every internal node's test as one row of an (F x I)
    selection, the (I x L) path match and the L-leaf value pick; the
    block-diagonal padding one kernel executes is not required work."""
    f, i, l = config["n_features"], config["n_internal"], config["n_leaves"]
    return 2.0 * config["n_trees"] * (f * i + i * l + l)


def bytes_per_variant(config: dict) -> float:
    """Feature row in, score out, float32."""
    return 4.0 * config["n_features"] + 4.0


def table_bytes(config: dict) -> float:
    """The model's tables, read once per call."""
    return 4.0 * config["n_trees"] * (2 * config["n_internal"] + config["n_leaves"])
