"""The scikit-learn random forest family: a ``RandomForestClassifier`` fitted
at the configuration's hyperparameters (the library's documented defaults) on
seeded rows of the benchmark's own callsets, its plain reference scorer (the
fitted trees walked in numpy, as ``predict_proba`` does), the work the
algorithm needs, and the one function that hands the estimator to the program.
Everything but ``to_program`` and ``require_table_argument`` is numpy and
scikit-learn only.

The fit is the set-up's longest step (about 90 s for 100 trees on a million
rows on eight cores), so the fitted estimator is kept under
``benchmarks/.work/`` by its seed, its rows and its hyperparameters, as the
references are, with a small summary of its trees beside it that the work
counts read."""

from __future__ import annotations

import json
import os
import pickle

import numpy as np

import fixtures
import reference
from reference import QUANTIZE

WORK = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".work")
#: bytes the walk reads a visited node: its five 4-byte fields (feature,
#: threshold, the child taken counted as both children's 8 bytes, value)
#: and the row's feature value
NODE_BYTES = 20 + 4


def _logit(x: np.ndarray) -> np.ndarray:
    """The label rule's logit over a few columns (``RUN_FEATURES`` order):
    higher QUAL and GQ make a true call likelier, a strand-biased SOR, a long
    homopolymer indel and a cycle-skipping SNP less. The classes overlap, as
    real TP/FP labels do, so unpruned trees grow deep."""
    col = {f: x[:, i].astype(np.float64) for i, f in enumerate(fixtures.RUN_FEATURES)}
    return (0.05 * (col["qual"] - 50.0) - 0.6 * (col["sor"] - 2.0)
            + 0.02 * (col["gq"] - 52.0) - 0.4 * col["hmer_indel_length"]
            + 0.5 * (col["cycleskip_status"] == 2))


def training_set(weights_seed: int, config: dict) -> tuple[np.ndarray, np.ndarray]:
    """``train_rows`` rows of the run's 19 columns, computed by the plain
    reference from a callset drawn with ``weights_seed`` on the
    configuration's first reference, and labels drawn from the rule's
    logistic with the same seed."""
    ref = config["references"][0]
    n, seed = config["train_rows"], int(weights_seed)
    os.makedirs(WORK, exist_ok=True)
    path = os.path.join(WORK, f"skrf_train_{seed}_{n}.vcf.tmp.{os.getpid()}")
    try:
        fixtures.write_callset(path, ref["reference_seed"], ref["genome_len"],
                               ref["n_contigs"], n, seed)
        with open(path, "rb") as fh:
            lines = [ln for ln in fh.read().split(b"\n") if ln and not ln.startswith(b"#")]
    finally:
        if os.path.exists(path):
            os.remove(path)
    body = fixtures.contig_body(ref["reference_seed"], ref["genome_len"] // ref["n_contigs"])
    x = reference.feature_matrix(reference.parse_records(lines), body, ref["n_contigs"])
    p = 1.0 / (1.0 + np.exp(-_logit(x)))
    y = (np.random.default_rng([seed, 2]).random(n) < p).astype(np.int64)
    return x, y


def _trees(clf) -> list[dict]:
    """Each fitted tree's arrays as the reference walks them: children,
    split column, float64 threshold, and each node's class-1 fraction
    (``predict_proba``'s normalisation of ``tree_.value``)."""
    out = []
    for est in clf.estimators_:
        tr = est.tree_
        v = tr.value[:, 0, :].astype(np.float64)
        total = v.sum(axis=1)
        p1 = v[:, 1] / np.where(total == 0.0, 1.0, total) if v.shape[1] == 2 \
            else np.full(tr.node_count, float(clf.classes_[0] == 1))
        out.append({"left": tr.children_left.astype(np.int64),
                    "right": tr.children_right.astype(np.int64),
                    "feature": tr.feature.astype(np.int64),
                    "threshold": tr.threshold.astype(np.float64),
                    "p1": p1})
    return out


def _summary(clf) -> dict:
    """What the work counts need: per tree its nodes, its depth, and the
    expected nodes on a training row's path (root and leaf included), from
    ``tree_.n_node_samples``: a row visits every node whose samples hold it."""
    trees = [e.tree_ for e in clf.estimators_]
    return {"n_trees": len(trees),
            "nodes": [int(t.node_count) for t in trees],
            "max_depth": [int(t.max_depth) for t in trees],
            "path_nodes": [float(t.n_node_samples.sum() / t.n_node_samples[0]) for t in trees]}


def _cache_stem(weights_seed: int, config: dict) -> str:
    import sklearn

    hp = "_".join(f"{k}-{v}" for k, v in sorted(config["hyperparameters"].items()))
    return os.path.join(WORK, f"skrf_sk{sklearn.__version__}_w{int(weights_seed)}_"
                              f"n{config['train_rows']}_{hp}")


def require_table_argument() -> None:
    """Exit before the fit where the program would compile the gather
    walk's node table into its programs as a constant: for this forest
    (820 MB of table) such a program ran the chip's 40 GiB host out of
    memory in its first warm-up file, where this check fails in seconds."""
    import jax

    from variantcalling_tpu.models import forest

    tiny = forest.FlatForest(
        feature=np.array([[0, forest.LEAF, forest.LEAF]], np.int32),
        threshold=np.zeros((1, 3), np.float32), left=np.array([[1, 1, 2]], np.int32),
        right=np.array([[2, 1, 2]], np.int32), value=np.array([[0.0, 0.0, 1.0]], np.float32),
        max_depth=1)
    build = getattr(forest, "make_margin_program", None)
    if build is None or not jax.tree.leaves(build(tiny, 1, strategy="gather").weights):
        raise SystemExit("skrf: this program compiles the gather walk's node table into its "
                         "programs as a constant; the configuration's 820 MB table needs it "
                         "as a program argument (forest.make_margin_program)")


def arrays(weights_seed: int, config: dict) -> dict:
    """The fitted estimator (``estimator``), its trees as the reference
    walks them (``trees``) and their summary (``summary``). The forest is
    fitted on a pandas frame, so ``feature_names_in_`` rides along into the
    pickle the program loads, with ``random_state=weights_seed`` and every
    core; a fit made before under the same key is loaded instead. First
    :func:`require_table_argument`."""
    require_table_argument()
    stem = _cache_stem(weights_seed, config)
    if os.path.exists(stem + ".pkl"):
        with open(stem + ".pkl", "rb") as fh:
            clf = pickle.load(fh)
    else:
        import pandas as pd
        from sklearn.ensemble import RandomForestClassifier

        x, y = training_set(weights_seed, config)
        clf = RandomForestClassifier(**config["hyperparameters"],
                                     random_state=int(weights_seed), n_jobs=-1)
        clf.fit(pd.DataFrame(x, columns=fixtures.RUN_FEATURES), y)
        tmp = f"{stem}.pkl.tmp.{os.getpid()}"
        with open(tmp, "wb") as fh:
            pickle.dump(clf, fh, protocol=pickle.HIGHEST_PROTOCOL)
        os.replace(tmp, stem + ".pkl")
    summary = _summary(clf)
    with open(stem + ".json", "w", encoding="utf-8") as fh:
        json.dump(summary, fh)
    return {"estimator": clf, "trees": _trees(clf), "summary": summary}


def to_program(config: dict, w: dict):
    """The program's model is the fitted estimator itself: the run pickles
    it, and the program's loader converts it (``registry._coerce`` →
    ``forest.from_sklearn``), as it does a user's model file."""
    return w["estimator"]


def score(w: dict, x: np.ndarray, precision: str = "f32") -> np.ndarray:
    """``predict_proba``'s class-1 column: in every tree a row goes left
    where its value, cast float32 → float64, is ``<=`` the float64
    threshold, and takes its leaf's class-1 fraction; the mean over the
    trees. One departure: the leaf fractions are summed tree by tree in
    float32 and the sum divided in float32 (scikit-learn sums in float64),
    because float32 is the configuration's stated precision; the two differ
    by under 1e-6 at these sizes. Below ``f32`` the features, thresholds,
    fractions and running sum are rounded to that precision."""
    q = QUANTIZE[precision]
    xq = q(x).astype(np.float64)
    total = np.zeros(len(x), np.float32)
    for tree in w["trees"]:
        left, right, feature = tree["left"], tree["right"], tree["feature"]
        thr = tree["threshold"] if precision == "f32" else q(tree["threshold"]).astype(np.float64)
        node = np.zeros(len(x), np.int64)
        rows = np.arange(len(x)) if left[0] >= 0 else np.arange(0)
        while rows.size:
            at = node[rows]
            go_left = xq[rows, feature[at]] <= thr[at]
            node[rows] = np.where(go_left, left[at], right[at])
            rows = rows[left[node[rows]] >= 0]
        total = q(total + q(tree["p1"][node].astype(np.float32)))
    return q(total / np.float32(len(w["trees"])))


# -- required work, from the fitted trees' summary -----------------------------

def _summary_of(config: dict) -> dict:
    """The summary the fit wrote beside the cached estimator (fitting first
    where there is none)."""
    path = _cache_stem(config["weights_seed"], config) + ".json"
    if not os.path.exists(path):
        arrays(config["weights_seed"], config)
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def flops_per_variant(config: dict) -> float:
    """One comparison at every split on a row's path, one add a tree for
    the leaf sum, one division for the mean."""
    s = _summary_of(config)
    return sum(p - 1.0 for p in s["path_nodes"]) + s["n_trees"] + 1.0


def bytes_per_variant(config: dict) -> float:
    """Every node on a row's path, in every tree, read once (its packed row
    and the feature value it tests), the row's 19 float32 features in and
    its score out."""
    s = _summary_of(config)
    return NODE_BYTES * sum(s["path_nodes"]) + 4.0 * len(fixtures.RUN_FEATURES) + 4.0


def table_bytes(config: dict) -> float:
    """No bytes a call beyond the paths: the walk reads only the nodes a
    row visits, each visit counted in ``bytes_per_variant``; the distinct
    nodes a dispatch touches are a subset of those visits."""
    return 0.0


def walk_loop_operand(n_trees: int, max_nodes: int) -> str:
    """The HLO type of the node table's feature column, which only the
    walk's tree loop carries whole (each tree's step slices it). It is the
    loop tuple's first array, so it survives the device trace's cut of an
    operation's name to its first 120 characters."""
    return f"s32[{n_trees * max_nodes}]"


def loop_operand(config: dict) -> str:
    """What ``readers/loop_roofline`` finds the walk's tree loop by, for the
    fitted forest: the program pads every tree to the largest tree's nodes."""
    s = _summary_of(config)
    return walk_loop_operand(s["n_trees"], max(s["nodes"]))
