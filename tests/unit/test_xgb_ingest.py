"""xgboost model ingestion parity (SURVEY §2.5 forest-loading hard part 3).

xgboost itself is not installed in this image, so parity is locked against
a reference traversal implementing xgboost's documented semantics —
``x < split_condition`` goes left, NaN takes the ``default_left`` branch,
margin = sum(leaf values) + logit(base_score) — over a hand-built model in
the ≥1.6 JSON format (the format ``Booster.save_model("m.json")`` emits,
ref setup/environment.yml xgboost 2.1.2).
"""

import json
import math
import pickle

import numpy as np
import pytest

from variantcalling_tpu.models import registry
from variantcalling_tpu.models.forest import predict_score, predict_score_gemm, to_gemm
from variantcalling_tpu.models.xgb import from_xgboost_json


def _xgb_tree(left, right, cond, sidx, default_left):
    n = len(left)
    return {
        "base_weights": [0.0] * n,
        "categories": [], "categories_nodes": [], "categories_segments": [],
        "categories_sizes": [],
        "default_left": [int(b) for b in default_left],
        "id": 0,
        "left_children": list(left),
        "loss_changes": [0.0] * n,
        "parents": [2147483647] * n,
        "right_children": list(right),
        "split_conditions": list(cond),
        "split_indices": list(sidx),
        "split_type": [0] * n,
        "sum_hessian": [1.0] * n,
        "tree_param": {"num_deleted": "0", "num_feature": "3",
                       "num_nodes": str(n), "size_leaf_vector": "1"},
    }


def _model_json(trees, base_score=0.5, feature_names=None):
    return {
        "learner": {
            "attributes": {},
            "feature_names": feature_names or [],
            "feature_types": [],
            "gradient_booster": {
                "model": {
                    "gbtree_model_param": {"num_parallel_tree": "1",
                                           "num_trees": str(len(trees))},
                    "iteration_indptr": list(range(len(trees) + 1)),
                    "tree_info": [0] * len(trees),
                    "trees": trees,
                },
                "name": "gbtree",
            },
            "learner_model_param": {"base_score": str(base_score),
                                    "boost_from_average": "1",
                                    "num_class": "0", "num_feature": "3",
                                    "num_target": "1"},
            "objective": {"name": "binary:logistic",
                          "reg_loss_param": {"scale_pos_weight": "1"}},
        },
        "version": [2, 1, 2],
    }


def _two_tree_model():
    # tree 0:       node0: f0 < 0.5 (default LEFT)
    #              /                \
    #        node1: f1 < -1.25     node2: leaf +0.6
    #        (default RIGHT)
    #        /          \
    #   leaf -0.4    leaf +0.2
    t0 = _xgb_tree(left=[1, 3, -1, -1, -1], right=[2, 4, -1, -1, -1],
                   cond=[0.5, -1.25, 0.6, -0.4, 0.2], sidx=[0, 1, 0, 0, 0],
                   default_left=[1, 0, 0, 0, 0])
    # tree 1: node0: f2 < 2.0 (default RIGHT); leaves -0.3 / +0.5
    t1 = _xgb_tree(left=[1, -1, -1], right=[2, -1, -1],
                   cond=[2.0, -0.3, 0.5], sidx=[2, 0, 0],
                   default_left=[0, 0, 0])
    return _model_json([t0, t1], base_score=0.3, feature_names=["f0", "f1", "f2"])


def _ref_predict(model_json, x):
    """Independent per-record traversal with xgboost's own rules."""
    learner = model_json["learner"]
    base = float(learner["learner_model_param"]["base_score"])
    margin0 = math.log(base / (1 - base))
    out = np.zeros(len(x))
    for i, row in enumerate(x):
        margin = margin0
        for tree in learner["gradient_booster"]["model"]["trees"]:
            node = 0
            while tree["left_children"][node] != -1:
                v = row[tree["split_indices"][node]]
                if np.isnan(v):
                    go_left = bool(tree["default_left"][node])
                else:
                    go_left = bool(np.float32(v) < np.float32(tree["split_conditions"][node]))
                node = tree["left_children"][node] if go_left else tree["right_children"][node]
            margin += tree["split_conditions"][node]
        out[i] = 1.0 / (1.0 + math.exp(-margin))
    return out


@pytest.fixture(scope="module")
def model():
    return _two_tree_model()


def _probe_matrix(rng):
    x = rng.normal(0, 1.5, size=(500, 3)).astype(np.float32)
    # exact-threshold hits: x == cond must route RIGHT (strict <)
    x[0] = [0.5, -1.25, 2.0]
    x[1] = [np.nextafter(np.float32(0.5), np.float32(-np.inf)), 0.0, 0.0]
    # NaN rows exercise default_left (left at tree0-node0, right elsewhere)
    x[2] = [np.nan, np.nan, np.nan]
    x[3, 1] = np.nan
    x[4, 2] = np.nan
    return x


def test_json_ingest_matches_reference_traversal(model, rng):
    forest = from_xgboost_json(model)
    assert forest.aggregation == "logit_sum"
    assert forest.feature_names == ["f0", "f1", "f2"]
    assert forest.default_left is not None and forest.default_left[0, 0]
    x = _probe_matrix(rng)
    expect = _ref_predict(model, x)
    got = np.asarray(predict_score(forest, x))
    np.testing.assert_allclose(got, expect, atol=1e-6)


def test_gemm_predictor_handles_missing(model, rng):
    forest = from_xgboost_json(model)
    x = _probe_matrix(rng)
    expect = _ref_predict(model, x)
    got = np.asarray(predict_score_gemm(to_gemm(forest, 3), x))
    np.testing.assert_allclose(got, expect, atol=1e-6)


def test_registry_loads_bare_json_and_pickled_dict(model, tmp_path, rng):
    jpath = tmp_path / "model.json"
    jpath.write_text(json.dumps(model))
    m1 = registry.load_model(str(jpath), "model")
    ppath = tmp_path / "model.pkl"
    with open(ppath, "wb") as fh:
        pickle.dump(model, fh)  # the parsed JSON dict pickled whole
    m2 = registry.load_model(str(ppath), "model")
    x = _probe_matrix(rng)
    expect = _ref_predict(model, x)
    for m in (m1, m2):
        np.testing.assert_allclose(np.asarray(predict_score(m, x)), expect, atol=1e-6)


def test_recycled_node_ids_keep_full_depth(rng):
    """Pruned xgboost trees recycle deleted node ids, so a child can have a
    SMALLER id than its parent. Depth derivation must not assume id order
    is topological — an underestimated max_depth truncates the fixed-round
    walk at an internal node (score silently 0.0 there)."""
    # node 1 (internal) is a child of node 3, which is a child of node 0:
    # ids 1 and 2 precede their ancestors, as after pruning + id reuse.
    #        0: f0<0.5 ── right ──> 4: leaf -0.1
    #        └ left ──> 3: f1<0.5 ── right ──> 5: leaf +0.3
    #                   └ left ──> 1: f2<0.5 ─ left/right ─> 2: +0.7 / 6: -0.9
    t = _xgb_tree(left=[3, 2, -1, 1, -1, -1, -1],
                  right=[4, 6, -1, 5, -1, -1, -1],
                  cond=[0.5, 0.5, 0.7, 0.5, -0.1, 0.3, -0.9],
                  sidx=[0, 2, 0, 1, 0, 0, 0],
                  default_left=[0] * 7)
    mj = _model_json([t], base_score=0.5)
    forest = from_xgboost_json(mj)
    assert forest.max_depth >= 4  # 3 edges root->leaf
    x = rng.normal(0, 1.5, size=(64, 3)).astype(np.float32)
    x[0] = [0.0, 0.0, 0.0]  # routes to the depth-3 leaf (+0.7)
    expect = _ref_predict(mj, x)
    np.testing.assert_allclose(np.asarray(predict_score(forest, x)), expect, atol=1e-6)
    np.testing.assert_allclose(np.asarray(predict_score_gemm(to_gemm(forest, 3), x)),
                               expect, atol=1e-6)


def test_cyclic_child_pointers_raise():
    """Corrupt child arrays (a node pointing back at itself/an ancestor)
    must raise, not hang — the BFS is bounded and deduplicated."""
    t = _xgb_tree(left=[1, 0, -1], right=[2, 2, -1],  # node 1 points back at 0
                  cond=[0.5, 0.5, 0.1], sidx=[0, 1, 0], default_left=[0, 0, 0])
    with pytest.raises(ValueError, match="cyclic"):
        from_xgboost_json(_model_json([t]))


def test_unsupported_models_raise(model):
    import copy

    dart = copy.deepcopy(model)
    dart["learner"]["gradient_booster"]["name"] = "dart"
    with pytest.raises(ValueError, match="dart"):
        from_xgboost_json(dart)
    multi = copy.deepcopy(model)
    multi["learner"]["learner_model_param"]["num_class"] = "3"
    with pytest.raises(ValueError, match="binary"):
        from_xgboost_json(multi)
    rank = copy.deepcopy(model)
    rank["learner"]["objective"]["name"] = "rank:ndcg"
    with pytest.raises(ValueError, match="logistic"):
        from_xgboost_json(rank)


def test_fused_pipeline_scores_xgboost_model(tmp_path):
    """An ingested xgboost model runs through the fused featurize+score
    program end to end (the path the reference's production pickles take)."""
    from variantcalling_tpu.featurize import BASE_FEATURES, host_featurize
    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.io.vcf import read_vcf
    from variantcalling_tpu.pipelines.filter_variants import fused_featurize_score
    from variantcalling_tpu.synthetic import make_fixtures

    d = str(tmp_path)
    make_fixtures(d, n=1200, genome_len=50_000)
    table = read_vcf(f"{d}/calls.vcf")
    fasta = FastaReader(f"{d}/ref.fa")
    # a model over real pipeline features: qual / gc_content / dp
    t0 = _xgb_tree(left=[1, -1, -1], right=[2, -1, -1],
                   cond=[50.0, -0.7, 0.9], sidx=[0, 0, 0], default_left=[1, 0, 0])
    t1 = _xgb_tree(left=[1, -1, -1], right=[2, -1, -1],
                   cond=[0.45, 0.3, -0.2], sidx=[1, 0, 0], default_left=[0, 0, 0])
    mj = _model_json([t0, t1], base_score=0.5,
                     feature_names=["qual", "gc_content", "dp"])
    forest = from_xgboost_json(mj)

    hf = host_featurize(table, fasta)
    score = fused_featurize_score(forest, hf, "TGCA")
    from variantcalling_tpu.featurize import materialize_features

    fs = materialize_features(hf, flow_order="TGCA")
    cols = np.stack([fs.columns[f].astype(np.float32) for f in ["qual", "gc_content", "dp"]], axis=1)
    expect = _ref_predict(mj, cols)
    np.testing.assert_allclose(score, expect, atol=1e-6)


def test_filter_variants_preserves_nan_for_default_left_models(tmp_path):
    """Records missing SOR/GQ must route through the model's default_left
    branch, not through a zero-filled feature (the reference feeds raw NaN
    into xgboost predict_proba)."""
    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.io.vcf import read_vcf
    from variantcalling_tpu.pipelines.filter_variants import filter_variants

    genome = "ACGTACGTGGCCAATTACGGATCCAGTCAATCGGATTACA" * 50
    (tmp_path / "ref.fa").write_text(">chr1\n" + "\n".join(
        genome[i:i + 60] for i in range(0, len(genome), 60)) + "\n")
    # half the records have no SOR and no GQ
    recs = []
    for i in range(40):
        pos = 100 + i * 40
        ref = genome[pos - 1]
        alt = "ACGT"[("ACGT".index(ref) + 1) % 4]
        info = "DP=30" if i % 2 else "DP=30;SOR=1.5"
        fmt = "GT:GQ\t0/1:50" if i % 2 == 0 else "GT\t0/1"
        recs.append(f"chr1\t{pos}\t.\t{ref}\t{alt}\t60\t.\t{info}\tGT" +
                    (":GQ\t0/1:50" if i % 2 == 0 else "\t0/1"))
    vcf = tmp_path / "in.vcf"
    vcf.write_text(
        "##fileformat=VCFv4.2\n"
        f"##contig=<ID=chr1,length={len(genome)}>\n"
        '##INFO=<ID=DP,Number=1,Type=Integer,Description="d">\n'
        '##INFO=<ID=SOR,Number=1,Type=Float,Description="s">\n'
        '##FORMAT=<ID=GT,Number=1,Type=String,Description="g">\n'
        '##FORMAT=<ID=GQ,Number=1,Type=Integer,Description="q">\n'
        "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\tS\n"
        + "\n".join(recs) + "\n")

    # split on sor with default LEFT: missing-SOR records must take the
    # left (leaf -2.0 -> low score) branch even though 0.0 < 9.9 would too;
    # distinguish via a second split where zero-fill and NaN diverge:
    # sor < -1.0 is FALSE for 0.0 (goes right, +2.0) but default_left=1
    # routes missing LEFT (-2.0)
    t0 = _xgb_tree(left=[1, -1, -1], right=[2, -1, -1],
                   cond=[-1.0, -2.0, 2.0], sidx=[0, 0, 0], default_left=[1, 0, 0])
    mj = _model_json([t0], base_score=0.5, feature_names=["sor"])
    forest = from_xgboost_json(mj)

    table = read_vcf(str(vcf))
    fasta = FastaReader(str(tmp_path / "ref.fa"))
    score, _filters = filter_variants(table, forest, fasta)

    import math
    lo = 1 / (1 + math.exp(2.0))   # missing SOR -> default left leaf -2.0
    hi = 1 / (1 + math.exp(-2.0))  # present SOR=1.5 -> right leaf +2.0
    has_sor = np.array(["SOR" in str(i) for i in (table.info if hasattr(table, "info") else [])])
    # derive presence from the table's own SOR column
    sor = table.info_field("SOR")
    present = ~np.isnan(sor)
    np.testing.assert_allclose(score[present], hi, atol=1e-6)
    np.testing.assert_allclose(score[~present], lo, atol=1e-6)
    assert present.any() and (~present).any()
