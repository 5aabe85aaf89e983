"""The scikit-learn random forest as the benchmark's ``skrf-t100-full-hg38x2``
configuration states it, on the CPU at a small size: the family's fit
(``benchmarks/families/skrf.py``, 8 trees at the library's defaults on 20,000
rows of the benchmark's own callsets) pickled as users hand it over, through
the program's loader and the CLI entry under the jit engine, whose ``auto``
takes the gather walk there as it does on a TPU for these unpruned trees.

- the family's plain scorer is scikit-learn's ``predict_proba``;
- every record's TREE_SCORE, FILTER and core fields agree with it, and the
  same comparison refuses the scorer in bfloat16;
- the gather walk's node table is an ARGUMENT of the compiled programs (no
  constant over 1 MiB in them) and two bucket sizes share its one device
  copy;
- the counters and the span the cell's metrics read, read by those readers;
- the configuration is the forest cell's but for the model, and the cell's
  metrics come last and list only that cell.
"""

from __future__ import annotations

import json
import os
import re

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
CONFIG = "skrf-t100-full-hg38x2"
CELL = CONFIG + ".wgs-batch"
GENOME_LEN, N_CONTIGS, N_VARIANTS, SEED, REF_SEED = 240_000, 4, 3000, 17, 1001
#: the small fit: the configuration's hyperparameters but for the tree count
N_TREES, TRAIN_ROWS = 8, 20_000
#: the output prints TREE_SCORE to 4 decimals; program and reference make the
#: same float32 decisions and the same float32 leaf sum, so they may part only
#: where that print rounds a tie: one unit of the fourth decimal
SCORE_TOL = 1e-4 + 1e-6
ONE_MIB = 1 << 20


def _config() -> dict:
    with open(os.path.join(BENCH, "configs", CONFIG + ".json"), encoding="utf-8") as fh:
        return json.load(fh)


def _small(config: dict) -> dict:
    return dict(config, train_rows=TRAIN_ROWS,
                hyperparameters=dict(config["hyperparameters"], n_estimators=N_TREES),
                references=[{"reference_seed": REF_SEED, "genome_len": GENOME_LEN,
                             "n_contigs": N_CONTIGS}])


@pytest.fixture(autouse=True)
def _engine_cache_isolated():
    yield
    from variantcalling_tpu import engine as engine_mod

    engine_mod.reset_for_tests()


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    mp.syspath_prepend(BENCH)
    import fixtures
    import lookup
    import run_cell

    from variantcalling_tpu.models import registry

    d = str(tmp_path_factory.mktemp("skrf_cell"))
    family = lookup.load("families", "skrf")
    mp.setattr(family, "WORK", os.path.join(d, "work"))
    config = _small(_config())
    weights = family.arrays(config["weights_seed"], config)
    ref, calls = os.path.join(d, "ref.fa"), os.path.join(d, "calls.vcf")
    fixtures.write_reference(ref, REF_SEED, GENOME_LEN, N_CONTIGS)
    fixtures.write_callset(calls, REF_SEED, GENOME_LEN, N_CONTIGS, N_VARIANTS, SEED)
    with open(calls, "rb") as fh:
        lines = run_cell.body_lines(fh.read())
    models = os.path.join(d, "models.pkl")
    registry.save_models(models, {config["model_name"]: family.to_program(config, weights)})
    yield {"dir": d, "family": family, "config": config, "weights": weights, "ref": ref,
           "calls": calls, "lines": lines, "models": models,
           "body": fixtures.contig_body(REF_SEED, GENOME_LEN // N_CONTIGS)}
    mp.undo()


def _features(world) -> np.ndarray:
    import reference

    return reference.feature_matrix(reference.parse_records(world["lines"]),
                                    world["body"], N_CONTIGS)


def _filter(world, monkeypatch, out: str) -> int:
    from variantcalling_tpu.pipelines.filter_variants import run as cli

    monkeypatch.setenv("VCTPU_ENGINE", "jit")
    monkeypatch.setenv("VCTPU_OBS", "1")
    return cli(["--input_file", world["calls"], "--model_file", world["models"],
                "--model_name", world["config"]["model_name"],
                "--reference_file", world["ref"], "--output_file", out])


def test_the_fit_is_unpruned_at_the_defaults_and_named_by_its_columns(world):
    import fixtures

    from variantcalling_tpu.models import forest as fmod
    from variantcalling_tpu.models.registry import load_model

    clf = world["weights"]["estimator"]
    assert clf.n_estimators == N_TREES and clf.max_depth is None
    assert clf.min_samples_leaf == 1 and clf.max_features == "sqrt" and clf.bootstrap
    assert list(clf.feature_names_in_) == fixtures.RUN_FEATURES
    summary = world["weights"]["summary"]
    assert min(summary["max_depth"]) > 12 and min(summary["nodes"]) > 2 * fmod.GEMM_MAX_LEAVES
    # the columns the callsets hold constant are never split on
    for name in ("dp", "af", "n_alts"):
        col = fixtures.RUN_FEATURES.index(name)
        assert not any((t["feature"] == col).any() for t in world["weights"]["trees"]), name
    # the program's loader converts the pickle; auto takes the gather walk
    # on a TPU for trees this size, as on a CPU
    forest = load_model(world["models"], world["config"]["model_name"])
    assert forest.aggregation == "mean" and forest.n_trees == N_TREES
    assert forest.max_depth == max(summary["max_depth"])
    assert fmod.resolve_strategy(forest, backend="tpu") == "gather"


def test_the_familys_scorer_is_predict_proba(world):
    """float32 leaf sum against scikit-learn's float64 one: the same leaves,
    summed in another precision."""
    import pandas as pd

    x = _features(world)
    clf = world["weights"]["estimator"]
    want = clf.predict_proba(pd.DataFrame(x, columns=clf.feature_names_in_))[:, 1]
    got = world["family"].score(world["weights"], x)
    assert got.dtype == np.float32
    assert np.abs(got.astype(np.float64) - want).max() <= 1e-6
    assert 0.2 < (want >= 0.5).mean() < 0.8  # both verdicts, in number


def test_scores_filters_and_records_agree_with_the_reference(world, monkeypatch, tmp_path):
    import reference
    import run_cell

    out = str(tmp_path / "out.vcf")
    assert _filter(world, monkeypatch, out) == 0
    with open(out, "rb") as fh:
        lines = run_cell.body_lines(fh.read())
    assert len(lines) == N_VARIANTS
    limits = world["config"]["limits"]
    got = reference.compare(world["lines"], lines, "skrf", world["weights"], world["body"],
                            N_CONTIGS, limits["score_gap_max"])
    assert got["score_gap_max"] <= SCORE_TOL <= limits["score_gap_max"]
    assert (got["filter_wrong"], got["records_wrong"]) == (0, 0)
    assert {b"PASS", b"LOW_SCORE"} <= set(reference.output_fields(lines)[2])
    # the counters and the span the cell's metrics read, read by their readers
    with open(out + ".obs.jsonl", encoding="utf-8") as fh:
        events = [json.loads(ln) for ln in fh if ln.strip()]
    counters = [e for e in events if e["kind"] == "metrics" and e["name"] == "final"][-1]["counters"]
    depth = max(world["weights"]["summary"]["max_depth"])
    assert counters["feed.dispatches"] >= 1
    assert counters["score.dispatches.gather"] == counters["feed.dispatches"]
    assert counters["forest.walk_levels"] == depth * counters["feed.dispatches"]
    loads = [e for e in events if e["kind"] == "span" and e["name"] == "model_load"]
    assert len(loads) == 1 and loads[0]["layer"] == "entry"
    import lookup

    ctx = {"obs_events": events}
    read = {}
    for name in ("gather_dispatch_share", "walk_levels_per_dispatch", "model_load_work_share"):
        with open(os.path.join(BENCH, "layer_metrics", name + ".json"), encoding="utf-8") as fh:
            how = json.load(fh)
        read[name] = lookup.load("readers", how["reader"]).read(ctx, **how["args"])
    assert read["gather_dispatch_share"] == 100.0
    assert read["walk_levels_per_dispatch"] == depth
    assert 0 < read["model_load_work_share"] < 100


def test_the_bf16_control_is_refused(world):
    import reference

    limits = world["config"]["limits"]
    got = reference.compare(world["lines"], [], "skrf", world["weights"], world["body"],
                            N_CONTIGS, limits["score_gap_max"], control=world["config"]["control"])
    assert got["score_gap_max"] > limits["score_gap_max"]
    # and the reference in the program's place, in its own precision, is not
    got = reference.compare(world["lines"], [], "skrf", world["weights"], world["body"],
                            N_CONTIGS, limits["score_gap_max"], control="f32")
    assert (got["score_gap_max"], got["filter_wrong"], got["records_wrong"]) == (0, 0, 0)


def _largest_constant_bytes(lowered) -> int:
    """Bytes of the largest ``stablehlo.constant`` in a lowered program."""
    sizes = [0]

    def walk(op):
        if op.operation.name == "stablehlo.constant":
            m = re.fullmatch(r"tensor<((?:\d+x)*)[a-z]*?(\d+)>", str(op.result.type))
            if m:
                dims = [int(d) for d in m.group(1).split("x") if d]
                sizes.append(int(np.prod(dims, dtype=np.int64)) * max(1, int(m.group(2)) // 8))
        for region in op.regions:
            for block in region.blocks:
                for inner in block.operations:
                    walk(inner)

    for op in lowered.compiler_ir("stablehlo").body.operations:
        walk(op)
    return max(sizes)


def test_the_node_table_is_an_argument_with_one_device_copy(world, monkeypatch):
    """The gather walk's node table rides every dispatch as the program's
    first argument: two bucket sizes lower with no constant over 1 MiB
    (the table itself is over 1 MiB), and both dispatches hand the program
    the one device copy made with it, which is the only copy alive."""
    import jax

    from tests.fixtures import fused_inputs_with_host_windows
    from variantcalling_tpu.featurize import BASE_FEATURES, _bucket
    from variantcalling_tpu.models.registry import load_model
    from variantcalling_tpu.parallel import shard_score
    from variantcalling_tpu.pipelines import filter_variants as fv

    names = list(BASE_FEATURES)
    model = load_model(world["models"], world["config"]["model_name"])
    fv._PREDICTOR_CACHE.clear()

    def table_copies():
        shapes = {(a.shape, a.dtype) for a in jax.tree.leaves(program.weights)}
        return {id(a) for a in jax.live_arrays() if (a.shape, a.dtype) in shapes}

    program = fv._fused_program(model, names, "TGCA", strategy="gather")
    leaves = jax.tree.leaves(program.weights)
    assert program.walk_levels == model.max_depth
    assert sum(a.nbytes for a in leaves) > ONE_MIB
    assert table_copies() == {id(a) for a in leaves}
    sizes = (300, 5000)
    assert _bucket(sizes[0]) != _bucket(sizes[1])
    for n in sizes:
        lowered = program.fn.lower(
            program.weights,
            jax.ShapeDtypeStruct((_bucket(n), 41), np.uint8),
            jax.ShapeDtypeStruct((_bucket(n), program.layout.words), np.uint32))
        assert _largest_constant_bytes(lowered) <= ONE_MIB
    seen = []
    real = fv._enqueue
    monkeypatch.setattr(fv, "_enqueue", lambda fn, sig, args: (
        seen.append((sig[0], args[0])), real(fn, sig, args))[1])
    plan = shard_score.MeshPlan(1, "1", "test")
    for n in sizes:
        fv._dispatch_fused([fused_inputs_with_host_windows(n, program, names, seed=n)], plan)
    assert [s for s, _ in seen] == [_bucket(n) for n in sizes]
    assert all(w is program.weights for _, w in seen)
    assert table_copies() == {id(a) for a in leaves}


def test_the_largest_constant_is_read_as_such():
    """The check above sees a table compiled in, as a program closing over
    it holds it."""
    import jax
    import jax.numpy as jnp

    table = jnp.arange(2 * ONE_MIB // 4, dtype=jnp.float32)
    lowered = jax.jit(lambda x: x + table[x.shape[0]]).lower(jnp.ones(3))
    assert _largest_constant_bytes(lowered) == 2 * ONE_MIB


def test_the_set_up_refuses_a_program_that_compiles_the_table_in(world, monkeypatch):
    """The family's set-up check passes on this program, and refuses, before
    any fit, one whose gather walk takes no table argument (its node table a
    compiled constant) or has no such function at all."""
    from variantcalling_tpu.models import forest as fmod

    world["family"].require_table_argument()
    real = fmod.make_margin_program
    monkeypatch.setattr(fmod, "make_margin_program",
                        lambda *a, **k: real(*a, **k)._replace(weights=()))
    with pytest.raises(SystemExit, match="program argument"):
        world["family"].require_table_argument()
    monkeypatch.delattr(fmod, "make_margin_program")
    with pytest.raises(SystemExit, match="program argument"):
        world["family"].arrays(7, world["config"])


def test_counter_quotient_reads_a_plain_ratio(monkeypatch):
    monkeypatch.syspath_prepend(BENCH)
    import lookup

    read = lookup.load("readers", "counter_quotient").read
    final = lambda c: {"kind": "metrics", "name": "final", "counters": c}  # noqa: E731
    events = [final({"forest.walk_levels": 46 * 14, "score.dispatches.gather": 14}),
              final({"forest.walk_levels": 46 * 13, "score.dispatches.gather": 13})]
    args = {"part": "forest.walk_levels", "whole": "score.dispatches.gather"}
    assert read({"obs_events": events}, **args) == 46
    # a program that counts no walk gives nothing to read; no dispatch, no ratio
    assert read({"obs_events": [final({"score.dispatches.gather": 3})]}, **args) is None
    assert read({"obs_events": [final({"forest.walk_levels": 0})]}, **args) is None


def test_the_configuration_is_the_forest_cells_but_for_the_model():
    def load(name):
        with open(os.path.join(BENCH, "configs", name + ".json"), encoding="utf-8") as fh:
            return json.load(fh)

    forest, skrf = load("forest-t40d6-hg38x2"), load(CONFIG)
    model_keys = {"name", "source", "deployment", "family", "model_name", "hyperparameters",
                  "n_trees", "depth", "n_internal", "n_leaves", "n_features", "features",
                  "train_rows", "strategy", "precision", "assumed"}
    # the one cut of this configuration alone: the walk's time a file
    assert skrf["variants_per_file"] < forest["variants_per_file"]
    assert skrf["reduced"] == forest["reduced"] == ["variants_per_file"]
    model_keys.add("variants_per_file")
    differ = {k for k in set(forest) | set(skrf) if forest.get(k) != skrf.get(k)}
    assert differ <= model_keys, differ - model_keys
    assert (skrf["family"], skrf["hyperparameters"]["n_estimators"], skrf["n_trees"]) == \
        ("skrf", 100, 100)
    assert skrf["hyperparameters"]["max_depth"] is None
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        bm = json.load(fh)
    (entry,) = [c for c in bm["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == skrf["reduced"] == ["variants_per_file"]
    (cell,) = [w for w in bm["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "wgs-batch", 1)


SKRF_METRICS = {"forest_gather_roofline": ("kernel forest gather walk", "loop_roofline"),
                "gather_dispatch_share": ("device featurize and score", "counter_ratio"),
                "walk_levels_per_dispatch": ("device featurize and score", "counter_quotient"),
                "model_load_work_share": ("CLI entry", "span_sum_share")}


@pytest.mark.parametrize("name", sorted(SKRF_METRICS))
def test_the_cells_metrics_come_last_and_list_only_that_cell(name, monkeypatch):
    monkeypatch.syspath_prepend(BENCH)
    import lookup

    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        bm = json.load(fh)
    assert [m["name"] for m in bm["per_layer"][-4:]] == list(SKRF_METRICS)
    (m,) = [m for m in bm["per_layer"] if m["name"] == name]
    layer, reader = SKRF_METRICS[name]
    assert (m["workloads"], m["moves"], m["layer"]) == ([CELL], "variants_per_s", layer)
    with open(os.path.join(BENCH, "layer_metrics", name + ".json"), encoding="utf-8") as fh:
        how = json.load(fh)
    assert how["reader"] == reader and callable(lookup.load("readers", reader).read)
