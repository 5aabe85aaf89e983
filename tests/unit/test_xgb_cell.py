"""The xgboost booster as the benchmark's ``xgb-t100d6-hg38x2`` configuration
states it, on the CPU at a small size: a JSON model of the family's draw
(``benchmarks/families/xgb.py``, 8 trees of ``max_depth`` 4) through the
program's ingestion and the CLI entry, on a seeded callset where QUAL, INFO
DP, INFO AF and FORMAT GQ are absent on a seeded share of the records, so
that default routing fires on some records and not on others.

- every record's TREE_SCORE and FILTER agree with the family's plain scorer
  (xgboost's documented prediction) under the jit engine's ``gather`` and
  ``wide`` programs and under the native engine;
- ``auto`` sends a ``default_left`` forest to ``pallas`` on a TPU, whose
  kernel routes the draw's missing values as every other program does;
- the counters the cell's metrics read: ``feed.nan_cells`` equals the absent
  values drawn, ``feed.float_cells`` the float cells written, and one
  ``score.dispatches.<strategy>`` a dispatch.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

native = pytest.importorskip("variantcalling_tpu.native")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
GENOME_LEN, N_CONTIGS, N_VARIANTS, SEED, REF_SEED = 240_000, 4, 3000, 17, 1001
#: share of the records on which each value is absent
ABSENT = {"qual": 0.15, "dp": 0.2, "af": 0.25, "gq": 0.15}
#: the small model of the family's draw
SMALL = {"n_trees": 8, "max_depth": 4, "missing_columns": []}
#: the output prints TREE_SCORE to 4 decimals; program and reference run the
#: same float32 arithmetic (leaf sum tree by tree, base margin, sigmoid), so
#: they may part only where that print rounds a tie: one unit of the fourth
#: decimal. A misrouted missing value moves a score by a leaf (0.3 times a
#: normal of sd 0.5), hundreds of such units.
SCORE_TOL = 1e-4 + 1e-6


@pytest.fixture(autouse=True)
def _engine_cache_isolated():
    yield
    from variantcalling_tpu import engine as engine_mod

    engine_mod.reset_for_tests()


def _with_missing_values(path: str, rng) -> dict:
    """Rewrite the benchmark's callset at ``path`` with INFO DP and AF on
    every record but a seeded share, and QUAL and GQ absent on a seeded
    share; returns the absent masks by column."""
    with open(path, encoding="utf-8") as fh:
        lines = fh.read().splitlines()
    header = [ln for ln in lines if ln.startswith("#")]
    body = [ln.split("\t") for ln in lines if not ln.startswith("#")]
    n = len(body)
    absent = {c: rng.random(n) < p for c, p in ABSENT.items()}
    dp, af = rng.uniform(0, 1, n), rng.uniform(0, 1, n)
    header[-1:-1] = ['##INFO=<ID=DP,Number=1,Type=Float,Description="Depth">',
                     '##INFO=<ID=AF,Number=A,Type=Float,Description="Allele frequency">']
    for i, f in enumerate(body):
        if absent["qual"][i]:
            f[5] = "."
        info = ([] if absent["dp"][i] else [f"DP={dp[i]:.3f}"]) + [f[7]] \
            + ([] if absent["af"][i] else [f"AF={af[i]:.3f}"])
        f[7] = ";".join(info)
        gt, depth, gq = f[9].split(":")
        f[9] = ":".join([gt, depth, "." if absent["gq"][i] else gq])
    with open(path, "w", encoding="utf-8") as fh:
        fh.write("\n".join(header + ["\t".join(f) for f in body]) + "\n")
    return absent


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    mp.syspath_prepend(BENCH)
    import fixtures
    import lookup

    from variantcalling_tpu.models import registry

    d = str(tmp_path_factory.mktemp("xgb_cell"))
    family = lookup.load("families", "xgb")
    weights = family.arrays(7, SMALL)
    ref, calls = os.path.join(d, "ref.fa"), os.path.join(d, "calls.vcf")
    fixtures.write_reference(ref, REF_SEED, GENOME_LEN, N_CONTIGS)
    fixtures.write_callset(calls, REF_SEED, GENOME_LEN, N_CONTIGS, N_VARIANTS, SEED)
    absent = _with_missing_values(calls, np.random.default_rng(SEED))
    models = os.path.join(d, "models.pkl")
    registry.save_models(models, {"xgb": family.to_program(SMALL, weights)})
    yield {"dir": d, "family": family, "weights": weights, "ref": ref,
           "calls": calls, "absent": absent, "models": models}
    mp.undo()


def _want(world) -> np.ndarray:
    """The family's plain scorer over the reference's features, each absent
    value NaN, as the program keeps it for a default_left model."""
    import fixtures
    import reference
    import run_cell

    with open(world["calls"], "rb") as fh:
        lines = run_cell.body_lines(fh.read())
    x = reference.feature_matrix(reference.parse_records(lines),
                                 fixtures.contig_body(REF_SEED, GENOME_LEN // N_CONTIGS),
                                 N_CONTIGS)
    for col, mask in world["absent"].items():
        x[mask, fixtures.RUN_FEATURES.index(col)] = np.nan
    return world["family"].score(world["weights"], x)


def _filter(world, monkeypatch, out: str, engine: str, strategy: str | None,
            obs: bool = False) -> int:
    from variantcalling_tpu.pipelines.filter_variants import run as cli

    monkeypatch.setenv("VCTPU_ENGINE", engine)
    if strategy is not None:
        monkeypatch.setenv("VCTPU_FOREST_STRATEGY", strategy)
    if obs:
        monkeypatch.setenv("VCTPU_OBS", "1")
    return cli(["--input_file", world["calls"], "--model_file", world["models"],
                "--model_name", "xgb", "--reference_file", world["ref"],
                "--output_file", out])


def test_the_draw_is_an_xgboost_document_of_the_configured_shape(world):
    import fixtures

    doc = world["weights"]["document"]["learner"]
    trees = doc["gradient_booster"]["model"]["trees"]
    assert len(trees) == SMALL["n_trees"]
    assert doc["feature_names"] == fixtures.RUN_FEATURES
    assert doc["objective"]["name"] == "binary:logistic"
    assert float(doc["learner_model_param"]["base_score"]) != 0.5
    splits = fixtures.RUN_FEATURES.index("n_alts")
    for t in trees:
        internal = np.asarray(t["left_children"]) >= 0
        assert internal.sum() == 2 ** SMALL["max_depth"] - 1  # complete
        assert (~internal).sum() == 2 ** SMALL["max_depth"]
        assert splits not in np.asarray(t["split_indices"])[internal]
        assert 0 < np.asarray(t["default_left"])[internal].mean() < 1
    # the program's ingestion keeps every default bit
    forest = world["family"].to_program(SMALL, world["weights"])
    assert forest.default_left is not None and forest.aggregation == "logit_sum"


def test_missing_values_are_absent_on_some_records_and_not_others(world):
    for col, mask in world["absent"].items():
        assert 0 < mask.sum() < N_VARIANTS, col


@pytest.mark.parametrize("engine,strategy", [("jit", "gather"), ("jit", "wide"),
                                             ("native", None)])
def test_scores_and_filters_agree_with_the_familys_scorer(world, monkeypatch, tmp_path,
                                                          engine, strategy):
    import reference
    import run_cell

    out = str(tmp_path / "out.vcf")
    assert _filter(world, monkeypatch, out, engine, strategy) == 0
    with open(out, "rb") as fh:
        lines = run_cell.body_lines(fh.read())
    assert len(lines) == N_VARIANTS
    _, got, filt = reference.output_fields(lines)
    want = _want(world)
    assert np.abs(got - want.astype(np.float64)).max() <= SCORE_TOL
    clear = np.abs(want - reference.PASS_THRESHOLD) > SCORE_TOL
    want_filter = np.where(want < reference.PASS_THRESHOLD, b"LOW_SCORE", b"PASS")
    assert all(f == w for c, f, w in zip(clear, filt, want_filter) if c)
    # the draw is not degenerate: both verdicts occur
    assert {b"PASS", b"LOW_SCORE"} <= set(filt)


def test_a_missing_value_routed_as_zero_is_caught(world):
    """The comparison above is tight enough to see the fault the cell guards
    against: the same records with every absent value read as 0."""
    import fixtures
    import reference
    import run_cell

    with open(world["calls"], "rb") as fh:
        lines = run_cell.body_lines(fh.read())
    x = reference.feature_matrix(reference.parse_records(lines),
                                 fixtures.contig_body(REF_SEED, GENOME_LEN // N_CONTIGS),
                                 N_CONTIGS)
    as_zero = world["family"].score(world["weights"], x)
    assert np.abs(as_zero - _want(world)).max() > 100 * SCORE_TOL


def test_auto_sends_a_default_left_forest_to_pallas_on_a_tpu(world, monkeypatch):
    import dataclasses

    from variantcalling_tpu.models import forest as fmod

    forest = world["family"].to_program(SMALL, world["weights"])
    assert fmod.resolve_strategy(forest, backend="tpu") == "pallas"
    plain = dataclasses.replace(forest, default_left=None)
    assert fmod.resolve_strategy(plain, backend="tpu") == "pallas"
    monkeypatch.setenv("VCTPU_PALLAS", "0")
    assert fmod.resolve_strategy(forest, backend="tpu") == "wide"


@pytest.mark.parametrize("n", [0, 1, 1000])
def test_the_kernel_routes_the_draws_missing_values_as_every_strategy(world, n):
    """The family's draw through every device program, the Pallas kernel in
    the interpreter: NaN in every column somewhere, a row of NaN only, the
    roots' thresholds met exactly, n not a multiple of the kernel's tile."""
    import jax
    import jax.numpy as jnp

    import fixtures

    from variantcalling_tpu.models import forest as fmod

    forest = world["family"].to_program(SMALL, world["weights"])
    rng = np.random.default_rng(n)
    names = fixtures.RUN_FEATURES
    lo, hi = (np.array([fixtures.FEATURE_RANGE[f][k] for f in names], np.float32)
              for k in (0, 1))
    x = (lo + rng.random((n, len(names))) * (hi - lo)).astype(np.float32)
    if n > 1:
        x[rng.random(x.shape) < 0.1] = np.nan
        for t in range(forest.n_trees):
            x[1 + t, forest.feature[t, 0]] = forest.threshold[t, 0]
        assert np.isnan(x).any(axis=0).all()
    x[:1] = np.nan
    xj = jnp.asarray(x)
    margins = {s: np.asarray(jax.jit(fmod.make_margin_predictor(
        forest, len(names), strategy=s, interpret=True))(xj))
        for s in ("gather", "gemm", "wide", "pallas")}
    for s, m in margins.items():
        assert m.shape == (n,) and m.tobytes() == margins["gather"].tobytes(), s


@pytest.mark.parametrize("strategy", ["gather", "wide"])
def test_the_counters_the_cell_reads(world, monkeypatch, tmp_path, strategy):
    out = str(tmp_path / "out.vcf")
    assert _filter(world, monkeypatch, out, "jit", strategy, obs=True) == 0
    with open(out + ".obs.jsonl", encoding="utf-8") as fh:
        events = [json.loads(ln) for ln in fh if ln.strip()]
    final = [e for e in events if e["kind"] == "metrics" and e["name"] == "final"]
    counters = final[-1]["counters"]
    drawn = sum(int(m.sum()) for m in world["absent"].values())
    assert counters["feed.nan_cells"] == drawn
    # qual, dp, sor, af and gq: the float32 columns of the run's wire row
    assert counters["feed.float_cells"] == 5 * N_VARIANTS
    assert counters["feed.dispatches"] >= 1
    assert counters[f"score.dispatches.{strategy}"] == counters["feed.dispatches"]
    others = {k: v for k, v in counters.items()
              if k.startswith("score.dispatches.") and k != f"score.dispatches.{strategy}"}
    assert others and not any(others.values())
