"""What the chip would be asked to compile, checked without a chip.

``jax.experimental.topologies.get_topology_desc(platform="tpu",
topology_name="v5e:2x2")`` describes four ``TPU v5 lite`` devices to the
installed libtpu, and ``jit(...).lower(<specs placed on them>).compile()``
runs the real XLA:TPU + Mosaic compilers against them. So "does every
program ``auto`` can pick on a TPU compile for a v5e — at dp=1 and inside
``shard_program`` at dp=4" is a tier-1 CPU test; the chip is only needed
for "does it run and is it right" (``chip_smoke.py``).

Plus the bring-up contracts that sit next to it: where the compile cache
goes, what an explicit ``--backend tpu`` means on a machine without one.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from variantcalling_tpu.models import forest as fmod

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ROWS = 4096


@pytest.fixture(scope="module")
def v5e():
    """(single-device sharding, dp=4 mesh, dp-sharded sharding) on a
    described v5e 2x2 host — capability probe: skipped where the
    installed jaxlib/libtpu cannot describe a TPU topology."""
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

    from variantcalling_tpu.parallel.mesh import DATA_AXIS, make_mesh

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure here is "no TPU compiler"
        pytest.skip(f"capability probe: no TPU topology description ({e})")
    assert [d.device_kind for d in topo.devices] == ["TPU v5 lite"] * 4
    mesh = make_mesh(n_data=4, n_model=1, devices=topo.devices)
    return (SingleDeviceSharding(topo.devices[0]), mesh,
            NamedSharding(mesh, P(DATA_AXIS)))


def _weight_specs(weights, sharding):
    """A program's weights operand as shapes placed by ``sharding``."""
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
                        weights)


def _compile_dp1_and_dp4(fn, weights, n_features, v5e):
    """``fn(weights, x)`` compiled as a dispatch calls it: the weights as an
    argument on the program's device (replicated over the dp=4 mesh)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from variantcalling_tpu.parallel import shard_score

    single, mesh, dp_sharded = v5e
    for program, sharding, w_sharding in (
            (fn, single, single),
            (shard_score.shard_program(fn, mesh, n_data_args=1, replicated_leading=1),
             dp_sharded, NamedSharding(mesh, P()))):
        spec = jax.ShapeDtypeStruct((ROWS, n_features), jnp.float32,
                                    sharding=sharding)
        compiled = jax.jit(program).lower(_weight_specs(weights, w_sharding),
                                          spec).compile()
        assert compiled is not None


def _tpu_strategy_program(strategy, forest, n_features):
    if strategy == "pallas":
        # the registry entry would warm the kernel up on THIS (CPU)
        # backend; build the same kernel directly, Mosaic-bound
        from variantcalling_tpu.models.forest_pallas import \
            make_wide_pallas_margin_predictor

        kernel = make_wide_pallas_margin_predictor(
            fmod.to_gemm(forest, n_features), interpret=False)
        return (lambda _w, x: kernel(x)), ()
    return fmod._build_margin_program(strategy, forest, n_features)


def test_every_strategy_auto_can_pick_on_a_tpu_compiles_for_v5e(v5e, rng, monkeypatch):
    """The test that would have caught both bring-up blockers without a
    chip: the Mosaic block-shape refusal (pallas) and the unvarying loop
    carries inside shard_map (every strategy at dp > 1). ``wide`` is what
    auto picks on a TPU under ``VCTPU_PALLAS=0``."""
    from tests.unit.test_xgb_ingest import _two_tree_model
    from variantcalling_tpu.models.xgb import from_xgboost_json
    from variantcalling_tpu.synthetic import synthetic_forest

    bench_shape = synthetic_forest(rng, n_trees=40, depth=6, n_features=12)
    widest_gemm = synthetic_forest(rng, n_trees=4, depth=10, n_features=12)
    too_wide = synthetic_forest(rng, n_trees=2, depth=11, n_features=12)
    missing_routing = from_xgboost_json(_two_tree_model())
    assert fmod.max_tree_leaves(widest_gemm) == fmod.GEMM_MAX_LEAVES
    cases = [(bench_shape, 12, "1"), (widest_gemm, 12, "1"), (too_wide, 12, "1"),
             (missing_routing, 3, "1"), (bench_shape, 12, "0")]
    picked = set()
    for forest, n_features, pallas_on in cases:
        monkeypatch.setenv("VCTPU_PALLAS", pallas_on)
        strategy = fmod.resolve_strategy(forest, n_features, backend="tpu")
        picked.add(strategy)
        _compile_dp1_and_dp4(
            *_tpu_strategy_program(strategy, forest, n_features), n_features, v5e)
    # the cases above cover everything auto can return on a TPU
    assert picked == {"pallas", "gather", "wide"}


def test_dan_program_compiles_for_v5e(v5e):
    from variantcalling_tpu.featurize import BASE_FEATURES
    from variantcalling_tpu.models import dan as dan_mod
    from variantcalling_tpu.synthetic import synthetic_dan

    names = list(BASE_FEATURES)
    model = synthetic_dan(np.random.default_rng(0), names, embed_dim=16,
                          hidden=256, n_layers=2)  # the served width
    score = dan_mod.make_score_predictor(model, names)
    _compile_dp1_and_dp4(lambda _w, x: score(x), (), len(names), v5e)


def test_window_gather_compiles_for_v5e_at_hg38_size(v5e):
    """The resident genome's window gather at the size the cells run it
    (hg38's 6.06 M rows of 128 words, the 262,144-row bucket) on one chip,
    and as a pure map over dp=4 with the genome replicated: two whole-row
    gather fusions, no per-byte index tensor, no collective."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from variantcalling_tpu.featurize import (GENOME_ROW_WORDS, WINDOW_RADIUS,
                                              windows_from_packed)
    from variantcalling_tpu.parallel import shard_score

    single, mesh, dp_sharded = v5e
    n_rows, bucket = 6_055_937, 262_144
    width = 2 * WINDOW_RADIUS + 1
    for program, genome_sharding, pos_sharding in (
            (windows_from_packed, single, single),
            (shard_score.shard_program(windows_from_packed, mesh, n_data_args=1,
                                       replicated_leading=1),
             NamedSharding(mesh, P()), dp_sharded)):
        compiled = jax.jit(program).lower(
            jax.ShapeDtypeStruct((n_rows, GENOME_ROW_WORDS), jnp.uint32,
                                 sharding=genome_sharding),
            jax.ShapeDtypeStruct((bucket,), jnp.uint32, sharding=pos_sharding)).compile()
        text = compiled.as_text()
        assert f"s32[{bucket * width}" not in text  # the per-byte index of the old gather
        for collective in ("all-gather", "all-reduce", "collective-permute", "all-to-all"):
            assert collective not in text
        # what one dispatch adds to the resident genome stays far under a chip
        assert compiled.memory_analysis().temp_size_in_bytes < (1 << 30)


@pytest.mark.parametrize("bucket", [262_144, 163_840, 98_304])
@pytest.mark.parametrize("family", ["dan", "forest"])
def test_fused_program_over_the_wire_compiles_for_v5e_at_the_cells_size(v5e, family, bucket):
    """The whole fused program as a dispatch calls it since the wire — the
    resident genome's rows and ONE ``uint32[bucket, 10]`` buffer, at the
    ladder's cap and at the two rungs a wgs cell's chunks ride (5 * 2**15
    and, the file's tail, 6 * 2**14: no powers of two) — for one
    chip and as a pure map over dp=4: the unpack (column slices, shifts,
    bitcasts) is accepted by the chip's compiler, adds no collective, and
    the buffer's narrow minor dimension does not blow its device footprint
    up (10 words a row are stored as 16, not 128)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from variantcalling_tpu.featurize import (BASE_FEATURES, GENOME_ROW_WORDS,
                                              _bucket)
    from variantcalling_tpu.pipelines import filter_variants as fv
    from variantcalling_tpu.synthetic import synthetic_dan, synthetic_forest

    single, mesh, dp_sharded = v5e
    names = list(BASE_FEATURES)
    rng = np.random.default_rng(0)
    model, strategy = (synthetic_dan(rng, names), None) if family == "dan" \
        else (synthetic_forest(rng, n_trees=40, depth=6), "wide")
    n_rows = 6_055_937
    assert _bucket(bucket) == bucket
    for use_mesh, genome_sharding, wire_sharding in (
            (None, single, single), (mesh, NamedSharding(mesh, P()), dp_sharded)):
        fn, layout, _fin, weights, _levels = fv._build_fused_program(
            model, names, "TGCA", True, strategy, use_mesh)
        assert layout.words == 10
        compiled = fn.lower(
            _weight_specs(weights, genome_sharding),
            jax.ShapeDtypeStruct((n_rows, GENOME_ROW_WORDS), jnp.uint32,
                                 sharding=genome_sharding),
            jax.ShapeDtypeStruct((bucket, layout.words), jnp.uint32,
                                 sharding=wire_sharding)).compile()
        text = compiled.as_text()
        for collective in ("all-gather", "all-reduce", "collective-permute", "all-to-all"):
            assert collective not in text
        mem = compiled.memory_analysis()
        genome_bytes = -(-n_rows // 8) * 8 * GENOME_ROW_WORDS * 4  # whole tiles of 8 rows
        assert mem.argument_size_in_bytes - genome_bytes <= bucket * 16 * 4
        assert mem.temp_size_in_bytes < (1 << 30)


# ---------------------------------------------------------------------------
# compile-cache placement
# ---------------------------------------------------------------------------

_CACHE_PROBE = """
import json, os, sys
sys.path.insert(0, {repo!r})
if {jax_first}:
    import jax
from variantcalling_tpu.utils import compile_cache
assert compile_cache.enable_persistent_cache()
import jax
print(json.dumps({{"dir": jax.config.jax_compilation_cache_dir,
                   "cache_dir": compile_cache.cache_dir(),
                   "default": compile_cache.DEFAULT_DIR}}))
"""


def _cache_probe(env_dir, jax_first):
    import json

    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "PYTHONPATH")}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    p = subprocess.run(
        [sys.executable, "-c",
         _CACHE_PROBE.format(repo=_REPO, jax_first=jax_first)],
        env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("jax_first", [True, False])
def test_compile_cache_follows_the_environment(tmp_path, jax_first):
    """JAX_COMPILATION_CACHE_DIR set -> that directory, whether or not
    jax was imported before the entry point enabled the cache (the CLI
    imports the tool module, and with it jax, first)."""
    want = str(tmp_path / "placed_from_outside")
    got = _cache_probe(want, jax_first)
    assert got["dir"] == want and got["cache_dir"] == want
    assert not os.path.exists(want) or os.listdir(want) == []


@pytest.mark.parametrize("jax_first", [True, False])
def test_compile_cache_defaults_to_one_fixed_in_checkout_dir(jax_first):
    got = _cache_probe(None, jax_first)
    assert got["dir"] == got["default"] == got["cache_dir"]
    assert got["default"] == os.path.join(_REPO, ".jax_cache")  # never ~ or a temp name


def test_compile_cache_never_names_a_dir_when_the_environment_does(monkeypatch, tmp_path):
    from variantcalling_tpu.utils import compile_cache

    calls = []
    monkeypatch.setattr(compile_cache, "_ENABLED", False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append(name))
    assert compile_cache.enable_persistent_cache()
    assert "jax_compilation_cache_dir" not in calls


# ---------------------------------------------------------------------------
# an explicit --backend is a requirement
# ---------------------------------------------------------------------------


def test_explicit_backend_tpu_without_a_tpu_exits_2(tmp_path, capsys):
    """``--backend tpu`` on a CPU-only process exits 2 before touching
    any input — it never carries on through the host engine."""
    from variantcalling_tpu.__main__ import main
    from variantcalling_tpu.serve import cli as serve_cli

    out = tmp_path / "out.vcf"
    rc = main(["filter_variants_pipeline", "--input_file", "absent.vcf",
               "--model_file", "absent.pkl", "--model_name", "m",
               "--reference_file", "absent.fa", "--output_file", str(out),
               "--backend", "tpu"])
    assert rc == 2 and not out.exists()
    assert serve_cli.run(["--backend", "tpu", "--port", "0"]) == 2


def test_pin_backend_none_takes_what_jax_initialized():
    from variantcalling_tpu import engine

    engine.pin_backend(None)
    engine.pin_backend("cpu")
    with pytest.raises(engine.EngineError, match="--backend tpu"):
        engine.pin_backend("tpu")


def test_the_xgb_cells_booster_compiles_for_v5e_and_names_its_roofline_ops(v5e, monkeypatch):
    """The benchmark's xgboost booster (100 trees of depth 6 with
    ``default_left`` bits, all 19 columns) as the cell dispatches it: the
    fused program over the wire at the 163,840-row rung, one chip, under
    ``auto``'s ``pallas`` on a TPU and under ``wide`` (``VCTPU_PALLAS=0``,
    other accelerators). Each compiles and adds under 1 GiB to the resident
    genome; the kernel's program holds ``forest_wide_block_missing``, the
    name ``forest_wide_block_missing_roofline`` reads the device trace by,
    and every operand shape ``forest_wide_roofline`` reads is the result of
    some operation of the wide program's compiled text, so neither metric
    can fall silent unnoticed."""
    import json

    from variantcalling_tpu.featurize import BASE_FEATURES, GENOME_ROW_WORDS
    from variantcalling_tpu.models.forest_pallas import \
        make_wide_pallas_margin_predictor
    from variantcalling_tpu.pipelines import filter_variants as fv

    bench = os.path.join(_REPO, "benchmarks")
    sys.path.insert(0, bench)
    try:
        import lookup
        from readers.ops_roofline import result_type

        with open(os.path.join(bench, "configs", "xgb-t100d6-hg38x2.json")) as fh:
            config = json.load(fh)
        metrics = {}
        for name in ("forest_wide_roofline", "forest_wide_block_missing_roofline"):
            with open(os.path.join(bench, "layer_metrics", name + ".json")) as fh:
                metrics[name] = json.load(fh)["args"]
        family = lookup.load("families", "xgb")
        model = family.to_program(config, family.arrays(config["weights_seed"], config))
    finally:
        sys.path.remove(bench)
    assert fmod.resolve_strategy(model, backend="tpu") == "pallas"
    # the registry would warm the kernel up on THIS (CPU) backend; build it
    # Mosaic-bound, as _tpu_strategy_program does
    build = fmod._build_margin_program
    def mosaic_bound(s, f, n, interpret=False, sharding=None):
        if s != "pallas":
            return build(s, f, n, interpret, sharding)
        kernel = make_wide_pallas_margin_predictor(fmod.to_gemm(f, n))
        return (lambda _w, x: kernel(x)), ()

    monkeypatch.setattr(fmod, "_build_margin_program", mosaic_bound)
    single, _, _ = v5e
    texts = {}
    for strategy in ("pallas", "wide"):
        fn, layout, _fin, weights, _levels = fv._build_fused_program(
            model, list(BASE_FEATURES), "TGCA", True, strategy, None)
        compiled = fn.lower(
            _weight_specs(weights, single),
            jax.ShapeDtypeStruct((6_055_937, GENOME_ROW_WORDS), jnp.uint32, sharding=single),
            jax.ShapeDtypeStruct((163_840, layout.words), jnp.uint32, sharding=single)).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < (1 << 30)
        texts[strategy] = compiled.as_text()
    assert metrics["forest_wide_block_missing_roofline"]["pattern"] in texts["pallas"]
    results = [result_type(ln.strip()) for ln in texts["wide"].splitlines()
               if ln.strip().startswith("%") and " = " in ln]
    for p in metrics["forest_wide_roofline"]["patterns"]:
        assert any(p in r for r in results), p


def test_the_gather_walk_compiles_for_v5e_with_its_table_as_an_argument(v5e, rng):
    """An unpruned forest's walk as the skrf cell dispatches it: the fused
    program over the wire at the 163,840-row rung, one chip, with the
    configuration's tree count and its node table an ARGUMENT. It compiles,
    adds under 1 GiB of scratch to the resident genome (a row gather of a
    packed ``(T*M, 5)`` table asked for 8.4 GB there), holds the table once
    among its arguments, and the whole feature column, by which
    ``forest_gather_roofline`` finds the walk's tree loop in the device
    trace, is the type of one ``while`` of its compiled text and of no
    operation that runs inside it, so the metric cannot fall silent or count
    twice unnoticed."""
    import json

    from variantcalling_tpu.featurize import BASE_FEATURES, GENOME_ROW_WORDS
    from variantcalling_tpu.pipelines import filter_variants as fv
    from variantcalling_tpu.synthetic import synthetic_forest

    bench = os.path.join(_REPO, "benchmarks")
    sys.path.insert(0, bench)
    try:
        import lookup

        with open(os.path.join(bench, "configs", "skrf-t100-full-hg38x2.json")) as fh:
            config = json.load(fh)
        with open(os.path.join(bench, "layer_metrics", "forest_gather_roofline.json")) as fh:
            assert json.load(fh)["reader"] == "loop_roofline"
        family = lookup.load("families", "skrf")
    finally:
        sys.path.remove(bench)
    forest = synthetic_forest(rng, n_trees=config["n_trees"], depth=8,
                              n_features=config["n_features"])
    single, _, _ = v5e
    fn, layout, _fin, weights, levels = fv._build_fused_program(
        forest, list(BASE_FEATURES), "TGCA", True, "gather", None)
    assert levels == forest.max_depth
    n_rows = 6_055_937
    compiled = fn.lower(
        _weight_specs(weights, single),
        jax.ShapeDtypeStruct((n_rows, GENOME_ROW_WORDS), jnp.uint32, sharding=single),
        jax.ShapeDtypeStruct((163_840, layout.words), jnp.uint32, sharding=single)).compile()
    mem = compiled.memory_analysis()
    table_bytes = sum(a.nbytes for a in jax.tree.leaves(weights))
    genome_bytes = -(-n_rows // 8) * 8 * GENOME_ROW_WORDS * 4
    assert mem.temp_size_in_bytes < (1 << 30)
    assert table_bytes <= mem.argument_size_in_bytes - genome_bytes < 2 * table_bytes + (16 << 20)
    mark = family.walk_loop_operand(*forest.feature.shape)
    ops = [ln.strip() for ln in compiled.as_text().splitlines()
           if ln.strip().startswith("%") and " = " in ln]
    # a parameter, a tuple and its elements name the array but run nothing
    inert = (" parameter(", " get-tuple-element(", " tuple(")
    holding = [op for op in ops if mark in op and not any(k in op for k in inert)]
    assert len(holding) == 1 and " while(" in holding[0], holding
