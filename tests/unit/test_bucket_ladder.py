"""The bucket ladder (``featurize._bucket``): the padded size of a dispatch.

Powers of two from 1,024 up to 32,768 rows, four rungs an octave above
(``m * 2**k``, ``m`` in 4..7), capped at ``CHUNK`` by ``_dispatch_fused``.
The properties over every row count, the rungs the benchmark's cells ride,
the layout census per device count, and the guarantee that lets the ladder
move at all: a row's score bits do not depend on the bucket it rode in.
"""

from __future__ import annotations

import numpy as np
import pytest

from variantcalling_tpu import featurize
from variantcalling_tpu.featurize import BASE_FEATURES, _bucket
from variantcalling_tpu.pipelines import filter_variants as fv
from variantcalling_tpu.synthetic import synthetic_dan, synthetic_forest
from tests.fixtures import fused_inputs_with_host_windows

NAMES = list(BASE_FEATURES)
FLOOR = 1 << 15


def _pow2(n: int) -> int:
    """The ladder before the quarter-octave rungs: the next power of two."""
    return max(featurize._PAD_MIN, 1 << (n - 1).bit_length())


@pytest.fixture(scope="module")
def sweep():
    """``(n, bucket(n))`` for EVERY row count a dispatch can have."""
    n = np.arange(1, fv.CHUNK + 1, dtype=np.int64)
    return n, np.array([_bucket(int(k)) for k in n], dtype=np.int64)


def _holds_rows(n, b):
    return (b >= n).all()


def _monotone(n, b):
    return (np.diff(b) >= 0).all()


def _idempotent(n, b):
    return all(_bucket(int(r)) == r for r in np.unique(b))


def _pow2_up_to_floor(n, b):
    low = b[n <= FLOOR]
    return (low == [_pow2(int(k)) for k in n[n <= FLOOR]]).all() \
        and (low >= featurize._PAD_MIN).all()


def _multiple_of_8192_above_floor(n, b):
    return (b[n > FLOOR] % 8192 == 0).all()


def _padding_under_a_fifth_above_floor(n, b):
    high = n > FLOOR
    return (4 * b[high] <= 5 * n[high]).all()  # bucket <= 1.25 n, in integers


def _never_above_the_power_of_two(n, b):
    return (b <= [_pow2(int(k)) for k in n]).all()


def _capped_at_chunk(n, b):
    return b.max() == fv.CHUNK == _bucket(fv.CHUNK)


@pytest.mark.parametrize("prop", [
    _holds_rows, _monotone, _idempotent, _pow2_up_to_floor,
    _multiple_of_8192_above_floor, _padding_under_a_fifth_above_floor,
    _never_above_the_power_of_two, _capped_at_chunk], ids=lambda f: f.__name__.strip("_"))
def test_ladder_property_over_every_row_count(sweep, prop):
    assert prop(*sweep)


def test_ladder_has_eighteen_rungs(sweep):
    rungs = sorted(set(sweep[1].tolist()))
    assert rungs == [1 << k for k in range(10, 16)] + [
        m << k for k in (13, 14, 15) for m in (5, 6, 7, 8)]


@pytest.mark.parametrize("rows,rung", [
    (1, 1024), (1024, 1024), (1025, 2048), (20_887, 32_768), (32_768, 32_768),
    (32_769, 40_960), (36_750, 40_960),  # a quarter of a wgs chunk
    (50_000, 57_344),                    # an exome request: 7 * 2**13
    (83_547, 98_304),                    # the wgs cells' tail chunk: 6 * 2**14
    (147_000, 163_840),                  # a wgs chunk of 8 MiB: 5 * 2**15
    (163_841, 196_608), (229_377, 262_144), (262_144, 262_144)])
def test_rows_ride_the_rung(rows, rung):
    assert _bucket(rows) == rung


@pytest.mark.parametrize("dp", [1, 2, 4])
def test_census_counts_eighteen_layouts(dp):
    """What ``_dispatch_fused`` does to a rung (round up to a ``dp``
    multiple, cap at the chunk size) adds no layout and merges none."""
    from tools import jaxpr_audit as ja

    layouts = ja.layout_census(dp)
    assert len(layouts) == 18
    assert all(d == dp and rows % dp == 0 for d, rows in layouts)
    assert {rows for _, rows in layouts} == {_bucket(r) for _, r in layouts}


# -- a row's bits do not depend on its bucket -----------------------------------


def _model(family: str):
    if family == "dan":
        return synthetic_dan(np.random.default_rng(5), NAMES)
    f = synthetic_forest(np.random.default_rng(3), n_trees=6, depth=4,
                         n_features=len(NAMES))
    f.feature_names = list(NAMES)
    return f


@pytest.mark.parametrize("dp", [1, 2])
@pytest.mark.parametrize("rows,rung", [(36_000, 5 << 13), (50_000, 7 << 13)])
@pytest.mark.parametrize("family", ["forest", "dan"])
def test_dispatch_bits_equal_the_power_of_two_buckets(
        fresh_predictor_cache, monkeypatch, family, rows, rung, dp):
    """A table scored through ``_dispatch_fused`` in a five- or seven-rung
    bucket gets, row for row, the bits it got in the power-of-two bucket
    (65,536 rows), on one device and sharded over two."""
    from variantcalling_tpu import engine as engine_mod
    from variantcalling_tpu.parallel import shard_score

    monkeypatch.setenv("VCTPU_ENGINE", "jit")
    monkeypatch.setenv("VCTPU_MESH_DEVICES", str(dp))
    engine_mod.reset_for_tests()
    plan = shard_score.resolve_plan("jit")
    assert plan.devices == dp
    program = fv._fused_program(_model(family), NAMES, "TGCA",
                                mesh=shard_score.mesh_for(plan))
    fi = fused_inputs_with_host_windows(rows, program, NAMES, seed=rows)
    seen = []
    real = fv._enqueue
    monkeypatch.setattr(fv, "_enqueue", lambda fn, sig, args: (
        seen.append(sig[0]), real(fn, sig, args))[1])
    on_rung = fv._dispatch_fused([fi], plan)
    monkeypatch.setattr(featurize, "_bucket", _pow2)
    on_pow2 = fv._dispatch_fused([fi], plan)
    assert seen == [rung, 1 << 16]
    assert on_rung.dtype == np.float32 and on_rung.shape == (rows,)
    assert np.array_equal(on_rung, on_pow2)  # bitwise
    assert len(np.unique(on_rung)) > 10  # varying, not trivially equal


# -- the mechanism's counters ---------------------------------------------------


@pytest.mark.parametrize("sizes", [(300,), (1500, 700, 36_000)], ids=["one", "three"])
def test_feed_rows_and_padded_rows_count_what_was_sent(
        fresh_predictor_cache, monkeypatch, tmp_path, sizes):
    """``feed.rows`` is the real rows sent, ``feed.padded_rows`` the rows of
    the buckets sent: one bucket for the packed rows of a dispatch."""
    from variantcalling_tpu import engine as engine_mod, obs
    from variantcalling_tpu.parallel import shard_score

    monkeypatch.setenv("VCTPU_ENGINE", "jit")
    monkeypatch.setenv("VCTPU_MESH_DEVICES", "1")
    engine_mod.reset_for_tests()
    plan = shard_score.resolve_plan("jit")
    program = fv._fused_program(_model("forest"), NAMES, "TGCA")
    inputs = [fused_inputs_with_host_windows(n, program, NAMES, seed=n) for n in sizes]
    run = obs.start_run("test_bucket_ladder", force_path=str(tmp_path / "obs.jsonl"))
    try:
        scores = fv._dispatch_fused(inputs, plan)
        counters = run.metrics.snapshot()["counters"]
    finally:
        obs.end_run(run, "ok")
    assert scores.shape == (sum(sizes),)
    assert counters["feed.dispatches"] == 1
    assert counters["feed.rows"] == sum(sizes)
    assert counters["feed.padded_rows"] == _bucket(sum(sizes))
