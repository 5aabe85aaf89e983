"""One predictor per model CONTENT per process (ISSUE 27): the content
digest, the single-flight miss of ``utils.keyed_cache`` and of
``_PREDICTOR_CACHE`` on top of it, and the first-call gate of a program
at a new bucket size."""

from __future__ import annotations

import dataclasses
import pickle
import threading
import time

import numpy as np
import pytest

from variantcalling_tpu import obs
from variantcalling_tpu.featurize import BASE_FEATURES
from variantcalling_tpu.models import registry
from variantcalling_tpu.models.threshold import ThresholdModel
from variantcalling_tpu.pipelines import filter_variants as fv
from variantcalling_tpu.synthetic import synthetic_dan, synthetic_forest
from variantcalling_tpu.utils import keyed_cache, trace

NAMES = list(BASE_FEATURES)
N_THREADS = 6
JOIN_S = 60


def _forest():
    f = synthetic_forest(np.random.default_rng(5), n_trees=4, depth=3,
                         n_features=len(NAMES))
    f.feature_names = list(NAMES)
    return f


def _dan():
    return synthetic_dan(np.random.default_rng(5), NAMES)


def _threshold():
    return ThresholdModel(feature_names=["qual", "dp"],
                          thresholds=np.asarray([30.0, 10.0], np.float32),
                          signs=np.asarray([1.0, 1.0], np.float32),
                          scales=np.asarray([5.0, 2.0], np.float32))


MAKERS = {"forest": _forest, "dan": _dan, "threshold": _threshold}


def _reload(model):
    """What every ``main()`` call does: a fresh unpickle of the model."""
    return pickle.loads(pickle.dumps(model))


def _lookup(entry: str, model, names=NAMES, **kw):
    if entry == "fused":
        return fv._fused_program(model, names, "TGCA", **kw)
    return fv._predictor_for(model, names, **kw)


@pytest.fixture()
def counters(tmp_path):
    """An open obs run; call it for (builds, reuses, waits)."""
    run = obs.start_run("test_predictor_cache",
                        force_path=str(tmp_path / "run.jsonl"))
    assert run is not None

    def read():
        snap = run.metrics.snapshot()["counters"]
        return tuple(snap.get(f"predictor.{k}", 0)
                     for k in ("builds", "reuses", "waits"))

    yield read
    obs.end_run(run, "ok")


def _wait_for(pred, what: str):
    deadline = time.monotonic() + JOIN_S
    while not pred():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.002)


def _run_threads(target, n=N_THREADS):
    """``target(i)`` on n threads released together -> [result | raised]."""
    barrier = threading.Barrier(n)
    out: list = [None] * n

    def body(i):
        barrier.wait(timeout=JOIN_S)
        try:
            out[i] = target(i)
        except BaseException as e:  # noqa: BLE001 - handed back to the test
            out[i] = e

    threads = [threading.Thread(target=body, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    return threads, out


def _join(threads):
    for t in threads:
        t.join(timeout=JOIN_S)
        assert not t.is_alive()


# ---------------------------------------------------------------------------
# the content digest
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", sorted(MAKERS))
def test_digest_is_equal_for_equal_content_and_memoised_per_object(family):
    model = MAKERS[family]()
    digest = registry.content_digest(model)
    assert digest.startswith(f"{family}:")
    assert registry.content_digest(_reload(model)) == digest
    assert registry.content_digest(MAKERS[family]()) == digest
    # once per object: the second call reads the memo, not the arrays
    memo = registry._DIGEST_MEMO[id(model)]
    assert memo[0]() is model and registry.content_digest(model) is memo[1]


def test_digest_memo_goes_with_the_model_and_survives_an_id_reuse():
    model = _forest()
    key = id(model)
    registry.content_digest(model)
    assert key in registry._DIGEST_MEMO
    del model
    assert key not in registry._DIGEST_MEMO
    # a stale entry under a reused id is not believed
    other = _threshold()
    registry._DIGEST_MEMO[id(other)] = (lambda: None, "forest:stale")
    assert registry.content_digest(other).startswith("threshold:")


def test_digest_refuses_what_is_not_a_registry_model():
    with pytest.raises(TypeError, match="no content digest"):
        registry.content_digest(object())


def _edited(model, field, edit):
    value = getattr(model, field)
    value = value.copy() if isinstance(value, np.ndarray) else value
    return dataclasses.replace(model, **{field: edit(value)})


def _bump(a):
    a.flat[0] += 1
    return a


FOREST_EDITS = {
    "feature": _bump, "threshold": _bump, "left": _bump, "right": _bump,
    "value": _bump,
    "threshold_dtype": lambda a: a.astype(np.float64),
    "value_shape": lambda a: a.reshape(a.shape[::-1]),
    "max_depth": lambda d: d + 1,
    "aggregation": lambda _a: "logit_sum",
    "base_score": lambda b: b + 0.25,
    "feature_names": lambda n: n[::-1],
    "pass_threshold": lambda p: p + 0.1,
    "default_left": lambda _d: np.zeros((4, 8), bool),
}


@pytest.mark.parametrize("edit", sorted(FOREST_EDITS))
def test_forest_digest_moves_with_every_field(edit):
    model = _forest()
    field = edit.split("_")[0] if edit.endswith(("_dtype", "_shape")) else edit
    other = _edited(model, field, FOREST_EDITS[edit])
    assert registry.content_digest(other) != registry.content_digest(model)


@pytest.mark.parametrize("field", ["thresholds", "signs", "scales",
                                   "feature_names", "pass_threshold"])
def test_threshold_digest_moves_with_every_field(field):
    model = _threshold()
    edit = {"feature_names": lambda n: n[::-1],
            "pass_threshold": lambda p: p + 0.1}.get(field, _bump)
    other = _edited(model, field, edit)
    assert registry.content_digest(other) != registry.content_digest(model)


# ---------------------------------------------------------------------------
# (a) equal content -> one program; (b) any difference -> a miss
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("entry", ["fused", "matrix"])
@pytest.mark.parametrize("family", sorted(MAKERS))
def test_two_unpickles_of_one_model_share_one_program(
        family, entry, fresh_predictor_cache, counters):
    model = MAKERS[family]()
    first, second = _reload(model), _reload(model)
    assert first is not second
    a = _lookup(entry, first)
    assert counters() == (1, 0, 0)
    b = _lookup(entry, second)
    assert counters() == (1, 1, 0)
    assert b is a and b[0] is a[0]  # the same jit object
    assert len(fresh_predictor_cache) == 1


def _one_dan_weight(model):
    params = {k: v.copy() for k, v in model.params_np.items()}
    params["w_in"].flat[0] += 1e-3
    return dataclasses.replace(model, params_np=params)


MISSES = {
    "forest_threshold": (_forest, lambda m: (
        _edited(m, "threshold", _bump), NAMES, {})),
    "dan_weight": (_dan, lambda m: (_one_dan_weight(m), NAMES, {})),
    "threshold_scale": (_threshold, lambda m: (
        _edited(m, "scales", _bump), NAMES, {})),
    "feature_order": (_forest, lambda m: (
        _reload(m), NAMES[1:] + NAMES[:1], {})),
    "strategy_token": (_forest, lambda m: (
        _reload(m), NAMES, {"strategy": "gather"})),
}


@pytest.mark.parametrize("entry", ["fused", "matrix"])
@pytest.mark.parametrize("case", sorted(MISSES))
def test_a_model_or_key_that_differs_never_shares(
        case, entry, fresh_predictor_cache, counters):
    make, change = MISSES[case]
    model = make()
    base = _lookup(entry, model)
    other_model, names, kw = change(model)
    other = _lookup(entry, other_model, names, **kw)
    assert other is not base and other[0] is not base[0]
    assert counters() == (2, 0, 0)
    # and each is found again under its own key
    assert _lookup(entry, _reload(model)) is base
    assert _lookup(entry, _reload(other_model), names, **kw) is other
    assert counters() == (2, 2, 0)


def test_wide_path_knobs_are_part_of_the_key(fresh_predictor_cache,
                                             monkeypatch):
    from variantcalling_tpu.models import forest as forest_mod

    model = _forest()
    base = _lookup("fused", model)
    monkeypatch.setenv(forest_mod.WIDE_CHUNK_ENV, "64")
    assert _lookup("fused", _reload(model)) is not base


def test_forest_finalize_resolves_finalize_margin_at_call_time(
        fresh_predictor_cache, monkeypatch):
    """The benchmark's planted fault patches ``forest.finalize_margin``
    and relies on a cache HIT still seeing the patch."""
    from variantcalling_tpu.models import forest as forest_mod

    finalize = _lookup("fused", _forest()).finalize
    monkeypatch.setattr(forest_mod, "finalize_margin",
                        lambda m, _forest: np.full_like(m, 7.0))
    again = _lookup("fused", _forest()).finalize
    assert again is finalize
    assert (again(np.zeros(3, np.float32)) == 7.0).all()


def test_clear_empties_the_cache_and_the_next_lookup_builds(
        fresh_predictor_cache, counters):
    model = _forest()
    _lookup("fused", model)
    assert len(fv._PREDICTOR_CACHE) == 1
    fv._PREDICTOR_CACHE.clear()
    assert len(fv._PREDICTOR_CACHE) == 0
    _lookup("fused", model)
    assert counters() == (2, 0, 0)


def test_cache_stays_bounded_fifo(fresh_predictor_cache):
    for i in range(fv._PREDICTOR_CACHE_MAX + 3):
        fv._predictor_for(_forest(), NAMES[i % 3:] + NAMES[:i % 3],
                          strategy=("gather", "gemm", "wide", "auto")[i // 3])
    assert len(fv._PREDICTOR_CACHE) <= fv._PREDICTOR_CACHE_MAX


# ---------------------------------------------------------------------------
# (c) / (d): the miss is single flight
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("entry", ["fused", "matrix"])
def test_threads_on_an_empty_cache_build_once(
        entry, fresh_predictor_cache, counters, monkeypatch, tmp_path):
    real = fv._raw_predictor
    calls = []

    def slow(*a, **kw):  # holds the build until every other thread waits
        calls.append(threading.current_thread().name)
        _wait_for(lambda: fv._PREDICTOR_CACHE.waiting() == N_THREADS - 1,
                  "the other threads to wait on the build")
        return real(*a, **kw)

    monkeypatch.setattr(fv, "_raw_predictor", slow)
    model = _forest()
    spans: list = [None] * N_THREADS

    def lookup(i):
        with trace.stage("fused_program", built=False) as sp:
            got = _lookup(entry, _reload(model))
            spans[i] = dict(sp.fields)
        return got

    threads, out = _run_threads(lookup)
    _join(threads)
    assert len(calls) == 1
    assert all(o is out[0] for o in out) and isinstance(out[0], tuple)
    assert counters() == (1, 0, N_THREADS - 1)
    assert sorted(s.get("built", False) for s in spans) \
        == [False] * (N_THREADS - 1) + [True]
    assert sorted(s.get("waited", False) for s in spans) \
        == [False] + [True] * (N_THREADS - 1)
    assert fv._PREDICTOR_CACHE.waiting() == 0


@pytest.mark.parametrize("entry", ["fused", "matrix"])
def test_a_failed_build_reaches_every_waiter_and_leaves_nothing(
        entry, fresh_predictor_cache, counters, monkeypatch):
    real = fv._raw_predictor
    boom = RuntimeError("the build failed")

    def failing(*a, **kw):
        _wait_for(lambda: fv._PREDICTOR_CACHE.waiting() == N_THREADS - 1,
                  "the other threads to wait on the build")
        raise boom

    monkeypatch.setattr(fv, "_raw_predictor", failing)
    model = _forest()
    threads, out = _run_threads(lambda _i: _lookup(entry, _reload(model)))
    _join(threads)
    assert all(o is boom for o in out)
    assert len(fv._PREDICTOR_CACHE) == 0 and fv._PREDICTOR_CACHE.waiting() == 0
    assert counters() == (0, 0, 0)
    monkeypatch.setattr(fv, "_raw_predictor", real)
    assert isinstance(_lookup(entry, _reload(model)), tuple)
    assert counters() == (1, 0, 0)


# ---------------------------------------------------------------------------
# (e) a program's first call at a bucket size
# ---------------------------------------------------------------------------


class _TracedProgram:
    """A jit object whose traces are counted, and held open at ``held``."""

    def __init__(self, held=(64,)):
        import jax

        self.traces: list = []
        self.hold = threading.Event()
        self.hold.set()

        def body(x):
            self.traces.append(x.shape)
            if x.shape == held:
                assert self.hold.wait(timeout=JOIN_S)
            return x * 2.0 + 1.0

        self.fn = jax.jit(body)


def _rows(n):
    import jax.numpy as jnp

    return (jnp.arange(n, dtype=jnp.float32),)


def test_first_call_at_a_bucket_size_is_made_by_one_thread(counters):
    prog = _TracedProgram()
    prog.hold.clear()
    sig = (64, "shapes")
    waited: list = [None] * N_THREADS

    def call(i):
        with trace.stage("dispatch_enqueue", rows=64, waited=False) as sp:
            res = fv._enqueue(prog.fn, sig, _rows(64))
            waited[i] = sp.fields["waited"]
        return np.asarray(res)

    threads, out = _run_threads(call)
    _wait_for(lambda: fv._FIRST_CALLS.waiting() == N_THREADS - 1,
              "the other threads to wait on the first call")
    # a second size does not wait on the first: it traces and returns
    # while the first size's first call is still held open
    other = np.asarray(fv._enqueue(prog.fn, (32, "shapes"), _rows(32)))
    assert other.shape == (32,) and prog.traces == [(64,), (32,)]
    assert fv._FIRST_CALLS.waiting() == N_THREADS - 1
    prog.hold.set()
    _join(threads)
    want = np.arange(64, dtype=np.float32) * 2.0 + 1.0
    for o in out:
        np.testing.assert_array_equal(o, want)
    assert prog.traces == [(64,), (32,)] and prog.fn._cache_size() == 2
    assert counters() == (0, 0, N_THREADS - 1)
    assert sorted(waited) == [False] + [True] * (N_THREADS - 1)
    # called once, the gate is a set lookup: no flight, no wait, no trace
    assert fv._CALLED_AT[prog.fn] == {sig, (32, "shapes")}
    threads, out = _run_threads(call)
    _join(threads)
    assert counters() == (0, 0, N_THREADS - 1) and len(prog.traces) == 2
    assert fv._FIRST_CALLS.waiting() == 0


def test_a_failed_first_call_reaches_every_waiter_and_is_made_again(counters):
    import jax

    state = {"fail": True}
    traces = []

    def body(x):
        traces.append(x.shape)
        if state["fail"]:
            _wait_for(lambda: fv._FIRST_CALLS.waiting() == N_THREADS - 1,
                      "the other threads to wait on the first call")
            raise ValueError("the trace failed")
        return x + 1.0

    fn = jax.jit(body)
    sig = (16, "shapes")
    threads, out = _run_threads(lambda _i: fv._enqueue(fn, sig, _rows(16)))
    _join(threads)
    assert all(isinstance(o, ValueError) for o in out)
    assert all(o is out[0] for o in out) and len(traces) == 1
    assert sig not in fv._CALLED_AT.get(fn, ())
    state["fail"] = False
    assert np.asarray(fv._enqueue(fn, sig, _rows(16)))[0] == 1.0
    assert sig in fv._CALLED_AT[fn] and len(traces) == 2


def test_called_sizes_go_with_the_program():
    import gc
    import weakref

    prog = _TracedProgram()
    fv._enqueue(prog.fn, (8, "shapes"), _rows(8))
    assert prog.fn in fv._CALLED_AT
    ref = weakref.ref(prog.fn)
    del prog
    gc.collect()
    assert ref() is None  # the table did not keep the program alive
    assert all(k is not None for k in fv._CALLED_AT)


def test_dispatch_gates_each_bucket_on_size_and_window_shape(
        fresh_predictor_cache, monkeypatch):
    """``_dispatch_fused`` hands ``_enqueue`` the bucket size and what else
    makes jax trace anew, under a ``dispatch_enqueue`` span. The wire's
    layout is static, so no dtype of a chunk's columns is part of it."""
    from tests.fixtures import fused_inputs_with_host_windows
    from variantcalling_tpu.featurize import _bucket as featurize_bucket
    from variantcalling_tpu.parallel import shard_score

    seen = []
    real = fv._enqueue

    def spy(fn, sig, call_args):
        seen.append((sig, trace.current_span(), call_args))
        return real(fn, sig, call_args)

    monkeypatch.setattr(fv, "_enqueue", spy)
    n = 300
    model = _forest()
    program = fv._fused_program(model, NAMES, "TGCA")
    layout = program[1]
    fi = fused_inputs_with_host_windows(n, program, NAMES)
    plan = shard_score.resolve_plan("jit")
    if plan.devices != 1:
        pytest.skip("single-device dispatch only")
    scores = fv._dispatch_fused([fi], plan)
    assert scores.shape == (n,)
    (sig, _span, call_args), = seen
    assert sig == (featurize_bucket(n), (41,))
    # the model's weights (the one device copy the program was built
    # with), then one buffer beside the windows: the rows are two arrays
    assert call_args[0] is program.weights
    assert [tuple(a.shape) for a in call_args[1:]] \
        == [(featurize_bucket(n), 41), (featurize_bucket(n), layout.words)]


# ---------------------------------------------------------------------------
# the shared primitive
# ---------------------------------------------------------------------------


def test_keyed_cache_hit_built_waited_and_distinct_keys():
    cache = keyed_cache.KeyedCache(4)
    release = threading.Event()

    def slow():
        assert release.wait(timeout=JOIN_S)
        return "slow"

    threads, out = _run_threads(lambda _i: cache.get("a", slow), n=3)
    _wait_for(lambda: cache.waiting() == 2, "two waiters")
    # a distinct key builds while "a" is in flight
    assert cache.get("b", lambda: "fast") == ("fast", keyed_cache.BUILT)
    release.set()
    _join(threads)
    assert sorted(how for _v, how in out) \
        == [keyed_cache.BUILT, keyed_cache.WAITED, keyed_cache.WAITED]
    assert {v for v, _how in out} == {"slow"}
    assert cache.get("a", slow) == ("slow", keyed_cache.HIT)
    assert "a" in cache and "c" not in cache and len(cache) == 2
    assert dict(cache.items()) == {"a": "slow", "b": "fast"}


def test_keyed_cache_builds_each_key_once_under_a_thread_storm():
    """More threads than cores, a shortened switch interval: a lost
    update in the tables would show as a second build of a key or as two
    threads holding different values for it."""
    import os
    import sys

    n_threads, keys = 4 * (os.cpu_count() or 4), list(range(16))
    cache = keyed_cache.KeyedCache(len(keys))
    builds: list = []

    def build(k):
        builds.append(k)
        time.sleep(0.001)
        return object()

    def storm(i):
        order = keys[i % len(keys):] + keys[:i % len(keys)]
        return [cache.get(k, lambda k=k: build(k)) for k in order]

    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads, out = _run_threads(storm, n=n_threads)
        _join(threads)
    finally:
        sys.setswitchinterval(prev)
    assert sorted(builds) == keys
    values = dict(cache.items())
    for i, got in enumerate(out):
        order = keys[i % len(keys):] + keys[:i % len(keys)]
        assert [v for v, _how in got] == [values[k] for k in order]
    hows = [how for got in out for _v, how in got]
    assert hows.count(keyed_cache.BUILT) == len(keys) and cache.waiting() == 0


def test_keyed_cache_keeps_a_none_value():
    cache = keyed_cache.KeyedCache(2)
    assert cache.get("k", lambda: None) == (None, keyed_cache.BUILT)
    assert cache.get("k", lambda: 1 / 0) == (None, keyed_cache.HIT)


def test_keyed_cache_evicts_first_in_and_tells_on_evict():
    gone = []
    cache = keyed_cache.KeyedCache(2, on_evict=gone.append)
    for k in "abc":
        cache.get(k, lambda k=k: k.upper())
    assert gone == ["a"] and dict(cache.items()) == {"b": "B", "c": "C"}
    assert cache.get("a", lambda: "again") == ("again", keyed_cache.BUILT)
    assert gone == ["a", "b"]


def test_single_flight_failure_is_shared_and_not_remembered():
    flights = keyed_cache.SingleFlight()
    boom = KeyError("once")

    def failing():
        _wait_for(lambda: flights.waiting() == 2, "two waiters")
        raise boom

    threads, out = _run_threads(lambda _i: flights.do("k", failing), n=3)
    _join(threads)
    assert all(o is boom for o in out) and flights.waiting() == 0
    assert flights.do("k", lambda: 5) == (5, False)


def test_single_flight_clear_lets_a_new_leader_in():
    flights = keyed_cache.SingleFlight()
    release = threading.Event()
    threads, out = _run_threads(
        lambda _i: flights.do("k", lambda: release.wait(timeout=JOIN_S) and "old"),
        n=1)
    _wait_for(lambda: "k" in flights._flights, "the first flight")
    flights.clear()
    assert flights.do("k", lambda: "new") == ("new", False)
    release.set()
    _join(threads)
    assert out == [("old", False)] and not flights._flights


def test_serve_resident_cache_counts_hits_and_misses(caplog):
    from variantcalling_tpu.serve import state

    cache = state._KeyedCache("model", 1)
    assert cache.get(("a", 1), lambda: "A") == "A"
    assert cache.get(("a", 1), lambda: 1 / 0) == "A"
    assert cache.get(("b", 1), lambda: "B") == "B"  # evicts ("a", 1)
    assert cache.stats() == {"entries": 1, "hits": 1, "misses": 2}


def test_device_genome_is_built_once_for_racing_threads(tmp_path, monkeypatch):
    from variantcalling_tpu import featurize

    built = []
    monkeypatch.setattr(featurize, "_DEVICE_GENOME_CACHE",
                        keyed_cache.KeyedCache(featurize._DEVICE_GENOME_MAX))

    def build(fasta, radius, sharding):
        built.append(fasta.path)
        _wait_for(lambda: featurize._DEVICE_GENOME_CACHE.waiting() == 2,
                  "two waiters")
        return object()

    monkeypatch.setattr(featurize, "_build_device_genome", build)

    class Fasta:
        path = str(tmp_path / "racing.fa")

    threads, out = _run_threads(lambda _i: featurize.device_genome(Fasta()), n=3)
    _join(threads)
    assert built == [Fasta.path] and all(o is out[0] for o in out)
    assert (Fasta.path, featurize.WINDOW_RADIUS, "None") \
        in featurize._DEVICE_GENOME_CACHE
