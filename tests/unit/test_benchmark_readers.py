"""The benchmark's readers of the program's spans, counters and scopes
(``benchmarks/readers/``), each on a hand-made input whose answer is worked
out by hand. Tier-1: a reader that misreads would misstate every later PR's
per-layer numbers, and the benchmark's own tests (``benchmarks/``) are not
part of this suite."""

from __future__ import annotations

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
S = 1e9  # the trace's clock counts nanoseconds


@pytest.fixture()
def bench(monkeypatch):
    """The benchmark's directory on ``sys.path`` for the test's duration,
    as ``run_cell.py`` puts it, and its by-name lookup."""
    monkeypatch.syspath_prepend(BENCH)
    import lookup

    return lookup


def span(name, lo, hi, trace="t0"):
    return (name, lo * S, (hi - lo) * S, trace)


#: device busy [2,3] and [6,7] of a 10 s window: 8 s idle. Thread A is in
#: score_stage [0,9], waiting [1,4], featurizing [4,5]; thread B builds a
#: program [0.5,1.5] and parses [3.5,4.5]; nobody has a span open after 9.
THREADS = {
    "vctpu-io-w0": [span("score_stage", 0, 9), span("dispatch_wait", 1, 4),
                    span("host_featurize", 4, 5)],
    "vctpu-io-w1": [span("fused_program", 0.5, 1.5), span("parse", 3.5, 4.5)],
}
BUSY = [(2 * S, 3 * S), (6 * S, 7 * S)]
#: by hand, piece by piece:
#: [0,.5] A in score_stage itself (no group)            -> unexplained .5
#: [.5,1] B program                                      -> program .5
#: [1,1.5] A waits (no vote), B program                  -> program .5
#: [1.5,2] only the waiter                               -> unexplained .5
#: [3,3.5] only the waiter                               -> unexplained .5
#: [3.5,4] waiter, B parse                               -> io .5
#: [4,4.5] two voters: A feed, B io                      -> feed .25, io .25
#: [4.5,5] A feed                                        -> feed .5
#: [5,6] and [7,9] A in score_stage itself               -> unexplained 3
#: [9,10] an empty piece                                 -> unexplained 1
BY_HAND = {"program": 1.0, "feed": 0.75, "io": 0.75, "unexplained": 5.5}


def test_idle_by_span_attributes_a_hand_made_timeline(bench):
    reader = bench.load("readers", "idle_by_span")
    got = reader.attribute(THREADS, BUSY, (0.0, 10 * S))
    assert got == pytest.approx(BY_HAND)
    assert sum(got.values()) == pytest.approx(8.0)  # all of the idle time
    # busy time outside the window and spans across its edges are clipped
    wide = reader.attribute(
        {"a": [span("host_featurize", -5, 20)]},
        [(-3 * S, -1 * S)] + BUSY + [(12 * S, 13 * S)], (0.0, 10 * S))
    assert wide == pytest.approx({"program": 0, "feed": 8.0, "io": 0,
                                  "unexplained": 0})


def context(**kw):
    ops = [("fusion.1", 2 * S, 1 * S), ("forest_wide_block.1", 6 * S, 1 * S)]
    ctx = {"device_events": [ops], "traced_s": 10.5, "busy_s": 2.0,
           "spans": [("bench:file0:ref1", 0.0, 5 * S), ("bench:file1:ref2", 5 * S, 5 * S)],
           "obs_events": []}
    ctx.update(kw)
    return ctx


def test_the_four_idle_shares_add_up_to_the_idle_share(bench, monkeypatch):
    import program_spans

    reader = bench.load("readers", "idle_by_span")
    monkeypatch.setattr(program_spans, "load",
                        lambda *a: {"threads": THREADS, "devices": [], "on_tpu": True})
    ctx = context()
    got = {g: reader.read(ctx, g) for g in ("program", "feed", "io", "unexplained")}
    # the harness's interval is half a second longer than the files' spans:
    # that half second is idle, and nobody's
    assert got == pytest.approx({"program": 100 * 1.0 / 10.5, "feed": 100 * 0.75 / 10.5,
                                 "io": 100 * 0.75 / 10.5, "unexplained": 100 * 6.0 / 10.5})
    idle = bench.load("readers", "device_idle").read(ctx)
    assert sum(got.values()) == pytest.approx(idle)
    # a program that wrote no span: nothing to read, and no error
    monkeypatch.setattr(program_spans, "load",
                        lambda *a: {"threads": {}, "devices": [], "on_tpu": True})
    assert reader.read(context(), "program") is None


def final(**counters):
    return {"kind": "metrics", "name": "final", "counters": counters}


@pytest.mark.parametrize("counter, per_file, want", [
    ("predictor.builds", True, 13.5),
    ("predictor.builds", False, 27.0),
    ("jax.backend_compiles", True, 0.0),   # declared and never moved: a reading
    ("jax.cache_load_s", True, 0.625),     # in one file's snapshot only
    ("no.such.counter", True, None),       # a program that does not count it
])
def test_obs_counter_sums_the_traced_files_snapshots(bench, counter, per_file, want):
    events = [final(**{"predictor.builds": 13, "jax.backend_compiles": 0}),
              {"kind": "metrics", "name": "snapshot", "counters": {"predictor.builds": 99}},
              final(**{"predictor.builds": 14, "jax.backend_compiles": 0,
                       "jax.cache_load_s": 1.25})]
    got = bench.load("readers", "obs_counter").read(
        context(obs_events=events), counter, per_file=per_file)
    assert got == (pytest.approx(want) if want is not None else None)


def test_every_new_metric_names_a_reader_and_moves_throughput(bench):
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        bm = json.load(fh)
    new = {"host_featurize_work_share", "feed_work_share", "program_work_share",
           "device_wait_work_share", "predictor_builds_per_file",
           "backend_compiles_per_file", "cache_load_s_per_file",
           "idle_program_share", "idle_feed_share", "idle_io_share",
           "idle_unexplained_share"}
    by_name = {m["name"]: m for m in bm["per_layer"]}
    assert new <= set(by_name)
    for name in new:
        m = by_name[name]
        assert m["moves"] == "variants_per_s" and "workloads" not in m
        with open(os.path.join(BENCH, "layer_metrics", name + ".json"),
                  encoding="utf-8") as fh:
            how = json.load(fh)
        assert callable(bench.load("readers", how["reader"]).read)
    # the work shares split score_stage by the program's span names
    split = set()
    for name in ("host_featurize_work_share", "feed_work_share",
                 "program_work_share", "device_wait_work_share"):
        with open(os.path.join(BENCH, "layer_metrics", name + ".json"),
                  encoding="utf-8") as fh:
            split |= set(json.load(fh)["args"]["families"])
    groups = bench.load("readers", "idle_by_span").GROUPS
    assert split | {"score_finalize"} == \
        set(groups["program"]) | set(groups["feed"]) | {"dispatch_wait"}
