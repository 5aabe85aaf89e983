"""The benchmark's readers of the program's spans, counters and scopes
(``benchmarks/readers/``), each on a hand-made input whose answer is worked
out by hand, and the required work a family states, against the program's
operand shapes. Tier-1: a reader that misreads would misstate every later PR's
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


# -- the serve cell's readers (ISSUE 30) ---------------------------------------

def serve_span(name, dur, req="r1"):
    return {"kind": "span", "name": name, "dur": dur, "req": req}


#: two requests: 4 s and 8 s from end to end, 1 s and 2 s of them in admission,
#: their pipelines 2 s and 4 s of wall
SERVED = [serve_span("serve_request", 4.0), serve_span("serve_admit", 1.0),
          serve_span("serve_request", 8.0, "r2"), serve_span("serve_admit", 2.0, "r2"),
          serve_span("score_stage", 3.0),
          {"kind": "profile", "name": "pipeline", "wall_s": 2.0, "req": "r1"},
          {"kind": "profile", "name": "pipeline", "wall_s": 4.0, "req": "r2"}]
SERVE_FINAL = [final(**{"serve.requests_genome_resident": 7, "served": 10,
                        "declared": 0})]


@pytest.mark.parametrize("reader, args, ctx, want", [
    ("wall_quantile", {"q": 0.5}, {"file_walls": [5.0, 1.0, 3.0, 2.0, 4.0]}, 3.0),
    # 0.95 x 4 intervals = 3.8: four fifths of the way from 4.0 to 5.0
    ("wall_quantile", {"q": 0.95}, {"file_walls": [5.0, 1.0, 3.0, 2.0, 4.0]}, 4.8),
    ("wall_quantile", {"q": 0.95}, {"file_walls": [2.5]}, 2.5),
    ("wall_quantile", {"q": 0.5}, {"file_walls": []}, None),
    ("span_share", {"part": "serve_admit", "whole": "serve_request"},
     {"obs_events": SERVED}, 100 * 3.0 / 12.0),
    ("span_share", {"part": "serve_admit", "whole": "serve_request"},
     {"obs_events": [serve_span("score_stage", 3.0)]}, None),  # a program without the span
    # 1 - 6 s of pipeline over (12 - 3) s holding a slot
    ("daemon_overhead", {}, {"obs_events": SERVED}, 100 * (1 - 6.0 / 9.0)),
    ("daemon_overhead", {}, {"obs_events": SERVED[4:]}, None),
    ("counter_ratio", {"part": "serve.requests_genome_resident", "whole": "served"},
     {"obs_events": SERVE_FINAL}, 70.0),
    ("counter_ratio", {"part": "declared", "whole": "served"},
     {"obs_events": SERVE_FINAL}, 0.0),      # declared and never moved: a reading
    ("counter_ratio", {"part": "no.such.counter", "whole": "served"},
     {"obs_events": SERVE_FINAL}, None),     # a program that does not count it
    ("counter_ratio", {"part": "served", "whole": "declared"},
     {"obs_events": SERVE_FINAL}, None),     # nothing to divide by
])
def test_serve_reader_on_hand_made_events(bench, reader, args, ctx, want):
    got = bench.load("readers", reader).read(ctx, **args)
    assert got == (pytest.approx(want) if want is not None else None)


def test_the_serve_cells_metrics_name_readers_and_list_only_that_cell(bench):
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        bm = json.load(fh)
    cell = "forest-t40d6-hg38x2-exome.serve-c4"
    new = {"request_p50_s": "daemon front", "request_p95_s": "daemon front",
           "admission_wait_share": "admission",
           "daemon_overhead_share": "daemon front",
           "resident_requests_share": "device featurize and score"}
    by_name = {m["name"]: m for m in bm["per_layer"]}
    for name, layer in new.items():
        m = by_name[name]
        assert (m["workloads"], m["moves"], m["layer"]) == ([cell], "variants_per_s", layer)
        with open(os.path.join(BENCH, "layer_metrics", name + ".json"),
                  encoding="utf-8") as fh:
            how = json.load(fh)
        assert callable(bench.load("readers", how["reader"]).read)
    # the cell has one chip, with nothing cut, and no roofline lists it
    (entry,) = [w for w in bm["workloads"] if w["name"] == cell]
    (config,) = [c for c in bm["configs"] if c["name"] == entry["config"]]
    assert entry["chips"] == 1 and config["reduced"] == []
    assert cell not in by_name["forest_wide_block_roofline"]["workloads"]
    with open(os.path.join(BENCH, "traffic", "serve-c4.json"), encoding="utf-8") as fh:
        traffic = json.load(fh)
    assert {k: traffic[k] for k in ("driver", "clients", "think_s", "warm",
                                    "warmup_requests_per_reference", "trace_requests",
                                    "check_sample_per_file")} == {
        "driver": "closed_loop_serve", "clients": 4, "think_s": 0, "warm": True,
        "warmup_requests_per_reference": 1, "trace_requests": 32,
        "check_sample_per_file": 65536}


def test_forest_required_flops_match_the_programs_gemm_shapes(bench):
    """The work ``step_mfu`` and ``forest_wide_block_roofline`` divide by
    (``families/forest.py``, from the configuration's shapes alone) must
    equal the FLOPs implied by the ACTUAL ``forest.to_gemm`` operand shapes
    of a forest of that configuration: the count cannot drift from the
    packing."""
    import numpy as np

    from variantcalling_tpu.models import forest as fmod
    from variantcalling_tpu.synthetic import synthetic_forest

    with open(os.path.join(BENCH, "configs", "forest-t40d6-hg38x2.json")) as fh:
        config = json.load(fh)
    f = synthetic_forest(np.random.default_rng(0), n_trees=config["n_trees"],
                         depth=config["depth"], n_features=config["n_features"])
    gf = fmod.to_gemm(f, config["n_features"])
    t, fdim, i = gf.a.shape
    l = gf.m2.shape[2]
    assert (t, fdim, i, l) == (config["n_trees"], config["n_features"],
                               config["n_internal"], config["n_leaves"])
    assert bench.load("families", "forest").flops_per_variant(config) \
        == 2 * t * (fdim * i + i * l + l) == 111_680


# -- the four-chip host (ISSUE 34) ---------------------------------------------

#: four chips over a 10 s interval: busy 1, 2, 2 (two operations that
#: overlap for half a second count once) and 0.5 s
FOUR_CHIPS = [[("fusion.1", 1 * S, 1 * S)],
              [("fusion.1", 1 * S, 2 * S)],
              [("fusion.1", 1 * S, 1.5 * S), ("fusion.2", 2 * S, 1 * S)],
              [("fusion.1", 9 * S, 0.5 * S)]]


@pytest.mark.parametrize("devices, what, want", [
    (FOUR_CHIPS, "least", 5.0), (FOUR_CHIPS, "spread", 15.0),
    (FOUR_CHIPS[:2], "spread", 10.0),
    (FOUR_CHIPS[:1], "least", None),   # one chip: no balance to read
    (FOUR_CHIPS[:1], "spread", None),
])
def test_chip_busy_reads_each_chips_share_of_the_traced_interval(
        bench, devices, what, want):
    reader = bench.load("readers", "chip_busy")
    got = reader.read(context(device_events=devices, traced_s=10.0), what)
    assert got == (pytest.approx(want) if want is not None else None)
    if len(devices) == 4:
        assert reader.shares(context(device_events=devices, traced_s=10.0)) \
            == pytest.approx([10.0, 20.0, 20.0, 5.0])


HOST4_CELL = "forest-t40d6-hg38x2-host4.wgs-batch"
HOST4_METRICS = {
    "mesh_devices": ("scoring mesh", "obs_counter", final(**{"mesh.devices": 4}), 4.0),
    "megabatch_fill_share": (
        "scoring mesh", "counter_ratio",
        final(**{"mesh.rows": 2_000_000, "mesh.padded_rows": 14 * 262_144}),
        100 * 2_000_000 / (14 * 262_144)),
    "mesh_dispatches_per_file": ("scoring mesh", "obs_counter",
                                 final(**{"mesh.dispatches": 14}), 14.0),
    "mesh_chunks_per_file": ("scoring mesh", "obs_counter",
                             final(**{"mesh.chunks": 14}), 14.0),
    "megabatch_pack_work_share": ("scoring mesh", "stage_share", None, 25.0),
    "chip_busy_min_share": ("device", "chip_busy", None, 5.0),
    "chip_busy_spread": ("device", "chip_busy", None, 15.0),
}


@pytest.mark.parametrize("name", sorted(HOST4_METRICS))
def test_the_host4_cells_metrics_read_what_the_program_writes(bench, name):
    """Each metric of the four-chip cell lists only that cell, names its
    reader, reads a hand-made context, and reads nothing (and does not
    raise) from a program that lacks the counter or span, as the parent does."""
    layer, reader, snapshot, want = HOST4_METRICS[name]
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        bm = json.load(fh)
    (m,) = [m for m in bm["per_layer"] if m["name"] == name]
    assert (m["workloads"], m["moves"], m["layer"]) == ([HOST4_CELL], "variants_per_s", layer)
    with open(os.path.join(BENCH, "layer_metrics", name + ".json"), encoding="utf-8") as fh:
        how = json.load(fh)
    assert how["reader"] == reader
    read = bench.load("readers", reader).read
    events = [snapshot] if snapshot else [
        {"kind": "profile", "name": "stage", "stage": "megabatch_pack", "work_s": 0.125},
        {"kind": "profile", "name": "pipeline", "wall_s": 0.5}]
    ctx = context(device_events=FOUR_CHIPS, traced_s=10.0, obs_events=events)
    assert read(ctx, **how["args"]) == pytest.approx(want)
    parent = context(obs_events=[final(**{"feed.dispatches": 14}),
                                 {"kind": "profile", "name": "pipeline", "wall_s": 0.5}])
    assert read(parent, **how["args"]) is None


@pytest.mark.parametrize("rows,padded,want", [
    (2_000_000, 13 * 163_840 + 98_304, 89.757),  # a wgs file on the rungs
    (2_000_000, 13 * 262_144 + 131_072, 56.514),  # the same file on powers of two
    (50_000, 57_344, 87.193)])                    # an exome request
def test_feed_fill_share_reads_the_feeds_two_counters(bench, rows, padded, want):
    """PR 35's one metric: every cell the benchmark had then lists it (a later
    PR's entries come after it and its cells), the accepted reader reads it,
    and a program without the counters (the parent) gives nothing to read."""
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        bm = json.load(fh)
    (m,) = [m for m in bm["per_layer"] if m["name"] == "feed_fill_share"]
    assert bm["per_layer"][34] is m
    assert m["workloads"] == [w["name"] for w in bm["workloads"]][:4]
    assert (m["moves"], m["layer"], m["source"], m["unit"], m["better"]) == (
        "variants_per_s", "streaming executor score stage", "program_counter", "%", "higher")
    with open(os.path.join(BENCH, "layer_metrics", "feed_fill_share.json"),
              encoding="utf-8") as fh:
        how = json.load(fh)
    assert how == {"reader": "counter_ratio",
                   "args": {"part": "feed.rows", "whole": "feed.padded_rows"}}
    read = bench.load("readers", how["reader"]).read
    ctx = context(obs_events=[final(**{"feed.rows": rows, "feed.padded_rows": padded})])
    assert read(ctx, **how["args"]) == pytest.approx(want, abs=1e-3)
    parent = context(obs_events=[final(**{"feed.dispatches": 14, "mesh.rows": 0})])
    assert read(parent, **how["args"]) is None


def test_the_host4_configuration_is_the_one_chip_one_on_another_cluster():
    """Key for key the one-chip file, except what states the deployment; one
    four-chip cell on the existing traffic mix."""
    def load(name):
        with open(os.path.join(BENCH, "configs", name + ".json"), encoding="utf-8") as fh:
            return json.load(fh)

    one, four = load("forest-t40d6-hg38x2"), load("forest-t40d6-hg38x2-host4")
    differ = {"name", "source", "deployment", "mesh", "guarantees", "assumed"}
    assert set(four) - set(one) == {"mesh", "guarantees"} and set(one) <= set(four)
    assert {k for k in one if one[k] != four[k]} == differ - {"mesh", "guarantees"}
    assert four["assumed"][1:] == one["assumed"]  # the cut's reason, then the same
    assert four["mesh"]["chips"] == four["mesh"]["dp"] == 4 and four["mesh"]["mp"] == 1
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        bm = json.load(fh)
    (cell,) = [w for w in bm["workloads"] if w["config"] == four["name"]]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (HOST4_CELL, "wgs-batch", 4)
    (entry,) = [c for c in bm["configs"] if c["name"] == four["name"]]
    assert entry["source"] == four["source"] and entry["reduced"] == four["reduced"]
    assert [w["name"] for w in bm["workloads"] if w["chips"] == 4] == [HOST4_CELL]


# -- the .vcf.gz cell's metrics (ISSUE 36) --------------------------------------

VCFGZ_CELL = "forest-t40d6-hg38x2-vcfgz.wgs-batch-bgzf"
VCFGZ_METRICS = {
    "inflate_work_share": ("ingest and parse", "stage_share", "inflate.w3", 25.0),
    "compress_work_share": ("render and commit", "stage_share", "compress_stage", 25.0),
    "index_work_share": ("render and commit", "stage_share", "tabix_index", 25.0),
    "bgzf_out_ratio": (
        "render and commit", "counter_ratio",
        final(**{"bgzf.out_bytes": 32_500_000, "bgzf.text_bytes_out": 140_000_000}),
        100 * 32_500_000 / 140_000_000),
    "bgzf_in_blocks_per_file": ("ingest and parse", "obs_counter",
                                final(**{"bgzf.in_blocks": 1684}), 1684.0),
    "tabix_index_skipped_per_file": ("render and commit", "obs_counter",
                                     final(**{"tabix.index_skipped": 0}), 0.0),
    # ISSUE 37: the index gathered inside the pipeline, one a file
    "tabix_index_streamed_per_file": ("render and commit", "obs_counter",
                                      final(**{"tabix.index_streamed": 1}), 1.0),
}


@pytest.mark.parametrize("name", sorted(VCFGZ_METRICS))
def test_the_vcfgz_cells_metrics_read_what_the_program_writes(bench, name):
    """Each of the six lists only the new cell, comes after everything the
    benchmark had, names a reader that was there, reads a hand-made context,
    and reads nothing (and does not raise) from a plain-text run of a program
    that lacks the span or the counter, as the parent does."""
    layer, reader, given, want = VCFGZ_METRICS[name]
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        bm = json.load(fh)
    (m,) = [m for m in bm["per_layer"][35:] if m["name"] == name]
    assert (m["workloads"], m["moves"], m["layer"]) == ([VCFGZ_CELL], "variants_per_s", layer)
    (cell,) = [w for w in bm["workloads"][4:] if w["name"] == VCFGZ_CELL]
    assert (cell["chips"], cell["traffic"]) == (1, "wgs-batch-bgzf")
    with open(os.path.join(BENCH, "layer_metrics", name + ".json"), encoding="utf-8") as fh:
        how = json.load(fh)
    assert how["reader"] == reader
    read = bench.load("readers", reader).read
    events = [given] if isinstance(given, dict) else [
        {"kind": "profile", "name": "stage", "stage": given, "work_s": 0.125},
        {"kind": "profile", "name": "pipeline", "wall_s": 0.5}]
    assert read(context(obs_events=events), **how["args"]) == pytest.approx(want)
    parent = context(obs_events=[final(**{"feed.dispatches": 14}),
                                 {"kind": "profile", "name": "stage",
                                  "stage": "render_stage.w0", "work_s": 0.1},
                                 {"kind": "profile", "name": "pipeline", "wall_s": 0.5}])
    assert read(parent, **how["args"]) is None


def test_the_vcfgz_configuration_is_the_forest_one_in_another_container():
    """Key for key the plain-text forest file, except what states the
    deployment; its traffic file differs from the plain-text one in the
    driver alone; the container block is htslib's."""
    def load(*parts):
        with open(os.path.join(BENCH, *parts), encoding="utf-8") as fh:
            return json.load(fh)

    plain = load("configs", "forest-t40d6-hg38x2.json")
    gz = load("configs", "forest-t40d6-hg38x2-vcfgz.json")
    assert set(gz) - set(plain) == {"container", "guarantees"} and set(plain) <= set(gz)
    assert {k for k in plain if plain[k] != gz[k]} == {"name", "source", "deployment", "assumed"}
    assert gz["assumed"][1:1 + len(plain["assumed"])] == plain["assumed"]
    box = gz["container"]
    assert (box["input"], box["output"], box["block_payload"], box["level"],
            box["index_regions_checked"]) == ("bgzf", "bgzf+tbi", 65280, 6, 64)
    # between the program's reading (1.0000) and level 1's (1.28), room on both sides
    assert 0.02 <= box["size_tolerance"] <= 0.15 and box["size_tolerance_why"]
    a, b = load("traffic", "wgs-batch.json"), load("traffic", "wgs-batch-bgzf.json")
    assert {k for k in a if a[k] != b[k]} == {"about", "driver"} and set(a) == set(b)
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        bm = json.load(fh)
    assert bm["configs"][-1]["name"] == gz["name"] == bm["workloads"][-1]["config"]
    assert bm["configs"][-1]["source"] == gz["source"]
    assert bm["configs"][-1]["reduced"] == gz["reduced"] == ["variants_per_file"]
