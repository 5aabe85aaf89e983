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
    # ISSUE 39: the members the native compressor gave to libdeflate
    "libdeflate_member_share": (
        "render and commit", "counter_ratio",
        final(**{"bgzf.libdeflate_members": 2518, "bgzf.deflate_members": 2519}),
        100 * 2518 / 2519),
}


@pytest.mark.parametrize("name", sorted(VCFGZ_METRICS))
def test_the_vcfgz_cells_metrics_read_what_the_program_writes(bench, name):
    """Each lists only the `.vcf.gz` cell, comes after everything the
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
    # the fifth configuration and cell (later ones come after them)
    entry, cell = bm["configs"][4], bm["workloads"][4]
    assert entry["name"] == gz["name"] == cell["config"]
    assert entry["source"] == gz["source"]
    assert entry["reduced"] == gz["reduced"] == ["variants_per_file"]


# -- idle time by the program's own layers, on-CPU shares, fixed work (ISSUE 38) --

def layer_events(**layer_of):
    """The obs ``span`` events a traced file would carry, one a name."""
    return [{"kind": "span", "name": n, "dur": 1.0, "layer": layer}
            for n, layer in layer_of.items()]


#: THREADS' spans as the program names their layers; ``score_stage`` and
#: ``dispatch_wait`` are of the layer that does not vote
LAYERS = dict(score_stage="wait", dispatch_wait="wait", host_featurize="feed",
              fused_program="program", parse="ingest")
#: BY_HAND's pieces, with ``io`` now the layer ``ingest``
BY_LAYER = {"program": 1.0, "feed": 0.75, "ingest": 0.75, "unnamed": 5.5}


def test_idle_by_layer_attributes_the_hand_made_timeline_by_a_given_grouping(bench):
    reader = bench.load("readers", "idle_by_layer")
    got = reader.attribute(THREADS, BUSY, (0.0, 10 * S),
                           lambda n: None if LAYERS[n] == "wait" else LAYERS[n])
    assert got == pytest.approx(BY_LAYER)
    assert sum(got.values()) == pytest.approx(8.0)  # all of the idle time
    # the grouping is the caller's: one group for everything, waiters too
    one = reader.attribute(THREADS, BUSY, (0.0, 10 * S), lambda n: "host")
    assert one == pytest.approx({"host": 7.0, "unnamed": 1.0})  # [9,10]: no span open


@pytest.fixture()
def traced_threads(bench, monkeypatch):
    import program_spans

    monkeypatch.setattr(program_spans, "load",
                        lambda *a: {"threads": THREADS, "devices": [], "on_tpu": True})
    reader = bench.load("readers", "idle_by_layer")
    monkeypatch.setattr(reader, "NOTES", os.devnull)
    return reader


def test_idle_by_layers_shares_add_up_to_the_idle_share(bench, traced_threads):
    ctx = context(obs_events=layer_events(**LAYERS))
    got = traced_threads.shares(ctx)
    # the harness's interval is half a second longer than the files' spans:
    # that half second is idle, and nobody's
    assert got == pytest.approx({"program": 100 * 1.0 / 10.5, "feed": 100 * 0.75 / 10.5,
                                 "ingest": 100 * 0.75 / 10.5, "unnamed": 100 * 6.0 / 10.5})
    assert "wait" not in got
    assert sum(got.values()) == pytest.approx(bench.load("readers", "device_idle").read(ctx))
    assert traced_threads.read(ctx, None) == pytest.approx(100 * 6.0 / 10.5)
    assert traced_threads.read(ctx, "feed") == pytest.approx(100 * 0.75 / 10.5)
    assert traced_threads.read(ctx, "daemon") is None  # a layer no event names


@pytest.mark.parametrize("layers, want", [
    # a thread inside a `wait` span does not vote: were the waiter a feeder,
    # [1,1.5] would be shared and [1.5,2], [3,3.5] its own
    (dict(LAYERS, dispatch_wait="feed"),
     {"program": 0.75, "feed": 2.25, "ingest": 0.5, "unnamed": 5.0}),
    # the layer is the events', not the reader's: call parse `commit` there
    # and its share follows
    (dict(LAYERS, parse="commit"),
     {"program": 1.0, "feed": 0.75, "commit": 0.75, "unnamed": 6.0}),
    # a name no event gives a layer lands in unnamed
    ({k: v for k, v in LAYERS.items() if k != "fused_program"},
     {"feed": 0.75, "ingest": 0.75, "unnamed": 7.0}),
    # a layer the events name and nobody was idle under reads 0, not nothing
    (dict(LAYERS, obs_close="tracing"),
     {"program": 1.0, "feed": 0.75, "ingest": 0.75, "tracing": 0.0, "unnamed": 6.0}),
], ids=["wait-does-not-vote", "layer-from-the-events", "no-event-is-unnamed",
        "named-and-never-idle"])
def test_idle_by_layer_takes_the_layers_from_the_events(traced_threads, layers, want):
    got = traced_threads.shares(context(obs_events=layer_events(**layers)))
    assert got == pytest.approx({k: 100 * v / 10.5 for k, v in want.items()})


@pytest.mark.parametrize("traced_s, unnamed, voted, beyond", [
    # the harness's interval half a second longer than the files' spans:
    # the remainder is what nobody was voted for and that half second
    (10.5, 6.0, 5.5, 0.5),
    # the interval exactly the files' window: counted both ways, one number
    (10.0, 5.5, 5.5, 0.0),
    # an interval shorter than the window the layers were voted in: they hold
    # more idle time (2.5 s) than it has (2.0 s), so the remainder is no
    # reading; the layers' own shares and the notes still say what was counted
    (4.0, None, 5.5, -6.0),
], ids=["beyond-the-window", "the-window-itself", "layers-over-the-idle-time"])
def test_idle_by_layers_unnamed_is_counted_two_ways_and_both_are_noted(
        traced_threads, monkeypatch, tmp_path, traced_s, unnamed, voted, beyond):
    notes = tmp_path / "idle_by_layer.txt"
    monkeypatch.setattr(traced_threads, "NOTES", str(notes))
    ctx = context(obs_events=layer_events(**LAYERS), traced_s=traced_s)
    share = 100 / traced_s
    assert traced_threads.read(ctx, "program") == pytest.approx(1.0 * share)
    got = traced_threads.read(ctx, None)
    assert got == (pytest.approx(unnamed * share) if unnamed is not None else None)
    noted = dict(ln.split("\t") for ln in notes.read_text().splitlines())
    assert float(noted["unnamed_voted_in_window"]) == pytest.approx(voted * share, abs=1e-3)
    assert float(noted["idle_beyond_window"]) == pytest.approx(beyond * share, abs=1e-3)
    assert ("unnamed" in noted) == (unnamed is not None)
    if unnamed is not None:  # the remainder is the sum of the two counts
        assert float(noted["unnamed"]) == pytest.approx((voted + beyond) * share, abs=1e-3)


def stage_row(stage, work, **kw):
    return {"kind": "profile", "name": "stage", "stage": stage, "work_s": work, **kw}


#: what the parent's program writes: spans without layers, rows without cpu_s
PARENT_EVENTS = [{"kind": "span", "name": "stream", "dur": 2.0},
                 {"kind": "span", "name": "parse", "dur": 0.5},
                 stage_row("parse.w0", 0.5), stage_row("render_stage.w0", 0.25),
                 {"kind": "profile", "name": "pipeline", "wall_s": 2.0}]


@pytest.mark.parametrize("reader, args, events, want", [
    # two workers' rows: (0.25 + 0.5) of (1.0 + 2.0); the family alone counts
    ("stage_cpu_share", {"families": ["parse"]},
     [stage_row("parse.w0", 1.0, cpu_s=0.25), stage_row("parse.w1", 2.0, cpu_s=0.5),
      stage_row("render_stage.w0", 4.0, cpu_s=4.0)], 25.0),
    # a row no span fed (no cpu_s) is on neither side
    ("stage_cpu_share", {"families": ["ingest", "parse"]},
     [stage_row("parse.w0", 1.0, cpu_s=0.5), stage_row("ingest", 9.0)], 50.0),
    ("stage_cpu_share", {"families": ["parse"]}, PARENT_EVENTS, None),
    ("stage_cpu_share", {"families": ["inflate"]},
     [stage_row("parse.w0", 1.0, cpu_s=0.5)], None),
    # (0.25 + 0.5 + 0.125 + 0.125) of (0.25 + 3.75)
    ("span_sum_share", {"parts": ["run_open", "stream_open", "stream_close", "commit"],
                        "whole": ["run_open", "stream"]},
     [{"kind": "span", "name": n, "dur": d} for n, d in
      [("run_open", 0.25), ("stream", 3.75), ("stream_open", 0.5),
       ("stream_close", 0.125), ("commit", 0.125), ("parse", 9.0)]], 25.0),
    # a request: no run_open, its stream is the whole
    ("span_sum_share", {"parts": ["run_open", "stream_open"], "whole": ["run_open", "stream"]},
     [{"kind": "span", "name": "stream", "dur": 2.0},
      {"kind": "span", "name": "stream_open", "dur": 0.5}], 25.0),
    ("span_sum_share", {"parts": ["run_open", "stream_open"], "whole": ["run_open", "stream"]},
     PARENT_EVENTS, None),
], ids=["cpu-two-workers", "cpu-unfed-row", "cpu-parent", "cpu-no-family",
        "fixed-work-a-file", "fixed-work-a-request", "fixed-work-parent"])
def test_on_cpu_and_span_sum_readers_on_hand_made_events(bench, reader, args, events, want):
    got = bench.load("readers", reader).read(context(obs_events=events), **args)
    assert got == (pytest.approx(want) if want is not None else None)


def test_the_parents_program_gives_idle_by_layer_nothing_to_read(traced_threads):
    assert traced_threads.shares(context(obs_events=PARENT_EVENTS)) is None
    assert traced_threads.read(context(obs_events=PARENT_EVENTS), None) is None


LAYER_METRICS_38 = {
    "parse_on_cpu_share": ("ingest and parse", "stage_cpu_share", "all"),
    "render_on_cpu_share": ("render and commit", "stage_cpu_share", "all"),
    "inflate_on_cpu_share": ("ingest and parse", "stage_cpu_share", [VCFGZ_CELL]),
    "idle_entry_share": ("CLI entry", "idle_by_layer", "all"),
    "idle_ingest_share": ("ingest and parse", "idle_by_layer", "all"),
    "idle_render_share": ("render and commit", "idle_by_layer", "all"),
    "idle_commit_share": ("render and commit", "idle_by_layer", "all"),
    "idle_daemon_share": ("daemon front", "idle_by_layer",
                          ["forest-t40d6-hg38x2-exome.serve-c4"]),
    "idle_tracing_share": ("CLI entry", "idle_by_layer", "batch"),
    "idle_unnamed_share": ("device", "idle_by_layer", "all"),
    "fixed_work_share": ("CLI entry", "span_sum_share", "all"),
}


@pytest.mark.parametrize("name", sorted(LAYER_METRICS_38))
def test_issue_38s_metrics_come_last_and_name_the_programs_layers(bench, name):
    """Each of the eleven comes after everything the benchmark had, lists its
    cells, names a reader this issue brought, and (the idle shares) a layer
    the program's own table has."""
    from variantcalling_tpu.utils import trace

    layer, reader, where = LAYER_METRICS_38[name]
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        bm = json.load(fh)
    cells = [w["name"] for w in bm["workloads"]]
    serve = "forest-t40d6-hg38x2-exome.serve-c4"
    want = where if isinstance(where, list) else {
        "all": cells, "batch": [c for c in cells if c != serve]}[where]
    assert len(bm["per_layer"]) >= 53
    (m,) = [m for m in bm["per_layer"][42:53] if m["name"] == name]
    assert (m["workloads"], m["moves"], m["layer"], m["unit"]) == (
        want, "variants_per_s", layer, "%")
    with open(os.path.join(BENCH, "layer_metrics", name + ".json"), encoding="utf-8") as fh:
        how = json.load(fh)
    assert how["reader"] == reader and callable(bench.load("readers", reader).read)
    if reader == "idle_by_layer" and how["args"]["layer"] is not None:
        assert how["args"]["layer"] in set(trace.LAYER_OF.values()) - {"wait"}
    elif reader == "span_sum_share":
        assert set(how["args"]["parts"]) | set(how["args"]["whole"]) <= set(trace.LAYER_OF)
    elif reader == "stage_cpu_share":
        assert set(how["args"]["families"]) <= set(trace.LAYER_OF)


# -- the xgboost cell ----------------------------------------------------------

XGB_CELL = "xgb-t100d6-hg38x2.wgs-batch"
XGB_METRICS = {"feed_missing_share": "streaming executor score stage",
               "wide_dispatch_share": "device featurize and score",
               "forest_wide_roofline": "kernel forest wide margin"}


@pytest.mark.parametrize("name", sorted(XGB_METRICS))
def test_the_xgb_cells_metrics_come_last_and_list_only_that_cell(bench, name):
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        bm = json.load(fh)
    assert [m["name"] for m in bm["per_layer"][54:57]] == list(XGB_METRICS)
    (m,) = [m for m in bm["per_layer"] if m["name"] == name]
    assert (m["workloads"], m["moves"], m["layer"]) == \
        ([XGB_CELL], "variants_per_s", XGB_METRICS[name])
    with open(os.path.join(BENCH, "layer_metrics", name + ".json"), encoding="utf-8") as fh:
        how = json.load(fh)
    assert callable(bench.load("readers", how["reader"]).read)


#: a traced pair's device events: two 16,384-row steps of the wide margin
#: (the pick, the mask select, a routing, a leaf pick), a gather and a
#: featurize fusion of the 163,840-row dispatch that no pattern may take
WIDE_OPS = [("%convolution_compare_fusion.3 = pred[16384,6300]{0,1} fusion(...)", 0, 2e6),
            ("%convert_select_fusion.2 = bf16[16384,6300]{0,1} fusion(...)", 0, 1e6),
            ("%fusion.78 = pred[16384,128]{0,1} fusion(...)", 0, 0.5e6),
            ("%select_reduce_fusion.4 = f32[16384,2]{0,1} fusion(...)", 0, 0.5e6),
            ("%convolution_compare_fusion.3 = pred[16384,6300]{0,1} fusion(...)", 0, 2e6),
            ("%fusion = u32[163840,128]{1,0} fusion(...)", 0, 7e6),
            # the block loop itself: its carried tuple holds a routed shape
            ("%while.30 = (s32[]{:T(128)}, f32[50,16384,2]{1,2,0}) while(...)", 0, 50e6),
            ("%convert_reduce_fusion.8 = (f32[163840]{0}) fusion(...)", 0, 9e6)]


def test_ops_roofline_sums_the_parts_ops_and_counts_tables_once_a_call(bench):
    with open(os.path.join(BENCH, "configs", "xgb-t100d6-hg38x2.json"), encoding="utf-8") as fh:
        config = json.load(fh)
    with open(os.path.join(BENCH, "layer_metrics", "forest_wide_roofline.json"),
              encoding="utf-8") as fh:
        how = json.load(fh)
    family = bench.load("families", "xgb")
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    rows = 4_000_000
    ctx = context(device_events=[WIDE_OPS], traced_rows=rows, config=config,
                  family=family, peaks=peaks)
    got = bench.load("readers", how["reader"]).read(ctx, **how["args"])
    # by hand: 6 ms of the part's ops; 2 calls open with the feature pick
    flops = 1_058_600 * rows
    nbytes = 80 * rows + 2 * 4 * 100 * (2 * 63 + 64)
    assert got == pytest.approx(100 * max(flops / 197e12, nbytes / 819e9) / 6e-3)
    # a program without the part's ops gives nothing to read
    ctx["device_events"] = [WIDE_OPS[5:]]
    assert bench.load("readers", how["reader"]).read(ctx, **how["args"]) is None


def test_xgb_required_work_is_the_forests_count_at_its_shape(bench):
    """1,058,600 FLOPs a variant: 2 T (F I + I L + L) at T=100, F=19, I=63,
    L=64, the shape the program's GEMM packing of the drawn booster has."""
    from variantcalling_tpu.models import forest as fmod

    with open(os.path.join(BENCH, "configs", "xgb-t100d6-hg38x2.json"), encoding="utf-8") as fh:
        config = json.load(fh)
    family = bench.load("families", "xgb")
    forest = family.to_program(config, family.arrays(config["weights_seed"], config))
    gf = fmod.to_gemm(forest, config["n_features"])
    assert (gf.a.shape, gf.m2.shape[2]) == \
        ((config["n_trees"], config["n_features"], config["n_internal"]), config["n_leaves"])
    assert family.flops_per_variant(config) == 1_058_600
    assert family.flops_per_variant(config) / \
        bench.load("families", "forest").flops_per_variant(
            {"n_trees": 40, "n_features": 12, "n_internal": 31, "n_leaves": 32}) \
        == pytest.approx(9.48, abs=0.01)


def test_the_xgb_configuration_is_the_forest_cells_but_for_the_model():
    def load(name):
        with open(os.path.join(BENCH, "configs", name + ".json"), encoding="utf-8") as fh:
            return json.load(fh)

    forest, xgb = load("forest-t40d6-hg38x2"), load("xgb-t100d6-hg38x2")
    for key in ("references", "variants_per_file", "published", "reduced", "env",
                "weights_seed", "control", "limits"):
        assert xgb[key] == forest[key], key
    assert (xgb["family"], xgb["n_trees"], xgb["max_depth"]) == ("xgb", 100, 6)
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        bm = json.load(fh)
    (cell,) = [w for w in bm["workloads"] if w["name"] == XGB_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("xgb-t100d6-hg38x2", "wgs-batch", 1)
