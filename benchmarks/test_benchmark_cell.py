"""CPU-only tests of the benchmark's own code (run by hand or with
``python -m pytest benchmarks/``; small sizes, no chip):

- the data files name configurations, traffic mixes and readers that exist;
- each reader reduces a hand-made context to the number worked out by hand;
- the required-work counts against the shapes, by hand;
- the control: the plain reference in the configuration's ``control``
  precision, put in the program's place,
  comes out as not correct under each configuration's limits;
- a run with the look for a chip skipped comes out correct, and with the
  program's answer altered where it is produced comes out not correct;
- ``--rehearse`` end to end exits non-zero and prints no result line;
- ``read_limits.py`` reads the program and the control over seeds and draws
  of the weights.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import fixtures  # noqa: E402
import lookup  # noqa: E402
import reference  # noqa: E402
import run_cell  # noqa: E402
import trace_reduce  # noqa: E402

BM = run_cell.load_json(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in BM["workloads"]]
FOREST_CELL = "forest-t40d6-hg38x2.wgs-batch"


def config_of(cell):
    return run_cell.Spec(cell).config


def test_every_data_file_names_things_that_exist():
    configs = {c["name"]: c for c in BM["configs"]}
    e2e = {m["name"] for m in BM["end_to_end"]}
    for w in BM["workloads"]:
        assert os.path.exists(os.path.join(ROOT, configs[w["config"]]["file"]))
        traffic = run_cell.load_json(BENCH, "traffic", w["traffic"] + ".json")
        assert callable(lookup.load("drivers", traffic["driver"]).window)
        cfg = config_of(w["name"])
        assert callable(lookup.load("families", cfg["family"]).score)
        assert cfg["reduced"] == configs[w["config"]]["reduced"]
        assert set(cfg["limits"]) == {"score_gap_max", "filter_wrong", "records_wrong"}
    for m in BM["per_layer"]:
        how = run_cell.load_json(BENCH, "layer_metrics", m["name"] + ".json")
        assert callable(run_cell.reader_fn(how["reader"]))
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_required_work_by_hand():
    forest, dan = config_of(CELLS[0]), config_of(CELLS[1])
    work, work_dan = lookup.load("families", "forest"), lookup.load("families", "dan")
    # 40 trees x (12x31 tests + 31x32 path match + 32 leaf picks) x 2
    assert work.flops_per_variant(forest) == 2 * 40 * (12 * 31 + 31 * 32 + 32) == 111_680
    assert work.bytes_per_variant(forest) == 4 * 12 + 4
    # (17 + 2x16) x 256 + 256 x 256 + 256, x 2
    assert work_dan.flops_per_variant(dan) == 2 * (49 * 256 + 256 * 256 + 256) == 156_672
    assert len(forest["features"]) == forest["n_features"]
    assert 2 ** forest["depth"] // 2 == forest["n_leaves"] == forest["n_internal"] + 1


def context(**kw):
    ctx = {"config": config_of(FOREST_CELL), "family": lookup.load("families", "forest"),
           "traced_s": 2.0, "traced_rows": 1_000_000,
           "peaks": {"flops_bf16": 200e12, "hbm_bytes_per_s": 800e9}, "busy_s": 0.5,
           "file_walls": [3.0, 1.0, 2.0], "n_files": 3, "obs_events": [],
           "device_events": [[]], "compile_events": {},
           "miss_event": run_cell.MISS_EVENT, "hit_event": run_cell.HIT_EVENT}
    ctx.update(kw)
    return ctx


def stage(name, work_s):
    return {"kind": "profile", "name": "stage", "stage": name, "work_s": work_s}


@pytest.mark.parametrize("reader,args,ctx,want", [
    ("file_median", {}, context(), 2.0),
    ("device_idle", {}, context(), 75.0),
    ("stage_share", {"families": ["render_stage", "writeback"]},
     context(obs_events=[stage("render_stage.w0", 1.0), stage("render_stage.w1", 0.5),
                         stage("writeback", 0.5), stage("parse.w0", 9.0),
                         {"kind": "profile", "name": "pipeline", "wall_s": 4.0}]), 50.0),
    ("stage_share", {"families": ["parse"]}, context(), None),
    ("cache_events", {"which": "miss"}, context(), 0.0),
    ("cache_events", {"which": "hit", "per_file": True},
     context(compile_events={run_cell.HIT_EVENT: 6}), 2.0),
    # 111,680 FLOPs x 1e6 rows / (2 s x 200e12) = 0.02792 %
    ("step_mfu", {}, context(), 100 * 111_680e6 / (2.0 * 200e12)),
    # two kernel events of 1 ms; least time 111,680e6 / 200e12 = 0.5584 ms
    ("kernel_roofline", {"pattern": "forest_wide_block"},
     context(device_events=[[("forest_wide_block.1", 0.0, 1e6), ("fusion.3", 5.0, 9e6),
                             ("forest_wide_block.1", 2e6, 1e6)]]),
     100 * (111_680e6 / 200e12) / 2e-3),
    ("kernel_roofline", {"pattern": "forest_wide_block"}, context(), None),
])
def test_reader_on_hand_made_context(reader, args, ctx, want):
    got = run_cell.reader_fn(reader)(ctx, **args)
    assert got == (None if want is None else pytest.approx(want))


def test_trace_reduction_on_hand_made_events():
    ops = [("a", 0.0, 2e9), ("b", 1e9, 2e9), ("a", 5e9, 1e9)]  # busy 0-3 s and 5-6 s
    spans = [("bench:file0:ref1", 0.0, 4e9), ("bench:file1:ref2", 4.5e9, 2.5e9)]
    assert trace_reduce.busy_seconds(ops) == pytest.approx(4.0)
    assert trace_reduce.top_ops(ops) == [["a", 3.0], ["b", 2.0]]
    gaps = dict(trace_reduce.idle_gaps(ops, spans))
    assert gaps == pytest.approx({
        "file0:ref1: after its last device operation": 1.0, "between files": 0.5,
        "file1:ref2: before its first device operation": 0.5,
        "file1:ref2: after its last device operation": 1.0})


def sample_lines(cell, tmp_path, n=4000):
    cfg = config_of(cell)
    ref = cfg["references"][0]
    glen = ref["genome_len"] // 1000
    path = os.path.join(tmp_path, "calls.vcf")
    fixtures.write_callset(path, ref["reference_seed"], glen, ref["n_contigs"], n, seed=11)
    with open(path, "rb") as fh:
        lines = run_cell.body_lines(fh.read())
    body = fixtures.contig_body(ref["reference_seed"], glen // ref["n_contigs"])
    weights = lookup.load("families", cfg["family"]).arrays(cfg["weights_seed"], cfg)
    return cfg, lines, body, weights, ref["n_contigs"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_lower_precision_is_not_correct(cell, tmp_path):
    cfg, lines, body, weights, nc = sample_lines(cell, tmp_path)
    got = reference.compare(lines, [], cfg["family"], weights, body, nc,
                            cfg["limits"]["score_gap_max"], control=cfg["control"])
    assert got["records"] == len(lines) == 4000
    assert got["score_gap_max"] > cfg["limits"]["score_gap_max"]
    # and the reference in the program's place, in its own precision, is
    got = reference.compare(lines, [], cfg["family"], weights, body, nc,
                            cfg["limits"]["score_gap_max"], control="f32")
    assert (got["score_gap_max"], got["filter_wrong"], got["records_wrong"]) == (0, 0, 0)


def drive(cell, monkeypatch):
    """The rest of a run with the look for a chip skipped, at 1/500 size."""
    monkeypatch.setenv("VCTPU_ENGINE", "jit")
    monkeypatch.setenv("VCTPU_OBS", "0")
    spec = run_cell.Spec(cell)
    for k, v in spec.config.get("env", {}).items():
        monkeypatch.setenv(k, v)
    return run_cell.Run(spec, seed=5, seconds=0.5, trace=False, divisor=500).go()


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct_and_an_altered_answer_is_not(cell, monkeypatch):
    result = drive(cell, monkeypatch)
    assert result["correct"] and result["failed"] == 0, result
    assert list(result)[-1] == "compared"
    # the answer altered where it is produced: every 7th score of a chunk
    if "forest" in cell:
        from variantcalling_tpu.models import forest as mod

        sound = mod.finalize_margin

        def broken(margin, forest):
            out = np.array(sound(margin, forest))
            out[::7] = np.minimum(out[::7] + np.float32(0.01), 1)
            return out

        monkeypatch.setattr(mod, "finalize_margin", broken)
    else:
        from variantcalling_tpu.models import dan as mod

        sound = mod.predict_score
        monkeypatch.setattr(mod, "predict_score",
                            lambda *a, **k: sound(*a, **k) * 0.97)
        from variantcalling_tpu.pipelines import filter_variants as fv

        fv._PREDICTOR_CACHE.clear()
    result = drive(cell, monkeypatch)
    assert not result["correct"], result
    assert result["compared"]["score_gap_max"]["value"] > \
        result["compared"]["score_gap_max"]["limit"]


def test_rehearsal_exits_non_zero_and_prints_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run_cell.py"), "--workload", FOREST_CELL,
         "--seed", "3000000019", "--seconds", "1", "--trace", "1", "--rehearse", "500"],
        env=env, capture_output=True, text=True, timeout=600, check=False)
    assert p.returncode == run_cell.EXIT_REHEARSAL, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "records_wrong 0 limit 0" in p.stderr


def test_without_a_tpu_nothing_runs_and_nothing_is_printed():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run_cell.py"), "--workload", FOREST_CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300, check=False)
    assert p.returncode == run_cell.EXIT_NO_TPU and p.stdout.strip() == ""


def test_read_limits_reads_program_and_control_over_seeds_and_weights():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = "dan-e16h256-hg38x2.wgs-batch"
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "read_limits.py"), "--workload", cell,
         "--seeds", "5,6", "--weights-seeds", "8,21", "--control", "bf16",
         "--seconds", "0.5", "--rehearse", "500", "--tag", ".test"],
        env=env, capture_output=True, text=True, timeout=600, check=False)
    assert p.returncode == 0 and p.stdout.strip() == "", p.stderr[-2000:]
    table = run_cell.load_json(run_cell.OUT_DIR, f"limits.{cell}.test.json")["rows"]
    limit = config_of(cell)["limits"]["score_gap_max"]
    assert [(r["weights_seed"], r["seed"]) for r in table] == [(8, 5), (21, 6)]
    assert all(r["score_gap_max"] <= limit < r["control_score_gap_max"] for r in table)
