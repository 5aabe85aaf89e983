"""CPU rehearsal of what this directory reads of the program's own spans,
counters and scopes (``python -m pytest benchmarks/``): one traced
``--rehearse`` run of a cell, then the loader and every new reader on what
that run left behind. The readers' hand-made cases are tier-1
(``tests/unit/test_benchmark_readers.py``)."""

import json
import os
import re
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import program_spans  # noqa: E402
import run_cell  # noqa: E402

CELL = "dan-e16h256-hg38x2.wgs-batch"
PARTS = {"host_featurize", "prepare_inputs", "fused_program", "dispatch_feed",
         "dispatch_enqueue", "dispatch_wait", "score_finalize"}
STAGES = {"parse", "score_stage", "render_stage", "writeback"}
IDLE = ["idle_program_share", "idle_feed_share", "idle_io_share",
        "idle_unexplained_share"]


@pytest.fixture(scope="module")
def rehearsed():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run_cell.py"), "--workload", CELL,
         "--seed", "2147483659", "--seconds", "1", "--trace", "1", "--rehearse", "500"],
        env=env, capture_output=True, text=True, timeout=600, check=False)
    assert p.returncode == run_cell.EXIT_REHEARSAL, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    line = re.search(r"no result line: (\{.*\})\s*$", p.stderr, re.M)
    assert line, p.stderr[-2000:]
    return json.loads(line.group(1))


def test_a_traced_rehearsal_reports_every_new_metric(rehearsed):
    m = {k: v["value"] for k, v in rehearsed["metrics"].items()}
    for name in ["host_featurize_work_share", "feed_work_share", "program_work_share",
                 "device_wait_work_share", "predictor_builds_per_file",
                 "backend_compiles_per_file", "cache_load_s_per_file"] + IDLE:
        assert isinstance(m.get(name), float), name
    assert sum(m[k] for k in IDLE) == pytest.approx(m["device_idle_share"], abs=1e-6)
    parts = (m["host_featurize_work_share"] + m["feed_work_share"]
             + m["program_work_share"] + m["device_wait_work_share"])
    assert 0 < parts <= m["score_stage_work_share"] * 1.0001
    assert m["predictor_builds_per_file"] >= 1 and m["backend_compiles_per_file"] == 0


def test_the_loader_finds_the_programs_spans_in_that_trace(rehearsed):
    loaded = program_spans.load()
    assert loaded is program_spans.load()  # opened once
    names = {s[0] for spans in loaded["threads"].values() for s in spans}
    assert PARTS | STAGES <= names
    traced = {s[3] for spans in loaded["threads"].values() for s in spans
              if s[0] in PARTS}
    assert traced and all(re.fullmatch(r"t\d+", t) for t in traced)
    assert loaded["devices"] and not loaded["on_tpu"]
    with open(program_spans.NOTES, encoding="utf-8") as fh:
        notes = fh.read()
    assert "span\tdispatch_wait\t" in notes and "device stat\t" in notes
