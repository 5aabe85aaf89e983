"""CPU rehearsal of the serve cell (``python -m pytest benchmarks/``): the
``closed_loop_serve`` driver and the readers that came with it, on what one
traced and one untraced ``--rehearse`` run of the cell leave behind. The
readers' hand-made cases are tier-1 (``tests/unit/test_benchmark_readers.py``);
``test_benchmark_cell.py`` runs every cell, this one too, in-process with a
sound and an altered answer."""

import json
import os
import re
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run_cell  # noqa: E402

CELL = "forest-t40d6-hg38x2-exome.serve-c4"
NEW = ["request_p50_s", "request_p95_s", "admission_wait_share",
       "daemon_overhead_share", "resident_requests_share"]


def rehearse(trace: int, **env) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    env.pop("BENCH_RUN", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run_cell.py"), "--workload", CELL,
         "--seed", "2147483659", "--seconds", "1", "--trace", str(trace),
         "--rehearse", "500"],
        env=env, capture_output=True, text=True, timeout=600, check=False)
    line = re.search(r"no result line: (\{.*\})\s*$", p.stderr, re.M)
    return {"rc": p.returncode, "stdout": p.stdout, "stderr": p.stderr,
            "result": json.loads(line.group(1)) if line else None}


@pytest.fixture(scope="module")
def traced():
    got = rehearse(1)
    assert got["rc"] == run_cell.EXIT_REHEARSAL and got["stdout"].strip() == "", \
        got["stderr"][-2000:]
    return got


def test_a_traced_rehearsal_is_correct_and_reports_the_new_metrics(traced):
    result = traced["result"]
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 32  # eight requests of each of four clients
    m = {k: v["value"] for k, v in result["metrics"].items()}
    for name in NEW:
        assert isinstance(m.get(name), float), name
    assert m["resident_requests_share"] == 100.0 and m["compiles_in_window"] == 0
    assert 0 < m["request_p50_s"] <= m["request_p95_s"]
    assert m["request_p50_s"] == pytest.approx(m["file_median_s"])
    # four clients on two slots: about half of a request's life is admission
    assert 10 < m["admission_wait_share"] < 90
    assert 0 < m["daemon_overhead_share"] < 100
    # every share the accepted cells report is read from per-request rows
    for name in ("parse_work_share", "score_stage_work_share", "render_work_share",
                 "host_featurize_work_share", "feed_work_share",
                 "program_work_share", "device_wait_work_share"):
        assert m[name] > 0, name
    idle = ["idle_program_share", "idle_feed_share", "idle_io_share",
            "idle_unexplained_share"]
    assert sum(m[k] for k in idle) == pytest.approx(m["device_idle_share"], abs=1e-6)


def test_the_daemon_warmed_both_genomes_and_answered_every_request(traced):
    warms = re.findall(r"warm on ref \d+: 200 .*?(\{.*\})$", traced["stderr"], re.M)
    assert len(warms) == 2
    last = json.loads(warms[-1])
    assert last["warmed"] == ["reference", "device_genome", "model", "program"]
    assert last["resident"]["device_genomes"]["entries"] == 2
    details = run_cell.load_json(run_cell.OUT_DIR, f"{CELL}.seed2147483659.trace1.json")
    rows = details["files"]
    assert all(r["rc"] == 0 and r["code"] == 200 and r["records"] == 100 for r in rows)
    traced_rows = [r for r in rows if r["traced"]]
    assert len(traced_rows) >= 32 and {r["client"] for r in rows} == {0, 1, 2, 3}
    # clients 0 and 2 start on the first reference, 1 and 3 on the second
    first = {r["client"]: r["ref"] for r in reversed(rows)}
    assert first[0] == first[2] != first[1] == first[3]


def test_an_untraced_rehearsal_reports_the_end_to_end_metrics():
    got = rehearse(0)
    assert got["rc"] == run_cell.EXIT_REHEARSAL and got["stdout"].strip() == ""
    assert got["result"]["correct"]
    assert set(got["result"]["metrics"]) == {"variants_per_s", "host_rss_peak_gb", "setup_s"}


def test_a_daemon_at_other_settings_than_the_configuration_states_ends_the_run():
    got = rehearse(0, VCTPU_SERVE_MAX_INFLIGHT="3")
    assert got["rc"] not in (0, run_cell.EXIT_REHEARSAL) and got["result"] is None
    assert "the configuration states" in got["stderr"]


def test_a_daemon_that_cannot_report_its_genomes_resident_ends_the_run(monkeypatch):
    """As a program whose ``/v1/warm`` loads the host side only would: the
    configuration states the references resident on the device."""
    from variantcalling_tpu.serve.daemon import Server

    monkeypatch.setenv("VCTPU_ENGINE", "jit")
    monkeypatch.setenv("VCTPU_OBS", "0")
    monkeypatch.setattr(Server, "resident_payload", lambda self: {})
    run = run_cell.Run(run_cell.Spec(CELL), seed=5, seconds=0.5, trace=False, divisor=500)
    with pytest.raises(SystemExit, match="0 of 2|None of 2"):
        run.set_up()
    assert run.server.stopped.is_set()
