"""Simulated multi-host scale-out (docs/scaleout.md): the REAL local
launcher end to end — ``tools/podrun`` spawning separate worker
processes with ``VCTPU_RANK``/``VCTPU_NUM_PROCESSES`` set (no
jax.distributed, no coordinator), the rank-sequenced merge, and the
SIGKILL-one-rank resume ladder.

The in-process siblings (tests/unit/test_rank_plan.py) prove the byte
math across the full matrix; this file proves the PROCESS boundary: env
propagation, per-rank obs logs, the launcher's distinct exit codes, and
journal/marker resume across a real worker death. Runs on the plain cpu
backend — this is the CI leg ``run_tests.sh`` wires behind
``VCTPU_SCALEOUT=1`` (and it rides tier-1 too; the fixtures are small).
"""

from __future__ import annotations

import json
import os
import pickle
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

native = pytest.importorskip("variantcalling_tpu.native")

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_RANKS = 2


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    from variantcalling_tpu.synthetic import make_fixtures, synthetic_forest

    d = str(tmp_path_factory.mktemp("scaleout"))
    make_fixtures(d, n=2500, genome_len=150_000)
    model = synthetic_forest(np.random.default_rng(0), n_trees=8, depth=4)
    with open(f"{d}/model.pkl", "wb") as fh:
        pickle.dump({"m": model}, fh)
    return {"dir": d, "n": 2500}


def _env(extra: dict | None = None) -> dict:
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("VCTPU_") and k not in ("XLA_FLAGS",
                                                       "PYTHONPATH")}
    env.update(PYTHONPATH=_REPO, JAX_PLATFORMS="cpu",
               VCTPU_STREAM_CHUNK_BYTES=str(1 << 14),
               VCTPU_IO_THREADS="2")
    env.update(extra or {})
    return env


def _cli_args(world, out: str) -> list[str]:
    d = world["dir"]
    return ["--input_file", f"{d}/calls.vcf", "--model_file",
            f"{d}/model.pkl", "--model_name", "m", "--reference_file",
            f"{d}/ref.fa", "--output_file", out, "--backend", "cpu"]


def _norm(data: bytes) -> bytes:
    # the ONE provenance-normalization spelling (chaoshunt shares it
    # with loadhunt and these suites)
    from tools.chaoshunt.harness import normalize_output

    return normalize_output(data)


def _leftovers(out: str) -> list[str]:
    d = os.path.dirname(out)
    base = os.path.basename(out)
    return sorted(p for p in os.listdir(d)
                  if p.startswith(base) and (".seg" in p or ".podrun" in p
                                             or ".partial" in p
                                             or ".journal" in p
                                             or ".podlog" in p))


def test_podrun_two_ranks_matches_single_rank_cli(world):
    """Acceptance: the 2-rank local-launcher run produces output
    byte-identical to the 1-rank run modulo ##vctpu_* headers, via real
    worker processes, with per-rank obs logs next to the destination and
    nothing left behind."""
    d = world["dir"]
    single = f"{d}/single.vcf"
    proc = subprocess.run(
        [sys.executable, "-m", "variantcalling_tpu",
         "filter_variants_pipeline", *_cli_args(world, single)],
        env=_env(), cwd=_REPO, timeout=240, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-2000:]

    pod = f"{d}/pod.vcf"
    proc = subprocess.run(
        [sys.executable, "-m", "tools.podrun", "--ranks", str(_RANKS),
         "--timeout", "200", "--", *_cli_args(world, pod)],
        env=_env({"VCTPU_OBS": "1"}), cwd=_REPO, timeout=240,
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]

    a, b = open(single, "rb").read(), open(pod, "rb").read()
    assert _norm(a) == _norm(b)
    assert f"##vctpu_ranks=n={_RANKS}".encode() in b
    assert b"##vctpu_ranks=" not in a  # single-rank: no pod provenance
    # per-rank obs logs landed next to the FINAL destination, suffixed
    # by distributed.rank() (VCTPU_RANK — no jax.distributed involved)
    assert os.path.exists(f"{pod}.obs.jsonl")
    assert os.path.exists(f"{pod}.obs.jsonl.rank1")
    # ... and the merged reader sees both ranks' heartbeats summing to n
    from variantcalling_tpu.obs import cli as obs_cli
    from variantcalling_tpu.obs import export as export_mod

    events = export_mod.read_run(f"{pod}.obs.jsonl")
    state = obs_cli.tail_state(events)
    assert state["progress"]["records"] == world["n"]
    assert _leftovers(pod) == [], _leftovers(pod)


def test_podrun_rank_kill_resumes_byte_identically(world):
    """Acceptance: SIGKILL one worker rank mid-run -> the launcher exits
    its DISTINCT code with the destination untouched; a relaunch resumes
    from the per-rank journals (and the surviving rank's .done marker)
    and commits byte-identically to the single-rank run."""
    d = world["dir"]
    single = f"{d}/kill_single.vcf"
    proc = subprocess.run(
        [sys.executable, "-m", "variantcalling_tpu",
         "filter_variants_pipeline", *_cli_args(world, single)],
        env=_env(), cwd=_REPO, timeout=240, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-2000:]
    want = _norm(open(single, "rb").read())

    out = f"{d}/kill_pod.vcf"
    # a persistent per-chunk delay keeps every rank mid-stream long
    # enough for the kill to land (the chaoshunt rank_kill recipe)
    env = _env({"VCTPU_FAULTS": "pipeline.stage_hang:0@0.05"})
    p = subprocess.Popen(
        [sys.executable, "-m", "tools.podrun", "--ranks", str(_RANKS),
         "--timeout", "200", "--", *_cli_args(world, out)],
        env=env, cwd=_REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    jpath = f"{out}.rank1of{_RANKS}.seg.journal"
    spath = f"{out}.podrun.json"
    killed = False
    deadline = time.time() + 200
    while time.time() < deadline and p.poll() is None:
        try:
            with open(jpath, encoding="utf-8") as fh:
                committed = max(0, len(fh.read().splitlines()) - 1)
        except OSError:
            committed = 0
        if committed >= 1:
            with open(spath, encoding="utf-8") as fh:
                state = json.load(fh)
            pid = next(w["pid"] for w in state["workers"]
                       if w["rank"] == 1)
            try:
                os.kill(pid, signal.SIGKILL)
                killed = True
            except ProcessLookupError:
                pass
            break
        time.sleep(0.02)
    stdout, _ = p.communicate(timeout=240)
    assert killed, f"kill never landed: {stdout[-2000:]}"
    assert p.returncode == 3, (p.returncode, stdout[-2000:])
    assert not os.path.exists(out), \
        "a rank SIGKILL must leave the destination untouched"
    # the killed rank left its journal+partial; the survivor its marker
    assert os.path.exists(jpath)
    assert os.path.exists(f"{out}.rank0of{_RANKS}.seg.done")

    # relaunch, fault-free: resume + marker-skip + merge
    proc = subprocess.run(
        [sys.executable, "-m", "tools.podrun", "--ranks", str(_RANKS),
         "--timeout", "200", "--", *_cli_args(world, out)],
        env=_env(), cwd=_REPO, timeout=240, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert _norm(open(out, "rb").read()) == want
    assert _leftovers(out) == [], _leftovers(out)


def test_worker_config_error_propagates_distinct_exit(world):
    """A worker that exits 2 (config error) must surface as podrun exit
    2 — never a merge of missing segments."""
    d = world["dir"]
    out = f"{d}/badcfg.vcf"
    env = _env({"VCTPU_FOREST_STRATEGY": "not-a-strategy"})
    proc = subprocess.run(
        [sys.executable, "-m", "tools.podrun", "--ranks", str(_RANKS),
         "--timeout", "120", "--", *_cli_args(world, out)],
        env=env, cwd=_REPO, timeout=200, capture_output=True, text=True)
    assert proc.returncode == 2, proc.stdout[-1500:] + proc.stderr[-1500:]
    assert not os.path.exists(out)
