"""Elastic pod e2e (docs/scaleout.md "Elastic membership"): the REAL
``tools/podrun --elastic`` coordinator driving separate span-worker
processes (``VCTPU_SPAN`` leases), a mid-run SIGKILL answered by a
re-cut + re-assignment WITHIN the same launch, and the membership
ledger in the obs stream.

The in-process siblings (tests/unit/test_elastic.py) prove the byte
math and the coordinator state machine; this file proves the PROCESS
boundary: env propagation, the lease files, per-span obs logs, the
self-healing relaunch-free recovery, and that the committed bytes are
LITERALLY identical to the single-rank run (span workers carry no
``##vctpu_ranks=`` header). Rides tier-1 — the fixtures are small —
and is the CI leg ``run_tests.sh`` wires behind ``VCTPU_SCALEOUT=1``.
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


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    from variantcalling_tpu.synthetic import make_fixtures, synthetic_forest

    d = str(tmp_path_factory.mktemp("elastic_e2e"))
    make_fixtures(d, n=2500, genome_len=150_000)
    model = synthetic_forest(np.random.default_rng(0), n_trees=8, depth=4)
    with open(f"{d}/model.pkl", "wb") as fh:
        pickle.dump({"m": model}, fh)
    single = f"{d}/single.vcf"
    proc = subprocess.run(
        [sys.executable, "-m", "variantcalling_tpu",
         "filter_variants_pipeline", *_cli_args(d, single)],
        env=_env(), cwd=_REPO, timeout=240, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return {"dir": d, "n": 2500, "want": open(single, "rb").read()}


def _env(extra: dict | None = None) -> dict:
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("VCTPU_") and k not in ("XLA_FLAGS",
                                                       "PYTHONPATH")}
    env.update(PYTHONPATH=_REPO, JAX_PLATFORMS="cpu",
               VCTPU_STREAM_CHUNK_BYTES=str(1 << 14),
               VCTPU_THREADS="2", VCTPU_IO_THREADS="2")
    env.update(extra or {})
    return env


def _cli_args(d: str, out: str) -> list[str]:
    return ["--input_file", f"{d}/calls.vcf", "--model_file",
            f"{d}/model.pkl", "--model_name", "m", "--reference_file",
            f"{d}/ref.fa", "--output_file", out, "--backend", "cpu"]


def _podrun(d, out, *flags, env=None, timeout=240):
    return subprocess.run(
        [sys.executable, "-m", "tools.podrun", "--elastic", "--ranks", "2",
         "--timeout", "200", *flags, "--", *_cli_args(d, out)],
        env=env or _env(), cwd=_REPO, timeout=timeout,
        capture_output=True, text=True)


def _leftovers(out: str) -> list[str]:
    d = os.path.dirname(out)
    base = os.path.basename(out)
    return sorted(p for p in os.listdir(d)
                  if p.startswith(base) and (".seg" in p or ".podlog" in p
                                             or ".partial" in p
                                             or ".journal" in p
                                             or ".podrun.json" in p))


def test_elastic_pod_literally_matches_single_rank(world):
    """Acceptance: the elastic pod's committed bytes equal the
    single-rank run EXACTLY — no provenance delta at all — with the
    membership ledger in the coordinator's obs stream and `vctpu obs
    summary` rolling the transitions up."""
    d = world["dir"]
    out = f"{d}/pod.vcf"
    proc = _podrun(d, out, env=_env({"VCTPU_OBS": "1"}))
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert open(out, "rb").read() == world["want"]
    assert b"##vctpu_ranks=" not in open(out, "rb").read()
    # the coordinator's own obs run carries the membership ledger
    pod_log = f"{out}.podrun.obs.jsonl"
    assert os.path.exists(pod_log)
    events = [json.loads(ln) for ln in open(pod_log, encoding="utf-8")]
    actions = [e.get("action") for e in events
               if e.get("kind") == "membership"]
    assert actions.count("join") == 2 and actions.count("leave") == 2
    # ... and the summary surface names the transitions
    proc = subprocess.run(
        [sys.executable, "-m", "variantcalling_tpu", "obs", "summary",
         pod_log],
        env=_env(), cwd=_REPO, timeout=120, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert "membership transitions:" in proc.stdout
    assert "join x2" in proc.stdout
    # per-span worker obs logs landed next to the destination
    assert [p for p in os.listdir(d)
            if p.startswith("pod.vcf.span") and p.endswith(".obs.jsonl")]
    assert _leftovers(out) == [], _leftovers(out)


def test_sigkill_mid_span_recovers_in_the_same_launch(world):
    """Acceptance: SIGKILL one span worker mid-stream — the coordinator
    re-cuts at the journal watermark, hands the journaled prefix to an
    adopter, re-offers the suffix, and the SAME launch commits bytes
    identical to the single-rank run. No relaunch, no leftovers."""
    d = world["dir"]
    out = f"{d}/killpod.vcf"
    # a persistent per-chunk delay keeps the workers mid-stream long
    # enough for the kill to land on a journaled span
    env = _env({"VCTPU_FAULTS": "pipeline.stage_hang:0@0.25"})
    p = subprocess.Popen(
        [sys.executable, "-m", "tools.podrun", "--elastic", "--ranks", "2",
         "--timeout", "200", "--grace", "0.5", "--",
         *_cli_args(d, out)],
        env=env, cwd=_REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    spath = f"{out}.podrun.json"
    killed = False
    deadline = time.time() + 150
    while time.time() < deadline and p.poll() is None:
        try:
            with open(spath, encoding="utf-8") as fh:
                state = json.load(fh)
            workers = state.get("workers") or []
            assert state.get("mode") == "elastic"
        except (OSError, ValueError):
            workers = []
        for w in workers:
            lo, hi = w["span"]
            jp = f"{out}.span{lo}-{hi}.seg.journal"
            try:
                with open(jp, encoding="utf-8") as fh:
                    committed = max(0, len(fh.read().splitlines()) - 1)
            except OSError:
                committed = 0
            if committed >= 1 and w.get("pid"):
                try:
                    os.kill(w["pid"], signal.SIGKILL)
                except ProcessLookupError:
                    continue
                killed = True
                break
        if killed:
            break
        time.sleep(0.02)
    stdout, _ = p.communicate(timeout=280)
    assert killed, f"kill never landed: {stdout[-2000:]}"
    # the SAME launch recovered: re-cut or re-assign, then success
    assert p.returncode == 0, (p.returncode, stdout[-2500:])
    assert open(out, "rb").read() == world["want"]
    assert ("membership: recut" in stdout
            or "membership: reassign" in stdout), stdout[-2500:]
    assert _leftovers(out) == [], _leftovers(out)


def test_chaos_modes_refused_joins_and_single_claimant(world):
    """The two built-in chaos drills: a duplicate claimant racing a live
    lease loses (exit 6, claim_lost counted); a join landing during the
    merge is refused by the persisted lease file. Bytes stay identical
    both times."""
    d = world["dir"]
    for mode, marker in (("steal_race", "claim_lost"),
                         ("join_during_merge", "join_refused")):
        out = f"{d}/{mode}.vcf"
        proc = _podrun(d, out, "--chaos", mode)
        assert proc.returncode == 0, (mode, proc.stdout[-2000:]
                                      + proc.stderr[-2000:])
        assert marker in proc.stdout, (mode, proc.stdout[-2000:])
        assert open(out, "rb").read() == world["want"]
        assert _leftovers(out) == [], (mode, _leftovers(out))


def test_chaos_flag_requires_elastic(world):
    d = world["dir"]
    proc = subprocess.run(
        [sys.executable, "-m", "tools.podrun", "--ranks", "2",
         "--chaos", "steal_race", "--",
         *_cli_args(d, "never.vcf")],
        env=_env(), cwd=_REPO, timeout=120, capture_output=True, text=True)
    assert proc.returncode == 2
