"""The serving fabric across REAL process boundaries
(docs/serving_fabric.md): ``tools/podrun.start_fabric`` boots one
router + two resident backend daemons as separate processes, a client
streams a filter request through the front door, and the seam-merged
response must be sha256-identical to the batch CLI modulo ``##vctpu_*``
provenance headers. The fleet must drain leak-free with per-tier obs
logs in the ``.backendN`` sibling layout the obs merge reads.

The in-process sibling (tests/unit/test_fabric.py) proves the router
logic across the full matrix; this file proves the PROCESS boundary:
ready-file handshakes, env propagation, streamed bodies over real
sockets, status-file drain reports. run_tests.sh wires it behind
``VCTPU_FABRIC=1`` (with the loadhunt ``backend_kill`` campaign)."""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.slow

native = pytest.importorskip("variantcalling_tpu.native")

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _sha(data: bytes) -> str:
    from tools.chaoshunt.harness import normalize_output

    return hashlib.sha256(normalize_output(data)).hexdigest()


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    from variantcalling_tpu.synthetic import make_fixtures, synthetic_forest

    d = tmp_path_factory.mktemp("fabric_fleet")
    make_fixtures(str(d), n=1500, genome_len=120_000)
    model_pkl = str(d / "model.pkl")
    with open(model_pkl, "wb") as fh:
        pickle.dump({"m": synthetic_forest(np.random.default_rng(0),
                                           n_trees=8, depth=4)}, fh)
    ref_out = str(d / "reference.vcf")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=_REPO)
    proc = subprocess.run(  # noqa: S603
        [sys.executable, "-m", "variantcalling_tpu",
         "filter_variants_pipeline", "--input_file", str(d / "calls.vcf"),
         "--model_file", model_pkl, "--model_name", "m",
         "--reference_file", str(d / "ref.fa"),
         "--output_file", ref_out, "--backend", "cpu"],
        env=env, cwd=_REPO, timeout=240, capture_output=True)
    assert proc.returncode == 0, proc.stderr.decode()[-400:]
    return {"dir": str(d), "input": str(d / "calls.vcf"),
            "model": model_pkl, "ref": str(d / "ref.fa"),
            "ref_sha": _sha(open(ref_out, "rb").read()), "env": env}


def test_fleet_parity_obs_layout_and_leakfree_drain(world, tmp_path):
    from tools import podrun
    from variantcalling_tpu.serve import transport

    base = str(tmp_path / "fleet")
    h = podrun.start_fabric(base, n_backends=2, env=world["env"])
    try:
        out = str(tmp_path / "fabric.vcf")
        code, stats = transport.client_filter(
            h.router_address,
            {"model": world["model"], "model_name": "m",
             "reference": world["ref"], "output_name": "fabric.vcf",
             "ranks": 2, "deadline_s": 120.0},
            world["input"], out, timeout=180.0)
        assert code == 200, stats
        assert stats["spans"] == 2
        assert _sha(open(out, "rb").read()) == world["ref_sha"]
    finally:
        report = podrun.stop_fabric(h)
    # drain reports: clean exits, self-reported zero leaked threads
    assert report["router"]["rc"] == 0, report
    assert report["router"].get("leaked") == [], report
    for i in (1, 2):
        assert report["backends"][i]["rc"] == 0, report
        assert report["backends"][i].get("leaked") == [], report
    # the obs sibling layout the merge path reads (router at <base>,
    # backend H at <base>.backendH) — one merged timeline with tiered
    # labels is locked by tests/unit/test_obs_profile.py
    obs_base = base + ".obs.jsonl"
    assert os.path.exists(obs_base)
    assert os.path.exists(obs_base + ".backend1")
    assert os.path.exists(obs_base + ".backend2")
    from variantcalling_tpu.obs import export

    events = export.read_run(obs_base)
    assert {e.get("backend", 0) for e in events} == {0, 1, 2}
    assert any(e.get("kind") == "membership" and e.get("action") == "join"
               for e in events)
