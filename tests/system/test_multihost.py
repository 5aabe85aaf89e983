"""Multi-host distributed backend: TWO real processes, ONE global mesh.

The reference has no distributed tests at all (SURVEY §4: "Multi-node:
none"); this goes beyond it: each subprocess is a "host" with 4 virtual
CPU devices, both initialize jax.distributed against a local coordinator,
form one 8-device (dp, mp) mesh, and reduce host-local SEC sample shards
into the cohort tensor with a cross-host psum. Both hosts must see the
identical, complete cohort.
"""

import functools
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# One tiny rank: join a 2-process jax.distributed cluster and run one
# process_allgather. On a jaxlib whose CPU backend lacks multiprocess
# collectives this fails FAST with "Multiprocess computations aren't
# implemented on the CPU backend" — the documented environmental failure
# of this whole file (docs/robustness.md).
_PROBE = """
import os
import numpy as np
import jax
jax.distributed.initialize(os.environ["VCTPU_PROBE_COORD"], 2,
                           int(os.environ["VCTPU_PROBE_PID"]))
from jax.experimental import multihost_utils
out = np.asarray(multihost_utils.process_allgather(np.asarray([1], np.int32)))
assert out.sum() == 2, out
print("PROBE_OK", flush=True)
"""


@functools.lru_cache(maxsize=1)
def _multiprocess_collectives_available() -> bool:
    """Capability probe, run once per session: can THIS jax/jaxlib
    actually execute a cross-process collective on the CPU backend?

    A real two-process attempt (not a version sniff): the failure mode
    this guards is a runtime property of the jaxlib build, and the probe
    fails in seconds when collectives are missing while proving the full
    init + allgather path when they exist.
    """
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env_base = {k: v for k, v in os.environ.items()
                if k not in ("PYTHONPATH", "JAX_PLATFORMS", "XLA_FLAGS",
                             "PYTHONSTARTUP")}
    env_base.update(JAX_PLATFORMS="cpu",
                    XLA_FLAGS="--xla_force_host_platform_device_count=1",
                    VCTPU_PROBE_COORD=f"127.0.0.1:{port}")
    procs = [subprocess.Popen([sys.executable, "-c", _PROBE],
                              env=dict(env_base, VCTPU_PROBE_PID=str(pid)),
                              stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                              text=True)
             for pid in range(2)]
    ok = True
    for p in procs:
        try:
            out, _ = p.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            return False
        ok = ok and p.returncode == 0 and "PROBE_OK" in out
    return ok


@pytest.fixture(scope="module")
def multiprocess_collectives():
    """Lazy capability gate: the two-subprocess probe runs only when one
    of these tests actually EXECUTES (module-scoped + lru_cache = once
    per session), never at collection — `pytest --collect-only` or a
    `-k unrelated` run must not pay a jax.distributed handshake."""
    if not _multiprocess_collectives_available():
        pytest.skip(
            "capability probe: this jaxlib CPU backend cannot execute "
            "multiprocess collectives ('Multiprocess computations aren't "
            "implemented') — environmental, documented in docs/robustness.md")


_WORKER = """
import os, sys
sys.path.insert(0, os.environ["VCTPU_TEST_REPO"])
import numpy as np
from variantcalling_tpu.parallel import distributed as dist

assert dist.init_from_env(), "env should request multi-host init"
import jax
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 8 and len(jax.local_devices()) == 4

pid = jax.process_index()
# RAGGED host-local shards from the env (host0,host1 sample counts) —
# covers unequal padded row counts (5 vs 4) and an EMPTY rank (0 vs 4),
# both of which desynced the global shape before the per-device
# shard-size agreement in aggregate_counts_across_hosts
shards = [int(s) for s in os.environ["VCTPU_TEST_SHARDS"].split(",")]
n_local = shards[pid]
local = (np.stack([np.full((6, 4), 10 * pid + s, dtype=np.float32) for s in range(n_local)])
         if n_local else np.zeros((0, 6, 4), dtype=np.float32))
cohort = dist.aggregate_counts_across_hosts(local)
expect = sum(10 * h + s for h in range(2) for s in range(shards[h]))
np.testing.assert_allclose(cohort, np.full((6, 4), float(expect)))

# ragged key allgather: union across hosts
keys = np.asarray([1, 5, 9] if pid == 0 else [2, 5], dtype=np.int64)
gathered = np.unique(dist.allgather_concat(keys))
np.testing.assert_array_equal(gathered, [1, 2, 5, 9])
print(f"WORKER_OK {pid} {float(cohort.sum())}", flush=True)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_two_workers(shards: str) -> None:
    port = _free_port()
    env_base = {
        k: v for k, v in os.environ.items()
        if k not in ("PYTHONPATH", "JAX_PLATFORMS", "XLA_FLAGS", "PYTHONSTARTUP")
    }
    env_base.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        VCTPU_COORDINATOR=f"127.0.0.1:{port}",
        VCTPU_NUM_PROCESSES="2",
        VCTPU_TEST_REPO=_REPO,
        VCTPU_TEST_SHARDS=shards,
    )
    procs = []
    for pid in range(2):
        env = dict(env_base, VCTPU_PROCESS_ID=str(pid))
        procs.append(subprocess.Popen([sys.executable, "-c", _WORKER], env=env,
                                      stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                                      text=True))
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out, err))
    sums = set()
    for rc, out, err in outs:
        assert rc == 0, f"worker failed rc={rc}\nstdout:{out}\nstderr:{err[-1500:]}"
        assert "WORKER_OK" in out, out
        sums.add(out.split("WORKER_OK")[1].split()[1])
    # both hosts saw the identical complete cohort
    assert len(sums) == 1, sums


def test_two_process_global_mesh_psum(tmp_path, multiprocess_collectives):
    _run_two_workers("3,4")


def test_ragged_padded_shards_5_vs_4(tmp_path, multiprocess_collectives):
    """5-vs-4 samples on 4-device hosts: padded row counts differ (8 vs 4)
    unless hosts agree on the per-device shard size first."""
    _run_two_workers("5,4")


def test_empty_rank_joins_collective(tmp_path, multiprocess_collectives):
    """A rank holding ZERO samples must still join the psum and receive
    the full cohort (previously: silent all-zero cohort on the empty rank
    and a Gloo deadlock on the other)."""
    _run_two_workers("0,4")


def test_two_rank_sec_training_cli(tmp_path, multiprocess_collectives):
    """Full sec_training CLI on two ranks, each holding its own sample
    VCFs: both must write the SAME cohort DB spanning all four samples —
    the reference's cohort build has no multi-node mode at all.

    The ranks deliberately see DIFFERENT contig sets (rank 0 only chr2,
    rank 1 chr1+chr2 in a different index order): packed keys encode the
    contig index, so the cohort is only correct if ranks canonicalize
    contigs before the union."""

    # tiny sample VCFs; loci given as (contig, pos, ad)
    def sample_vcf(path, contig_decl, loci_ad):
        lines = ["##fileformat=VCFv4.2"]
        lines += [f"##contig=<ID={c},length=10000>" for c in contig_decl]
        lines += ['##FORMAT=<ID=GT,Number=1,Type=String,Description="g">',
                  '##FORMAT=<ID=AD,Number=R,Type=Integer,Description="a">',
                  "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\tS"]
        for c, pos, ad in loci_ad:
            lines.append(f"{c}\t{pos}\t.\tA\tG\t50\tPASS\t.\tGT:AD\t0/1:{ad}")
        open(path, "w").write("\n".join(lines) + "\n")

    samples = {
        0: [("s0a", ["chr2"], [("chr2", 100, "20,5"), ("chr2", 200, "30,2")]),
            ("s0b", ["chr2"], [("chr2", 100, "18,7")])],
        1: [("s1a", ["chr1", "chr2"], [("chr1", 50, "25,3"), ("chr2", 100, "9,1")]),
            ("s1b", ["chr1", "chr2"], [("chr1", 50, "22,4"), ("chr2", 200, "12,8")])],
    }
    for pid, ss in samples.items():
        for name, contig_decl, loci in ss:
            sample_vcf(str(tmp_path / f"{name}.vcf"), contig_decl, loci)

    port = _free_port()
    env_base = {k: v for k, v in os.environ.items()
                if k not in ("PYTHONPATH", "JAX_PLATFORMS", "XLA_FLAGS", "PYTHONSTARTUP")}
    env_base.update(JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4",
                    VCTPU_COORDINATOR=f"127.0.0.1:{port}", VCTPU_NUM_PROCESSES="2",
                    PYTHONPATH=_REPO)
    procs = []
    for pid, ss in samples.items():
        inputs = [str(tmp_path / f"{n}.vcf") for n, _, _ in ss]
        cmd = [sys.executable, "-m", "variantcalling_tpu", "sec_training",
               "--inputs", *inputs, "--min_samples", "2",
               "--output_file", str(tmp_path / f"db_{pid}.h5")]
        env = dict(env_base, VCTPU_PROCESS_ID=str(pid))
        procs.append(subprocess.Popen(cmd, env=env, cwd=_REPO,
                                      stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                                      text=True))
    for p in procs:
        out, err = p.communicate(timeout=240)
        assert p.returncode == 0, f"rc={p.returncode}\n{out}\n{err[-2000:]}"

    from variantcalling_tpu.sec.db import SecDb

    db0 = SecDb.load(str(tmp_path / "db_0.h5"))
    db1 = SecDb.load(str(tmp_path / "db_1.h5"))
    assert db0.n_samples == db1.n_samples == 4
    assert db0.contigs == db1.contigs == ["chr1", "chr2"]
    np.testing.assert_array_equal(db0.keys, db1.keys)
    np.testing.assert_allclose(db0.counts, db1.counts)
    # chr1:50 (2 samples), chr2:100 (3), chr2:200 (2) all pass min_samples=2
    assert len(db0) == 3
    idx = {c: i for i, c in enumerate(db0.contigs)}
    decoded = {(int(k) >> 40, int(k) & ((1 << 40) - 1)) for k in db0.keys}
    assert decoded == {(idx["chr1"], 50), (idx["chr2"], 100), (idx["chr2"], 200)}
    # cross-rank merge at chr2:100: ref counts 20+18+9 from three samples
    row = db0.counts[list(db0.keys).index((idx["chr2"] << 40) | 100)]
    assert row[0] == 20 + 18 + 9


@pytest.mark.flakehunt
def test_two_rank_filter_variants_pipeline_cli(tmp_path, multiprocess_collectives):
    """Full flagship filter_variants_pipeline on TWO ranks (4 virtual
    devices each): ranks score contiguous slices on their local meshes,
    allgather scores+filters, and rank 0 alone writes the shared output
    path (non-zero ranks delegate — concurrent identical writes would
    race on a shared filesystem) — matching a single-process run.

    Round-5 flake postmortem: this byte-compare was load-flaky because
    scores were not bit-stable across engine/mesh variation — XLA's f32
    tree-sum reduce reassociates differently across device layouts, and a
    native hiccup silently swapped scoring engines mid-run. Both causes
    are fixed structurally (canonical sequential tree accumulation +
    shared host finalization in models/forest.py; the run-level engine
    contract in variantcalling_tpu/engine.py), and the test is now
    flakehunt-marked so `VCTPU_FLAKEHUNT=1 ./run_tests.sh` /
    tools/flakehunt.sh keep measuring its pass rate under load."""
    from variantcalling_tpu.synthetic import make_fixtures

    d = str(tmp_path)
    make_fixtures(d, n=6000, genome_len=300_000)
    # a model pickle the CLI can load
    import pickle

    from variantcalling_tpu.synthetic import synthetic_forest

    model = synthetic_forest(np.random.default_rng(0), n_trees=10, depth=5)
    with open(f"{d}/model.pkl", "wb") as fh:
        pickle.dump({"rf_model_ignore_gt_incl_hpol_runs": model}, fh)

    port = _free_port()
    env_base = {k: v for k, v in os.environ.items()
                if k not in ("PYTHONPATH", "JAX_PLATFORMS", "XLA_FLAGS", "PYTHONSTARTUP")}
    env_base.update(JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4",
                    VCTPU_COORDINATOR=f"127.0.0.1:{port}", VCTPU_NUM_PROCESSES="2",
                    PYTHONPATH=_REPO)
    procs = []
    for pid in range(2):
        cmd = [sys.executable, "-m", "variantcalling_tpu", "filter_variants_pipeline",
               "--input_file", f"{d}/calls.vcf", "--model_file", f"{d}/model.pkl",
               "--model_name", "rf_model_ignore_gt_incl_hpol_runs",
               "--reference_file", f"{d}/ref.fa",
               "--output_file", f"{d}/out_shared.vcf"]
        env = dict(env_base, VCTPU_PROCESS_ID=str(pid))
        procs.append(subprocess.Popen(cmd, env=env, cwd=_REPO,
                                      stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                                      text=True))
    rank_logs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:  # a wedged rank must not leak its peer
                q.kill()
            raise
        assert p.returncode == 0, f"rc={p.returncode}\n{out}\n{err[-2000:]}"
        rank_logs.append(out + err)

    a = open(f"{d}/out_shared.vcf", "rb").read()
    assert a.count(b"TREE_SCORE=") == 6000
    # exactly one rank committed the shared path: either the serial
    # allgather path's writeback delegation, or — when the ranks took
    # the rank-partitioned streaming path (docs/scaleout.md) — rank 0's
    # rank-sequenced merge after the completion barrier
    assert sum("delegated to rank 0" in log
               or "commit delegated to rank 0" in log
               for log in rank_logs) == 1

    # single-process run must produce the same bytes modulo the
    # ##vctpu_* provenance headers (a 2-rank run records
    # ##vctpu_ranks=n=2; a single-rank run records no such line)
    env1 = dict(env_base)
    for k in ("VCTPU_COORDINATOR", "VCTPU_NUM_PROCESSES"):
        env1.pop(k, None)
    p1 = subprocess.run(
        [sys.executable, "-m", "variantcalling_tpu", "filter_variants_pipeline",
         "--input_file", f"{d}/calls.vcf", "--model_file", f"{d}/model.pkl",
         "--model_name", "rf_model_ignore_gt_incl_hpol_runs",
         "--reference_file", f"{d}/ref.fa",
         "--output_file", f"{d}/out_single.vcf"],
        env=env1, cwd=_REPO, capture_output=True, text=True, timeout=300)
    assert p1.returncode == 0, p1.stderr[-2000:]

    from tools.chaoshunt.harness import normalize_output as norm

    assert norm(open(f"{d}/out_single.vcf", "rb").read()) == norm(a)
