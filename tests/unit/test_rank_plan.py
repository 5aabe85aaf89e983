"""Rank-partitioned scale-out (docs/scaleout.md): plan resolution, the
deterministic span partition, the seam-aware rank-sequenced commit, and
the completed-segment skip path.

The three contracts under lock:

- **Partition exactness**: the per-rank spans tile the record region at
  every rank count, for plain-text AND BGZF inputs — no record lost,
  none duplicated, whatever the chunk/block layout.
- **Byte parity**: the merged pod output equals the single-rank run
  modulo the ``##vctpu_*`` provenance headers, across rank counts,
  output containers and engines (the flakehunt matrix).
- **Seam framing**: a ``.gz`` merge re-carries the 65280-byte BGZF block
  carry across rank seams exactly as a serial writer would — including
  seams that land mid-block.
"""

from __future__ import annotations

import argparse
import gzip
import itertools
import json
import os
import pickle

import numpy as np
import pytest

from variantcalling_tpu.engine import EngineError
from variantcalling_tpu.io import bgzf as bgzf_mod
from variantcalling_tpu.parallel import rank_plan as rank_plan_mod

native = pytest.importorskip("variantcalling_tpu.native")


@pytest.fixture(autouse=True)
def _engine_cache_isolated():
    yield
    from variantcalling_tpu import engine as engine_mod

    engine_mod.reset_for_tests()


_WATCHED_DIRS: list[str] = []


@pytest.fixture(autouse=True)
def _leak_sentinel():
    yield
    from tests.conftest import assert_no_stream_leaks

    assert_no_stream_leaks(_WATCHED_DIRS)


# ---------------------------------------------------------------------------
# plan resolution
# ---------------------------------------------------------------------------


def test_resolve_env_plan(monkeypatch):
    monkeypatch.setenv("VCTPU_RANK", "1")
    monkeypatch.setenv("VCTPU_NUM_PROCESSES", "4")
    plan = rank_plan_mod.resolve()
    assert (plan.rank, plan.ranks, plan.source) == (1, 4, "env")
    assert plan.header_line() == "##vctpu_ranks=n=4"


def test_resolve_requires_num_processes(monkeypatch):
    monkeypatch.setenv("VCTPU_RANK", "0")
    monkeypatch.delenv("VCTPU_NUM_PROCESSES", raising=False)
    with pytest.raises(EngineError, match="VCTPU_NUM_PROCESSES"):
        rank_plan_mod.resolve()


def test_resolve_rejects_out_of_range_rank(monkeypatch):
    monkeypatch.setenv("VCTPU_RANK", "2")
    monkeypatch.setenv("VCTPU_NUM_PROCESSES", "2")
    with pytest.raises(EngineError, match="out of range"):
        rank_plan_mod.resolve()


def test_resolve_single_without_env(monkeypatch):
    monkeypatch.delenv("VCTPU_RANK", raising=False)
    monkeypatch.delenv("VCTPU_NUM_PROCESSES", raising=False)
    plan = rank_plan_mod.resolve()
    assert (plan.rank, plan.ranks) == (0, 1)


def test_obs_rank_suffix_reads_env_before_jax(monkeypatch):
    """Satellite: the obs log suffix must resolve from VCTPU_RANK (the
    local launcher) — not from an uninitialized jax backend that would
    silently report rank 0."""
    from variantcalling_tpu import obs

    monkeypatch.setenv("VCTPU_RANK", "3")
    monkeypatch.setenv("VCTPU_NUM_PROCESSES", "4")
    assert obs._rank_suffixed("/x/log.jsonl") == "/x/log.jsonl.rank3"
    monkeypatch.setenv("VCTPU_RANK", "0")
    assert obs._rank_suffixed("/x/log.jsonl") == "/x/log.jsonl"


def test_output_header_records_and_strips_ranks_line():
    from variantcalling_tpu.io.vcf import parse_header_bytes
    from variantcalling_tpu.pipelines.filter_variants import \
        _ensure_output_header

    head = (b"##fileformat=VCFv4.2\n##vctpu_ranks=n=7\n"
            b"#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\n")
    header, _ = parse_header_bytes(head)
    plan = rank_plan_mod.RankPlan(ranks=2, rank=1, source="env", reason="t")
    _ensure_output_header(header, rank_plan=plan)
    lines = [ln for ln in header.lines if ln.startswith("##vctpu_ranks=")]
    assert lines == ["##vctpu_ranks=n=2"]  # stale n=7 REPLACED, not kept
    # single-rank: the stale line is stripped entirely
    header2, _ = parse_header_bytes(head)
    _ensure_output_header(
        header2, rank_plan=rank_plan_mod.RankPlan(1, 0, "single", "t"))
    assert not [ln for ln in header2.lines
                if ln.startswith("##vctpu_ranks=")]


# ---------------------------------------------------------------------------
# the span partition: exact tiling at every rank count
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.synthetic import make_fixtures, synthetic_forest

    d = str(tmp_path_factory.mktemp("rankplan"))
    make_fixtures(d, n=2500, genome_len=150_000)
    with open(f"{d}/calls.vcf", "rb") as fh:
        text = fh.read()
    with bgzf_mod.BgzfWriter(f"{d}/calls.vcf.gz") as w:
        w.write(text)
    model = synthetic_forest(np.random.default_rng(0), n_trees=8, depth=4)
    with open(f"{d}/model.pkl", "wb") as fh:
        pickle.dump({"m": model}, fh)
    _WATCHED_DIRS.append(d)
    return {"dir": d, "n": 2500, "model": model,
            "fasta": FastaReader(f"{d}/ref.fa")}


def _raw_bytes(reader) -> bytes:
    return b"".join(bytes(memoryview(b)) if isinstance(b, np.ndarray)
                    else bytes(b) for b, _ in reader.iter_raw())


@pytest.mark.parametrize("suffix", ["", ".gz"])
@pytest.mark.parametrize("ranks", [2, 3, 8])
def test_rank_spans_tile_the_record_region(world, suffix, ranks):
    """Concatenating every rank's raw span bytes reproduces the serial
    record stream EXACTLY — the partition rule loses nothing and
    duplicates nothing, at any rank count, either container."""
    from variantcalling_tpu.io.vcf import VcfChunkReader

    path = f"{world['dir']}/calls.vcf{suffix}"
    serial = _raw_bytes(VcfChunkReader(path, chunk_bytes=1 << 15,
                                       io_threads=1))
    got = b"".join(
        _raw_bytes(VcfChunkReader(path, chunk_bytes=1 << 15, io_threads=1,
                                  rank_span=(r, ranks)))
        for r in range(ranks))
    assert got == serial


def test_rank_span_boundaries_identical_across_io_threads(world):
    """The cut rule is a pure function of the input bytes — the worker
    count must not move a rank's span (parallel BGZF window vs the
    serial member stream)."""
    from variantcalling_tpu.io.vcf import VcfChunkReader

    path = f"{world['dir']}/calls.vcf.gz"
    for r in range(3):
        a = _raw_bytes(VcfChunkReader(path, chunk_bytes=1 << 15,
                                      io_threads=1, rank_span=(r, 3)))
        b = _raw_bytes(VcfChunkReader(path, chunk_bytes=1 << 15,
                                      io_threads=4, rank_span=(r, 3)))
        assert a == b, f"rank {r} span moved with the worker count"


def test_rank_span_rejects_plain_gzip(world, tmp_path):
    from variantcalling_tpu.io.vcf import VcfChunkReader

    path = str(tmp_path / "plain.vcf.gz")
    with open(f"{world['dir']}/calls.vcf", "rb") as fh:
        with gzip.open(path, "wb") as gz:
            gz.write(fh.read())
    with pytest.raises(EngineError, match="BGZF-framed"):
        VcfChunkReader(path, rank_span=(0, 2))
    # single-rank reads of the same file stay fine
    assert len(list(VcfChunkReader(path, io_threads=1).iter_raw())) > 0


# ---------------------------------------------------------------------------
# seam framing: the BGZF carry across rank seams
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("body_sizes", [
    # every seam lands mid-block: no body is a multiple of 65280
    (100_000, 70_001, 3),
    # a seam exactly AT a block boundary, then mid-block again
    (bgzf_mod.MAX_BLOCK_DATA * 2, 65_279, 65_281),
    # an EMPTY rank segment between two others
    (50_000, 0, 50_001),
])
def test_merge_recarries_bgzf_seams_like_a_serial_writer(tmp_path,
                                                         body_sizes):
    """The rank-sequenced committer's .gz output is byte-identical to a
    serial BgzfWriter of header+bodies — the 65280-byte block carry is
    re-carried deterministically across every rank seam, including
    seams that land mid-block (the ISSUE's named hazard)."""
    rng = np.random.default_rng(7)
    header = b"##fileformat=VCFv4.2\n#CHROM\tPOS\n"

    def body(n):
        if n == 0:
            return b""
        b = bytes(rng.integers(33, 126, size=n, dtype=np.uint8))
        return b[:-1] + b"\n"

    bodies = [body(n) for n in body_sizes]
    out = str(tmp_path / "merged.vcf.gz")
    ranks = len(bodies)
    ident = {"k": 1}
    for r, bo in enumerate(bodies):
        seg = rank_plan_mod.segment_path(out, r, ranks)
        with open(seg, "wb") as fh:
            fh.write(header + bo)
        rank_plan_mod.write_marker(seg, dict(ident, ranks=[r, ranks]),
                                   {"n": 0, "n_pass": 0, "chunks": 1})
    rank_plan_mod.merge_ranks(out, ranks)
    got = open(out, "rb").read()
    serial = str(tmp_path / "serial.vcf.gz")
    with bgzf_mod.BgzfWriter(serial) as w:
        w.write(header)
        for bo in bodies:
            w.write(bo)
    assert got == open(serial, "rb").read()
    assert gzip.decompress(got) == header + b"".join(bodies)


# ---------------------------------------------------------------------------
# the flakehunt parity matrix: merged pod bytes == single-rank bytes
# ---------------------------------------------------------------------------


def _norm(data: bytes) -> bytes:
    # the ONE provenance-normalization spelling (chaoshunt shares it
    # with loadhunt and these suites)
    from tools.chaoshunt.harness import normalize_output

    return normalize_output(data)


def _ns(inp, out):
    return argparse.Namespace(
        input_file=inp, output_file=out, runs_file=None,
        hpol_filter_length_dist=[10, 10], blacklist=None,
        blacklist_cg_insertions=False, annotate_intervals=[],
        flow_order="TGCA", is_mutect=False, limit_to_contig=None)


def _run_pod(world, inp, out, ranks, monkeypatch, engine):
    """Sequential in-process pod: ranks share no state, so running the
    worker bodies one after another in one process is byte-equivalent
    to N processes — what the subprocess e2e (tests/system/
    test_scaleout.py) proves for the real launcher."""
    from variantcalling_tpu import engine as engine_mod
    from variantcalling_tpu.io import vcf as vcf_mod
    from variantcalling_tpu.pipelines.filter_variants import run_streaming

    monkeypatch.setattr(vcf_mod, "STREAM_CHUNK_BYTES", 1 << 15)
    monkeypatch.setenv("VCTPU_IO_THREADS", "2")
    monkeypatch.setenv("VCTPU_ENGINE", engine)
    engine_mod.reset_for_tests()
    total = 0
    for r in range(ranks):
        plan = rank_plan_mod.RankPlan(ranks=ranks, rank=r, source="env",
                                      reason="test")
        seg = rank_plan_mod.segment_path(out, r, ranks)
        stats = run_streaming(_ns(inp, seg), world["model"], world["fasta"],
                              {}, None, rank_plan=plan)
        assert stats is not None
        total += stats["n"]
        rank_plan_mod.write_marker(
            seg, rank_plan_mod.segment_identity(_ns(inp, out), plan), stats)
    assert total == world["n"]
    return rank_plan_mod.merge_ranks(out, ranks)


@pytest.mark.flakehunt
@pytest.mark.parametrize("engine", ["native", "jit"])
def test_pod_parity_matrix(world, monkeypatch, engine):
    """Acceptance: merged pod output == single-rank output modulo the
    ##vctpu_* headers, for ranks {1,2,4} x {plain, BGZF} output, per
    engine (ordering-sensitive: flakehunt repeats it)."""
    from variantcalling_tpu import engine as engine_mod
    from variantcalling_tpu.io import vcf as vcf_mod
    from variantcalling_tpu.pipelines.filter_variants import run_streaming

    d = world["dir"]
    inp = f"{d}/calls.vcf"
    oracle: dict[str, bytes] = {}
    for out_sfx in ("", ".gz"):
        monkeypatch.setattr(vcf_mod, "STREAM_CHUNK_BYTES", 1 << 15)
        monkeypatch.setenv("VCTPU_IO_THREADS", "2")
        monkeypatch.setenv("VCTPU_ENGINE", engine)
        engine_mod.reset_for_tests()
        ref = f"{d}/mref_{engine}.vcf{out_sfx}"
        assert run_streaming(_ns(inp, ref), world["model"], world["fasta"],
                             {}, None) is not None
        raw = open(ref, "rb").read()
        oracle[out_sfx] = _norm(gzip.decompress(raw) if out_sfx else raw)
    for ranks, out_sfx in itertools.product((1, 2, 4), ("", ".gz")):
        out = f"{d}/mpod_{engine}_{ranks}{out_sfx.replace('.', '_')}.vcf{out_sfx}"
        _run_pod(world, inp, out, ranks, monkeypatch, engine)
        raw = open(out, "rb").read()
        got = _norm(gzip.decompress(raw) if out_sfx else raw)
        assert got == oracle[out_sfx], (engine, ranks, out_sfx)
        if ranks > 1:
            # >1-rank outputs carry the pod provenance line
            text = gzip.decompress(raw) if out_sfx else raw
            assert f"##vctpu_ranks=n={ranks}".encode() in text
        os.remove(out)


# ---------------------------------------------------------------------------
# merge preconditions + the completed-segment skip path
# ---------------------------------------------------------------------------


def _stage_segments(out, bodies, ident):
    header = b"##fileformat=VCFv4.2\n#CHROM\tPOS\n"
    ranks = len(bodies)
    for r, bo in enumerate(bodies):
        seg = rank_plan_mod.segment_path(out, r, ranks)
        with open(seg, "wb") as fh:
            fh.write(header + bo)
        rank_plan_mod.write_marker(seg, dict(ident, ranks=[r, ranks]),
                                   {"n": 1, "n_pass": 1, "chunks": 1})
    return ranks


def test_merge_refuses_missing_segment(tmp_path):
    out = str(tmp_path / "o.vcf")
    _stage_segments(out, [b"a\n", b"b\n"], {"k": 1})
    os.remove(rank_plan_mod.segment_path(out, 1, 2))
    with pytest.raises(rank_plan_mod.MergeError, match="segment missing"):
        rank_plan_mod.merge_ranks(out, 2)
    assert not os.path.exists(out)


def test_merge_refuses_cross_rank_identity_drift(tmp_path):
    out = str(tmp_path / "o.vcf")
    _stage_segments(out, [b"a\n", b"b\n"], {"k": 1})
    seg1 = rank_plan_mod.segment_path(out, 1, 2)
    rank_plan_mod.write_marker(seg1, {"k": 2, "ranks": [1, 2]},
                               {"n": 1, "n_pass": 1, "chunks": 1})
    with pytest.raises(rank_plan_mod.MergeError, match="DIFFERENT"):
        rank_plan_mod.merge_ranks(out, 2)


def test_merge_refuses_header_drift(tmp_path):
    out = str(tmp_path / "o.vcf")
    _stage_segments(out, [b"a\n", b"b\n"], {"k": 1})
    seg1 = rank_plan_mod.segment_path(out, 1, 2)
    with open(seg1, "wb") as fh:
        fh.write(b"##fileformat=VCFv4.3\n#CHROM\tPOS\nb\n")
    rank_plan_mod.write_marker(seg1, {"k": 1, "ranks": [1, 2]},
                               {"n": 1, "n_pass": 1, "chunks": 1})
    with pytest.raises(rank_plan_mod.MergeError, match="header differs"):
        rank_plan_mod.merge_ranks(out, 2)


def test_merge_infers_rank_count_and_sweeps(tmp_path):
    out = str(tmp_path / "o.vcf")
    ranks = _stage_segments(out, [b"a\n", b"b\n", b"c\n"], {"k": 1})
    assert rank_plan_mod.discover_ranks(out) == ranks
    stats = rank_plan_mod.merge_ranks(out)  # N inferred from disk
    assert stats["ranks"] == 3
    assert open(out, "rb").read().endswith(b"a\nb\nc\n")
    assert rank_plan_mod.discover_ranks(out) is None  # segments swept


def test_valid_segment_skip_and_invalidation(tmp_path):
    seg = str(tmp_path / "o.vcf.rank0of2.seg")
    with open(seg, "wb") as fh:
        fh.write(b"#h\nbody\n")
    ident = {"k": 1, "ranks": [0, 2]}
    rank_plan_mod.write_marker(seg, ident, {"n": 5, "n_pass": 2,
                                            "chunks": 1})
    assert rank_plan_mod.valid_segment(seg, ident) == {
        "n": 5, "n_pass": 2, "chunks": 1}
    # a different identity (other input/config/rank layout) recomputes
    assert rank_plan_mod.valid_segment(seg, {"k": 2, "ranks": [0, 2]}) \
        is None
    # a torn/edited segment recomputes even under the same identity
    with open(seg, "ab") as fh:
        fh.write(b"x")
    assert rank_plan_mod.valid_segment(seg, ident) is None


def test_merge_ranks_cli_exit_codes(tmp_path, capsys):
    missing = str(tmp_path / "nope.vcf")
    assert rank_plan_mod.run([missing]) == 3  # no segments: merge error
    assert "no rank segments" in capsys.readouterr().err
    out = str(tmp_path / "o.vcf")
    _stage_segments(out, [b"a\n", b"b\n"], {"k": 1})
    assert rank_plan_mod.run([out, "--ranks", "2"]) == 0
    assert os.path.exists(out)


def test_segment_identity_pins_rank_layout_and_engine(tmp_path):
    inp = str(tmp_path / "in.vcf")
    open(inp, "w").write("#h\n")
    ns = _ns(inp, str(tmp_path / "o.vcf"))
    plan_a = rank_plan_mod.RankPlan(2, 0, "env", "t")
    plan_b = rank_plan_mod.RankPlan(4, 0, "env", "t")
    ia = rank_plan_mod.segment_identity(ns, plan_a, "native")
    ib = rank_plan_mod.segment_identity(ns, plan_b, "native")
    ic = rank_plan_mod.segment_identity(ns, plan_a, "jit")
    assert ia != ib and ia != ic
    assert ia == rank_plan_mod.segment_identity(ns, plan_a, "native")
    assert json.loads(json.dumps(ia)) == ia  # marker-serializable
