"""Fused native chunk scoring (ISSUE 12 tentpole): the single-call
parse-output -> featurize -> forest body (``native.fused_chunk_score``)
and its wiring into the streaming executor's zero-wait chunk feed.

Locks the contracts the fusion must keep:

- **Margin parity**: the fused kernel's canonical-order margins are
  bit-identical to the unfused reference (per-contig
  ``featurize_gather`` + ``matrix_forest_predict``) across contig runs,
  contig-edge windows, missing contigs and empty runs.
- **Byte parity end to end**: streaming CLI output is byte-identical
  across {fused-native, unfused-native reference, jit} x
  ``VCTPU_IO_THREADS`` {1, 4} x ``VCTPU_MESH_DEVICES`` {1, 2} — modulo
  the ``##vctpu_*`` header lines naming the configuration (the PR 2
  invariant extended to the fused path).
- **Sorted-runs gate**: an unsorted chunk falls back to the reference
  path (same bytes), never a wrong-contig window.
- **Run memoization**: ``featurize._contig_runs`` derives a table's runs
  once and serves repeats from the table-attached memo; native-scan
  codes already in appearance order come back without a remap copy.
"""

from __future__ import annotations

import argparse
import os

import numpy as np
import pytest

native = pytest.importorskip("variantcalling_tpu.native")

if not native.available():  # pragma: no cover - toolchain-less containers
    pytest.skip("native library unavailable", allow_module_level=True)


@pytest.fixture(autouse=True)
def _engine_cache_isolated():
    yield
    from variantcalling_tpu import engine as engine_mod

    engine_mod.reset_for_tests()


@pytest.fixture(scope="module")
def fused_world(tmp_path_factory):
    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.synthetic import make_fixtures, synthetic_forest

    d = str(tmp_path_factory.mktemp("fusednative"))
    make_fixtures(d, n=4000, genome_len=200_000)
    model = synthetic_forest(np.random.default_rng(0), n_trees=8, depth=4)
    return {"dir": d, "n": 4000, "model": model,
            "fasta": FastaReader(f"{d}/ref.fa")}


# ---------------------------------------------------------------------------
# kernel-level margin parity
# ---------------------------------------------------------------------------


def _synthetic_chunk(rng, n, seq_lens):
    """Contig-run chunk inputs with edge/out-of-range positions mixed in."""
    seqs = [rng.integers(0, 5, ln, dtype=np.uint8) if ln else
            np.empty(0, dtype=np.uint8) for ln in seq_lens]
    bounds = np.linspace(0, n, len(seqs) + 1).astype(np.int64)
    pos0 = np.empty(n, dtype=np.int64)
    for r, s in enumerate(seqs):
        lo, hi = int(bounds[r]), int(bounds[r + 1])
        pos0[lo:hi] = np.sort(rng.integers(-30, max(len(s), 1) + 30,
                                           hi - lo))
    aux = {
        "is_indel": rng.integers(0, 2, n).astype(np.uint8),
        "indel_nuc": rng.integers(0, 5, n).astype(np.int32),
        "ref_code": rng.integers(0, 4, n).astype(np.int32),
        "alt_code": rng.integers(0, 4, n).astype(np.int32),
    }
    aux["is_snp"] = ((aux["is_indel"] == 0)
                     & (rng.random(n) < 0.8)).astype(np.uint8)
    return seqs, bounds, pos0, aux


@pytest.mark.parametrize("seq_lens", [(120_000,), (90_000, 50_000, 0),
                                      (0,), (64, 70_000)])
def test_fused_chunk_score_margin_parity(seq_lens):
    """Fused single-call margins == per-contig featurize_gather + fused
    column walk, bit for bit — incl. contig-edge windows (pad path),
    missing contigs (all-N) and tiny contigs."""
    from variantcalling_tpu.synthetic import synthetic_forest

    rng = np.random.default_rng(7)
    n = 3000
    seqs, bounds, pos0, aux = _synthetic_chunk(rng, n, seq_lens)
    fo = np.array([3, 2, 1, 0], dtype=np.int32)  # TGCA
    radius = 20
    outs = (np.empty(n, np.int32), np.empty(n, np.int32),
            np.empty(n, np.float32), np.empty(n, np.int32),
            np.empty(n, np.int32), np.empty(n, np.int32))
    for r, seq in enumerate(seqs):
        lo, hi = int(bounds[r]), int(bounds[r + 1])
        ok = native.featurize_gather(
            seq, pos0[lo:hi], radius,
            *(aux[k][lo:hi] for k in ("is_indel", "indel_nuc", "ref_code",
                                      "alt_code", "is_snp")),
            fo, tuple(o[lo:hi] for o in outs))
        assert ok
    forest = synthetic_forest(rng, n_trees=8, depth=4, n_features=10)
    host_a = rng.normal(size=n).astype(np.float32)
    host_b = rng.integers(0, 50, n).astype(np.int32)
    host_c = rng.random(n).astype(np.float64)
    host_d = rng.integers(0, 2, n).astype(np.uint8)
    hl, hn, gc, cy, lm, rm = outs
    ref_cols = [host_a, hl, hn, gc, host_b, cy, lm, host_c, rm, host_d]
    margin_ref = native.matrix_forest_predict(
        ref_cols, forest.feature, forest.threshold, forest.left,
        forest.right, forest.value, None, forest.max_depth, "sum", 0.0)
    assert margin_ref is not None
    cols = [host_a, None, None, None, host_b, None, None, host_c, None,
            host_d]
    dev_cols = np.array([1, 2, 3, 5, 6, 8], dtype=np.int32)
    margin = native.fused_chunk_score(
        seqs, bounds, pos0, radius, aux["is_indel"], aux["indel_nuc"],
        aux["ref_code"], aux["alt_code"], aux["is_snp"], fo, cols, dev_cols,
        forest.feature, forest.threshold, forest.left, forest.right,
        forest.value, None, forest.max_depth, "sum", 0.0)
    assert margin is not None
    assert np.array_equal(margin, margin_ref)


def test_fused_chunk_score_empty_chunk():
    from variantcalling_tpu.synthetic import synthetic_forest

    rng = np.random.default_rng(0)
    forest = synthetic_forest(rng, n_trees=4, depth=3, n_features=7)
    fo = np.array([3, 2, 1, 0], dtype=np.int32)
    cols = [np.empty(0, np.float32)] + [None] * 6
    margin = native.fused_chunk_score(
        [np.empty(0, np.uint8)], np.array([0, 0], np.int64),
        np.empty(0, np.int64), 20,
        np.empty(0, np.uint8), np.empty(0, np.int32), np.empty(0, np.int32),
        np.empty(0, np.int32), np.empty(0, np.uint8), fo, cols,
        np.array([1, 2, 3, 4, 5, 6], np.int32),
        forest.feature, forest.threshold, forest.left, forest.right,
        forest.value, None, forest.max_depth, "sum", 0.0)
    assert margin is not None and len(margin) == 0


# ---------------------------------------------------------------------------
# _contig_runs memoization
# ---------------------------------------------------------------------------


def test_contig_runs_memoized_and_identity_codes(fused_world):
    from variantcalling_tpu.featurize import _contig_runs
    from variantcalling_tpu.io.vcf import VcfChunkReader

    table = next(iter(VcfChunkReader(f"{fused_world['dir']}/calls.vcf",
                                     io_threads=1)))
    assert table.chrom_codes is not None
    codes, uniques, bounds = _contig_runs(table, len(table))
    assert bounds is not None
    # native-scan codes are first-appearance ordered on a sorted file:
    # the fast path must return them as-is, no remap copy
    assert codes is table.chrom_codes
    # repeat calls serve the table-attached memo (identical objects)
    again = _contig_runs(table, len(table))
    assert again[0] is codes and again[1] is uniques and again[2] is bounds
    # per-contig slices agree with the chrom column
    for ui, contig in enumerate(uniques):
        lo, hi = int(bounds[ui]), int(bounds[ui + 1])
        assert all(c == contig for c in table.chrom[lo:hi])


# ---------------------------------------------------------------------------
# streaming byte-parity matrix
# ---------------------------------------------------------------------------


def _stream(w, out, monkeypatch, *, engine, fused, io_threads, devices):
    from variantcalling_tpu import engine as engine_mod
    from variantcalling_tpu.io import vcf as vcf_mod
    from variantcalling_tpu.pipelines.filter_variants import run_streaming

    monkeypatch.setattr(vcf_mod, "STREAM_CHUNK_BYTES", 1 << 15)
    monkeypatch.setenv("VCTPU_ENGINE", engine)
    monkeypatch.setenv("VCTPU_NATIVE_FUSED", "1" if fused else "0")
    monkeypatch.setenv("VCTPU_IO_THREADS", str(io_threads))
    monkeypatch.setenv("VCTPU_MESH_DEVICES", str(devices))
    engine_mod.reset_for_tests()
    args = argparse.Namespace(
        input_file=f"{w['dir']}/calls.vcf", output_file=out, runs_file=None,
        hpol_filter_length_dist=[10, 10], blacklist=None,
        blacklist_cg_insertions=False, annotate_intervals=[],
        flow_order="TGCA", is_mutect=False, limit_to_contig=None)
    return run_streaming(args, w["model"], w["fasta"], {}, None)


from tests.fixtures import strip_vctpu_header as _modulo_header  # noqa: E402


@pytest.mark.flakehunt
@pytest.mark.parametrize("io_threads", [1, 4])
@pytest.mark.parametrize("devices", [1, 2])
def test_streaming_byte_parity_fused_vs_reference_vs_jit(
        fused_world, monkeypatch, io_threads, devices):
    """Acceptance (ISSUE 12): fused-native vs unfused-native reference vs
    jit produce byte-identical records across IO-thread counts and mesh
    device counts, modulo the ``##vctpu_*`` configuration header lines.
    Ordering-sensitive under the pooled zero-wait layout: flakehunt
    repeats it."""
    w = fused_world
    d = w["dir"]
    legs = (("fused", "native", True), ("reference", "native", False),
            ("jit", "jit", True))
    oracle = None
    for name, engine, fused in legs:
        out = f"{d}/fmx_{name}_{io_threads}_{devices}.vcf"
        stats = _stream(w, out, monkeypatch, engine=engine, fused=fused,
                        io_threads=io_threads, devices=devices)
        assert stats is not None and stats["n"] == w["n"], \
            (name, io_threads, devices)
        body = _modulo_header(open(out, "rb").read())
        if oracle is None:
            oracle = body
        else:
            assert body == oracle, (name, io_threads, devices)


def test_unsorted_chunk_falls_back_to_reference_path(fused_world,
                                                     monkeypatch, tmp_path):
    """A chunk whose contigs are NOT contiguous runs cannot take the
    fused single-call (its run table would lie about windows): the
    fused scorer declines and the reference path scores it — same
    scores either way. Built from an INTERLEAVED two-contig VCF (the
    fixture callset is single-contig, where every permutation is still
    one run)."""
    from variantcalling_tpu import engine as engine_mod
    from variantcalling_tpu.featurize import _contig_runs
    from variantcalling_tpu.io.vcf import read_vcf
    from variantcalling_tpu.pipelines.filter_variants import FilterContext

    w = fused_world
    path = str(tmp_path / "interleaved.vcf")
    rng = np.random.default_rng(5)
    lines = ["##fileformat=VCFv4.2",
             "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO"]
    for i in range(120):
        contig = "chr1" if i % 2 == 0 else "chrMissing"
        pos = int(rng.integers(1, 150_000))
        lines.append(f"{contig}\t{pos}\t.\tA\tC\t{30 + i % 7}\t.\tDP=10")
    with open(path, "w", encoding="utf-8") as fh:
        fh.write("\n".join(lines) + "\n")
    table = read_vcf(path)
    # the interleave must actually break run contiguity, or this test
    # proves nothing
    assert _contig_runs(table, len(table))[2] is None
    monkeypatch.setenv("VCTPU_ENGINE", "native")
    engine_mod.reset_for_tests()
    ctx = FilterContext(w["model"], w["fasta"])
    monkeypatch.setenv("VCTPU_NATIVE_FUSED", "1")
    s_fused, _ = ctx.score_table(table)
    monkeypatch.setenv("VCTPU_NATIVE_FUSED", "0")
    s_ref, _ = ctx.score_table(table)
    assert np.array_equal(s_fused, s_ref)


# ---------------------------------------------------------------------------
# TREE_SCORE formatter: bytes/offsets match the numpy %g definition
# ---------------------------------------------------------------------------


def test_format_float_info_parity_across_sizes():
    """The TREE_SCORE formatter's bytes and offsets equal the numpy
    ``b"%g"`` definition across sizes and NaN densities (incl. long
    all-NaN stretches). Kept deliberately serial — a sharded variant
    measured 2x slower (page-fault traffic on the worst-case buffer;
    rationale at ``vctpu_format_float_info``) — so this locks the
    byte contract whatever the implementation does next."""
    rng = np.random.default_rng(3)
    for n in (1, 5, 4095, 4096, 4097, 100_001):
        vals = np.round(rng.normal(scale=30, size=n), 4)
        vals[rng.random(n) < 0.15] = np.nan
        if n == 4096:
            vals[: n // 2] = np.nan  # a long all-NaN stretch
        out = native.format_float_info(vals, b";TREE_SCORE=")
        assert out is not None
        buf, offs = out
        parts = [b"" if np.isnan(v) else b";TREE_SCORE=" + (b"%g" % v)
                 for v in vals]
        assert buf.tobytes() == b"".join(parts)
        assert np.array_equal(np.diff(offs),
                              np.asarray([len(p) for p in parts]))
