"""The fixture library's contract (``variantcalling_tpu/synthetic.py``).

Some thirty test files, ``chip_smoke.py``, ``__graft_entry__.py`` and the
chaos and load harnesses build their inputs with ``make_fixtures`` and
``make_fixtures_fast``, and several lock output digests on top of them, so
the bytes each builder writes for given arguments are pinned here (the
digests were taken from the builders as they stood before they moved
here). The rest holds the files to what their readers assume: records
sorted within a contig, REF equal to the reference, header lengths equal
to the FASTA's.
"""

from __future__ import annotations

import hashlib
import json
import os

import numpy as np
import pytest

from variantcalling_tpu import synthetic
from variantcalling_tpu.io.fasta import FastaReader
from variantcalling_tpu.io.vcf import read_vcf

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _sha(path) -> str:
    with open(path, "rb") as fh:
        return hashlib.sha256(fh.read()).hexdigest()


def _digests(d) -> dict:
    return {name: _sha(os.path.join(d, name)) for name in sorted(os.listdir(d))}


# ---------------------------------------------------------------------------
# pinned bytes
# ---------------------------------------------------------------------------

#: (n, genome_len) -> sha256 of what make_fixtures writes: the three shapes
#: its callers use most
SLOW_PINS = {
    (4000, 200_000): {
        "ref.fa": "94f6fb05cd41004501664b37cfd1e36a8861e3ea1a82898dc030c97700b48ad2",
        "calls.vcf": "49a8f66bea5cddb3b125effc8099759d84c7811ee7f299ff0d436368794ec353"},
    (2500, 150_000): {
        "ref.fa": "a7f8d98b654b02be12da69d543216d0df4667f7f45787f813678a674e904687d",
        "calls.vcf": "2b6b4f9b3cd73d10440faaa9a0f1504b764ac3b3493fa0039fd73ac258e9dfb9"},
    (12000, 300_000): {
        "ref.fa": "a6241acc8d8940072499c137e777a043cf1897adec047c591f5c0249a181f713",
        "calls.vcf": "001c7c53070c5cc3449819a3578ba614bc5213081e1f3f9b023d98ef868d2add"},
}

#: (n, genome_len, n_contigs, seed) -> sha256 of what make_fixtures_fast
#: writes: one contig (the shape __graft_entry__.py uses), the defaults, and
#: another seed at hg38's 24 contigs
FAST_PINS = {
    (3000, 100_000, 1, 7): {
        "ref.fa": "7a8838950c4a194e82ea6f724c3e6cf1b028b3763d88565d01f67854498d957b",
        "ref.fa.fai": "bdf17339673f78ffda4653e76511eac5872e2a1ba22d0edf910dc45b7eb2fb89",
        "calls.vcf": "c8143706a4f2201136c487030ae31d0dd3b3e882cb11fcca8991cc2d6e528c67"},
    (2000, 120_000, 4, 7): {
        "ref.fa": "27f167d65daefd850f56d130c7f3ddbf43c2cec8a3202b663b694e28eda78e5d",
        "ref.fa.fai": "83ccc10f24613d560b594e3a42d54c5dbd0e9a6d9740d2e733166389f8ffb846",
        "calls.vcf": "1133c5b4b61b908dd671d9f3d0a718a5414cfe62b3bec90a6bff864491551ba7"},
    (2400, 240_000, 24, 11): {
        "ref.fa": "7a13a4a97e6f21bc4bff558183c7b47f8542d31a26b76bf2f4fadc07fa0c8ca8",
        "ref.fa.fai": "9d784dfae9817052414673ed899b342d1dc1426df25df9122b71dd55d7f3dfad",
        "calls.vcf": "b1e80661ae5de8154775551fc4ec18cec9c258a6485f3eccf3e9360cd66df096"},
}


@pytest.mark.parametrize("shape", sorted(SLOW_PINS))
def test_make_fixtures_bytes_are_pinned(tmp_path, shape):
    n, genome_len = shape
    synthetic.make_fixtures(str(tmp_path), n=n, genome_len=genome_len)
    assert _digests(tmp_path) == SLOW_PINS[shape]


@pytest.mark.parametrize("shape", sorted(FAST_PINS))
def test_make_fixtures_fast_bytes_are_pinned(tmp_path, shape):
    n, genome_len, n_contigs, seed = shape
    synthetic.make_fixtures_fast(str(tmp_path), n=n, genome_len=genome_len,
                                 n_contigs=n_contigs, seed=seed)
    assert _digests(tmp_path) == FAST_PINS[shape]


# ---------------------------------------------------------------------------
# what the files' readers assume
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("builder,n,genome_len", [
    (synthetic.make_fixtures, 1500, 60_000),
    (synthetic.make_fixtures, 3000, 100_000),
    (synthetic.make_fixtures_fast, 1500, 60_000),
    (synthetic.make_fixtures_fast, 3001, 100_002),  # neither divides by 4 contigs, nor by 60 bases a line
], ids=lambda v: getattr(v, "__name__", str(v)))
def test_callset_agrees_with_its_reference(tmp_path, builder, n, genome_len):
    d = str(tmp_path)
    builder(d, n=n, genome_len=genome_len)
    table = read_vcf(f"{d}/calls.vcf")
    with FastaReader(f"{d}/ref.fa") as fasta:
        assert len(table.pos) == n
        chrom = np.asarray(table.chrom).astype(str)
        pos = np.asarray(table.pos)
        # contigs in the FASTA's order, positions strictly rising in each
        contigs = [c for c in fasta.references if (chrom == c).any()]
        assert list(dict.fromkeys(chrom.tolist())) == contigs
        for c in contigs:
            assert (np.diff(pos[chrom == c]) > 0).all()
        for c, p, ref in zip(chrom, pos, table.ref):
            assert fasta.fetch(c, int(p) - 1, int(p) - 1 + len(ref)) == ref
        assert table.header.contig_lengths == {
            c: fasta.get_reference_length(c) for c in fasta.references}


def test_make_fixtures_writes_snps_insertions_and_deletions(tmp_path):
    d = str(tmp_path)
    synthetic.make_fixtures(d, n=2000, genome_len=80_000)
    table = read_vcf(f"{d}/calls.vcf")
    ref_len = np.array([len(r) for r in table.ref])
    alt_len = np.array([len(str(a).split(",")[0]) for a in table.alt])
    kinds = {"snp": ((ref_len == 1) & (alt_len == 1)).mean(),
             "ins": (alt_len > ref_len).mean(), "del": (ref_len > alt_len).mean()}
    assert kinds["snp"] == pytest.approx(0.70, abs=0.04)
    assert kinds["ins"] == pytest.approx(0.15, abs=0.03)
    assert kinds["del"] == pytest.approx(0.15, abs=0.03)


@pytest.mark.parametrize("n_contigs", [1, 4, 24])
def test_make_fixtures_fast_writes_equal_contigs(tmp_path, n_contigs):
    d = str(tmp_path)
    synthetic.make_fixtures_fast(d, n=1200, genome_len=120_000, n_contigs=n_contigs)
    with FastaReader(f"{d}/ref.fa") as fasta:
        names = fasta.references
        assert names == [f"chr{i + 1}" for i in range(n_contigs)]
        assert {fasta.get_reference_length(c) for c in names} == {120_000 // n_contigs}
    # the index it ships is the index a reader would build and write
    with open(f"{d}/ref.fa.fai") as fh:
        shipped = fh.read()
    os.remove(f"{d}/ref.fa.fai")
    FastaReader(f"{d}/ref.fa").close()
    with open(f"{d}/ref.fa.fai") as fh:
        assert fh.read() == shipped


def test_make_fixtures_fast_refuses_more_variants_than_positions(tmp_path):
    with pytest.raises(ValueError, match="distinct variants"):
        synthetic.make_fixtures_fast(str(tmp_path), n=5000, genome_len=4000, n_contigs=1)


def test_forest_shape_is_the_benchmarks():
    """``BENCHMARK.json`` names these two constants as the source of its
    forest configuration; ``chip_smoke.py`` builds its forest from them."""
    with open(os.path.join(ROOT, "benchmarks", "configs", "forest-t40d6-hg38x2.json")) as fh:
        config = json.load(fh)
    assert (synthetic.N_TREES, synthetic.DEPTH) == (config["n_trees"], config["depth"])
