"""Seeded inputs of the benchmark: reference genomes and callsets (a model
family's weights are drawn in ``families/<family>.py``).

Everything here is the yardstick's own (numpy only, nothing imported from the
program): the plain reference in ``reference.py`` recomputes windows and
weights from the same seeds, without reading anything the program wrote.

- a reference is ``n_contigs`` contigs of ``genome_len // n_contigs`` bases;
  contig ``i`` is one random body shifted by ``i`` in the base code (so the
  contigs differ, and generating 3.1 Gbp costs one 129 Mbp draw), written as
  a 60-column FASTA with its ``.fai``;
- a callset is the copy of ``bench.make_fixtures_fast``'s writer (70% SNPs,
  30% one-base insertions, uniform positions, QUAL / SOR / GT:DP:GQ), with
  the seed and the contig count as arguments.
"""

from __future__ import annotations

import os

import numpy as np

BASES = np.frombuffer(b"ACGT", dtype=np.uint8)
LINE = 60
MOTIF_VOCAB = 5 ** 5

#: the full feature layout a run assembles, in the program's column order
#: (featurize.BASE_FEATURES): models name their columns, the run orders them
RUN_FEATURES = [
    "qual", "dp", "sor", "af", "gq", "is_het", "is_snp", "is_indel", "is_ins",
    "indel_length", "hmer_indel_length", "hmer_indel_nuc", "gc_content",
    "cycleskip_status", "left_motif", "right_motif", "ref_code", "alt_code",
    "n_alts",
]
#: value range of each column on the generated callsets: (low, high, integral)
FEATURE_RANGE = {
    "qual": (10.0, 95.0, False), "dp": (0.0, 1.0, False), "sor": (0.0, 4.0, False),
    "af": (0.0, 1.0, False), "gq": (5, 99, True), "is_het": (0, 1, True),
    "is_snp": (0, 1, True), "is_indel": (0, 1, True), "is_ins": (0, 1, True),
    "indel_length": (0, 1, True), "hmer_indel_length": (0, 6, True),
    "hmer_indel_nuc": (0, 4, True), "gc_content": (0.15, 0.85, False),
    "cycleskip_status": (-1, 2, True), "left_motif": (0.0, 3124.0, False),
    "right_motif": (0.0, 3124.0, False), "ref_code": (0, 4, True),
    "alt_code": (0, 4, True), "n_alts": (1, 1, True),
}


# -- references ---------------------------------------------------------------

def contig_names(n_contigs: int) -> list[str]:
    return [f"chr{i + 1}" for i in range(n_contigs)]


def contig_body(reference_seed: int, clen: int) -> np.ndarray:
    """uint8 codes (A0 C1 G2 T3) of contig 0; contig i is ``(body + i) & 3``."""
    return np.random.default_rng(reference_seed).integers(
        0, 4, size=clen, dtype=np.uint8)


def write_reference(path: str, reference_seed: int, genome_len: int,
                    n_contigs: int) -> None:
    """FASTA + .fai at ``path``; written to a temporary name and renamed, so
    a file that exists is whole."""
    clen = genome_len // n_contigs
    body = contig_body(reference_seed, clen)
    k = clen // LINE
    fai = []
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as fh:
        for i, name in enumerate(contig_names(n_contigs)):
            seq = BASES[(body + np.uint8(i)) & np.uint8(3)]
            fh.write(f">{name}\n".encode())
            fai.append(f"{name}\t{clen}\t{fh.tell()}\t{LINE}\t{LINE + 1}\n")
            lines = np.empty((k, LINE + 1), np.uint8)
            lines[:, :LINE] = seq[: k * LINE].reshape(k, LINE)
            lines[:, LINE] = 0x0A
            fh.write(memoryview(lines).cast("B"))
            if clen > k * LINE:
                fh.write(seq[k * LINE:].tobytes() + b"\n")
    with open(path + ".fai", "wt") as fh:
        fh.writelines(fai)
    os.replace(tmp, path)


# -- callsets -----------------------------------------------------------------

def write_callset(path: str, reference_seed: int, genome_len: int,
                  n_contigs: int, n: int, seed: int) -> None:
    """``n`` sorted variants over the reference of ``reference_seed``: REF is
    the reference base at POS, so the rendered record and the window agree."""
    clen = genome_len // n_contigs
    body = contig_body(reference_seed, clen)
    rng = np.random.default_rng([int(seed), int(reference_seed)])
    bases = np.frombuffer(b"ACGT", dtype="S1")
    contigs = contig_names(n_contigs)
    per = n // n_contigs
    header = ["##fileformat=VCFv4.2"]
    header += [f"##contig=<ID={c},length={clen}>" for c in contigs]
    header += [
        '##INFO=<ID=SOR,Number=1,Type=Float,Description="Symmetric odds ratio">',
        '##FORMAT=<ID=GT,Number=1,Type=String,Description="Genotype">',
        '##FORMAT=<ID=DP,Number=1,Type=Integer,Description="Depth">',
        '##FORMAT=<ID=GQ,Number=1,Type=Integer,Description="Genotype quality">',
        "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\tHG002",
    ]
    with open(path, "wb") as fh:
        fh.write(("\n".join(header) + "\n").encode())
        for ci, c in enumerate(contigs):
            m = per + (n - per * n_contigs if ci == n_contigs - 1 else 0)
            if m > (clen - 200) // 2:
                raise ValueError(f"cannot place {m} distinct variants on a "
                                 f"{clen} bp contig")
            # unique sorted positions without a clen-sized permutation:
            # oversample, dedupe, top up, thin back to m
            cand = np.unique(rng.integers(100, clen - 100, size=m + m // 32 + 64,
                                          dtype=np.int64))
            while len(cand) < m:
                extra = rng.integers(100, clen - 100,
                                     size=2 * (m - len(cand)) + 64, dtype=np.int64)
                cand = np.unique(np.concatenate([cand, extra]))
            if len(cand) > m:
                cand = cand[np.sort(rng.choice(len(cand), size=m, replace=False))]
            pos = cand + 1
            ref_codes = (body[pos - 1] + np.uint8(ci)) & np.uint8(3)
            shift = rng.integers(1, 4, m).astype(np.uint8)
            alt_codes = (ref_codes + shift) % 4
            ref_b = bases[ref_codes].astype("S2")
            alt_b = bases[alt_codes].astype("S2")
            ins = rng.random(m) >= 0.7  # 30% insertions: REF=anchor, ALT=anchor+base
            alt_b[ins] = np.char.add(bases[ref_codes[ins]], bases[alt_codes[ins]])
            qual = np.char.mod(b"%.2f", rng.uniform(10, 95, m))
            sor = np.char.add(b"SOR=", np.char.mod(b"%.2f", rng.uniform(0, 4, m)))
            gt = np.where(rng.random(m) < 0.6, b"0/1", b"1/1").astype("S3")
            dp = np.char.mod(b"%d", rng.integers(4, 70, m))
            gq = np.char.mod(b"%d", rng.integers(5, 99, m))
            tab = np.full(m, b"\t", dtype="S1")
            parts = [np.full(m, c.encode(), dtype=f"S{len(c)}"), tab,
                     np.char.mod(b"%d", pos), tab, np.full(m, b".", "S1"), tab,
                     ref_b, tab, alt_b, tab, qual, tab, np.full(m, b".", "S1"),
                     tab, sor, tab, np.full(m, b"GT:DP:GQ", "S8"), tab,
                     gt, np.full(m, b":", "S1"), dp, np.full(m, b":", "S1"), gq]
            acc = parts[0]
            for p in parts[1:]:
                acc = np.char.add(acc, p)
            fh.write(b"\n".join(acc.tolist()) + b"\n")
