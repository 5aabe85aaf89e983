"""Synthetic genome/VCF fixture generation shared by the test suite.

The reference ships git-lfs golden resources (unhydrated in this snapshot);
this framework instead synthesizes deterministic fixtures: a small random
reference genome with homopolymer structure, and VCFs with SNPs / hmer and
non-hmer indels / multiallelics over it.
"""

from __future__ import annotations

import gzip

import numpy as np

BASES = "ACGT"


def make_genome(rng: np.random.Generator, contigs: dict[str, int]) -> dict[str, str]:
    """Random genome with injected homopolymer runs (for hmer feature tests)."""
    out = {}
    for name, length in contigs.items():
        arr = rng.integers(0, 4, size=length)
        # inject homopolymer runs of length 3-14 at ~1/200bp
        n_runs = length // 200
        starts = rng.integers(0, max(1, length - 20), size=n_runs)
        for s in starts:
            run_len = int(rng.integers(3, 15))
            arr[s : s + run_len] = arr[s]
        out[name] = "".join(BASES[i] for i in arr)
    return out


def write_fasta(path: str, genome: dict[str, str], line_len: int = 60) -> None:
    with open(path, "wt") as fh:
        for name, seq in genome.items():
            fh.write(f">{name}\n")
            for i in range(0, len(seq), line_len):
                fh.write(seq[i : i + line_len] + "\n")


def synth_variants(
    rng: np.random.Generator,
    genome: dict[str, str],
    n: int,
    p_snp: float = 0.7,
    p_ins: float = 0.15,
) -> list[dict]:
    """Sorted list of variant dicts: chrom,pos(1-based),ref,alts,qual,gt."""
    recs = []
    for contig, seq in genome.items():
        n_contig = max(1, int(n * len(seq) / sum(len(s) for s in genome.values())))
        positions = np.sort(rng.choice(np.arange(10, len(seq) - 20), size=n_contig, replace=False))
        for pos0 in positions:
            ref_base = seq[pos0]
            r = rng.random()
            if r < p_snp:  # SNP
                alt = BASES[(BASES.index(ref_base) + int(rng.integers(1, 4))) % 4]
                ref = ref_base
                alts = [alt]
            elif r < p_snp + p_ins:  # insertion after pos0
                ins = "".join(BASES[i] for i in rng.integers(0, 4, size=int(rng.integers(1, 4))))
                ref = ref_base
                alts = [ref_base + ins]
            else:  # deletion
                del_len = int(rng.integers(1, 4))
                ref = seq[pos0 : pos0 + 1 + del_len]
                alts = [ref_base]
            gt = (0, 1) if rng.random() < 0.6 else (1, 1)
            recs.append(
                {
                    "chrom": contig,
                    "pos": int(pos0) + 1,
                    "ref": ref,
                    "alts": alts,
                    "qual": float(np.round(rng.uniform(10, 90), 2)),
                    "gt": gt,
                }
            )
    recs.sort(key=lambda r: (r["chrom"], r["pos"]))
    return recs


def write_vcf(
    path: str,
    records: list[dict],
    contigs: dict[str, int],
    sample: str = "SAMPLE",
    extra_info_defs: list[str] | None = None,
) -> None:
    """Write records (dicts from synth_variants, optionally with 'info'/'filter'/'pl' keys)."""
    lines = [
        "##fileformat=VCFv4.2",
        '##FILTER=<ID=PASS,Description="All filters passed">',
        '##INFO=<ID=DP,Number=1,Type=Integer,Description="Depth">',
        '##INFO=<ID=VARIANT_TYPE,Number=1,Type=String,Description="Variant type">',
        '##FORMAT=<ID=GT,Number=1,Type=String,Description="Genotype">',
        '##FORMAT=<ID=GQ,Number=1,Type=Integer,Description="Genotype quality">',
        '##FORMAT=<ID=PL,Number=G,Type=Integer,Description="Phred-scaled likelihoods">',
        '##FORMAT=<ID=AD,Number=R,Type=Integer,Description="Allele depths">',
    ]
    lines += extra_info_defs or []
    lines += [f"##contig=<ID={c},length={l}>" for c, l in contigs.items()]
    lines.append(f"#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\t{sample}")
    for r in records:
        gt = r.get("gt", (0, 1))
        pl = r.get("pl")
        fmt_keys = ["GT"]
        fmt_vals = ["/".join(str(a) for a in gt)]
        if "gq" in r:
            fmt_keys.append("GQ")
            fmt_vals.append(str(r["gq"]))
        if pl is not None:
            fmt_keys.append("PL")
            fmt_vals.append(",".join(str(int(x)) for x in pl))
        if "ad" in r:
            fmt_keys.append("AD")
            fmt_vals.append(",".join(str(int(x)) for x in r["ad"]))
        info = r.get("info", f"DP={int(r.get('dp', 30))}")
        lines.append(
            "\t".join(
                [
                    r["chrom"],
                    str(r["pos"]),
                    r.get("id", "."),
                    r["ref"],
                    ",".join(r["alts"]),
                    f"{r['qual']:g}",
                    r.get("filter", "PASS"),
                    info,
                    ":".join(fmt_keys),
                    ":".join(fmt_vals),
                ]
            )
        )
    text = "\n".join(lines) + "\n"
    if str(path).endswith(".gz"):
        with gzip.open(path, "wt") as fh:
            fh.write(text)
    else:
        with open(path, "wt") as fh:
            fh.write(text)


def write_bam(path: str, contigs: dict[str, int], reads: list[dict]) -> None:
    """Minimal BAM writer for reader/coverage tests.

    Each read dict: contig (name), pos (0-based), cigar [(op_char, len)],
    optional mapq (60), flag (0), quals (list[int], default 30s),
    seq (str, default all-N).
    """
    import struct

    ops = "MIDNSHP=X"
    names = list(contigs)
    body = bytearray()
    body += b"BAM\x01"
    text = b"@HD\tVN:1.6\n" + b"".join(
        f"@SQ\tSN:{n}\tLN:{l}\n".encode() for n, l in contigs.items()
    )
    body += struct.pack("<i", len(text)) + text
    body += struct.pack("<i", len(names))
    for n in names:
        nb = n.encode() + b"\x00"
        body += struct.pack("<i", len(nb)) + nb + struct.pack("<i", contigs[n])
    for r in reads:
        cigar = r["cigar"]
        read_len = sum(l for op, l in cigar if op in "MIS=X")
        quals = r.get("quals", [30] * read_len)
        name = r.get("name", "r").encode() + b"\x00"
        rec = bytearray()
        rec += struct.pack("<i", names.index(r["contig"]))
        rec += struct.pack("<i", r["pos"])
        mapq = r.get("mapq", 60)
        rec += struct.pack("<I", (4680 << 16) | (mapq << 8) | len(name))
        rec += struct.pack("<I", (r.get("flag", 0) << 16) | len(cigar))
        rec += struct.pack("<i", read_len)
        rec += struct.pack("<iii", -1, -1, 0)
        rec += name
        for op, l in cigar:
            rec += struct.pack("<I", (l << 4) | ops.index(op))
        seq = r.get("seq")
        if seq is None:
            rec += b"\xff" * ((read_len + 1) // 2)  # seq nibbles (N)
        else:
            nib_map = {"A": 1, "C": 2, "G": 4, "T": 8, "N": 15}
            nibs = [nib_map.get(b, 15) for b in seq.upper()[:read_len]]
            nibs += [15] * (read_len - len(nibs))
            if len(nibs) % 2:
                nibs.append(0)
            rec += bytes((nibs[i] << 4) | nibs[i + 1] for i in range(0, len(nibs), 2))
        rec += bytes(quals[:read_len])
        for tag, val in r.get("tags", {}).items():
            rec += tag.encode()[:2]
            if isinstance(val, int):
                rec += b"i" + struct.pack("<i", val)
            elif isinstance(val, float):
                rec += b"f" + struct.pack("<f", val)
            else:
                rec += b"Z" + str(val).encode() + b"\x00"
        body += struct.pack("<i", len(rec)) + rec
    with gzip.open(path, "wb") as fh:
        fh.write(bytes(body))


def strip_vctpu_header(data: bytes) -> bytes:
    """Everything except the ``##vctpu_*`` configuration header lines —
    the ONE place engines/strategies/mesh layouts may legitimately differ
    between otherwise byte-identical filter outputs. The single spelling
    of the parity-modulo-header rule, shared by every cross-configuration
    byte-parity test."""
    return b"\n".join(ln for ln in data.split(b"\n")
                      if not ln.startswith(b"##vctpu_"))


def fused_inputs_with_host_windows(n: int, program, names: list[str], seed: int = 0):
    """One chunk of ``n`` random rows with host windows, ready for
    ``filter_variants._dispatch_fused`` under ``program`` (the
    ``(jitted, layout, finalize)`` of ``_fused_program(..., genome_resident=False)``)."""
    from variantcalling_tpu.featurize import AlleleColumns, HostFeatures
    from variantcalling_tpu.pipelines.filter_variants import _FusedInputs

    rng = np.random.default_rng(seed)
    windows = rng.integers(0, 4, size=(n, 41)).astype(np.uint8)
    cols = {f: rng.integers(0, 2, n).astype(np.float32) if i % 2
            else rng.uniform(0, 50, n).astype(np.float32)
            for i, f in enumerate(program[1].host_names)}
    is_snp = rng.random(n) < 0.7
    alle = AlleleColumns(is_snp=is_snp, is_indel=~is_snp, is_ins=~is_snp,
                         indel_length=(~is_snp).astype(np.int32),
                         indel_nuc=np.where(is_snp, 4, rng.integers(0, 4, n)).astype(np.int32),
                         ref_code=rng.integers(0, 4, n).astype(np.int32),
                         alt_code=rng.integers(0, 4, n).astype(np.int32),
                         n_alts=np.ones(n, np.int32))
    hf = HostFeatures(alle=alle, windows=windows, cols=cols, names=list(names))
    return _FusedInputs(n, program, None, 0, windows, None, hf)
