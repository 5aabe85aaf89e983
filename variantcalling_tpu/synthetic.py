"""Synthetic inputs, the one fixture library of the tests and the tools.

- models: :func:`synthetic_forest`, :func:`synthetic_dan`;
- files: :func:`make_fixtures` (one contig, SNPs, insertions and deletions,
  a record a Python line) and :func:`make_fixtures_fast` (equal contigs,
  vectorized, with the ``.fai``) write ``ref.fa`` and ``calls.vcf`` into a
  directory. Their bytes for given arguments are pinned by
  tests/unit/test_synthetic_fixtures.py: many tests lock output digests
  on them;
- the fused hot path (:func:`fused_hot_path` and its arguments), the same
  program as the pipeline's device stage (featurization kernels +
  flat-forest inference), for __graft_entry__.py's compile check.
"""

from __future__ import annotations

import os

import numpy as np

from variantcalling_tpu.models.forest import FlatForest, make_predictor
from variantcalling_tpu.ops import features as fops

N_HOT_FEATURES = 12  # features assembled by fused_hot_path below
WINDOW = 41
N_TREES = 40  # the forest shape of benchmarks/configs/forest-t40d6-hg38x2.json
DEPTH = 6
E2E_N = 1_000_000  # make_fixtures' defaults: variants, and bp of genome
E2E_GENOME = 10_000_000


def synthetic_batch(n: int, rng: np.random.Generator):
    """(windows, scalar dict, is_indel, indel_nuc) shaped like real featurized input."""
    windows = rng.integers(0, 4, size=(n, WINDOW), dtype=np.uint8)
    scalars = {
        "qual": rng.uniform(0, 100, n).astype(np.float32),
        "dp": rng.integers(1, 60, n).astype(np.float32),
        "sor": rng.uniform(0, 4, n).astype(np.float32),
        "af": rng.uniform(0, 1, n).astype(np.float32),
        "gq": rng.integers(0, 99, n).astype(np.float32),
        "is_het": rng.integers(0, 2, n).astype(np.float32),
    }
    is_indel = rng.random(n) < 0.1
    indel_nuc = np.where(is_indel, rng.integers(0, 4, n), 4).astype(np.int32)
    return windows, scalars, is_indel, indel_nuc


def synthetic_forest(rng: np.random.Generator, n_trees: int = 40, depth: int = 12,
                     n_features: int = N_HOT_FEATURES) -> FlatForest:
    """Random but structurally-valid forest: complete binary trees, leaf level at the bottom."""
    m = 2**depth
    feature = rng.integers(0, n_features, size=(n_trees, m)).astype(np.int32)
    left = np.minimum(2 * np.arange(m) + 1, m - 1).astype(np.int32)
    right = np.minimum(2 * np.arange(m) + 2, m - 1).astype(np.int32)
    is_leaf = np.arange(m) >= (m // 2 - 1)
    feature[:, is_leaf] = -1
    return FlatForest(
        feature=feature,
        threshold=rng.uniform(0, 50, size=(n_trees, m)).astype(np.float32),
        left=np.broadcast_to(np.where(is_leaf, np.arange(m), left), (n_trees, m)).astype(np.int32),
        right=np.broadcast_to(np.where(is_leaf, np.arange(m), right), (n_trees, m)).astype(np.int32),
        value=rng.uniform(0, 1, size=(n_trees, m)).astype(np.float32),
        max_depth=depth,
    )


def synthetic_dan(rng: np.random.Generator, feature_names: list[str],
                  embed_dim: int = 4, hidden: int = 16, n_layers: int = 2):
    """Random but structurally-valid DAN over a real feature layout: the
    numeric block is every feature except the motif-code columns, so the
    model scores through the same fused (N, F) matrix path as a trained
    one (models/dan.make_score_predictor). Deterministic in ``rng``."""
    import jax

    from variantcalling_tpu.models import dan as dan_mod

    numeric_features = [f for f in feature_names
                        if f not in ("left_motif", "right_motif")]
    cfg = dan_mod.DanConfig(n_numeric=len(numeric_features),
                            embed_dim=embed_dim, hidden=hidden,
                            n_layers=n_layers)
    params = dan_mod.init_params(
        cfg, jax.random.PRNGKey(int(rng.integers(0, 2**31 - 1))))
    # init_params zeroes the output head (a training-friendly init): a
    # synthetic scorer needs VARYING scores or every parity/digest check
    # downstream would pass trivially on a constant-0.5 output
    params["w_out"] = jax.random.normal(
        jax.random.PRNGKey(int(rng.integers(0, 2**31 - 1))),
        params["w_out"].shape) * (1.0 / np.sqrt(hidden))
    model = dan_mod.DanModel.from_params(
        cfg, params, feature_names=list(feature_names),
        numeric_features=numeric_features)
    # normalization keeps the random logits in sigmoid's useful range for
    # arbitrary feature scales (qual ~ [0, 100], flags ~ {0, 1})
    model.norm_mu = np.zeros(len(numeric_features), np.float32)
    model.norm_sd = np.full(len(numeric_features), 10.0, np.float32)
    return model


def make_fixtures(d: str, n: int = E2E_N, genome_len: int = E2E_GENOME) -> None:
    """HG002-like synthetic fixture: random genome + sorted SNP/indel VCF."""
    rng = np.random.default_rng(0)
    bases = np.frombuffer(b"ACGT", dtype="S1")
    arr = rng.integers(0, 4, size=genome_len)
    seq = bases[arr].tobytes().decode()
    with open(os.path.join(d, "ref.fa"), "w") as fh:
        fh.write(">chr1\n")
        for i in range(0, genome_len, 60):
            fh.write(seq[i : i + 60] + "\n")

    pos = np.sort(rng.choice(np.arange(100, genome_len - 100), size=n, replace=False)) + 1
    kind = rng.random(n)  # <0.7 SNP, <0.85 ins, else del
    qual = rng.uniform(10, 95, n)
    dp = rng.integers(4, 70, n)
    gq = rng.integers(5, 99, n)
    sor = rng.uniform(0, 4, n)
    shift = rng.integers(1, 4, n)
    het = rng.random(n) < 0.6
    lines = []
    for i in range(n):
        p0 = pos[i] - 1
        ref = seq[p0]
        if kind[i] < 0.7:
            alt = "ACGT"[(("ACGT".index(ref)) + shift[i]) % 4]
        elif kind[i] < 0.85:
            alt = ref + "ACGT"[shift[i]]
        else:
            ref = seq[p0 : p0 + 1 + shift[i]]
            alt = seq[p0]
        gt = "0/1" if het[i] else "1/1"
        lines.append(
            f"chr1\t{pos[i]}\t.\t{ref}\t{alt}\t{qual[i]:.2f}\t.\tSOR={sor[i]:.2f}\tGT:DP:GQ\t{gt}:{dp[i]}:{gq[i]}"
        )
    with open(os.path.join(d, "calls.vcf"), "w") as fh:
        fh.write("##fileformat=VCFv4.2\n")
        fh.write(f"##contig=<ID=chr1,length={genome_len}>\n")
        fh.write('##INFO=<ID=SOR,Number=1,Type=Float,Description="Symmetric odds ratio">\n')
        fh.write('##FORMAT=<ID=GT,Number=1,Type=String,Description="Genotype">\n')
        fh.write('##FORMAT=<ID=DP,Number=1,Type=Integer,Description="Depth">\n')
        fh.write('##FORMAT=<ID=GQ,Number=1,Type=Integer,Description="Genotype quality">\n')
        fh.write("#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\tHG002\n")
        fh.write("\n".join(lines) + "\n")


def make_fixtures_fast(d: str, n: int, genome_len: int, n_contigs: int = 4,
                       seed: int = 7) -> None:
    """Vectorized fixture writer for BASELINE scale (5M variants): all
    columns are built as numpy byte arrays and joined once — no
    per-record Python, so generating the fixture costs seconds."""
    rng = np.random.default_rng(seed)
    bases = np.frombuffer(b"ACGT", dtype="S1")
    clen = genome_len // n_contigs
    contigs = [f"chr{i + 1}" for i in range(n_contigs)]
    # ONE random contig body reused for every contig: callers check bytes
    # and counts, not biology, and regenerating 3.1 Gbp of random bases 24x
    # dominated a genome-scale fixture's cost
    arr = rng.integers(0, 4, size=clen).astype(np.uint8)
    enc = {c: arr for c in contigs}
    seq = bases[arr].view(np.uint8)
    k = clen // 60
    body = np.concatenate(
        [seq[: k * 60].reshape(k, 60),
         np.full((k, 1), ord("\n"), np.uint8)], axis=1).tobytes()
    tail = seq[k * 60:]
    tail_b = tail.tobytes() + b"\n" if len(tail) else b""
    fai_lines = []
    with open(os.path.join(d, "ref.fa"), "wb") as fh:
        for c in contigs:
            fh.write(f">{c}\n".encode())
            # reference FASTAs ship indexed (the CLI flag is "Indexed
            # reference FASTA file"), so the fixture writes the .fai too —
            # the pipeline's warmup then measures what production pays
            fai_lines.append(f"{c}\t{clen}\t{fh.tell()}\t60\t61\n")
            fh.write(body)
            if tail_b:
                fh.write(tail_b)
    with open(os.path.join(d, "ref.fa.fai"), "wt") as fh:
        fh.writelines(fai_lines)

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
    with open(os.path.join(d, "calls.vcf"), "wb") as fh:
        fh.write(("\n".join(header) + "\n").encode())
        for ci, c in enumerate(contigs):
            m = per + (n - per * n_contigs if ci == n_contigs - 1 else 0)
            # unique sorted positions WITHOUT materializing a clen-sized
            # arange (rng.choice(replace=False) permutes the whole contig —
            # ~1 GB and seconds per contig at hg38 scale): oversample,
            # dedupe, then thin uniformly back to m
            if m > clen - 200:  # more variants than distinct positions exist
                raise ValueError(
                    f"cannot place {m} distinct variants on a {clen} bp contig")
            cand = np.unique(rng.integers(100, clen - 100, size=m + m // 32 + 64,
                                          dtype=np.int64))
            while len(cand) < m:  # dense callsets: top up until m distinct
                extra = rng.integers(100, clen - 100, size=2 * (m - len(cand)) + 64,
                                     dtype=np.int64)
                cand = np.unique(np.concatenate([cand, extra]))
            if len(cand) > m:
                cand = cand[np.sort(rng.choice(len(cand), size=m, replace=False))]
            pos = cand + 1
            ref_codes = enc[c][pos - 1]
            shift = rng.integers(1, 4, m).astype(np.uint8)
            alt_codes = (ref_codes + shift) % 4
            ref_b = bases[ref_codes].astype("S2")
            alt_b = bases[alt_codes].astype("S2")
            kind = rng.random(m)
            ins = kind >= 0.7  # 30% insertions: REF=anchor, ALT=anchor+base
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


def fused_hot_path(forest: FlatForest):
    """The filter device program: windows+scalars -> features -> TREE_SCORE.

    Returns a jittable fn(windows, qual, dp, sor, af, gq, is_het, is_indel,
    indel_nuc) mirroring the pipeline's featurize+score stage. Inference
    strategy picks GEMM (MXU matmuls) on TPU, gather walk on CPU
    (models/forest.make_predictor).
    """
    import jax.numpy as jnp

    predictor = make_predictor(forest, N_HOT_FEATURES)

    def fwd(windows, qual, dp, sor, af, gq, is_het, is_indel, indel_nuc):
        center = windows.shape[1] // 2
        gc = fops.gc_content(windows, center, radius=10)
        hmer_len, hmer_nuc = fops.hmer_indel_features(windows, center, is_indel, indel_nuc)
        left_m, right_m = fops.motif_codes(windows, center)
        x = jnp.stack(
            [
                qual,
                dp,
                sor,
                af,
                gq,
                is_het,
                is_indel.astype(jnp.float32),
                hmer_len.astype(jnp.float32),
                hmer_nuc.astype(jnp.float32),
                gc,
                (left_m % 125).astype(jnp.float32),
                (right_m % 125).astype(jnp.float32),
            ],
            axis=1,
        )
        return predictor(x)

    return fwd


def hot_path_args(n: int, seed: int = 1):
    """Device-ready positional args for fused_hot_path."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    windows, scalars, is_indel, indel_nuc = synthetic_batch(n, rng)
    return (
        jnp.asarray(windows),
        jnp.asarray(scalars["qual"]),
        jnp.asarray(scalars["dp"]),
        jnp.asarray(scalars["sor"]),
        jnp.asarray(scalars["af"]),
        jnp.asarray(scalars["gq"]),
        jnp.asarray(scalars["is_het"]),
        jnp.asarray(is_indel),
        jnp.asarray(indel_nuc),
    )

