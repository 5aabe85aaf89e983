"""Native CPU hot-path parity: the C++ featurize/gather/predict/format
kernels must match their jitted/numpy twins EXACTLY — on a single-device
CPU the filter pipeline routes through them (filter_variants.
_native_cpu_featurize_score), so any drift would silently change scores.

The pytest suite itself runs on an 8-device virtual mesh (conftest), where
the pipeline keeps the jitted path — these tests call the native entry
points directly, plus one single-device subprocess that byte-compares the
flagship output between both paths.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from variantcalling_tpu import native
from variantcalling_tpu.featurize import CENTER, DEVICE_FEATURES, device_feature_dict
from variantcalling_tpu.models import forest as fm
from variantcalling_tpu.ops.features import A, C, G, T

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

pytestmark = pytest.mark.skipif(not native.available(), reason="native library unavailable")


def _inputs(rng, n):
    W = 2 * CENTER + 1
    windows = rng.integers(0, 5, (n, W)).astype(np.uint8)  # incl. N
    windows[: n // 2] = rng.integers(0, 4, (n // 2, W)).astype(np.uint8)
    is_indel = rng.random(n) < 0.3
    indel_nuc = np.where(rng.random(n) < 0.7, rng.integers(0, 4, n), 4).astype(np.int32)
    ref_code = rng.integers(0, 4, n).astype(np.int32)
    alt_code = rng.integers(0, 4, n).astype(np.int32)
    is_snp = (~is_indel) & (rng.random(n) < 0.9)
    return windows, is_indel, indel_nuc, ref_code, alt_code, is_snp


def test_featurize_windows_exact_parity(rng):
    """All six DEVICE_FEATURES bitwise-match the jitted kernels, including
    N-rich windows (flow-signature truncation, gc denominator)."""
    windows, is_indel, indel_nuc, ref_code, alt_code, is_snp = _inputs(rng, 30000)
    flow = "TGCA"
    fo = np.asarray([{"A": A, "C": C, "G": G, "T": T}[c] for c in flow], np.int32)
    ref = device_feature_dict(jnp.asarray(windows), jnp.asarray(is_indel),
                              jnp.asarray(indel_nuc), jnp.asarray(ref_code),
                              jnp.asarray(alt_code), jnp.asarray(is_snp),
                              center=CENTER, flow_order=flow)
    nat = native.featurize_windows(windows, CENTER, is_indel, indel_nuc,
                                   ref_code, alt_code, is_snp, fo)
    assert nat is not None
    for k in DEVICE_FEATURES:
        np.testing.assert_array_equal(np.asarray(ref[k]), nat[k], err_msg=k)


def test_gather_windows_contig_matches_numpy(rng):
    """Window gather incl. out-of-contig edges (reads as N, code 4)."""
    seq = rng.integers(0, 4, 5000).astype(np.uint8)
    radius = 20
    pos0 = np.concatenate([np.asarray([0, 3, 4999, 4980]),
                           rng.integers(0, 5000, 500)]).astype(np.int64)
    rows = native.gather_windows_contig(seq, pos0, radius)
    assert rows is not None
    padded = np.concatenate([np.full(radius, 4, np.uint8), seq, np.full(radius, 4, np.uint8)])
    idx = (pos0 + radius)[:, None] + np.arange(-radius, radius + 1)[None, :]
    expect = padded[idx]
    np.testing.assert_array_equal(rows, expect)


def test_featurize_gather_fused_matches_two_step(rng, tmp_path):
    """Fused gather+featurize == gather_windows -> featurize_windows on a
    multi-contig table with contig-edge anchors, a missing contig (all-N
    windows), and an unsorted-contig interleave (mask scatter path)."""
    from variantcalling_tpu.featurize import (classify_alleles, featurize_gather_fused,
                                              gather_windows)
    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.io.vcf import read_vcf

    bases = "ACGT"
    seqs = {"chr1": "".join(rng.choice(list(bases), 3000)),
            "chr2": "".join(rng.choice(list(bases), 900))}
    fa = tmp_path / "g.fa"
    with open(fa, "w") as fh:
        for c, s in seqs.items():
            fh.write(f">{c}\n")
            for i in range(0, len(s), 60):
                fh.write(s[i : i + 60] + "\n")

    for interleave in (False, True):
        recs = []
        for c, length in (("chr1", 3000), ("chr2", 900), ("chrMISSING", 500)):
            pos = sorted(set([1, 2, length, length - 1] +
                             [int(p) for p in rng.integers(1, length + 1, 60)]))
            for p in pos:
                ref = seqs.get(c, "A" * (length + 1))[p - 1] if c in seqs else "A"
                alt = bases[(bases.index(ref) + 1) % 4]
                if rng.random() < 0.3:
                    alt = ref + alt  # insertion
                recs.append((c, p, ref, alt))
        if interleave:
            recs = recs[::2] + recs[1::2]  # contigs no longer contiguous runs
        vcf = tmp_path / f"t{int(interleave)}.vcf"
        with open(vcf, "w") as fh:
            fh.write("##fileformat=VCFv4.2\n#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\n")
            for c, p, r, a in recs:
                fh.write(f"{c}\t{p}\t.\t{r}\t{a}\t50\t.\t.\n")
        table = read_vcf(str(vcf))
        reader = FastaReader(str(fa))
        alle = classify_alleles(table)
        fo = np.asarray([{"A": A, "C": C, "G": G, "T": T}[c] for c in "TGCA"], np.int32)
        fused = featurize_gather_fused(table, reader, alle, fo)
        assert fused is not None
        win = gather_windows(table, reader)
        two_step = native.featurize_windows(win, CENTER, alle.is_indel, alle.indel_nuc,
                                            alle.ref_code, alle.alt_code, alle.is_snp, fo)
        for k in DEVICE_FEATURES:
            np.testing.assert_array_equal(fused[k], two_step[k],
                                          err_msg=f"{k} interleave={interleave}")


def test_forest_predict_matches_jax_walk(rng):
    """Native walk == predict_score for mean and logit_sum aggregations,
    NaN-right routing without default_left, and default_left routing."""
    from variantcalling_tpu.synthetic import synthetic_forest

    model = synthetic_forest(rng, n_trees=17, depth=5, n_features=6)
    x = rng.normal(0, 30, (20000, 6)).astype(np.float32)
    x[::11, 3] = np.nan
    for agg in ("mean", "logit_sum"):
        m = fm.FlatForest(feature=model.feature, threshold=model.threshold,
                          left=model.left, right=model.right, value=model.value,
                          max_depth=model.max_depth, aggregation=agg,
                          base_score=0.25)
        nf = fm.native_host_predictor(m)
        assert nf is not None
        ref = np.asarray(fm.predict_score(m, jnp.asarray(x)))
        np.testing.assert_allclose(nf(x), ref, atol=2e-7, err_msg=agg)
    # default_left: NaN routes left where dl set
    dl = rng.random(model.feature.shape) < 0.5
    m2 = fm.FlatForest(feature=model.feature, threshold=model.threshold,
                       left=model.left, right=model.right, value=model.value,
                       max_depth=model.max_depth, aggregation="logit_sum",
                       base_score=0.0, default_left=dl)
    nf2 = fm.native_host_predictor(m2)
    ref2 = np.asarray(fm.predict_score(m2, jnp.asarray(x)))
    np.testing.assert_allclose(nf2(x), ref2, atol=2e-7)


def test_matrix_forest_predict_bit_identical_to_two_step(rng):
    """The fused column->tile->walk path must produce bit-identical scores
    to build_matrix + forest_predict over mixed column dtypes (f32/f64/
    i32/uint8/bool incl. NaN routing with and without default_left)."""
    import dataclasses

    from variantcalling_tpu.models import forest as fm2
    from variantcalling_tpu.synthetic import synthetic_forest

    n, f = 100_000, 7
    cols = [rng.random(n).astype(np.float32),
            rng.random(n).astype(np.float64),
            rng.integers(-5, 90, n).astype(np.int32),
            rng.integers(0, 200, n).astype(np.uint8),
            (rng.random(n) < 0.5),
            np.where(rng.random(n) < 0.1, np.nan, rng.random(n)).astype(np.float32),
            rng.random(n).astype(np.float32)]
    for with_dl in (False, True):
        forest = synthetic_forest(rng, n_trees=9, depth=5, n_features=f)
        if with_dl:
            forest = dataclasses.replace(
                forest,
                default_left=(rng.random(forest.feature.shape) < 0.5).astype(np.uint8))
        x = native.build_matrix(cols)
        two_step = fm2.native_host_predictor(forest)(x)
        fused = fm2.native_cols_predictor(forest)(cols)
        assert fused is not None
        np.testing.assert_array_equal(fused, two_step, err_msg=f"dl={with_dl}")


def test_format_float_info_matches_numpy_g(rng):
    """';KEY=%g' rendering matches np.char.mod byte-for-byte (NaN -> empty)."""
    vals = np.round(rng.random(5000) * 100, 4)
    vals[::17] = np.nan
    vals[1] = 0.0
    vals[2] = 1e-7
    vals[3] = 123456789.0
    got = native.format_float_info(vals, b";TREE_SCORE=")
    assert got is not None
    buf, offs = got
    f64 = vals.astype(np.float64)
    expect = np.where(~np.isnan(f64),
                      np.char.add(b";TREE_SCORE=", np.char.mod(b"%g", f64)),
                      b"").tolist()
    for i in range(len(vals)):
        assert bytes(buf[offs[i]:offs[i + 1]]) == expect[i], i


def test_encode_column_factorized(rng):
    from variantcalling_tpu.io.vcf import _encode_column_factorized

    vals = np.asarray(rng.choice(["PASS", "LOW_SCORE", "COHORT_FP;HPOL_RUN", ""], 4000),
                      dtype=object)
    vals[::97] = None  # factorize turns None into NaN — both must encode '.'
    buf, offs = _encode_column_factorized(vals, len(vals))
    for i in range(len(vals)):
        expect = (vals[i] if vals[i] not in ("", None) else ".").encode()
        assert bytes(buf[offs[i]:offs[i + 1]]) == expect, i


def test_single_device_pipeline_byte_identical_to_jit_path(tmp_path):
    """One subprocess per path (native CPU vs jitted, single device): the
    flagship filter output must be byte-identical."""
    script = r"""
import os, sys
sys.path.insert(0, os.environ["VCTPU_TEST_REPO"])
import numpy as np
from variantcalling_tpu.io.fasta import FastaReader
from variantcalling_tpu.io.vcf import read_vcf, write_vcf
from variantcalling_tpu.pipelines.filter_variants import filter_variants
from variantcalling_tpu.synthetic import make_fixtures, synthetic_forest
d = os.environ["VCTPU_TEST_DIR"]
if not os.path.exists(os.path.join(d, "calls.vcf")):
    make_fixtures(d, n=4000, genome_len=200_000)
table = read_vcf(os.path.join(d, "calls.vcf"))
fasta = FastaReader(os.path.join(d, "ref.fa"))
model = synthetic_forest(np.random.default_rng(0), n_trees=10, depth=5)
score, filters = filter_variants(table, model, fasta)
table.header.ensure_filter("LOW_SCORE", "x")
table.header.ensure_info("TREE_SCORE", "1", "Float", "y")
write_vcf(os.path.join(d, os.environ["VCTPU_TEST_OUT"]), table, new_filters=filters,
          extra_info={"TREE_SCORE": np.round(score, 4)}, verbatim_core=True)
print("PIPE_OK")
"""
    env_base = {k: v for k, v in os.environ.items()
                if k not in ("PYTHONPATH", "JAX_PLATFORMS", "XLA_FLAGS", "PYTHONSTARTUP")}
    env_base.update(JAX_PLATFORMS="cpu", VCTPU_TEST_REPO=_REPO,
                    VCTPU_TEST_DIR=str(tmp_path))
    for out_name, extra in (("out_native.vcf", {}),
                            ("out_jit.vcf", {"VCTPU_NATIVE_FOREST": "0"})):
        env = dict(env_base, VCTPU_TEST_OUT=out_name, **extra)
        p = subprocess.run([sys.executable, "-c", script], env=env, cwd=_REPO,
                           capture_output=True, text=True, timeout=300)
        assert p.returncode == 0 and "PIPE_OK" in p.stdout, p.stderr[-2000:]
    a = (tmp_path / "out_native.vcf").read_bytes()
    b = (tmp_path / "out_jit.vcf").read_bytes()
    assert a == b


def test_gather_windows_interleaved_contigs(tmp_path, rng):
    """Unsorted VCFs (contig runs interleaved) take the boolean-mask path;
    windows must land on the right rows either way."""
    from variantcalling_tpu.featurize import gather_windows
    from variantcalling_tpu.io.fasta import FastaReader, encode_seq
    from variantcalling_tpu.io.vcf import read_vcf

    g1 = "".join(rng.choice(list("ACGT"), 300))
    g2 = "".join(rng.choice(list("ACGT"), 300))
    (tmp_path / "ref.fa").write_text(f">chr1\n{g1}\n>chr2\n{g2}\n")
    recs = [("chr1", 60), ("chr2", 80), ("chr1", 120), ("chr2", 200), ("chr1", 250)]
    lines = ["##fileformat=VCFv4.2",
             "##contig=<ID=chr1,length=300>", "##contig=<ID=chr2,length=300>",
             "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO"]
    genome = {"chr1": g1, "chr2": g2}
    for c, p in recs:
        lines.append(f"{c}\t{p}\t.\t{genome[c][p-1]}\tA\t50\tPASS\t.")
    (tmp_path / "in.vcf").write_text("\n".join(lines) + "\n")
    table = read_vcf(str(tmp_path / "in.vcf"))
    fasta = FastaReader(str(tmp_path / "ref.fa"))
    windows = gather_windows(table, fasta)
    for i, (c, p) in enumerate(recs):
        enc = encode_seq(genome[c])
        center = windows.shape[1] // 2
        assert windows[i, center] == enc[p - 1], (i, c, p)
        np.testing.assert_array_equal(
            windows[i, center - 5:center + 6], enc[p - 6:p + 5])
