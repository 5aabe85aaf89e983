"""End-to-end system test of filter_variants_pipeline on a synthetic callset
(reference test-strategy analog: golden end-to-end runs, SURVEY.md §4)."""

import pickle

import numpy as np
import pytest

from tests import fixtures
from variantcalling_tpu.featurize import featurize
from variantcalling_tpu.io.fasta import FastaReader
from variantcalling_tpu.io.vcf import read_vcf
from variantcalling_tpu.models import registry
from variantcalling_tpu.models.forest import from_sklearn
from variantcalling_tpu.pipelines import filter_variants as fvp


@pytest.fixture(scope="module")
def synthetic_world(tmp_path_factory):
    rng = np.random.default_rng(7)
    tmp = tmp_path_factory.mktemp("fvp")
    contigs = {"chr1": 20000, "chr2": 10000}
    genome = fixtures.make_genome(rng, contigs)
    fasta_path = tmp / "ref.fa"
    fixtures.write_fasta(str(fasta_path), genome)
    recs = fixtures.synth_variants(rng, genome, 400)
    for r in recs:
        r["pl"] = [30, 0, 40]
        r["gq"] = int(rng.integers(10, 90))
        r["ad"] = [int(rng.integers(5, 30)), int(rng.integers(1, 30))]
    vcf_path = tmp / "calls.vcf.gz"
    fixtures.write_vcf(str(vcf_path), recs, contigs)

    # homopolymer runs bed: long A-runs in chr1 (synthesized independent of genome)
    runs_bed = tmp / "runs.bed"
    runs_bed.write_text("chr1\t1000\t1015\nchr1\t5000\t5012\nchr2\t2000\t2005\n")

    # LCR-like annotation bed
    lcr_bed = tmp / "LCR-test.bed"
    lcr_bed.write_text("chr1\t0\t4000\nchr2\t8000\t10000\n")

    # blacklist: 5 specific loci from the callset
    bl = [(recs[i]["chrom"], recs[i]["pos"]) for i in (3, 10, 50, 100, 200)]
    bl_path = tmp / "blacklist.pkl"
    with open(bl_path, "wb") as fh:
        pickle.dump(bl, fh)

    # train a toy sklearn RF on the true features so scores are deterministic
    from sklearn.ensemble import RandomForestClassifier

    table = read_vcf(str(vcf_path))
    fasta = FastaReader(str(fasta_path))
    fs = featurize(table, fasta)
    x = fs.matrix()
    y = (x[:, fs.feature_names.index("qual")] > 50).astype(int)
    clf = RandomForestClassifier(n_estimators=10, max_depth=5, random_state=0).fit(x, y)
    model_path = tmp / "model.pkl"
    registry.save_models(
        str(model_path),
        {"rf_model_ignore_gt_incl_hpol_runs": from_sklearn(clf, feature_names=fs.feature_names)},
    )
    return {
        "tmp": tmp,
        "recs": recs,
        "vcf": str(vcf_path),
        "fasta": str(fasta_path),
        "runs": str(runs_bed),
        "lcr": str(lcr_bed),
        "blacklist": str(bl_path),
        "model": str(model_path),
        "clf": clf,
        "bl_loci": bl,
    }


def test_filter_pipeline_end_to_end(synthetic_world):
    w = synthetic_world
    out = w["tmp"] / "filtered.vcf.gz"
    rc = fvp.run(
        [
            "--input_file", w["vcf"],
            "--model_file", w["model"],
            "--model_name", "rf_model_ignore_gt_incl_hpol_runs",
            "--runs_file", w["runs"],
            "--blacklist", w["blacklist"],
            "--reference_file", w["fasta"],
            "--output_file", str(out),
            "--annotate_intervals", w["lcr"],
            "--hpol_filter_length_dist", "10", "10",
            "--backend", "cpu",
        ]
    )
    assert rc == 0
    result = read_vcf(str(out))
    assert len(result) == len(w["recs"])

    # TREE_SCORE parity with sklearn predict_proba
    table = read_vcf(w["vcf"])
    fasta = FastaReader(w["fasta"])
    from variantcalling_tpu.io.bed import read_bed

    fs = featurize(table, fasta, annotate_intervals={"LCR-test": read_bed(w["lcr"])})
    base_cols = [f for f in fs.feature_names if f != "LCR-test"]
    ref_scores = w["clf"].predict_proba(fs.matrix(base_cols))[:, 1]
    got = result.info_field("TREE_SCORE")
    np.testing.assert_allclose(got, np.round(ref_scores, 4), atol=2e-4)

    # PASS/LOW_SCORE consistent with threshold 0.5
    filters = result.filters
    bl_set = set(w["bl_loci"])
    for i in range(len(result)):
        locus = (result.chrom[i], int(result.pos[i]))
        if locus in bl_set:
            assert "COHORT_FP" in filters[i]
            continue
        if ref_scores[i] >= 0.5:
            assert filters[i] in ("PASS", "PASS;HPOL_RUN") or filters[i].startswith("PASS")
        else:
            assert "LOW_SCORE" in filters[i]

    # HPOL_RUN marking: all variants within 10bp of a >=10bp run are marked
    from variantcalling_tpu.io.bed import read_bed as rb

    runs = rb(w["runs"])
    long_runs = [
        (c, s, e) for c, s, e in zip(runs.chrom, runs.start, runs.end) if e - s >= 10
    ]
    n_hpol = 0
    for i in range(len(result)):
        near = any(
            result.chrom[i] == c and s - 10 <= result.pos[i] - 1 <= e + 9
            for c, s, e in long_runs
        )
        if near:
            assert "HPOL_RUN" in filters[i]
            n_hpol += 1
        else:
            assert "HPOL_RUN" not in filters[i]

    # header declares new filters/info
    header_text = "\n".join(result.header.lines)
    for fid in ("LOW_SCORE", "COHORT_FP", "HPOL_RUN", "TREE_SCORE"):
        assert fid in header_text


def test_filter_pipeline_single_contig(synthetic_world):
    w = synthetic_world
    out = w["tmp"] / "chr2.vcf.gz"
    rc = fvp.run(
        [
            "--input_file", w["vcf"],
            "--model_file", w["model"],
            "--model_name", "rf_model_ignore_gt_incl_hpol_runs",
            "--reference_file", w["fasta"],
            "--output_file", str(out),
            "--limit_to_contig", "chr2",
            "--backend", "cpu",
        ]
    )
    assert rc == 0
    result = read_vcf(str(out))
    assert len(result) == sum(1 for r in w["recs"] if r["chrom"] == "chr2")
    assert all(c == "chr2" for c in result.chrom)


def test_genome_resident_scoring_matches_host_windows(tmp_path, rng):
    """The device-resident-genome window gather must score identically to
    the host window path (featurize.device_genome / windows_from_packed)."""
    from variantcalling_tpu.featurize import host_featurize
    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.io.vcf import read_vcf
    from variantcalling_tpu.pipelines.filter_variants import fused_featurize_score
    from variantcalling_tpu.synthetic import make_fixtures, synthetic_forest

    d = str(tmp_path)
    make_fixtures(d, n=3000, genome_len=100_000)
    table = read_vcf(f"{d}/calls.vcf")
    fasta = FastaReader(f"{d}/ref.fa")
    model = synthetic_forest(np.random.default_rng(0), n_trees=10, depth=5)
    s_host = fused_featurize_score(model, host_featurize(table, fasta), "TGCA")
    hf_dev = host_featurize(table, fasta, compute_windows=False)
    assert hf_dev.windows is None
    s_dev = fused_featurize_score(model, hf_dev, "TGCA", table=table, fasta=fasta)
    np.testing.assert_allclose(s_host, s_dev, atol=1e-6)


def test_globalize_positions_exact_past_int32_at_hg38_scale():
    """Global byte positions past 2^31 must come out exact in the ONE uint32
    a record puts on the wire — jax without x64 has no int64, and int32
    ends at 2.1e9 where hg38 with its gaps ends at 3.2e9."""
    from variantcalling_tpu.featurize import (GENOME_ROW_BYTES, DeviceGenome,
                                              globalize_positions,
                                              packed_position_fill)
    from variantcalling_tpu.io.vcf import VariantTable, VcfHeader

    big = 3_100_000_000  # chrX-at-end-of-hg38 scale global offset
    n_rows = (big + 60_000_000) // GENOME_ROW_BYTES + 1
    genome = DeviceGenome(rows=np.empty((n_rows, 0), dtype=np.uint32),
                          offsets={"chrX": big, "chr1": 40},
                          lengths={"chrX": 50_000_000, "chr1": 1_000})
    n = 5
    table = VariantTable(
        header=VcfHeader(),
        chrom=np.array(["chrX", "chrX", "chr1", "chrUn", "chrX"], dtype=object),
        pos=np.array([1, 49_999_999, 500, 100, 7_654_321], dtype=np.int64),
        vid=np.array(["."] * n, dtype=object), ref=np.array(["A"] * n, dtype=object),
        alt=np.array(["G"] * n, dtype=object), qual=np.zeros(n),
        filters=np.array(["PASS"] * n, dtype=object), info=np.array(["."] * n, dtype=object),
    )
    gpos = globalize_positions(table, genome)
    assert gpos.dtype == np.uint32 and gpos.shape == (n,)
    assert int(gpos[0]) == big + 0 and int(gpos[0]) > np.iinfo(np.int32).max
    assert int(gpos[1]) == big + 49_999_998
    assert int(gpos[2]) == 40 + 499
    assert int(gpos[4]) == big + 7_654_320
    # unknown contig resolves past the genome end (all-N window), and the
    # row its window would start in is still an int32-safe number
    assert int(gpos[3]) == packed_position_fill(genome) == n_rows * GENOME_ROW_BYTES
    assert (int(gpos[3]) - 20) // GENOME_ROW_BYTES >= n_rows - 1
    assert n_rows < (1 << 23)


def test_fused_narrow_columns_bit_identical_to_f32_matrix(tmp_path):
    """The fused path's narrow wire dtypes (uint8 host columns, packed
    uint32 positions) must reproduce the stacked-f32-matrix scores exactly
    — the _narrow_column contract is exactness, not approximation."""
    from variantcalling_tpu.featurize import featurize, host_featurize
    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.io.vcf import read_vcf
    from variantcalling_tpu.pipelines.filter_variants import (fused_featurize_score,
                                                              score_variants)
    from variantcalling_tpu.synthetic import make_fixtures, synthetic_forest

    d = str(tmp_path)
    make_fixtures(d, n=2000, genome_len=60_000)
    table = read_vcf(f"{d}/calls.vcf")
    fasta = FastaReader(f"{d}/ref.fa")
    model = synthetic_forest(np.random.default_rng(1), n_trees=8, depth=5)

    fs = featurize(table, fasta)
    ref = score_variants(model, fs.matrix(), fs.feature_names)
    fused = fused_featurize_score(model, host_featurize(table, fasta), "TGCA")
    np.testing.assert_array_equal(fused, ref)


def test_fused_threshold_model_matches_direct_predict(tmp_path):
    """ThresholdModel must flow through the fused tuple-of-columns program
    (it consumes the stacked matrix assembled on device) and match its
    direct predict_score on the materialized f32 matrix."""
    from variantcalling_tpu.featurize import featurize, host_featurize
    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.io.vcf import read_vcf
    from variantcalling_tpu.models.threshold import ThresholdModel, predict_score
    from variantcalling_tpu.pipelines.filter_variants import fused_featurize_score
    from variantcalling_tpu.synthetic import make_fixtures

    d = str(tmp_path)
    make_fixtures(d, n=1500, genome_len=60_000)
    table = read_vcf(f"{d}/calls.vcf")
    fasta = FastaReader(f"{d}/ref.fa")

    hf = host_featurize(table, fasta)
    model = ThresholdModel(
        feature_names=["qual", "gc_content"],
        thresholds=np.asarray([40.0, 0.5], np.float32),
        signs=np.asarray([1.0, -1.0], np.float32),
        scales=np.asarray([10.0, 0.2], np.float32),
        all_feature_names=list(hf.names),
    )
    fs = featurize(table, fasta)
    ref = np.asarray(predict_score(model, fs.matrix(), fs.feature_names))
    # host-window fused path
    fused = fused_featurize_score(model, hf, "TGCA")
    np.testing.assert_allclose(fused, ref, atol=1e-6)
    # genome-resident fused path (packed uint32 positions)
    hf_dev = host_featurize(table, fasta, compute_windows=False)
    fused_dev = fused_featurize_score(model, hf_dev, "TGCA", table=table, fasta=fasta)
    np.testing.assert_allclose(fused_dev, ref, atol=1e-6)


def test_filter_pipeline_output_is_byte_deterministic(synthetic_world):
    """Two runs over the same inputs must write byte-identical VCFs —
    guards nondeterminism creep (unordered dicts, unstable sorts, device
    scheduling) in the flagship path."""
    import gzip

    w = synthetic_world
    outs = []
    for tag in ("det_a", "det_b"):
        out = w["tmp"] / f"{tag}.vcf.gz"
        rc = fvp.run([
            "--input_file", w["vcf"],
            "--model_file", w["model"],
            "--model_name", "rf_model_ignore_gt_incl_hpol_runs",
            "--runs_file", w["runs"],
            "--blacklist", w["blacklist"],
            "--reference_file", w["fasta"],
            "--output_file", str(out),
            "--annotate_intervals", w["lcr"],
            "--backend", "cpu",
        ])
        assert rc == 0
        outs.append(gzip.open(out, "rb").read())
    assert outs[0] == outs[1]
