import numpy as np
import pytest

import jax.numpy as jnp

from variantcalling_tpu.featurize import GENOME_ROW_BYTES as _ROW
from variantcalling_tpu.featurize import WINDOW_RADIUS as _R
from variantcalling_tpu.io.fasta import encode_seq
from variantcalling_tpu.ops import features as fops
from variantcalling_tpu.ops import intervals as iops


def win(seq: str) -> np.ndarray:
    return encode_seq(seq)[None, :]


def test_gc_content():
    # 21bp window, center=10, radius=10: count G/C over the full window
    w = win("A" * 10 + "G" + "C" * 10)
    gc = fops.gc_content(jnp.asarray(w), center=10, radius=10)
    assert float(gc[0]) == pytest.approx(11 / 21)
    # N excluded from denominator
    w = win("N" * 10 + "G" + "A" * 10)
    gc = fops.gc_content(jnp.asarray(w), center=10, radius=10)
    assert float(gc[0]) == pytest.approx(1 / 11)


def test_run_length_at():
    w = win("ACGTTTTTACGTACGTACGTA")
    rl = fops.run_length_at(jnp.asarray(w), start=3)
    assert int(rl[0]) == 5
    rl = fops.run_length_at(jnp.asarray(w), start=0)
    assert int(rl[0]) == 1
    # run to the end of the window
    w = win("AAAAA")
    assert int(fops.run_length_at(jnp.asarray(w), start=0)[0]) == 5


def test_hmer_indel_features():
    # deletion of T in a TTTT run: window center anchor A, next bases TTTT
    w = win("CCCCCATTTTGGGGGGGGGGG")  # center=5 is A
    hl, hn = fops.hmer_indel_features(
        jnp.asarray(w), 5, jnp.array([True]), jnp.array([3])  # T
    )
    assert int(hl[0]) == 4
    assert int(hn[0]) == 3
    # indel nuc mismatch with next base -> not hmer
    hl, hn = fops.hmer_indel_features(jnp.asarray(w), 5, jnp.array([True]), jnp.array([2]))
    assert int(hl[0]) == 0
    assert int(hn[0]) == 4
    # SNP -> not hmer
    hl, hn = fops.hmer_indel_features(jnp.asarray(w), 5, jnp.array([False]), jnp.array([3]))
    assert int(hl[0]) == 0


def test_motif_codes():
    w = win("ACGTACGTACGTACGTACGTA")
    left, right = fops.motif_codes(jnp.asarray(w), center=10, k=5)
    # left motif = w[5:10] = "CGTAC", right = w[11:16] = "TACGT"
    def pack(s):
        return sum(int(encode_seq(s)[i]) * 5 ** (4 - i) for i in range(5))

    assert int(left[0]) == pack("CGTAC")
    assert int(right[0]) == pack("TACGT")


def test_cycle_skip_status():
    # classic cycle-skip example under TGCA flow order:
    # context ...T [C->T] A...: merging hmers changes flow count
    w = win("AAAAAAAAAATCAAAAAAAAA")  # center=10 is T? no: w[10]='T'? seq: 10 A's then T C ...
    # build explicit: left context AAAA, center X, right context CAAA
    w = win("AAAAAAAAAACCAAAAAAAAA")
    ref = jnp.array([1])  # C at center
    alt = jnp.array([0])  # A
    status = fops.cycle_skip_status(jnp.asarray(w), 10, ref, alt, jnp.array([True]))
    assert int(status[0]) in (0, 2)
    # non-SNP is NA (-1)
    status = fops.cycle_skip_status(jnp.asarray(w), 10, ref, alt, jnp.array([False]))
    assert int(status[0]) == -1
    # a guaranteed skip: ref TGT vs alt TTT under TGCA (G hmer disappears)
    w2 = win("AAAAAAAAATGTAAAAAAAAA")
    # center=10 is G
    status = fops.cycle_skip_status(jnp.asarray(w2), 10, jnp.array([2]), jnp.array([3]), jnp.array([True]))
    assert int(status[0]) == 2


def test_flow_key_length_known():
    fo = jnp.array([3, 2, 1, 0])  # TGCA
    seq = jnp.asarray(encode_seq("TGCA")[None, :])
    # each base consumed by its own flow: 4 flows
    assert int(fops._flow_key_length(seq, fo, 20)[0]) == 4
    seq = jnp.asarray(encode_seq("TTTT")[None, :])
    assert int(fops._flow_key_length(seq, fo, 20)[0]) == 1
    seq = jnp.asarray(encode_seq("AT")[None, :])
    # flows: T(no),G(no),C(no),A(yes=4 flows),T(consume T=5)
    assert int(fops._flow_key_length(seq, fo, 20)[0]) == 5


def test_interval_membership_and_distance():
    coords = iops.GenomeCoords({"chr1": 1000, "chr2": 500})
    gpos = coords.globalize(np.array(["chr1", "chr1", "chr2", "chrX"], dtype=object), np.array([10, 700, 100, 5]))
    assert gpos[2] == 1100
    assert gpos[3] == -1
    gs = np.array([5, 1050])
    ge = np.array([20, 1200])
    m = iops.membership(gpos, gs, ge)
    np.testing.assert_array_equal(m, [True, False, True, False])
    d = iops.distance_to_nearest(gpos, gs, ge)
    assert d[0] == 0
    assert d[1] == min(700 - 19, 1050 - 700)  # distance to end of iv0 vs start of iv1
    assert d[2] == 0
    # whole-genome scale: > int32 coordinates must survive
    big = iops.GenomeCoords({"c1": 3_000_000_000, "c2": 1_000_000})
    g2 = big.globalize(np.array(["c2"], dtype=object), np.array([500]))
    assert g2[0] == 3_000_000_500
    assert iops.membership(g2, np.array([3_000_000_000]), np.array([3_000_001_000]))[0]


_GATHER_ROWS = 1024


def _table(chrom, pos):
    """A VariantTable of SNP records at (chrom, 1-based pos)."""
    from variantcalling_tpu.io.vcf import VariantTable, VcfHeader

    n = len(pos)
    return VariantTable(
        header=VcfHeader(), chrom=np.array(chrom, dtype=object),
        pos=np.array(pos, dtype=np.int64),
        vid=np.array(["."] * n, dtype=object), ref=np.array(["A"] * n, dtype=object),
        alt=np.array(["G"] * n, dtype=object), qual=np.zeros(n),
        filters=np.array(["PASS"] * n, dtype=object), info=np.array(["."] * n, dtype=object))


@pytest.fixture(scope="module")
def row_genome():
    """A synthetic genome in the device layout: 2 MiB + 300 bytes of random
    codes (so windows cross the old 2^20-byte block edge and the last real
    row is followed by a trailing pad), the leading gap of N every built
    genome has, and the jitted gather at one padded shape."""
    import jax

    from variantcalling_tpu.featurize import (DeviceGenome, _genome_rows,
                                              packed_position_fill,
                                              windows_from_packed)

    rng = np.random.default_rng(3)
    flat = rng.integers(0, 5, size=(2 << 20) + 300).astype(np.uint8)
    flat[:2 * _R] = 4
    genome = DeviceGenome(_genome_rows([flat]), offsets={"c1": 2 * _R},
                          lengths={"c1": len(flat) - 2 * _R})
    fill = packed_position_fill(genome)
    gather = jax.jit(windows_from_packed)
    rows = jnp.asarray(genome.rows)

    def windows(gpos):
        gpos = np.asarray(gpos, dtype=np.uint32)
        assert len(gpos) <= _GATHER_ROWS
        padded = np.full(_GATHER_ROWS, fill, dtype=np.uint32)
        padded[:len(gpos)] = gpos
        return np.asarray(gather(rows, jnp.asarray(padded)))[:len(gpos)]

    return flat, genome, windows


def _flat_expectation(flat, gpos):
    idx = np.asarray(gpos, dtype=np.int64)[:, None] + np.arange(-_R, _R + 1)[None, :]
    ok = (idx >= 0) & (idx < len(flat))
    return np.where(ok, flat[np.clip(idx, 0, len(flat) - 1)], 4).astype(np.uint8)


def _bad_positions(flat, genome):
    """What globalize_positions gives records with no place in the genome:
    an unknown contig, position 0, and one ``radius`` past the contig's
    end — beside one within ``radius`` of the end, which keeps its place."""
    from variantcalling_tpu.featurize import globalize_positions

    clen = genome.lengths["c1"]
    table = _table(["chrUn", "c1", "c1", "c1"], [100, 0, clen + _R + 1, clen + 5])
    gpos = globalize_positions(table, genome)
    assert gpos.dtype == np.uint32
    assert gpos[3] == 2 * _R + clen + 4  # within radius of the end: resolved
    return gpos


_GATHER_CASES = {
    # start = gpos - R at each of the four byte alignments within a word
    "byte_alignment_0": lambda flat, g: [_R + 4000],
    "byte_alignment_1": lambda flat, g: [_R + 4001],
    "byte_alignment_2": lambda flat, g: [_R + 4002],
    "byte_alignment_3": lambda flat, g: [_R + 4003],
    # every start from 44 bytes before a row's end to its successor's third byte
    "crossing_a_512_byte_row": lambda flat, g: np.arange(7 * _ROW - 44, 7 * _ROW + 3) + _R,
    "crossing_the_old_2_20_block": lambda flat, g: np.arange((1 << 20) - 45, (1 << 20) + 45),
    "first_window": lambda flat, g: [_R, _R + 1, _R + 2, _R + 3],
    # the window begins in the last real row and runs into the closing row of N
    "last_real_row": lambda flat, g: np.arange(len(flat) - 60, len(flat) + _R),
    "inside_the_trailing_pad": lambda flat, g: np.arange(len(flat) + _R, (g.rows.shape[0] - 1) * _ROW + 2 * _R),
    "packed_position_fill": lambda flat, g: [g.rows.shape[0] * _ROW],
    "bad_positions_of_globalize": _bad_positions,
    "random_1000": lambda flat, g: np.random.default_rng(11).integers(_R, len(flat) + 600, size=1000),
}


@pytest.mark.parametrize("case", list(_GATHER_CASES))
def test_row_gather_matches_flat_numpy(row_genome, case):
    """The device gather — two whole-row lookups, barrel shift, funnel
    shift, byte split — against plain indexing of the flat genome, where
    out-of-range bytes read N."""
    from variantcalling_tpu.featurize import packed_position_fill

    flat, genome, windows = row_genome
    gpos = np.asarray(_GATHER_CASES[case](flat, genome), dtype=np.int64)
    assert len(gpos) and gpos.min() >= _R
    got = windows(gpos)
    assert got.shape == (len(gpos), 2 * _R + 1) and got.dtype == np.uint8
    np.testing.assert_array_equal(got, _flat_expectation(flat, gpos))
    if case in ("packed_position_fill", "inside_the_trailing_pad"):
        assert (got == 4).all()
    if case == "bad_positions_of_globalize":
        assert (gpos[:3] == packed_position_fill(genome)).all() and (got[:3] == 4).all()
        assert (got[3] != 4).any()


def test_genome_rows_layout_shares_the_one_concatenation():
    """Byte p of the genome is bits 8*(p&3) of word p>>2; whole rows, closed
    by one row of N; the word view is the concatenation itself, not a copy."""
    from variantcalling_tpu.featurize import GENOME_ROW_WORDS, _genome_rows

    parts = [np.full(40, 4, np.uint8), np.arange(1000, dtype=np.uint8) % 4]
    rows = _genome_rows(parts)
    assert rows.dtype == np.uint32 and rows.shape == (4, GENOME_ROW_WORDS)
    assert rows.base is not None and not rows.flags.owndata  # a view
    flat = np.concatenate(parts)
    p = np.arange(len(flat))
    np.testing.assert_array_equal((rows.reshape(-1)[p >> 2] >> (8 * (p & 3))) & 0xFF, flat)
    assert (rows[-1] == 0x04040404).all()
    assert (rows.reshape(-1).view(np.uint8)[len(flat):] == 4).all()


def test_device_gather_equals_host_gather_on_multi_contig_fasta(tmp_path):
    """windows_from_packed over device_genome == gather_windows (host), row
    for row: three contigs of unlike lengths, first and last bases, a
    position within ``radius`` past a contig's end, one past that, and a
    contig the FASTA does not have."""
    from variantcalling_tpu.featurize import (device_genome, gather_windows,
                                              globalize_positions,
                                              windows_from_packed)
    from variantcalling_tpu.io.fasta import FastaReader

    rng = np.random.default_rng(5)
    lens = {"chrA": 700, "chrB": 1301, "chrC": 517}
    fa = tmp_path / "multi.fa"
    with open(fa, "w") as fh:
        for name, n in lens.items():
            seq = "".join("ACGTN"[i] for i in rng.integers(0, 5, size=n))
            fh.write(f">{name}\n")
            fh.writelines(seq[i:i + 60] + "\n" for i in range(0, n, 60))
    chrom, pos = [], []
    for name, n in lens.items():
        for p1 in [1, 2, _R, _R + 1, n // 2, n - _R, n - 1, n, n + 5, n + _R, n + _R + 1, n + 400]:
            chrom.append(name)
            pos.append(p1)
        extra = rng.integers(1, n + 1, size=40)
        chrom += [name] * len(extra)
        pos += list(extra)
    chrom.append("chrNotThere")
    pos.append(10)
    table = _table(chrom, pos)
    fasta = FastaReader(str(fa))
    genome = device_genome(fasta)
    assert set(genome.offsets) == set(lens)
    got = np.asarray(windows_from_packed(genome.rows, jnp.asarray(globalize_positions(table, genome))))
    want = gather_windows(table, fasta)
    np.testing.assert_array_equal(got, want)
    assert (want[-1] == 4).all() and (want != 4).any()


def test_window_gather_takes_row_indices_not_byte_indices():
    """The regression guard: the gather this replaced took one index pair
    per BYTE (41 x rows). Lowered at 1,024 rows, no gather in the StableHLO
    may take more than 2 x rows index vectors, nor all of them together."""
    import re

    import jax

    from variantcalling_tpu.featurize import GENOME_ROW_WORDS, windows_from_packed

    rows = 1024
    text = jax.jit(windows_from_packed).lower(
        jax.ShapeDtypeStruct((64, GENOME_ROW_WORDS), jnp.uint32),
        jax.ShapeDtypeStruct((rows,), jnp.uint32)).as_text()
    gathers = [ln for ln in text.splitlines() if "stablehlo.gather" in ln or "dynamic_gather" in ln]
    assert gathers, "no gather found: the pattern below guards nothing"
    counts = []
    for ln in gathers:
        operands = re.search(r":\s*\(tensor<[^>]*>,\s*tensor<([0-9x]*)x[a-z]+[0-9]+>\)", ln)
        assert operands, ln
        dims = [int(d) for d in operands.group(1).split("x") if d]
        index_vector_dim = int(re.search(r"index_vector_dim = (\d+)", ln).group(1))
        counts.append(int(np.prod([d for i, d in enumerate(dims) if i != index_vector_dim])))
    assert max(counts) <= 2 * rows and sum(counts) <= 2 * rows, counts


def test_genome_packable_is_the_uint32_range(tmp_path):
    """Packable iff every byte position, and the fill past the closing row,
    fits uint32 — from contig lengths alone; the builder refuses the rest."""
    from variantcalling_tpu import featurize

    class Lengths:
        path = "lengths-only"

        def __init__(self, *lens):
            self.references = [f"c{i}" for i in range(len(lens))]
            self._lens = dict(zip(self.references, lens))

        def get_reference_length(self, c):
            return self._lens[c]

    assert featurize.genome_packable(Lengths(3_100_000_000, 57_000_000))  # hg38 scale
    edge = (1 << 32) - 3 * featurize.GENOME_ROW_BYTES
    assert featurize.genome_packable(Lengths(edge - 200))
    assert not featurize.genome_packable(Lengths(edge + 2 * featurize.GENOME_ROW_BYTES))
    with pytest.raises(ValueError, match="uint32"):
        featurize._build_device_genome(Lengths(1 << 32), featurize.WINDOW_RADIUS, None)


def test_genome_cache_key_shared_across_consumers(tmp_path):
    """The small-job resident guard must answer the same for every consumer:
    featurize() and the filter pipeline both key the genome cache through
    standard_genome_sharding(), so one consumer's upload makes the cache
    hit visible to the other regardless of call order."""
    from variantcalling_tpu.featurize import (_genome_resident_worthwhile,
                                              device_genome,
                                              standard_genome_sharding)
    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.io.vcf import VariantTable, VcfHeader

    genome = "ACGT" * 500
    fa = tmp_path / "tiny.fa"
    fa.write_text(">chr1\n" + genome + "\n")
    fasta = FastaReader(str(fa))

    tiny = VariantTable(
        header=VcfHeader(lines=[]),
        chrom=np.array(["chr1"] * 3, dtype=object), pos=np.array([10, 20, 30]),
        vid=np.array(["."] * 3, dtype=object), ref=np.array(["A"] * 3, dtype=object),
        alt=np.array(["C"] * 3, dtype=object), qual=np.ones(3),
        filters=np.array(["PASS"] * 3, dtype=object),
        info=np.array(["."] * 3, dtype=object),
    )
    sh = standard_genome_sharding()
    # small job, nothing cached -> host path (both consumers agree)
    assert not _genome_resident_worthwhile(tiny, fasta, sharding=sh)
    # any consumer uploads through the shared helper...
    device_genome(fasta, sharding=sh)
    # ...and now BOTH consumers see the cache hit with the same key
    assert _genome_resident_worthwhile(tiny, fasta, sharding=sh)
    assert _genome_resident_worthwhile(tiny, fasta, sharding=standard_genome_sharding())


def test_single_device_plan_keeps_the_genome_on_one_device(tmp_path):
    """A dp=1 plan on a multi-device host (this harness forces 8) must not
    replicate the genome over the other devices: a program fed an argument
    committed to N devices runs on all N, and XLA cannot auto-partition
    the Mosaic forest kernel — the first four-chip run died there."""
    import jax

    from variantcalling_tpu.featurize import (device_genome,
                                              standard_genome_sharding)
    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.parallel import shard_score

    assert len(jax.local_devices()) > 1
    fa = tmp_path / "tiny.fa"
    fa.write_text(">chr1\n" + "ACGT" * 500 + "\n")
    plan1 = shard_score.MeshPlan(1, "1", "test")
    sh = standard_genome_sharding(shard_score.mesh_for(plan1))
    assert sh is None and standard_genome_sharding() is None
    genome = device_genome(FastaReader(str(fa)), sharding=sh)
    assert len(genome.rows.sharding.device_set) == 1
    plan4 = shard_score.MeshPlan(4, "4", "test")
    sh4 = standard_genome_sharding(shard_score.mesh_for(plan4))
    assert sh4.is_fully_replicated and len(sh4.device_set) == 4


def test_flow_signature_matches_scan_reference(rng):
    """The closed-form flow signature must agree with the sequential flow
    scan on flow count AND zero-pattern comparison for random haplotype
    pairs, incl. N-truncated rows (contig edges)."""
    fo = jnp.asarray([0, 2, 1, 3], dtype=jnp.int32)  # TGCA order as codes
    n, L = 3000, 9
    ref = rng.integers(0, 4, size=(n, L)).astype(np.uint8)
    alt = ref.copy()
    alt[:, L // 2] = rng.integers(0, 4, size=n)  # center substitution
    # sprinkle Ns to exercise truncation
    ref[rng.random((n, L)) < 0.02] = 4
    alt[: n // 2, :] = np.where(rng.random((n // 2, L)) < 0.02, 4, alt[: n // 2, :])

    max_flows = 4 * L + 4
    for hap in (ref, alt):
        flows_ref, key_ref = fops._flow_keys(jnp.asarray(hap), fo, max_flows)
        flows_new, _sig = fops._flow_signature(jnp.asarray(hap), fo)
        np.testing.assert_array_equal(np.asarray(flows_new), np.asarray(flows_ref))

    fr, kr = fops._flow_keys(jnp.asarray(ref), fo, max_flows)
    fa, ka = fops._flow_keys(jnp.asarray(alt), fo, max_flows)
    _, sr = fops._flow_signature(jnp.asarray(ref), fo)
    _, sa = fops._flow_signature(jnp.asarray(alt), fo)
    old_change = np.asarray(jnp.any((kr == 0) != (ka == 0), axis=1))
    new_change = np.asarray(jnp.any(sr != sa, axis=1))
    # the comparisons only matter where flow counts agree (else status=2)
    same_flows = np.asarray(fr) == np.asarray(fa)
    np.testing.assert_array_equal(new_change[same_flows], old_change[same_flows])
