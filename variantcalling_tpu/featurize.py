"""Variant featurization: VariantTable + reference genome -> device feature tensors.

This is the front half of the north-star hot path
(filter_variants_pipeline, docs/filter_variants_pipeline.md): the reference
computes per-variant annotations in pandas; here host code gathers fixed
-width reference windows and allele scalars, and
:mod:`variantcalling_tpu.ops.features` kernels compute the window-derived
features on device, fused with classifier inference.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import numpy as np

import jax

from variantcalling_tpu import obs
from variantcalling_tpu.io.bed import IntervalSet
from variantcalling_tpu.io.fasta import FastaReader, encode_seq
from variantcalling_tpu.io.vcf import VariantTable
from variantcalling_tpu.ops import features as fops
from variantcalling_tpu.ops import intervals as iops
from variantcalling_tpu.utils.keyed_cache import KeyedCache
from variantcalling_tpu.utils.trace import stage

WINDOW_RADIUS = 20  # bases either side of the anchor in the gathered window
CENTER = WINDOW_RADIUS

# feature order of the assembled matrix; models store this list as metadata
BASE_FEATURES = [
    "qual",
    "dp",
    "sor",
    "af",
    "gq",
    "is_het",
    "is_snp",
    "is_indel",
    "is_ins",
    "indel_length",
    "hmer_indel_length",
    "hmer_indel_nuc",
    "gc_content",
    "cycleskip_status",
    "left_motif",
    "right_motif",
    "ref_code",
    "alt_code",
    "n_alts",
]


@dataclass
class AlleleColumns:
    """Host-derived per-variant allele scalars (first ALT; multiallelic flagged)."""

    is_snp: np.ndarray
    is_indel: np.ndarray
    is_ins: np.ndarray
    indel_length: np.ndarray
    indel_nuc: np.ndarray  # 0..3 if single-nucleotide indel diff else 4
    ref_code: np.ndarray  # anchor base code for SNPs (else 4)
    alt_code: np.ndarray
    n_alts: np.ndarray


def classify_alleles(table: VariantTable) -> AlleleColumns:
    """Indel/SNP classification from REF/ALT strings (parity: classify_indel,
    ugbio_core.vcfbed.variant_annotation; run_no_gt_report.py:92).

    Served from the native scan cache when the table came through the C++
    ingest (io/vcf._read_vcf_native) — zero per-record Python on that path.
    """
    if table.aux is not None:
        a = table.aux.alle
        cls = a["aclass"]
        return AlleleColumns(  # fresh arrays: the cache must stay pristine
            is_snp=(cls & 1).astype(bool),
            is_indel=(cls & 2).astype(bool),
            is_ins=(cls & 4).astype(bool),
            indel_length=a["indel_length"].copy(),
            indel_nuc=a["indel_nuc"].copy(),
            ref_code=a["ref_code"].copy(),
            alt_code=a["alt_code"].copy(),
            n_alts=a["n_alts"].copy(),
        )
    n = len(table)
    is_snp = np.zeros(n, dtype=bool)
    is_indel = np.zeros(n, dtype=bool)
    is_ins = np.zeros(n, dtype=bool)
    indel_length = np.zeros(n, dtype=np.int32)
    indel_nuc = np.full(n, 4, dtype=np.int32)
    ref_code = np.full(n, 4, dtype=np.int32)
    alt_code = np.full(n, 4, dtype=np.int32)
    n_alts = table.n_alts()
    code = {"A": 0, "C": 1, "G": 2, "T": 3}
    for i in range(n):
        ref = table.ref[i]
        alt_s = table.alt[i]
        if alt_s in (".", ""):
            continue
        alt = alt_s.split(",")[0]
        if alt in ("<NON_REF>", "<*>") or alt.startswith("<"):
            continue
        if len(ref) == len(alt) == 1:
            is_snp[i] = True
            ref_code[i] = code.get(ref.upper(), 4)
            alt_code[i] = code.get(alt.upper(), 4)
        elif len(ref) != len(alt):
            is_indel[i] = True
            if len(alt) > len(ref):
                is_ins[i] = True
                diff = alt[len(ref) :] if alt.startswith(ref) else alt[1:]
            else:
                diff = ref[len(alt) :] if ref.startswith(alt) else ref[1:]
            indel_length[i] = abs(len(alt) - len(ref))
            u = set(diff.upper())
            if len(u) == 1:
                indel_nuc[i] = code.get(next(iter(u)), 4)
    return AlleleColumns(is_snp, is_indel, is_ins, indel_length, indel_nuc, ref_code, alt_code, n_alts)


# device-resident genome: fasta path -> (device rows, offsets, lengths).
# Shipping the genome to HBM once turns per-run window transfer (41 bytes a
# variant) into an on-device gather fed by ONE uint32 per variant: its byte
# position in the concatenation of all contigs, which are joined by
# 2*WINDOW_RADIUS-wide N gaps so windows never leak across contig
# boundaries (hg38 + gaps is ~3.2e9: it fits uint32, not int32 — the only
# signed width jax indexes with without x64). The bytes live on the device
# as little-endian 32-bit words in rows of GENOME_ROW_WORDS = 128 — one
# row is the 128 lanes of a vector register, 512 bases — closed by one row
# of N, so a window is TWO WHOLE-ROW LOOKUPS (the row its first byte falls
# in and that row's successor; a row number is < 2^23, int32-safe at any
# genome size) and the per-window alignment is vector shifts after the
# gather (windows_from_packed). Whole rows because of what an index costs
# on the chip: a uint8 array read with one index per BYTE takes 26 ns a
# byte on a v5e (41 x 262,144 indices a dispatch: 0.28 s), two row lookups
# and the shifts 43 ns a WINDOW (PERF.md, PR 31). One layout for every
# genome size; the fused program compiles ONCE (per-contig arrays would
# retrace per contig length). Two entries cached (the sharded + unsharded
# variants of one genome; ~3.1GB HBM each for hg38).
_DEVICE_GENOME_MAX = 2
# chunk featurization fans out on the IO pool (vctpu-lint VCT010): a miss
# is single flight (utils/keyed_cache.py) — two workers racing the SAME
# genome would otherwise both encode and upload ~3.1GB to HBM — while
# builds of DISTINCT keys (different fasta/radius/sharding) proceed
# concurrently instead of queueing behind a multi-second upload they do
# not want.
_DEVICE_GENOME_CACHE = KeyedCache(_DEVICE_GENOME_MAX)
# tables below this size featurize through the host window gather — a tiny
# job must not pay a whole-genome encode + HBM upload
GENOME_RESIDENT_MIN_VARIANTS = 100_000


def _genome_resident_worthwhile(table, fasta, radius: int | None = None,
                                sharding=None) -> bool:
    """True when the EXACT genome entry the caller would use is already
    resident, or the table is big enough to amortize the upload. Matching
    on path alone would route small jobs onto a cache MISS (different
    radius/sharding key) and re-upload the genome for 50 variants."""
    key = (getattr(fasta, "path", id(fasta)),
           WINDOW_RADIUS if radius is None else radius, str(sharding))
    return key in _DEVICE_GENOME_CACHE or len(table) >= GENOME_RESIDENT_MIN_VARIANTS
GENOME_ROW_WORDS = 128
GENOME_ROW_BYTES = 4 * GENOME_ROW_WORDS


class DeviceGenome:
    """The concatenated genome on the device: ``rows`` is
    ``uint32[n, GENOME_ROW_WORDS]``, byte ``p`` of the concatenation in bits
    ``8 * (p & 3)`` of word ``p >> 2``, the last row all N; ``offsets`` and
    ``lengths`` give each contig's first byte and length. A position on the
    wire is that byte number as one uint32 (:func:`globalize_positions`)."""

    __slots__ = ("rows", "offsets", "lengths")

    def __init__(self, rows, offsets: dict[str, int], lengths: dict[str, int]):
        self.rows = rows
        self.offsets = offsets
        self.lengths = lengths


def device_genome(fasta: FastaReader, radius: int = WINDOW_RADIUS,
                  sharding=None) -> DeviceGenome:
    key = (getattr(fasta, "path", id(fasta)), radius, str(sharding))
    genome, _how = _DEVICE_GENOME_CACHE.get(
        key, lambda: _build_device_genome(fasta, radius, sharding))
    return genome


def device_genome_stats() -> dict:
    """How many reference genomes the device holds, and their bytes
    (``vctpu serve``: ``/v1/status``, ``/v1/warm``)."""
    genomes = [g for _, g in _DEVICE_GENOME_CACHE.items()]
    return {"entries": len(genomes),
            "bytes": sum(int(g.rows.nbytes) for g in genomes)}


def _genome_rows(parts: list[np.ndarray]) -> np.ndarray:
    """``parts`` (uint8 base codes) joined, padded with N to whole rows and
    closed by one row of N — in ONE concatenation, which the word view then
    shares: a second 3.1 GB host copy is what this must not make."""
    total = sum(len(p) for p in parts)
    tail = np.full((-total) % GENOME_ROW_BYTES + GENOME_ROW_BYTES, 4, dtype=np.uint8)
    return np.concatenate(parts + [tail]).view("<u4").reshape(-1, GENOME_ROW_WORDS)


def _build_device_genome(fasta: FastaReader, radius: int,
                         sharding) -> DeviceGenome:
    if not genome_packable(fasta, radius):
        raise ValueError(f"{getattr(fasta, 'path', fasta)}: positions do not fit "
                         "uint32 (callers ask genome_packable first)")
    gap = np.full(2 * radius, 4, dtype=np.uint8)
    parts = [gap]
    offsets: dict[str, int] = {}
    lengths: dict[str, int] = {}
    cur = len(gap)
    for contig in fasta.references:
        seq = encode_seq(fasta.fetch(contig, 0, fasta.get_reference_length(contig)))
        offsets[contig] = cur
        lengths[contig] = len(seq)
        parts.append(seq)
        parts.append(gap)
        cur += len(seq) + len(gap)
    rows = _genome_rows(parts)
    del parts
    # the part of set-up a mesh multiplies by its devices (a replicated
    # sharding sends every chip the whole genome); under obs the span
    # holds the copy to its end, not just the enqueue
    n_dev = len(sharding.device_set) if sharding is not None else 1
    with stage("genome_upload", bytes=int(rows.nbytes), devices=n_dev):
        arr = jax.device_put(rows, sharding) if sharding is not None else jax.device_put(rows)
        if obs.active():
            arr.block_until_ready()
    return DeviceGenome(arr, offsets, lengths)


def globalize_positions(table: VariantTable, genome: DeviceGenome,
                        radius: int = WINDOW_RADIUS) -> np.ndarray:
    """One uint32 per record: the byte position of its anchor in the
    device genome (4 bytes a variant on the wire).

    Unknown contigs and positions past the contig end (wrong reference
    build / truncated FASTA) get :func:`packed_position_fill`, past the
    genome's end, so their windows read all-N — the host gather's safety
    behavior. Positions within ``radius`` past the end still resolve
    idx-wise into the N gap, exactly like the host path.
    """
    # per-contig lookup through the parser's integer contig codes: one
    # dict probe per contig, not one string conversion per record
    codes, uniques, _ = _contig_runs(table, len(table))
    off = np.asarray([genome.offsets.get(c, np.nan) for c in uniques],
                     dtype=np.float64)[codes]  # NaN = unknown contig
    clen = np.asarray([genome.lengths.get(c, np.nan) for c in uniques],
                      dtype=np.float64)[codes]
    pos0 = table.pos.astype(np.int64) - 1
    gpos = pos0 + np.nan_to_num(off, nan=0).astype(np.int64)
    bad = np.isnan(off) | (pos0 < 0) | (pos0 >= np.nan_to_num(clen, nan=-1) + radius)
    gpos[bad] = packed_position_fill(genome)
    return gpos.astype(np.uint32)


def genome_packable(fasta: FastaReader, radius: int = WINDOW_RADIUS) -> bool:
    """Whether every position of the genome, and the fill past its end, fits
    one uint32 — computable from contig lengths alone, BEFORE paying the
    encode + HBM upload (~4.29 Gbp incl. N gaps; hg38 is 3.2)."""
    gap = 2 * radius
    total = gap + sum(fasta.get_reference_length(c) + gap for c in fasta.references)
    n_rows = -(-total // GENOME_ROW_BYTES) + 1
    return n_rows * GENOME_ROW_BYTES < (1 << 32)


def packed_position_fill(genome: DeviceGenome) -> int:
    """The position of padding rows and of records with no place in the
    genome: the first byte past the closing row of N, so the window falls
    in no real row and reads all-N."""
    return int(genome.rows.shape[0]) * GENOME_ROW_BYTES


def windows_from_packed(genome_rows, gpos, radius: int = WINDOW_RADIUS):
    """(N, 2R+1) uint8 windows centered on the uint32 byte positions
    ``gpos``, gathered on device; bytes outside the genome read N=4.

    Traceable — used inside the fused featurize+score program so the window
    tensor never exists host-side. One row index per window, not one index
    per byte: the window's first byte is ``start = gpos - R``, in word
    ``(start >> 2) & 127`` of row ``start >> 9``; that row and its successor
    are fetched whole (the form of an embedding lookup), a barrel shift
    over the lane axis on the bits of the word number brings the window's
    first word to lane 0, a funnel shift of adjacent words by
    ``8 * (start & 3)`` bits brings its first byte to bit 0, and the words
    are split into bytes. Everything after the two lookups is elementwise.
    A position under ``radius`` reads all-N: the genome begins with a gap
    of ``2 * radius`` N, so no real window starts before byte 0.
    """
    import jax.numpy as jnp

    width = 2 * radius + 1
    n_words = -(-(width + 3) // 4) + 1  # the window at any byte alignment, + the funnel's next word
    lane_bits = GENOME_ROW_WORDS.bit_length() - 1
    assert n_words + GENOME_ROW_WORDS - 1 <= 2 * GENOME_ROW_WORDS, "window wider than two genome rows"
    g = gpos.astype(jnp.uint32)
    start = g - jnp.uint32(radius)
    n_real = genome_rows.shape[0] - 1  # the last row is the closing N
    row = (start >> (lane_bits + 2)).astype(jnp.int32)
    valid = (g >= radius) & (row < n_real)
    row = jnp.clip(row, 0, n_real - 1)
    x = jnp.concatenate([genome_rows.at[r].get(mode="promise_in_bounds")
                         for r in (row, row + 1)], axis=1)
    word = (start >> 2) & jnp.uint32(GENOME_ROW_WORDS - 1)
    for bit in reversed(range(lane_bits)):  # barrel shift: 64, 32, ... 1 lanes
        step = 1 << bit
        keep = n_words + step - 1  # what the lower bits' stages may still reach
        take = ((word >> bit) & 1).astype(bool)[:, None]
        x = jnp.where(take, x[:, step:step + keep], x[:, :keep])
    shift = ((start & 3) << 3)[:, None]  # funnel shift: 0, 8, 16 or 24 bits
    lo, hi = x[:, :-1], x[:, 1:]
    words = jnp.where(shift == 0, lo, (lo >> shift) | (hi << ((32 - shift) & 31)))
    octets = (words[:, :, None] >> (8 * jnp.arange(4, dtype=jnp.uint32))) & 0xFF
    win = octets.astype(jnp.uint8).reshape(words.shape[0], -1)[:, :width]
    return jnp.where(valid[:, None], win, jnp.uint8(4))


def _contig_runs(table_or_chrom, n: int):
    """Factorized contig column + contiguous-run bounds (or None).

    Sorted VCFs put each contig in ONE contiguous run, so per-contig work
    can slice row ranges instead of boolean-masking (a mask pass + scatter
    costs ~4 full sweeps of a window tensor at 5M variants). Shared by
    :func:`gather_windows` and :func:`featurize_gather_fused` so the fused
    fast path and its fallback can never disagree on contig handling.
    Returns (codes, uniques, bounds) with bounds None when runs are not
    contiguous (callers fall back to masks).

    Accepts the :class:`VariantTable` itself when available: the native
    scan already factorized CHROM into integer codes, and re-factorizing
    1M Python strings per chunk was ~15% of the streaming score stage's
    GIL-holding glue (the per-chunk pandas factorize on the hot path).
    The derived runs are MEMOIZED on the table — the scoring body asks
    for them up to three times per chunk (window gather, fused
    featurize, the fused native scorer), and re-deriving runs the parser
    already knows was pure repeat work. Native-scan codes are assigned
    in first-appearance order, so the sorted common case skips the
    remap LUT pass entirely (codes returned as-is, zero copies).
    """
    chrom = table_or_chrom
    codes = getattr(table_or_chrom, "chrom_codes", None)
    if codes is not None:
        memo = getattr(table_or_chrom, "_contig_runs_memo", None)
        if memo is not None:
            return memo
        names = table_or_chrom.chrom_names
        change = np.flatnonzero(codes[1:] != codes[:-1]) + 1 if n > 1 \
            else np.empty(0, np.int64)
        starts = np.concatenate([[0], change]).astype(np.int64) if n else \
            np.empty(0, np.int64)
        run_codes = codes[starts] if n else np.empty(0, codes.dtype)
        if len(np.unique(run_codes)) == len(run_codes):
            # each contig appears in exactly one run (the sorted case):
            # remap the dictionary codes to appearance order so callers'
            # enumerate(uniques) indexing matches the mask codes
            uniques = np.asarray([names[c] for c in run_codes], dtype=object)
            bounds = np.concatenate([starts, [n]])
            if np.array_equal(run_codes, np.arange(len(run_codes))):
                # native-scan codes already ARE appearance order (the
                # parser assigns them first-seen): no LUT, no remap copy
                out_codes = codes
            else:
                lut = np.zeros(len(names), dtype=np.int64)
                lut[run_codes] = np.arange(len(run_codes))
                out_codes = lut[codes]
            memo = (out_codes, uniques, bounds)
            try:
                table_or_chrom._contig_runs_memo = memo
            except AttributeError:
                pass  # slotted/frozen table: memo is best-effort
            return memo
        chrom = table_or_chrom.chrom  # unsorted chunk: factorize below
    elif not isinstance(table_or_chrom, np.ndarray) and hasattr(table_or_chrom, "chrom"):
        chrom = table_or_chrom.chrom
    import pandas as pd

    codes, uniques = pd.factorize(np.asarray(chrom), use_na_sentinel=False)
    change = np.flatnonzero(codes[1:] != codes[:-1]) + 1 if n > 1 else np.empty(0, np.int64)
    contiguous = len(change) == len(uniques) - 1
    bounds = np.concatenate([[0], change, [n]]) if contiguous else None
    return codes, uniques, bounds


def gather_windows(table: VariantTable, fasta: FastaReader, radius: int = WINDOW_RADIUS) -> np.ndarray:
    """(N, 2*radius+1) uint8 reference windows centered on each variant anchor.

    One contig-sequence encode per contig, then a vectorized gather — the
    host-side analog of the reference's per-record pyfaidx fetches.
    """
    from variantcalling_tpu import native

    n = len(table)
    out = np.full((n, 2 * radius + 1), 4, dtype=np.uint8)
    codes, uniques, bounds = _contig_runs(table, n)
    contiguous = bounds is not None
    pos0 = table.pos - 1

    def gather_one(seq, sub, target=None):
        rows = native.gather_windows_contig(seq, sub, radius, out=target)
        if rows is None:
            # numpy fallback: padded fancy-index gather; positions beyond
            # the contig (wrong reference build / truncated FASTA) read as
            # N instead of crashing the whole ingest
            padded = np.concatenate([np.full(radius, 4, np.uint8), seq, np.full(radius, 4, np.uint8)])
            idx = (sub + radius)[:, None] + np.arange(-radius, radius + 1)[None, :]
            valid = (idx >= 0) & (idx < len(padded))
            rows = np.where(valid, padded[np.clip(idx, 0, len(padded) - 1)], 4)
        return rows

    for ui, contig in enumerate(uniques):
        if contig not in fasta.references:
            continue
        seq = fasta.fetch_encoded(contig)
        if contiguous:
            lo, hi = int(bounds[ui]), int(bounds[ui + 1])
            target = out[lo:hi]
            rows = gather_one(seq, pos0[lo:hi].astype(np.int64, copy=False), target=target)
            if rows is not target:
                out[lo:hi] = rows  # fallback produced a fresh array
        else:
            m = codes == ui
            out[m] = gather_one(seq, pos0[m].astype(np.int64, copy=False))
    return out


def featurize_gather_fused(table: VariantTable, fasta: FastaReader, alle,
                           flow_order: np.ndarray,
                           radius: int = WINDOW_RADIUS) -> dict | None:
    """The six window-derived DEVICE_FEATURES columns via the fused native
    gather+featurize kernel — the (N, 2r+1) window tensor is never
    materialized (two full sweeps of it saved on the 5M CPU hot path).
    Mirrors :func:`gather_windows`' contig handling exactly: per-contig
    contiguous runs when the VCF is sorted, scatter via masks otherwise,
    contigs missing from the FASTA read as all-N. Returns None when the
    native kernel is unavailable (caller gathers + featurizes separately).
    """
    from variantcalling_tpu import native

    if not native.available():
        return None
    n = len(table)
    outs = (np.empty(n, np.int32), np.empty(n, np.int32), np.empty(n, np.float32),
            np.empty(n, np.int32), np.empty(n, np.int32), np.empty(n, np.int32))
    codes, uniques, bounds = _contig_runs(table, n)
    contiguous = bounds is not None
    pos0 = table.pos - 1
    aux = (alle.is_indel, alle.indel_nuc, alle.ref_code, alle.alt_code, alle.is_snp)
    empty = np.empty(0, dtype=np.uint8)  # missing contig -> every window all-N
    for ui, contig in enumerate(uniques):
        seq = fasta.fetch_encoded(contig) if contig in fasta.references else empty
        if contiguous:
            lo, hi = int(bounds[ui]), int(bounds[ui + 1])
            ok = native.featurize_gather(
                seq, pos0[lo:hi].astype(np.int64, copy=False), radius,
                *(a[lo:hi] for a in aux), flow_order,
                tuple(o[lo:hi] for o in outs))
        else:
            m = codes == ui
            sub_outs = tuple(np.empty(int(m.sum()), o.dtype) for o in outs)
            ok = native.featurize_gather(
                seq, pos0[m].astype(np.int64, copy=False), radius,
                *(a[m] for a in aux), flow_order, sub_outs)
            if ok:
                for o, so in zip(outs, sub_outs):
                    o[m] = so
        if not ok:
            return None
    hl, hn, gc, cy, lm, rm = outs
    return {"hmer_indel_length": hl, "hmer_indel_nuc": hn, "gc_content": gc,
            "cycleskip_status": cy, "left_motif": lm, "right_motif": rm}


@dataclass
class FeatureSet:
    """Named per-variant feature columns + assembly into a (N, F) matrix."""

    columns: dict[str, np.ndarray]
    feature_names: list[str]
    windows: np.ndarray | None = None  # (N, 2*WINDOW_RADIUS+1) uint8 ref context

    def matrix(self, names: list[str] | None = None) -> np.ndarray:
        names = names or self.feature_names
        return np.stack([np.asarray(self.columns[f], dtype=np.float32) for f in names], axis=1)

    def __len__(self) -> int:
        return len(next(iter(self.columns.values())))


def _compute_af(table: VariantTable) -> np.ndarray:
    """Allele fraction per record: FORMAT AD (alt/sum) where present, else INFO AF."""
    info_af = table.info_field("AF", dtype=np.float64).astype(np.float32)
    if table.aux is not None:
        ad1 = table.aux.ad[:, 1]
        tot = np.where(np.isnan(table.aux.ad[:, 2]), 0, table.aux.ad[:, 2])
        alt = np.where(np.isnan(ad1) | (ad1 < 0), 0, ad1)
    else:
        ad = table.format_numeric("AD")
        if ad.shape[1] < 2:
            return info_af
        tot = np.sum(np.where(ad > 0, ad, 0), axis=1)
        alt = np.where(ad[:, 1] > 0, ad[:, 1], 0)
    with np.errstate(invalid="ignore", divide="ignore"):
        ad_af = np.where(tot > 0, alt / np.maximum(tot, 1), np.nan).astype(np.float32)
    return np.where(np.isnan(ad_af), info_af, ad_af)


def device_feature_dict(windows, is_indel, indel_nuc, ref_code, alt_code, is_snp,
                        *, center: int, flow_order: str) -> dict:
    """The window-kernel block, traceable inside any jitted program.

    Single source of truth for the DEVICE_FEATURES columns — featurize()'s
    standalone program and the filter pipeline's fused featurize+score
    program both call this, so train/serve feature parity holds by
    construction.
    """
    gc = fops.gc_content(windows, center, radius=10)
    hmer_len, hmer_nuc = fops.hmer_indel_features(windows, center, is_indel, indel_nuc)
    left_motif, right_motif = fops.motif_codes(windows, center, k=5)
    cyc = fops.cycle_skip_status(windows, center, ref_code, alt_code, is_snp, flow_order=flow_order)
    return {
        "hmer_indel_length": hmer_len,
        "hmer_indel_nuc": hmer_nuc,
        "gc_content": gc,
        "cycleskip_status": cyc,
        "left_motif": left_motif,
        "right_motif": right_motif,
    }


@partial(jax.jit, static_argnames=("center", "flow_order"))
def _device_feature_program(windows, is_indel, indel_nuc, ref_code, alt_code, is_snp,
                            *, center: int, flow_order: str):
    """Jitted standalone wrapper over :func:`device_feature_dict`.

    Module-level so the jit cache persists across featurize() calls — the
    cycle-skip lax.scan in particular must not retrace per call (it costs a
    full XLA compile). Cache key = (padded batch shape, center, flow_order).
    """
    d = device_feature_dict(windows, is_indel, indel_nuc, ref_code, alt_code, is_snp,
                            center=center, flow_order=flow_order)
    return tuple(d[k] for k in DEVICE_FEATURES)


_PAD_MIN = 1 << 10
#: up to here a bucket is a power of two: a whole one costs the device about
#: 2 ms, and its padding is not worth a compiled program
_QUARTER_RUNGS_ABOVE = 1 << 15


def _bucket(n: int) -> int:
    """Padded batch size for ``n`` rows: bounds the distinct compiled shapes.

    The next power of two from ``_PAD_MIN`` up to 32,768 rows; above that
    the next of four rungs an octave (``m * 2**k``, ``m`` in 4..7, each a
    multiple of 8,192), so padding is under a fifth of a dispatch at any
    row count. A function of ``n`` alone.
    """
    b = _PAD_MIN
    while b < n:
        b <<= 1
    if b <= _QUARTER_RUNGS_ABOVE:
        return b
    step = b >> 3  # the octave (b/2, b] in four steps
    return -(-n // step) * step


# feature columns produced ON DEVICE by the window kernels; everything else
# in BASE_FEATURES comes from host-side allele/FORMAT/INFO columns
DEVICE_FEATURES = (
    "hmer_indel_length",
    "hmer_indel_nuc",
    "gc_content",
    "cycleskip_status",
    "left_motif",
    "right_motif",
)


@dataclass
class HostFeatures:
    """Host half of featurization: windows + every non-window column.

    ``names`` is the FULL feature order (host + device columns interleaved
    per BASE_FEATURES); consumers either run the device program to fill the
    device columns (featurize) or fuse them into a larger device program
    (filter_variants' featurize+score fusion). Made with
    ``base_columns=False`` it holds only what Python alone can make —
    ``alle`` is None and ``cols`` has the extra INFO and interval columns —
    for the wire's native fill (:mod:`variantcalling_tpu.wire`), which makes
    the rest from the scan's arrays under the same ``keep_nan``.
    """

    alle: AlleleColumns | None
    windows: np.ndarray  # (N, 2*WINDOW_RADIUS+1) uint8
    cols: dict[str, np.ndarray]  # host columns only
    names: list[str]  # full feature order, incl. DEVICE_FEATURES
    keep_nan: bool = False


def host_featurize(
    table: VariantTable,
    fasta: FastaReader,
    annotate_intervals: dict[str, IntervalSet] | None = None,
    extra_info_fields: list[str] | None = None,
    compute_windows: bool = True,
    keep_nan: bool = False,
    base_columns: bool = True,
) -> HostFeatures:
    """``compute_windows=False`` skips the host window gather — for the
    device-resident-genome scoring path, where windows are gathered in HBM.

    ``keep_nan=True`` preserves NaN for absent QUAL/INFO/FORMAT values
    instead of zero-filling — required when the scoring model carries
    xgboost default_left routing, whose semantics are defined ON the
    missing values (the reference feeds raw NaN into predict_proba).

    ``base_columns=False`` leaves out the allele classification and the 13
    base columns (see :class:`HostFeatures`).
    """
    windows = gather_windows(table, fasta) if compute_windows else None

    def missing(a):
        return a if keep_nan else np.nan_to_num(a, nan=0.0)

    alle, cols = None, {}
    if base_columns:
        alle = classify_alleles(table)
        gts = table.genotypes()
        is_het = (gts[:, 0] != gts[:, 1]) & (gts[:, 1] >= 0)
        gq = table.format_numeric("GQ", max_len=1, missing=np.nan)[:, 0]
        cols = {
            "qual": missing(table.qual),
            "dp": missing(table.info_field("DP")),
            "sor": missing(table.info_field("SOR")),
            "af": missing(_compute_af(table)),
            "gq": missing(gq),
            "is_het": is_het.astype(np.float32),
            "is_snp": alle.is_snp.astype(np.float32),
            "is_indel": alle.is_indel.astype(np.float32),
            "is_ins": alle.is_ins.astype(np.float32),
            "indel_length": alle.indel_length,
            "ref_code": alle.ref_code,
            "alt_code": alle.alt_code,
            "n_alts": alle.n_alts,
        }
    names = list(BASE_FEATURES)

    for f in extra_info_fields or []:
        cols[f] = missing(table.info_field(f)).astype(np.float32)
        names.append(f)

    if annotate_intervals:
        coords = iops.GenomeCoords(
            table.header.contig_lengths
            or {c: fasta.get_reference_length(c) for c in fasta.references}
        )
        gpos = coords.globalize(np.asarray(table.chrom), table.pos - 1)
        for name, iv in annotate_intervals.items():
            gs, ge = coords.globalize_intervals(iv)
            cols[name] = iops.membership(gpos, gs, ge).astype(np.float32)
            names.append(name)

    return HostFeatures(alle=alle, windows=windows, cols=cols, names=names,
                        keep_nan=keep_nan)


def standard_genome_sharding(mesh=None):
    """The ONE sharding every consumer passes to device_genome: replicated
    over ``mesh`` when the caller resolved a >1-device run scoring mesh
    (the filter pipeline's mesh plan), else None — the genome lives on
    the process's default device and the program that reads it runs
    there, on one device. Mesh-plan callers route their possibly-None
    mesh through here unconditionally, so a single-device plan and every
    no-arg consumer agree on the cache key.

    A single-device plan must NOT replicate over the other local devices:
    a program fed one argument committed to N devices runs on all N
    (N-fold redundant work for the jnp strategies), and XLA refuses to
    auto-partition a Mosaic kernel at all — on a four-chip host the
    explicit ``VCTPU_MESH_DEVICES=1`` run and the recovery ladder's dp=1
    restart died with "Mosaic kernels cannot be automatically
    partitioned" until this returned None for them.

    All genome-cache keys include the sharding, so consumers that chose
    shardings independently would split the cache — and the small-job
    guard (_genome_resident_worthwhile) would answer differently
    depending on which consumer ran first. Routing through this helper
    makes the key identical by construction; mesh-plan callers must pass
    the SAME resolved mesh everywhere (FilterContext does).
    """
    from variantcalling_tpu.parallel.mesh import replicated

    return replicated(mesh) if mesh is not None else None


def featurize(
    table: VariantTable,
    fasta: FastaReader,
    annotate_intervals: dict[str, IntervalSet] | None = None,
    flow_order: str = fops.DEFAULT_FLOW_ORDER,
    extra_info_fields: list[str] | None = None,
) -> FeatureSet:
    """Full featurization: BASE_FEATURES + one 0/1 column per annotation interval.

    Window features come from the device-resident genome (one HBM upload
    per FASTA, on-device gather — run_comparison/train_models share the
    filter pipeline's hot-path design); device kernels are jit-compiled
    once per padded batch shape.
    """
    resident = genome_packable(fasta) and _genome_resident_worthwhile(
        table, fasta, sharding=standard_genome_sharding())
    hf = host_featurize(table, fasta, annotate_intervals=annotate_intervals,
                        extra_info_fields=extra_info_fields,
                        compute_windows=not resident)
    if resident:
        return materialize_features(hf, flow_order=flow_order, table=table, fasta=fasta)
    return materialize_features(hf, flow_order=flow_order)


@partial(jax.jit, static_argnames=("center", "flow_order"))
def _device_feature_program_genome(genome_rows, gpos, is_indel, indel_nuc,
                                   ref_code, alt_code, is_snp, *, center: int,
                                   flow_order: str):
    """Standalone window-kernel program over the device-resident genome."""
    windows = windows_from_packed(genome_rows, gpos, radius=center)
    d = device_feature_dict(windows, is_indel, indel_nuc, ref_code, alt_code, is_snp,
                            center=center, flow_order=flow_order)
    return tuple(d[k] for k in DEVICE_FEATURES)


def materialize_features(hf: HostFeatures, flow_order: str = fops.DEFAULT_FLOW_ORDER,
                         table: VariantTable | None = None,
                         fasta: FastaReader | None = None) -> FeatureSet:
    """Run the device window kernels over a HostFeatures batch and merge.

    With host windows absent and (table, fasta) given, windows are gathered
    on device from the resident genome (no host window tensor at all).
    """
    alle, windows = hf.alle, hf.windows
    genome_path = windows is None and table is not None and fasta is not None
    n = len(table) if genome_path else len(windows)
    b = _bucket(n)

    def pad(a, fill=0):
        a = np.asarray(a)
        return np.pad(a, [(0, b - n)] + [(0, 0)] * (a.ndim - 1), constant_values=fill)

    alle_args = (
        pad(alle.is_indel),
        pad(alle.indel_nuc, fill=4),
        pad(alle.ref_code, fill=4),
        pad(alle.alt_code, fill=4),
        pad(alle.is_snp),
    )
    if genome_path:
        genome = device_genome(fasta, sharding=standard_genome_sharding())
        gpos = globalize_positions(table, genome)
        device_out = _device_feature_program_genome(
            genome.rows, pad(gpos, fill=packed_position_fill(genome)), *alle_args,
            center=CENTER, flow_order=flow_order,
        )
    else:
        device_out = _device_feature_program(
            pad(windows, fill=4), *alle_args, center=CENTER, flow_order=flow_order,
        )
    # one bulk fetch for all six outputs (each np.asarray would sync separately)
    fetched = jax.device_get(device_out)
    cols = dict(hf.cols)
    cols.update({k: v[:n] for k, v in zip(DEVICE_FEATURES, fetched)})
    return FeatureSet(columns=cols, feature_names=hf.names, windows=windows)
