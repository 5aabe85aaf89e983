"""The plain reference: what the filter path has to write, in numpy float32.

Imports nothing of the program and reads nothing the program made. From the
text of input records (the benchmark's own callset) and the reference's seed
it recomputes, record by record: the parsed columns, the 41-base window from
the genome, the window features (GC share, homopolymer indel, motif codes,
cycle-skip status by the flow-key definition), the family's score
(``families/<family>.py``: ``score(weights, x, precision)``), and the score
as the output renders it (4 decimals) with its FILTER. ``precision`` below
``f32`` is the control: the same mathematics in the nearest lower precision,
which ``correct`` has to refuse.
"""

from __future__ import annotations

import numpy as np

import fixtures
import lookup

RADIUS = 20  # bases either side of the anchor
N = 4  # base code of "not a base"
FLOW_ORDER = (3, 2, 1, 0)  # TGCA
PASS_THRESHOLD = 0.5
_CODE = {"A": 0, "C": 1, "G": 2, "T": 3}


# -- records ------------------------------------------------------------------

def parse_records(lines: list[bytes]) -> dict:
    """Columns of the given VCF body lines (one sample, first ALT)."""
    n = len(lines)
    out = {k: np.zeros(n, np.float32) for k in
           ("qual", "dp", "sor", "af", "gq", "is_het")}
    chrom, pos, ref, alt = [], np.zeros(n, np.int64), [], []
    for i, ln in enumerate(lines):
        f = ln.decode().split("\t")
        chrom.append(f[0])
        pos[i] = int(f[1])
        ref.append(f[3])
        alt.append(f[4].split(",")[0])
        out["qual"][i] = np.float32(f[5]) if f[5] != "." else 0.0
        info = dict(kv.split("=", 1) for kv in f[7].split(";") if "=" in kv)
        for key, col in (("DP", "dp"), ("SOR", "sor"), ("AF", "af")):
            if key in info:
                out[col][i] = np.float32(info[key])
        sample = dict(zip(f[8].split(":"), f[9].split(":")))
        if sample.get("GQ", ".") != ".":
            out["gq"][i] = np.float32(sample["GQ"])
        gt = sample.get("GT", "./.").replace("|", "/").split("/")
        out["is_het"][i] = float(len(gt) == 2 and gt[1] != "." and gt[0] != gt[1])
    out.update(chrom=chrom, pos=pos, ref=ref, alt=alt)
    return out


def allele_columns(ref: list[str], alt: list[str]) -> dict:
    n = len(ref)
    c = {k: np.zeros(n, np.int32) for k in
         ("is_snp", "is_indel", "is_ins", "indel_length")}
    c.update({k: np.full(n, N, np.int32) for k in ("indel_nuc", "ref_code", "alt_code")})
    for i, (r, a) in enumerate(zip(ref, alt)):
        if len(r) == len(a) == 1:
            c["is_snp"][i] = 1
            c["ref_code"][i] = _CODE.get(r.upper(), N)
            c["alt_code"][i] = _CODE.get(a.upper(), N)
        elif len(r) != len(a):
            c["is_indel"][i] = 1
            long_, short = (a, r) if len(a) > len(r) else (r, a)
            c["is_ins"][i] = int(len(a) > len(r))
            diff = long_[len(short):] if long_.startswith(short) else long_[1:]
            c["indel_length"][i] = abs(len(a) - len(r))
            if len(set(diff.upper())) == 1:
                c["indel_nuc"][i] = _CODE.get(diff[0].upper(), N)
    return c


def windows(body: np.ndarray, contig_index: np.ndarray, pos: np.ndarray) -> np.ndarray:
    """(n, 41) base codes centred on POS; past a contig's ends reads N."""
    idx = (pos - 1)[:, None] + np.arange(-RADIUS, RADIUS + 1)[None, :]
    inside = (idx >= 0) & (idx < len(body))
    base = (body[np.clip(idx, 0, len(body) - 1)] + contig_index[:, None].astype(np.uint8)) & 3
    return np.where(inside, base, N).astype(np.uint8)


# -- window features ----------------------------------------------------------

def gc_content(w: np.ndarray) -> np.ndarray:
    span = w[:, RADIUS - 10: RADIUS + 11]
    gc = ((span == 1) | (span == 2)).sum(axis=1).astype(np.float32)
    return gc / np.maximum((span != N).sum(axis=1), 1).astype(np.float32)


def hmer_indel(w: np.ndarray, is_indel: np.ndarray, indel_nuc: np.ndarray):
    """Length and base of the reference homopolymer right of the anchor, for
    an indel of that one base; 0 and N otherwise."""
    right = w[:, RADIUS + 1:]
    same = right == right[:, :1]
    run = np.where(same.all(axis=1), right.shape[1], np.argmin(same, axis=1))
    hmer = (is_indel == 1) & (indel_nuc < N) & (indel_nuc == right[:, 0])
    return (np.where(hmer, run, 0).astype(np.int32),
            np.where(hmer, indel_nuc, N).astype(np.int32))


def motif_codes(w: np.ndarray, k: int = 5):
    powers = 5 ** np.arange(k - 1, -1, -1)
    return ((w[:, RADIUS - k: RADIUS] * powers).sum(axis=1).astype(np.int32),
            (w[:, RADIUS + 1: RADIUS + 1 + k] * powers).sum(axis=1).astype(np.int32))


def flow_key(hap: np.ndarray) -> np.ndarray:
    """(n, flows) homopolymer length read at each flow of the cyclic flow
    order until every haplotype is used up (0 past its end); the first N ends
    a haplotype."""
    n, length = hap.shape
    is_n = hap == N
    eff = np.where(is_n.any(axis=1), np.argmax(is_n, axis=1), length)
    ptr = np.zeros(n, np.int64)
    rows = np.arange(n)
    key = []
    for t in range(4 * length):
        base = FLOW_ORDER[t % 4]
        run = np.zeros(n, np.int64)
        going = ptr < eff
        while going.any():
            at = np.minimum(ptr + run, length - 1)
            going = going & (ptr + run < eff) & (hap[rows, at] == base)
            run += going
        key.append(np.where(ptr < eff, run, -1))
        ptr = ptr + run
    return np.stack(key, axis=1)


def cycle_skip(w: np.ndarray, ref_code, alt_code, is_snp, context: int = 4):
    """0 no skip, 1 possible (same flow count, another zero pattern), 2 skip
    (the alternate haplotype needs another number of flows), -1 not a SNP."""
    left, right = w[:, RADIUS - context: RADIUS], w[:, RADIUS + 1: RADIUS + 1 + context]
    keys = [flow_key(np.concatenate([left, c[:, None].astype(np.uint8), right], axis=1))
            for c in (ref_code, alt_code)]
    flows = [(k >= 0).sum(axis=1) for k in keys]
    pattern = (keys[0] > 0) != (keys[1] > 0)
    status = np.where(flows[0] != flows[1], 2, np.where(pattern.any(axis=1), 1, 0))
    return np.where(is_snp == 1, status, -1).astype(np.int32)


def feature_matrix(rec: dict, body: np.ndarray, n_contigs: int) -> np.ndarray:
    """(n, 19) float32 in ``fixtures.RUN_FEATURES`` order."""
    names = {c: i for i, c in enumerate(fixtures.contig_names(n_contigs))}
    ci = np.array([names[c] for c in rec["chrom"]], np.int64)
    al = allele_columns(rec["ref"], rec["alt"])
    w = windows(body, ci, rec["pos"])
    hlen, hnuc = hmer_indel(w, al["is_indel"], al["indel_nuc"])
    lm, rm = motif_codes(w)
    cols = dict(rec, **al, hmer_indel_length=hlen, hmer_indel_nuc=hnuc,
                gc_content=gc_content(w), left_motif=lm, right_motif=rm,
                cycleskip_status=cycle_skip(w, al["ref_code"], al["alt_code"], al["is_snp"]),
                n_alts=np.ones(len(ci), np.int32))
    return np.stack([np.asarray(cols[f], np.float32) for f in fixtures.RUN_FEATURES], axis=1)


# -- precisions ---------------------------------------------------------------

def _bf16(a):
    import ml_dtypes

    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


def _int8(a):
    a = np.asarray(a, np.float32)
    scale = max(float(np.abs(a).max()), 1e-30) / 127.0
    return (np.clip(np.round(a / scale), -127, 127) * scale).astype(np.float32)


QUANTIZE = {"f32": lambda a: np.asarray(a, np.float32), "bf16": _bf16, "int8": _int8}


# -- the comparison -----------------------------------------------------------

def output_fields(lines: list[bytes]):
    """(CHROM POS REF ALT core, TREE_SCORE, FILTER) of output body lines."""
    core, score, filt = [], np.full(len(lines), np.nan), []
    for i, ln in enumerate(lines):
        f = ln.split(b"\t")
        core.append((f[0], f[1], f[3], f[4]))
        filt.append(f[6])
        for kv in f[7].split(b";"):
            if kv.startswith(b"TREE_SCORE="):
                score[i] = float(kv[11:])
    return core, score, filt


def compare(in_lines: list[bytes], out_lines: list[bytes], family: str, weights: dict,
            body: np.ndarray, n_contigs: int, score_limit: float,
            control: str | None = None) -> dict:
    """The numbers ``correct`` is decided by, for one sample of records.

    ``score_gap_max``: widest |TREE_SCORE written - reference score at 4
    decimals|. ``filter_wrong``: records whose FILTER is not PASS/LOW_SCORE by
    the reference's score, those within ``score_limit`` of the threshold left
    out. ``records_wrong``: output records whose CHROM/POS/REF/ALT are not the
    input's, or that carry no score. With ``control`` the reference computed
    in that precision stands in the program's place."""
    rec = parse_records(in_lines)
    x = feature_matrix(rec, body, n_contigs)
    score = lookup.load("families", family).score
    want = score(weights, x)
    in_core = [tuple(ln.split(b"\t")[i] for i in (0, 1, 3, 4)) for ln in in_lines]
    if control is None:
        core, got, filt = output_fields(out_lines)
    else:
        got = np.round(score(weights, x, precision=control), 4).astype(np.float64)
        core = in_core
        filt = [b"LOW_SCORE" if s < PASS_THRESHOLD else b"PASS" for s in got]
    records_wrong = sum(a != b for a, b in zip(core, in_core)) + int(np.isnan(got).sum()) \
        + abs(len(core) - len(in_core))
    gap = np.abs(np.nan_to_num(got, nan=9.0) - np.round(want, 4).astype(np.float64))
    want_filter = np.where(want < PASS_THRESHOLD, b"LOW_SCORE", b"PASS")
    clear = np.abs(want.astype(np.float64) - PASS_THRESHOLD) > score_limit
    filter_wrong = int(sum(bool(c) and f != w for c, f, w in zip(clear, filt, want_filter)))
    return {"records": len(in_lines), "score_gap_max": float(gap.max()) if len(gap) else 0.0,
            "score_gap_mean": float(gap.mean()) if len(gap) else 0.0,
            "filter_wrong": filter_wrong, "records_wrong": int(records_wrong)}
