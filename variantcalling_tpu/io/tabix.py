"""Tabix (.tbi) index writer for BGZF-compressed VCF/BED.

The reference indexes every compressed artifact by shelling out to
``tabix`` (bash/index_vcf_file.sh, compress_gvcf.py:214). This module
builds the index in-process over the framework's own BGZF layer, so
written ``.vcf.gz`` files remain drop-in consumable by htslib tools
(bcftools/IGV expect a sibling ``.tbi``).

Format per the tabix spec (SAMv1/tabix.pdf): BGZF-wrapped payload of
UCSC-binned chunk lists + a 16kb linear index, virtual file offsets =
(compressed block offset << 16) | in-block offset.
"""

from __future__ import annotations

import struct
import zlib
from dataclasses import dataclass

import numpy as np

from variantcalling_tpu import obs
from variantcalling_tpu.io.bgzf import (BGZF_EOF, MAX_BLOCK_DATA, BgzfWriter,
                                        compress_block, scan_block_spans)

TBI_MAGIC = b"TBI\x01"
FMT_VCF = 2
FMT_BED = 0x10000  # generic, 0-based half-open
LINEAR_SHIFT = 14


def reg2bin(beg: int, end: int) -> int:
    """UCSC binning: smallest bin fully containing [beg, end) (0-based)."""
    end -= 1
    if beg >> 14 == end >> 14:
        return ((1 << 15) - 1) // 7 + (beg >> 14)
    if beg >> 17 == end >> 17:
        return ((1 << 12) - 1) // 7 + (beg >> 17)
    if beg >> 20 == end >> 20:
        return ((1 << 9) - 1) // 7 + (beg >> 20)
    if beg >> 23 == end >> 23:
        return ((1 << 6) - 1) // 7 + (beg >> 23)
    if beg >> 26 == end >> 26:
        return ((1 << 3) - 1) // 7 + (beg >> 26)
    return 0


def _iter_bgzf_blocks(path: str):
    """Yield (compressed_offset, uncompressed_bytes) per BGZF block."""
    with open(path, "rb") as fh:
        data = fh.read()
    off = 0
    n = len(data)
    while off < n:
        if data[off : off + 2] != b"\x1f\x8b":
            raise ValueError(f"{path}: not BGZF at offset {off}")
        xlen = struct.unpack_from("<H", data, off + 10)[0]
        xoff = off + 12
        bsize = None
        while xoff < off + 12 + xlen:
            si1, si2, slen = data[xoff], data[xoff + 1], struct.unpack_from("<H", data, xoff + 2)[0]
            if si1 == 0x42 and si2 == 0x43:
                bsize = struct.unpack_from("<H", data, xoff + 4)[0] + 1
            xoff += 4 + slen
        if bsize is None:
            raise ValueError(f"{path}: missing BC subfield at offset {off}")
        payload = data[off + 12 + xlen : off + bsize - 8]
        yield off, zlib.decompress(payload, wbits=-15)
        off += bsize


class _RefIndex:
    def __init__(self):
        self.bins: dict[int, list[tuple[int, int]]] = {}
        self.linear: dict[int, int] = {}
        self.records = 0

    def add(self, beg: int, end: int, v_start: int, v_end: int) -> None:
        self.records += 1
        b = reg2bin(beg, end)
        chunks = self.bins.setdefault(b, [])
        # merge adjacent chunks (htslib does the same compaction)
        if chunks and chunks[-1][1] >= v_start:
            chunks[-1] = (chunks[-1][0], v_end)
        else:
            chunks.append((v_start, v_end))
        for w in range(beg >> LINEAR_SHIFT, ((max(end, beg + 1) - 1) >> LINEAR_SHIFT) + 1):
            if w not in self.linear or v_start < self.linear[w]:
                self.linear[w] = v_start


def build_tabix_index(
    path: str,
    preset: int = FMT_VCF,
    col_seq: int = 1,
    col_beg: int = 2,
    col_end: int = 0,
    meta_char: str = "#",
) -> str:
    """Build ``<path>.tbi`` for a BGZF VCF/BED; returns the index path.

    Record spans: VCF preset uses POS .. POS+len(REF); BED uses cols 2/3.
    Counter ``tabix.records`` (live only under obs): the records indexed.
    """
    names: list[str] = []
    refs: dict[str, _RefIndex] = {}
    # working buffer + segment map: segments[k] = (buf_index, coff, uoff0)
    # means buf[buf_index:] (until the next segment) lives in the block at
    # compressed offset coff, starting at in-block offset uoff0
    buf = b""
    segments: list[tuple[int, int, int]] = []

    def voffset(i: int) -> int:
        k = len(segments) - 1
        while k > 0 and segments[k][0] > i:
            k -= 1
        buf_index, coff, uoff0 = segments[k]
        return (coff << 16) | (i - buf_index + uoff0)

    for coff, chunk in _iter_bgzf_blocks(path):
        segments.append((len(buf), coff, 0))
        buf += chunk
        pos = 0
        while True:
            nl = buf.find(b"\n", pos)
            if nl < 0:
                break
            _index_line(
                buf[pos:nl], names, refs, voffset(pos), voffset(nl + 1) if nl + 1 < len(buf) else ((coff << 16) | len(chunk)),
                preset, col_seq, col_beg, col_end, meta_char,
            )
            pos = nl + 1
        # drop consumed bytes; rebase surviving segments
        if pos:
            buf = buf[pos:]
            kept = [(bi - pos, c, u) for bi, c, u in segments if bi >= pos]
            # the segment the pointer landed inside survives with shifted uoff
            inside = [(bi, c, u) for bi, c, u in segments if bi < pos]
            if inside:
                bi, c, u = inside[-1]
                kept.insert(0, (0, c, u + (pos - bi)))
            segments = kept
    out = path + ".tbi"
    _write_tbi(out, names, refs, preset, col_seq, col_beg, col_end, meta_char)
    obs.counter("tabix.records").add(sum(r.records for r in refs.values()))
    return out


def _index_line(line, names, refs, v_start, v_end, preset, col_seq, col_beg, col_end, meta_char):
    if not line or line.startswith(meta_char.encode()):
        return
    fields = line.split(b"\t")
    try:
        chrom = fields[col_seq - 1].decode()
        beg = int(fields[col_beg - 1])
    except (IndexError, ValueError):
        return
    if preset == FMT_VCF:
        beg -= 1  # VCF is 1-based
        ref_allele = fields[3] if len(fields) > 3 else b"N"
        end = beg + max(len(ref_allele), 1)
    else:
        end = int(fields[col_end - 1]) if col_end and len(fields) >= col_end else beg + 1
    if chrom not in refs:
        names.append(chrom)
        refs[chrom] = _RefIndex()
    refs[chrom].add(beg, end, v_start, v_end)


def _tbi_header(names, preset=FMT_VCF, col_seq=1, col_beg=2, col_end=0, meta_char="#") -> bytes:
    """The payload up to the first contig: magic, format block, names."""
    nm = b"".join(n.encode() + b"\x00" for n in names)
    return (TBI_MAGIC + struct.pack("<i", len(names))
            + struct.pack("<6i", preset, col_seq, col_beg, col_end, ord(meta_char), 0)
            + struct.pack("<i", len(nm)) + nm)


def _write_tbi(out, names, refs, preset, col_seq, col_beg, col_end, meta_char):
    payload = bytearray(_tbi_header(names, preset, col_seq, col_beg, col_end, meta_char))
    for name in names:
        ref = refs[name]
        payload += struct.pack("<i", len(ref.bins))
        for b, chunks in sorted(ref.bins.items()):
            payload += struct.pack("<Ii", b, len(chunks))
            for s, e in chunks:
                payload += struct.pack("<QQ", s, e)
        if ref.linear:
            n_intv = max(ref.linear) + 1
            ioff = np.zeros(n_intv, dtype=np.uint64)
            prev = 0
            for w in range(n_intv):
                if w in ref.linear:
                    prev = ref.linear[w]
                ioff[w] = prev
            payload += struct.pack("<i", n_intv) + ioff.tobytes()
        else:
            payload += struct.pack("<i", 0)
    with open(out, "wb") as fh:
        data = bytes(payload)
        for i in range(0, max(len(data), 1), 65280):
            fh.write(compress_block(data[i : i + 65280]))
        fh.write(BGZF_EOF)


def write_indexed_vcf(path: str, write_fn) -> str:
    """Helper: write a BGZF VCF via ``write_fn(file_like)`` then index it."""
    with BgzfWriter(path) as fh:
        write_fn(fh)
    return build_tabix_index(path)


# -------------------------------------------------- the index of a run ---
#
# A run that writes a ``.vcf.gz`` itself knows everything the second pass
# of :func:`build_tabix_index` would find out: each record's contig, POS
# and len(REF) from the parser, its bytes from the renderer, and the
# members from the compressor, which frames the text into consecutive
# MAX_BLOCK_DATA payloads whatever the chunk borders are. So the ``.tbi``
# is gathered in two halves, :func:`chunk_index_facts` on the workers that
# render a chunk (native/src/vctpu_tabix.cc) and :class:`StreamedIndex`
# where the chunks pass in file order, and comes out byte for byte
# (inflated) what the second pass writes, which stays the oracle of
# tests/unit/test_tabix_streamed.py.


@dataclass
class ChunkIndexFacts:
    """What one rendered chunk alone says of the index: record numbers
    within the chunk, text offsets from the chunk's first byte."""

    names: list[str]  # the chunk's contigs, in order, none twice
    first_beg: int  # its first and last record's 0-based start
    last_beg: int
    ends: np.ndarray  # (n,) where each record's line ends in the body
    run_first: np.ndarray  # first record of each run of one contig and bin
    run_contig: np.ndarray  # ... its contig, an index into ``names``
    run_bin: np.ndarray
    win_contig: np.ndarray  # the 16 kb windows the chunk touches ...
    win: np.ndarray
    win_start: np.ndarray  # ... and where the first record over each starts


def chunk_index_facts(body, table) -> ChunkIndexFacts | None:
    """The worker's half: ``table``'s records as :func:`_index_line` reads
    them, laid on the lines of the rendered ``body`` (what is written, so
    whichever renderer made it), in one native pass and never a Python
    step a record. None where the chunk cannot vouch for the index and
    the second pass has to: a table without the native scan's columns, a
    body that is not one line a record, records out of order (a position
    before the one before it, a contig twice), a start before 0, a name
    the scan's 64-byte dictionary cut short."""
    from variantcalling_tpu import native

    codes, aux = table.chrom_codes, table.aux
    if codes is None or aux is None or "ref_len" not in aux.alle or not len(table):
        return None
    got = native.tabix_chunk_facts(body, codes, table.pos, aux.alle["ref_len"])
    if got is None:
        return None
    ends, contig_first, run_first, run_contig, run_bin, win_contig, win, win_start = got
    names = [str(c) for c in table.chrom_names[codes[contig_first]]]
    if len(set(names)) != len(names) or max(len(c.encode()) for c in names) >= 63:
        return None
    return ChunkIndexFacts(
        names=names, first_beg=int(table.pos[0]) - 1, last_beg=int(table.pos[-1]) - 1,
        ends=ends, run_first=run_first, run_contig=run_contig, run_bin=run_bin,
        win_contig=win_contig, win=win, win_start=win_start)


class StreamedIndex:
    """The ordered half: chunks' facts and the compressor's members in
    file order, then the ``.tbi``. One thread at a time calls it (the
    committer before the pipeline starts and after it ends, the compress
    stage between). ``complete`` turns False, for good, once something
    passed that the facts do not cover; the caller then indexes the
    committed file by the second pass."""

    def __init__(self):
        self.complete = True
        self.records = 0
        self._contigs: dict[str, int] = {}  # name -> number, by first appearance
        self._last_beg = 0
        self._runs: list[tuple] = []  # a chunk: (contig, bin, text start, text end)
        self._wins: list[tuple] = []  # a chunk: (contig, window, text start)
        # (compressed offset, payload bytes) of each member that holds text
        self._members: list[tuple[int, int]] = []
        self._file_bytes = 0

    def add_blocks(self, blob) -> None:
        """What ``BgzfChunkCompressor.add`` / ``finish`` returned, as written."""
        if not self.complete or not blob:
            return
        spans = scan_block_spans(blob)
        if spans is None:
            self.complete = False
            return
        self._members += [(self._file_bytes + off, isize)
                          for off, _bsize, isize in spans if isize]  # not the EOF member
        self._file_bytes += len(blob)

    def add_chunk(self, facts: ChunkIndexFacts | None, base: int) -> None:
        """A chunk whose body starts at text offset ``base`` (the header's
        bytes come first). None: a body with no facts (a cached one)."""
        if not self.complete:
            return
        if facts is None:
            self.complete = False
            return
        contig = np.empty(len(facts.names), dtype=np.int64)
        for j, name in enumerate(facts.names):
            known = self._contigs.get(name)
            if known is None:
                known = self._contigs[name] = len(self._contigs)
            elif j or known != len(self._contigs) - 1 or facts.first_beg < self._last_beg:
                self.complete = False  # a contig again, or a position going back
                return
            contig[j] = known
        self._last_beg = facts.last_beg
        ends = facts.ends
        # a record that ends with its member ends at (member << 16 | payload)
        # and the next starts at (next member << 16): no chunk of a bin
        # crosses that, so a run is cut where a record starts a member
        cut = np.flatnonzero((ends[:-1] + base) % MAX_BLOCK_DATA == 0) + 1
        first = np.union1d(facts.run_first, cut)
        run = np.searchsorted(facts.run_first, first, "right") - 1
        last = np.append(first[1:], len(ends)) - 1
        self._runs.append((contig[facts.run_contig[run]], facts.run_bin[run],
                           base + np.where(first > 0, ends[first - 1], 0),
                           base + ends[last]))
        self._wins.append((contig[facts.win_contig], facts.win, base + facts.win_start))
        self.records += len(ends)

    def write(self, path: str) -> bool:
        """Write ``path`` (a ``.tbi``) and say so; False, and nothing
        written, where the members are not consecutive MAX_BLOCK_DATA
        payloads that hold every record."""
        coff, sizes = np.array(self._members, dtype=np.int64).reshape(-1, 2).T
        runs = [np.concatenate(c) for c in zip(*self._runs)] or [np.empty(0, np.int64)] * 4
        contig, bins, u_start, u_end = runs
        if (not self.complete or np.any(sizes[:-1] != MAX_BLOCK_DATA)
                or (len(u_end) and u_end[-1] > sizes.sum())):
            return False

        def voffset(u, is_end):
            # an end is named in the member that holds its newline
            k = (u - is_end) // MAX_BLOCK_DATA
            return ((coff[k] << 16) | (u - k * MAX_BLOCK_DATA)).astype("<u8")

        # neighbours of one bin on either side of a chunk border are one
        # chunk of the index, as _RefIndex.add merges them
        joined = np.zeros(len(bins), dtype=bool)
        joined[1:] = ((contig[1:] == contig[:-1]) & (bins[1:] == bins[:-1])
                      & (u_start[1:] == u_end[:-1]) & (u_end[:-1] % MAX_BLOCK_DATA != 0))
        head = np.flatnonzero(~joined)
        tail = np.append(head[1:], len(bins)) - 1
        contig, bins = contig[head], bins[head]
        v_start, v_end = voffset(u_start[head], 0), voffset(u_end[tail], 1)
        order = np.lexsort((bins, contig))  # stable: file order within a bin
        contig, bins, v_start, v_end = contig[order], bins[order], v_start[order], v_end[order]
        wins = [np.concatenate(c) for c in zip(*self._wins)] or [np.empty(0, np.int64)] * 3
        win_contig, win, win_start = wins
        parts = [_tbi_header(list(self._contigs))]
        for number in range(len(self._contigs)):
            lo, hi = np.searchsorted(contig, [number, number + 1])
            parts.append(_bins_payload(bins[lo:hi], v_start[lo:hi], v_end[lo:hi]))
            mine = win_contig == number
            parts.append(_linear_payload(win[mine], voffset(win_start[mine], 0)))
        with BgzfWriter(path) as fh:
            fh.write(b"".join(parts))
        return True


def _bins_payload(bins, v_start, v_end) -> bytes:
    """One contig's ``n_bin`` and bins, from its chunks sorted by bin:
    32-bit words, a bin's two before its chunks' four each."""
    n = len(bins)
    new = np.ones(n, dtype=bool)
    new[1:] = bins[1:] != bins[:-1]
    first = np.flatnonzero(new)
    words = np.empty(1 + 2 * len(first) + 4 * n, dtype="<u4")
    words[0] = len(first)
    at = 1 + 2 * np.arange(len(first)) + 4 * first
    words[at] = bins[first]
    words[at + 1] = np.diff(np.append(first, n))
    at = 3 + 2 * (np.cumsum(new) - 1) + 4 * np.arange(n)
    for k, half in enumerate((v_start & 0xFFFFFFFF, v_start >> 32, v_end & 0xFFFFFFFF, v_end >> 32)):
        words[at + k] = half
    return words.tobytes()


def _linear_payload(win, v_start) -> bytes:
    """One contig's ``n_intv`` and linear index: each window's first
    record in file order, a window no record touches filled forward."""
    uniq, where = np.unique(win, return_index=True)  # first appearances
    at = np.searchsorted(uniq, np.arange(uniq[-1] + 1), "right") - 1
    ioff = np.where(at >= 0, v_start[where][at], 0).astype("<u8")
    return struct.pack("<i", len(ioff)) + ioff.tobytes()


# ---------------------------------------------------------------- reader ---


def _reg2bins(beg: int, end: int) -> list[int]:
    """All bins overlapping [beg, end) (tabix spec reg2bins)."""
    bins = [0]
    end -= 1
    for shift, base in ((26, 1), (23, 9), (20, 73), (17, 585), (14, 4681)):
        bins.extend(range(base + (beg >> shift), base + (end >> shift) + 1))
    return bins


class TabixIndex:
    """Parsed .tbi: per-contig bins/chunks + linear index, query support."""

    def __init__(self, names, bins, linear, preset, col_seq, col_beg, col_end, meta_char):
        self.names = names
        self.bins = bins  # name -> {bin: [(v_start, v_end)]}
        self.linear = linear  # name -> np.uint64 array
        self.preset = preset
        self.col_seq, self.col_beg, self.col_end = col_seq, col_beg, col_end
        self.meta_char = meta_char

    @staticmethod
    def load(path: str) -> "TabixIndex":
        chunks_data = b"".join(chunk for _, chunk in _iter_bgzf_blocks(path))
        if chunks_data[:4] != TBI_MAGIC:
            raise ValueError(f"{path}: not a TBI index")
        off = 4
        (n_ref,) = struct.unpack_from("<i", chunks_data, off)
        off += 4
        preset, col_seq, col_beg, col_end, meta, _skip = struct.unpack_from("<6i", chunks_data, off)
        off += 24
        (l_nm,) = struct.unpack_from("<i", chunks_data, off)
        off += 4
        names = chunks_data[off : off + l_nm].rstrip(b"\x00").split(b"\x00")
        names = [n.decode() for n in names]
        off += l_nm
        bins: dict[str, dict[int, list[tuple[int, int]]]] = {}
        linear: dict[str, np.ndarray] = {}
        for name in names:
            (n_bin,) = struct.unpack_from("<i", chunks_data, off)
            off += 4
            b: dict[int, list[tuple[int, int]]] = {}
            for _ in range(n_bin):
                bin_id, n_chunk = struct.unpack_from("<Ii", chunks_data, off)
                off += 8
                cs = []
                for _ in range(n_chunk):
                    s, e = struct.unpack_from("<QQ", chunks_data, off)
                    off += 16
                    cs.append((s, e))
                b[bin_id] = cs
            (n_intv,) = struct.unpack_from("<i", chunks_data, off)
            off += 4
            linear[name] = np.frombuffer(chunks_data, dtype=np.uint64, count=n_intv, offset=off).copy()
            off += 8 * n_intv
            bins[name] = b
        return TabixIndex(names, bins, linear, preset, col_seq, col_beg, col_end, chr(meta))

    def query_chunks(self, chrom: str, beg: int, end: int) -> list[tuple[int, int]]:
        """Candidate (v_start, v_end) chunks for 0-based [beg, end)."""
        if chrom not in self.bins:
            return []
        min_off = 0
        lin = self.linear.get(chrom)
        if lin is not None and len(lin) and (beg >> LINEAR_SHIFT) < len(lin):
            min_off = int(lin[beg >> LINEAR_SHIFT])
        out = []
        for b in _reg2bins(beg, end):
            for s, e in self.bins[chrom].get(b, []):
                if e > min_off:
                    out.append((max(s, min_off), e))
        out.sort()
        # merge overlapping chunk ranges so no line is read (or yielded) twice
        merged: list[tuple[int, int]] = []
        for s, e in out:
            if merged and s <= merged[-1][1]:
                merged[-1] = (merged[-1][0], max(merged[-1][1], e))
            else:
                merged.append((s, e))
        return merged


def read_region_lines(vcf_path: str, chrom: str, beg: int, end: int, index: TabixIndex | None = None):
    """Record lines overlapping 0-based [beg, end), via the .tbi index.

    Seeks straight to candidate BGZF blocks (virtual offsets), so a region
    read touches only the blocks that cover it.
    """
    index = index or TabixIndex.load(vcf_path + ".tbi")
    chunks = index.query_chunks(chrom, beg, end)
    if not chunks:
        return
    with open(vcf_path, "rb") as fh:
        data = fh.read()

    def inflate_block(coff: int) -> tuple[bytes, int]:
        xlen = struct.unpack_from("<H", data, coff + 10)[0]
        xoff = coff + 12
        bsize = None
        while xoff < coff + 12 + xlen:
            si1, si2, slen = data[xoff], data[xoff + 1], struct.unpack_from("<H", data, xoff + 2)[0]
            if si1 == 0x42 and si2 == 0x43:
                bsize = struct.unpack_from("<H", data, xoff + 4)[0] + 1
            xoff += 4 + slen
        return zlib.decompress(data[coff + 12 + xlen : coff + bsize - 8], wbits=-15), coff + bsize

    cache: dict[int, tuple[bytes, int]] = {}
    for v_start, v_end in chunks:
        coff, uoff = v_start >> 16, v_start & 0xFFFF
        end_coff, end_uoff = v_end >> 16, v_end & 0xFFFF
        text = bytearray()
        while True:
            if coff not in cache:
                cache[coff] = inflate_block(coff)
            chunk_data, next_coff = cache[coff]
            stop = end_uoff if coff == end_coff else len(chunk_data)
            text += chunk_data[uoff:stop]
            if coff == end_coff or next_coff >= len(data):
                break
            coff, uoff = next_coff, 0
        for line in bytes(text).split(b"\n"):
            if not line or line.startswith(index.meta_char.encode()):
                continue
            fields = line.split(b"\t")
            try:
                c = fields[index.col_seq - 1].decode()
                p = int(fields[index.col_beg - 1])
            except (IndexError, ValueError):
                continue
            if index.preset == FMT_VCF:
                rb = p - 1
                re_ = rb + max(len(fields[3]) if len(fields) > 3 else 1, 1)
            else:
                rb = p
                re_ = int(fields[index.col_end - 1]) if index.col_end else rb + 1
            if c == chrom and rb < end and re_ > beg:
                yield line.decode()
