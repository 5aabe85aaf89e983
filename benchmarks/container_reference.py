"""The container of a ``.vcf.gz`` deployment, from the specifications alone
(SAMv1 section 4.1 "The BGZF compression format", tabix.pdf): a plain BGZF
writer, a validator of a written ``.vcf.gz``, the size its text takes at the
stated level, and a reader of its ``.tbi``. Standard library only (``zlib``,
``gzip``, ``struct``; ``os`` and ``random`` beside them); imports nothing of
the program, so what the program writes is held to the formats and not to
its own codec.

The scores and FILTERs of the records are ``reference.compare``'s, on the
inflated text; this file judges the container.
"""

from __future__ import annotations

import gzip
import os
import random
import struct
import zlib

PAYLOAD = 65280  # htslib: uncompressed bytes a member
LEVEL = 6
MAX_PAYLOAD = 65536  # the specification: a member inflates to at most 64 KiB
#: the 28-byte empty member that ends a BGZF file (SAMv1 4.1.2)
EOF = bytes.fromhex("1f8b08040000000000ff0600424302001b0003000000000000000000")
TBI_MAGIC = b"TBI\x01"
VCF_PRESET = (2, 1, 2, 0, ord("#"), 0)  # format, col_seq, col_beg, col_end, meta, skip
WINDOW_SHIFT = 14  # the linear index's 16,384-base windows


# -- (a) the writer -----------------------------------------------------------

def member(data: bytes, level: int = LEVEL) -> bytes:
    """One BGZF member: a gzip member whose extra field holds ``BC``, length
    2, and the member's whole size less one."""
    co = zlib.compressobj(level, zlib.DEFLATED, -15)
    deflated = co.compress(data) + co.flush()
    size = 12 + 6 + len(deflated) + 8
    return (b"\x1f\x8b\x08\x04" + struct.pack("<IBBH", 0, 0, 0xFF, 6)
            + b"BC" + struct.pack("<HH", 2, size - 1) + deflated
            + struct.pack("<II", zlib.crc32(data), len(data)))


def compress_file(plain: str, out: str, level: int = LEVEL,
                  payload: int = PAYLOAD) -> int:
    """``plain`` as BGZF at ``out`` (members of ``payload`` bytes, then the
    EOF member); the bytes written."""
    n = 0
    with open(plain, "rb") as src, open(out, "wb") as dst:
        while True:
            data = src.read(payload)
            if not data:
                break
            n += dst.write(member(data, level))
        n += dst.write(EOF)
    return n


# -- (b) the validator --------------------------------------------------------

def frame(data: bytes, off: int) -> tuple[int, int]:
    """``(length of the extra field, whole size)`` of the member at ``off``,
    by its ``BC`` subfield. Raises ``ValueError`` where the member is not
    framed as the specification says."""
    n = len(data)
    if n - off < 18 or data[off:off + 4] != b"\x1f\x8b\x08\x04":
        raise ValueError(f"no gzip member with an extra field at byte {off}")
    xlen = struct.unpack_from("<H", data, off + 10)[0]
    x, xend, size = off + 12, off + 12 + xlen, None
    while x + 4 <= xend <= n:
        slen = struct.unpack_from("<H", data, x + 2)[0]
        if data[x:x + 2] == b"BC" and slen == 2:
            size = struct.unpack_from("<H", data, x + 4)[0] + 1
        x += 4 + slen
    if size is None:
        raise ValueError(f"member at byte {off} has no BC subfield")
    if off + size > n or size < 12 + xlen + 8:
        raise ValueError(f"member at byte {off} says {size} bytes, {n - off} are left")
    return xlen, size


def members(data: bytes) -> list[tuple[int, int, int]]:
    """``(offset, size, inflated size)`` of every member of a BGZF file, by
    its framing alone."""
    out, off = [], 0
    while off < len(data):
        _, size = frame(data, off)
        isize = struct.unpack_from("<I", data, off + size - 4)[0]
        if isize > MAX_PAYLOAD:
            raise ValueError(f"member at byte {off} inflates to {isize} bytes, "
                             f"over {MAX_PAYLOAD}")
        out.append((off, size, isize))
        off += size
    return out


def inflate_member(data: bytes, off: int) -> tuple[bytes, int]:
    """The text of the member at ``off`` (one ``zlib`` inflate, its CRC and
    length checked) and the offset of the next."""
    xlen, size = frame(data, off)
    text = zlib.decompress(data[off + 12 + xlen:off + size - 8], wbits=-15)
    crc, isize = struct.unpack_from("<II", data, off + size - 8)
    if zlib.crc32(text) != crc or len(text) != isize:
        raise ValueError(f"member at byte {off}: CRC or length does not match")
    return text, off + size


def validate_container(path: str) -> dict:
    """A written ``.vcf.gz``: every member framed with ``BC`` and a payload of
    at most 65,536 bytes, the chain ending in the 28-byte EOF member with
    nothing after it, and no ``.partial`` left beside it. Returns what it
    counted; raises ``ValueError`` saying what is wrong."""
    with open(path, "rb") as fh:
        data = fh.read()
    chain = members(data)  # a byte after the chain's end is a bad member
    if not chain or data[chain[-1][0]:] != EOF:
        raise ValueError(f"{path} does not end in the 28-byte EOF member")
    if any(isize == 0 for _, _, isize in chain[:-1]):
        raise ValueError(f"{path} holds an empty member before its end")
    here, base = os.path.split(path)
    left = [f for f in os.listdir(here or ".")
            if f.startswith(base) and ".partial" in f[len(base):]]
    if left:
        raise ValueError(f"{path} has partial files left beside it: {left}")
    return {"bytes": len(data), "blocks": len(chain),
            "text_bytes": sum(isize for _, _, isize in chain),
            "payload_max": max(isize for _, _, isize in chain)}


def inflate_file(path: str, plain: str) -> int:
    """``path`` through Python's ``gzip`` (which checks every member's CRC)
    into ``plain``; the bytes written."""
    n = 0
    with gzip.open(path, "rb") as src, open(plain, "wb") as dst:
        while True:
            data = src.read(1 << 24)
            if not data:
                return n
            n += dst.write(data)


# -- (c) the level held -------------------------------------------------------

def check_size(container_bytes: int, plain: str, tolerance: float,
               level: int = LEVEL) -> dict:
    """The output is no larger than (1 + ``tolerance``) times what the plain
    writer gives for the same text at ``level``."""
    want = compress_file(plain, os.devnull, level)
    ratio = container_bytes / want
    if ratio > 1.0 + tolerance:
        raise ValueError(f"{container_bytes} bytes for a text the plain writer "
                         f"puts in {want} at level {level}: {ratio:.4f} times, "
                         f"over 1 + {tolerance}")
    return {"reference_bytes": want, "size_ratio": ratio}


# -- (d) the index ------------------------------------------------------------

def read_tbi(path: str) -> dict:
    """The header and the linear indexes of a ``.tbi`` (tabix.pdf): the
    chunks of the binning index are stepped over."""
    with gzip.open(path, "rb") as fh:
        data = fh.read()
    if data[:4] != TBI_MAGIC:
        raise ValueError(f"{path} does not start with the TBI magic")
    n_ref, *preset = struct.unpack_from("<7i", data, 4)
    l_nm = struct.unpack_from("<i", data, 32)[0]
    names = data[36:36 + l_nm].split(b"\x00")[:-1]
    off, linear = 36 + l_nm, []
    for _ in range(n_ref):
        n_bin = struct.unpack_from("<i", data, off)[0]
        off += 4
        for _ in range(n_bin):
            n_chunk = struct.unpack_from("<i", data, off + 4)[0]
            off += 8 + 16 * n_chunk
        n_intv = struct.unpack_from("<i", data, off)[0]
        linear.append(struct.unpack_from(f"<{n_intv}Q", data, off + 4))
        off += 4 + 8 * n_intv
    return {"preset": tuple(preset), "names": [n.decode() for n in names],
            "linear": linear}


def first_overlaps(text: bytes, wanted: set) -> dict:
    """For each ``(contig, window)`` of ``wanted`` the byte offset in ``text``
    of the first record, in file order, that overlaps it: a VCF record
    covers POS - 1 up to POS - 1 + len(REF), 0-based and half open."""
    found, off = {}, 0
    for line in text.split(b"\n"):
        if line and line[0] != 35:  # '#'
            chrom, pos, _, ref, _ = line.split(b"\t", 4)
            beg = int(pos) - 1
            for w in range(beg >> WINDOW_SHIFT,
                           ((beg + max(len(ref), 1) - 1) >> WINDOW_SHIFT) + 1):
                if (chrom, w) in wanted and (chrom, w) not in found:
                    found[chrom, w] = off
        off += len(line) + 1
    return found


def check_index(path: str, plain: str, contigs: list[str], lengths: list[int],
                regions: int, seed) -> dict:
    """``path``'s ``.tbi``: the magic, the VCF preset, the contigs' names,
    and for ``regions`` windows drawn from ``seed`` that the linear index's
    virtual offset leads, by a seek and one inflate, to the first record of
    the inflated text (``plain``) that overlaps the window. A window that no
    record overlaps checks nothing; half of them at least have to."""
    tbi = read_tbi(path + ".tbi")
    if tbi["preset"] != VCF_PRESET:
        raise ValueError(f"{path}.tbi: preset {tbi['preset']}, not VCF's {VCF_PRESET}")
    if tbi["names"] != list(contigs):
        raise ValueError(f"{path}.tbi names {tbi['names']}, the reference {contigs}")
    rng = random.Random(str(seed))
    drawn = []
    for _ in range(regions):
        ci = rng.randrange(len(contigs))
        drawn.append((ci, rng.randrange(max(1, lengths[ci] >> WINDOW_SHIFT))))
    with open(plain, "rb") as fh:
        text = fh.read()
    with open(path, "rb") as fh:
        data = fh.read()
    first = first_overlaps(text, {(contigs[ci].encode(), w) for ci, w in drawn})
    checked = 0
    for ci, w in drawn:
        at = first.get((contigs[ci].encode(), w))
        if at is None:
            continue
        where = f"{path}.tbi: {contigs[ci]} window {w}"
        if w >= len(tbi["linear"][ci]):
            raise ValueError(f"{where} is past the linear index's "
                             f"{len(tbi['linear'][ci])} windows")
        voff = tbi["linear"][ci][w]
        block, next_off = inflate_member(data, voff >> 16)
        within = voff & 0xFFFF
        if within == len(block):  # the end of a member is the start of the next
            block, within = inflate_member(data, next_off)[0], 0
        line = text[at:text.index(b"\n", at) + 1]
        got = block[within:within + len(line)]
        if not got or got != line[:len(got)]:
            raise ValueError(f"{where}: the offset leads to {got[:60]!r}, the "
                             f"first record there is {line[:60]!r}")
        checked += 1
    if 2 * checked < regions:
        raise ValueError(f"{path}: records overlap only {checked} of {regions} "
                         "drawn windows")
    return {"regions": regions, "regions_checked": checked}
