"""Pure-Python BGZF codec (blocked gzip, the htslib container framing).

The reference leans on bgzip/tabix binaries for every compressed artifact
(bash/index_vcf_file.sh, compress_gvcf.py:214). Writing plain gzip would
break the drop-in contract — ``tabix``/``bcftools index`` refuse non-BGZF
input — so this framework's writers emit true BGZF blocks: independent
<=64KiB gzip members carrying the BC extra-field with the block size, and
the canonical 28-byte EOF sentinel. Reading BGZF needs nothing special
(it is valid multi-member gzip).

BGZF members are INDEPENDENT deflate streams, which is what makes the
parallel host-IO paths possible (docs/streaming_executor.md): the sharded
ingest splits compressed input at member boundaries (:func:`scan_block_spans`)
and inflates shards on a worker pool; the streaming writeback compresses
chunk bodies block-parallel through :class:`BgzfChunkCompressor`, whose
framing is byte-identical to a serial :class:`BgzfWriter` by construction.

Full members deflate in the native compressor, with libdeflate at the same
level where the host's ``libdeflate.so.0`` loads and with zlib otherwise
(``native.bgzf_engine``); a file's last partial member, and every member
without the native library, take zlib through :func:`compress_block`. So
the bytes are identical across thread counts, chunk cuts and the two
writers for one engine, and not zlib's where libdeflate loaded: smaller,
and the same text inflated.
"""

from __future__ import annotations

import struct
import zlib

from variantcalling_tpu import obs

MAX_BLOCK_DATA = 65280  # uncompressed payload per block (htslib convention)
BGZF_EOF = bytes.fromhex("1f8b08040000000000ff0600424302001b0003000000000000000000")


def compress_block(data, level: int = 6) -> bytes:
    """One complete BGZF block for <=64KiB of payload (bytes-like)."""
    co = zlib.compressobj(level, zlib.DEFLATED, -15)
    deflated = co.compress(data) + co.flush()
    bsize = len(deflated) + 26  # header(18) + deflated + crc/isize(8)
    if bsize - 1 > 0xFFFF:
        raise ValueError("BGZF block overflow (incompressible 64K payload)")
    header = (
        b"\x1f\x8b\x08\x04"  # magic, CM=deflate, FLG=FEXTRA
        + b"\x00\x00\x00\x00"  # MTIME
        + b"\x00\xff"  # XFL, OS=unknown
        + struct.pack("<H", 6)  # XLEN
        + b"BC"
        + struct.pack("<H", 2)
        + struct.pack("<H", bsize - 1)  # spec: BSIZE = total block size - 1
    )
    trailer = struct.pack("<II", zlib.crc32(data) & 0xFFFFFFFF, len(data) & 0xFFFFFFFF)
    return header + deflated + trailer


def scan_block_spans(buf) -> list[tuple[int, int, int]] | None:
    """Walk the BGZF member chain of ``buf`` (bytes-like, random access).

    Returns ``[(compressed_offset, compressed_size, uncompressed_size)]``
    per member — the shard map of the parallel ingest — or None when the
    stream is not cleanly BGZF-framed end to end (plain single-member
    gzip, a missing BC subfield, or a truncated chain): callers then use
    the serial gzip path, which handles those exactly as before.
    """
    mv = memoryview(buf)
    n = len(mv)
    spans: list[tuple[int, int, int]] = []
    off = 0
    try:
        while off < n:
            if n - off < 18 or bytes(mv[off:off + 4]) != b"\x1f\x8b\x08\x04":
                return None  # not BGZF-framed (magic/FEXTRA missing)
            (xlen,) = struct.unpack("<H", mv[off + 10:off + 12])
            xoff = off + 12
            xend = xoff + xlen
            if xend > n:
                return None
            bsize = None
            while xoff + 4 <= xend:
                si1, si2 = mv[xoff], mv[xoff + 1]
                (slen,) = struct.unpack("<H", mv[xoff + 2:xoff + 4])
                if si1 == 0x42 and si2 == 0x43 and slen == 2:
                    if xoff + 6 > n:
                        return None  # truncated inside the BC payload
                    (b,) = struct.unpack("<H", mv[xoff + 4:xoff + 6])
                    bsize = b + 1
                xoff += 4 + slen
            if bsize is None or off + bsize > n or bsize < 12 + xlen + 8:
                return None
            (isize,) = struct.unpack("<I", mv[off + bsize - 4:off + bsize])
            spans.append((off, bsize, isize))
            off += bsize
    except struct.error:
        return None  # truncated mid-field: same contract as any bad chain
    return spans


def group_spans(spans, shard_bytes: int) -> list[list[tuple[int, int, int]]]:
    """Group consecutive BGZF member spans into inflate shards of
    ~``shard_bytes`` decompressed bytes — the ONE shard-packing rule of
    the parallel ingest stream."""
    groups: list[list[tuple[int, int, int]]] = []
    cur: list[tuple[int, int, int]] = []
    acc = 0
    for span in spans:
        cur.append(span)
        acc += span[2]
        if acc >= shard_bytes:
            groups.append(cur)
            cur, acc = [], 0
    if cur:
        groups.append(cur)
    return groups


def inflate_spans(buf, spans) -> bytes:
    """Inflate a run of BGZF members of ``buf`` (one ingest shard's work;
    each member is an independent raw-deflate stream). zlib releases the
    GIL, so shards genuinely overlap on the IO worker pool. Counts what
    it read and made (``bgzf.in_blocks`` / ``in_bytes`` /
    ``text_bytes_in``; live only under obs)."""
    mv = memoryview(buf)
    out = []
    for off, bsize, _isize in spans:
        (xlen,) = struct.unpack("<H", mv[off + 10:off + 12])
        out.append(zlib.decompress(mv[off + 12 + xlen:off + bsize - 8], wbits=-15))
    text = b"".join(out)
    if obs.active():
        obs.counter("bgzf.in_blocks").add(len(spans))
        obs.counter("bgzf.in_bytes").add(sum(s[1] for s in spans))
        obs.counter("bgzf.text_bytes_in").add(len(text))
    return text


def _compress_full_blocks(chunk, level: int, pool=None) -> bytes:
    """BGZF blocks (no EOF sentinel) for a multiple-of-MAX_BLOCK_DATA
    payload — the ONE compressed-framing spelling shared by
    :class:`BgzfWriter` and :class:`BgzfChunkCompressor`, so serial and
    streaming outputs cannot drift. ``chunk`` is bytes-like and is never
    copied here: the native engine deflates straight from the caller's
    buffer (block-sharded internally); without it, blocks deflate on
    ``pool`` when given (the writeback fan-out), inline otherwise.
    """
    from variantcalling_tpu import native

    out = native.bgzf_compress(chunk, level)
    if out is not None:
        return out[:-28]  # strip the EOF sentinel; close()/finish() writes it once
    view = memoryview(chunk)
    blocks = [view[i:i + MAX_BLOCK_DATA] for i in range(0, len(view), MAX_BLOCK_DATA)]
    if pool is not None and len(blocks) > 1:
        from variantcalling_tpu.parallel.pipeline import imap_ordered

        return b"".join(imap_ordered(pool, lambda b: compress_block(b, level),
                                     blocks, window=2 * pool.threads))
    return b"".join(compress_block(b, level) for b in blocks)


class BgzfWriter:
    """File-like text/binary writer emitting BGZF blocks."""

    def __init__(self, path: str, level: int = 6):
        self._fh = open(path, "wb")
        self._buf = bytearray()
        self._level = level

    def write(self, data: str | bytes | memoryview) -> int:
        if isinstance(data, str):
            data = data.encode("utf-8")
        n_in = len(data)
        # large-write fast path (the streaming executor hands multi-MB
        # chunk bodies): compress straight from the caller's buffer —
        # the memoryview rides through to the compressor, so the chunk
        # body is never copied on its way to deflate
        if not self._buf and n_in >= MAX_BLOCK_DATA:
            view = memoryview(data)
            n_full = (n_in // MAX_BLOCK_DATA) * MAX_BLOCK_DATA
            self._fh.write(_compress_full_blocks(view[:n_full], self._level))
            if n_full < n_in:
                self._buf += view[n_full:]
            return n_in
        self._buf += data
        if len(self._buf) >= MAX_BLOCK_DATA:
            n_full = (len(self._buf) // MAX_BLOCK_DATA) * MAX_BLOCK_DATA
            chunk = bytes(self._buf[:n_full])
            del self._buf[:n_full]
            self._fh.write(_compress_full_blocks(chunk, self._level))
        return n_in

    def close(self) -> None:
        if self._fh.closed:
            return
        if self._buf:
            self._fh.write(compress_block(bytes(self._buf), self._level))
            self._buf.clear()
        self._fh.write(BGZF_EOF)
        self._fh.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def _count_out(text: int, compressed: int, blocks: int) -> None:
    """The compress stage's counters (live only under obs): text taken
    in, container bytes and members handed to the committer."""
    if obs.active():
        obs.counter("bgzf.text_bytes_out").add(text)
        obs.counter("bgzf.out_bytes").add(compressed)
        obs.counter("bgzf.out_blocks").add(blocks)


class BgzfChunkCompressor:
    """Deterministic BGZF framing for the streaming writeback's compress
    stage (docs/streaming_executor.md "Parallel host IO").

    The byte stream is split into consecutive ``MAX_BLOCK_DATA`` payloads
    exactly as a serial :class:`BgzfWriter` would (the carry is always
    ``stream_length mod MAX_BLOCK_DATA``, independent of write sizes), so
    the compressed output is byte-identical to the serial writer's on the
    same deflate engine regardless of chunk boundaries or worker count.
    :meth:`add` runs on ONE pipeline stage thread in chunk order — the
    carry is therefore deterministic — while the deflate work itself fans
    out (native block-sharded compressor, or per-block on ``pool``).
    """

    def __init__(self, level: int = 6, pool=None):
        self._carry = bytearray()
        self._level = level
        self._pool = pool
        self.bytes_in = 0

    def add(self, body) -> bytes:
        """Compressed blocks for every full payload of carry+body; the
        remainder becomes the next carry. ``body`` is bytes-like and is
        not copied when it alone covers the full blocks."""
        from variantcalling_tpu.utils import faults

        # injection point "io.shard_compress": a compress-worker death is
        # a stage exception — the pipeline cancels cleanly and the atomic
        # commit discards the torn .partial (test_streaming_faults)
        faults.check("io.shard_compress")
        view = memoryview(body) if not isinstance(body, memoryview) else body
        self.bytes_in += len(view)
        before = len(self._carry)
        out = self._add(view)
        _count_out(len(view), len(out),
                   (before + len(view) - len(self._carry)) // MAX_BLOCK_DATA)
        return out

    def _add(self, view) -> bytes:
        if not self._carry:
            n_full = (len(view) // MAX_BLOCK_DATA) * MAX_BLOCK_DATA
            out = _compress_full_blocks(view[:n_full], self._level,
                                        self._pool) if n_full else b""
            if n_full < len(view):
                self._carry += view[n_full:]
            return out
        need = MAX_BLOCK_DATA - len(self._carry)
        if len(view) < need:
            self._carry += view
            return b""
        self._carry += view[:need]
        head = bytes(self._carry)
        self._carry.clear()
        rest = view[need:]
        n_full = (len(rest) // MAX_BLOCK_DATA) * MAX_BLOCK_DATA
        out = _compress_full_blocks(head, self._level, self._pool)
        if n_full:
            out += _compress_full_blocks(rest[:n_full], self._level, self._pool)
        if n_full < len(rest):
            self._carry += rest[n_full:]
        return out

    def finish(self) -> bytes:
        """The final partial block (if any) + the EOF sentinel — the same
        tail a serial :class:`BgzfWriter.close` writes."""
        out = b""
        if self._carry:
            out = compress_block(bytes(self._carry), self._level)
            self._carry.clear()
        # the EOF member is a block of the file too: ``bgzf.out_bytes``
        # ends up the committed file's size
        _count_out(0, len(out) + len(BGZF_EOF), (1 if out else 0) + 1)
        return out + BGZF_EOF


def open_bgzf_text(path: str, level: int = 6) -> BgzfWriter:
    return BgzfWriter(path, level)
