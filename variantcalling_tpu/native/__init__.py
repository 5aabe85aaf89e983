"""Native (C++) host engine: BGZF codec, BAM depth walker, interval joins.

The reference's native layer is external subprocessed binaries (samtools,
bgzip/tabix, bedtools — SURVEY.md §2.5); ours is an in-process shared
library (``src/vctpu_native.cc``) compiled on demand with g++ and bound via
ctypes (pybind11 is not in the image). Every entry point has a pure-Python
fallback at its call site (io/bam.py depth walk, io/vcf.py + io/bed.py
compressed-text ingest, io/bgzf.py block writer), so the framework works
without a toolchain; with one, ingest runs at C speed and feeds flat
arrays straight to the device kernels.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

from variantcalling_tpu import obs
from variantcalling_tpu.obs.sampler import native_span

_DIR = os.path.dirname(__file__)
_SRC = os.path.join(_DIR, "src", "vctpu_native.cc")
_SRC_CRAM = os.path.join(_DIR, "src", "vctpu_cram.cc")
_SRC_MATCH = os.path.join(_DIR, "src", "vctpu_match.cc")
_SRC_GBT = os.path.join(_DIR, "src", "vctpu_gbt.cc")
_SRC_FEAT = os.path.join(_DIR, "src", "vctpu_features.cc")
_SRC_FUSED = os.path.join(_DIR, "src", "vctpu_fused.cc")
_SRC_WIRE = os.path.join(_DIR, "src", "vctpu_wire.cc")
_SRC_TABIX = os.path.join(_DIR, "src", "vctpu_tabix.cc")
#: every translation unit of the library, in link order
_SRCS = (_SRC, _SRC_CRAM, _SRC_MATCH, _SRC_GBT, _SRC_FEAT, _SRC_FUSED, _SRC_WIRE,
         _SRC_TABIX)
#: shared inline headers — hashed into the build key (an edit must
#: rebuild every TU that includes them) but not compiled standalone
_HDRS = (os.path.join(_DIR, "src", "vctpu_threads.h"),
         os.path.join(_DIR, "src", "vctpu_feat_row.h"),
         os.path.join(_DIR, "src", "vctpu_forest_tile.h"))
_LOCK = threading.Lock()
_LIB: ctypes.CDLL | None = None
_TRIED = False

_i64 = ctypes.c_int64
_u8p = ctypes.POINTER(ctypes.c_uint8)
_i32p = ctypes.POINTER(ctypes.c_int32)
_i64p = ctypes.POINTER(ctypes.c_int64)
_i8p = ctypes.POINTER(ctypes.c_int8)


_CXXFLAGS = ["-O3", "-march=native", "-funroll-loops", "-shared", "-fPIC", "-std=c++17"]


def _cpu_tag() -> str:
    """ISA fingerprint folded into the build cache key: -march=native
    binaries must not be reused by a host lacking the builder's
    extensions (shared site-packages / NFS homes / mixed pods)."""
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if line.startswith("flags"):
                    return hashlib.sha256(line.encode()).hexdigest()[:8]
    except OSError:
        pass
    import platform

    return platform.machine()


def _build() -> str | None:
    hasher = hashlib.sha256()
    hasher.update(" ".join(_CXXFLAGS).encode())  # flag changes rebuild too
    hasher.update(_cpu_tag().encode())  # so does a different host ISA
    for src in (*_SRCS, *_HDRS):
        with open(src, "rb") as fh:
            hasher.update(fh.read())
    tag = hasher.hexdigest()[:12]
    out = os.path.join(_DIR, f"_vctpu_native_{tag}.so")
    if os.path.exists(out):
        return out
    # per-process tmp name keeps os.replace atomic under concurrent builds
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = ["g++", *_CXXFLAGS, "-o", tmp, *_SRCS, "-lz"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=300)
        os.replace(tmp, out)
    except (OSError, subprocess.SubprocessError):
        return None
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass
    return out


def get_lib() -> ctypes.CDLL | None:
    """Compile (once, cached by source hash) and load the native library."""
    from variantcalling_tpu.utils import faults

    # injection point "native.build": simulates a build/load failure (even
    # when a cached .so exists) so REQUIRE_NATIVE / engine-resolution
    # failure paths are testable on a host whose toolchain works
    if faults.should_fire("native.build"):
        return None
    global _LIB, _TRIED
    with _LOCK:
        if _TRIED:
            return _LIB
        _TRIED = True
        from variantcalling_tpu import knobs

        if knobs.get_bool("VCTPU_NO_NATIVE"):
            return None
        path = _build()
        if path is None:
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            return None
        lib.vctpu_bgzf_uncompressed_size.restype = _i64
        lib.vctpu_bgzf_uncompressed_size.argtypes = [_u8p, _i64]
        lib.vctpu_gzip_inflate.restype = _i64
        lib.vctpu_gzip_inflate.argtypes = [_u8p, _i64, _u8p, _i64]
        lib.vctpu_bgzf_inflate.restype = _i64
        lib.vctpu_bgzf_inflate.argtypes = [_u8p, _i64, _u8p, _i64]
        lib.vctpu_bgzf_compress.restype = _i64
        lib.vctpu_bgzf_compress.argtypes = [_u8p, _i64, _u8p, _i64, ctypes.c_int,
                                            ctypes.c_int, _i64p]
        lib.vctpu_bgzf_engine.restype = ctypes.c_int
        lib.vctpu_bgzf_engine.argtypes = []
        lib.vctpu_bam_depth.restype = _i64
        lib.vctpu_bam_depth.argtypes = [
            _u8p, _i64, _i64p, _i64p, ctypes.c_int32, _i32p,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_uint32,
        ]
        lib.vctpu_interval_membership.restype = None
        lib.vctpu_interval_membership.argtypes = [_i64p, _i64p, _i64, _i64p, _i64, _u8p]
        lib.vctpu_vcf_assemble.restype = _i64
        lib.vctpu_vcf_assemble.argtypes = [
            _u8p, _i64, _i64,
            _i64p, _i64p, _i64p, _i64p,
            _u8p, _i64p, _u8p, _i64p,
            _u8p, _i64,
        ]
        lib.vctpu_cram_header.restype = _i64
        lib.vctpu_cram_header.argtypes = [_u8p, _i64, _u8p, _i64]
        lib.vctpu_cram_count.restype = _i64
        lib.vctpu_cram_count.argtypes = [_u8p, _i64]
        lib.vctpu_match_contig.restype = _i64
        lib.vctpu_match_contig.argtypes = [
            _u8p, _i64,
            _i64, _i64p, _u8p, _i64p, _u8p, _i64p, _i8p,
            _i64, _i64p, _u8p, _i64p, _u8p, _i64p, _i8p,
            ctypes.c_int32,
            _u8p, _u8p, _u8p, _u8p, _i64p, _i64p,
        ]
        lib.vctpu_cram_pileup.restype = _i64
        lib.vctpu_cram_pileup.argtypes = [
            _u8p, _i64, ctypes.c_int32, _i64, _i64, _u8p, _i64, _i32p,
        ]
        lib.vctpu_cram_scan.restype = _i64
        lib.vctpu_cram_scan.argtypes = [
            _u8p, _i64, _i64, _i32p, _i64p, _i32p, _i32p, _i32p, _i32p,
        ]
        lib.vctpu_cram_depth.restype = _i64
        lib.vctpu_cram_depth.argtypes = [
            _u8p, _i64, _i64p, _i64p, ctypes.c_int32, _i32p,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_uint32,
        ]
        lib.vctpu_vcf_count.restype = _i64
        lib.vctpu_vcf_count.argtypes = [_u8p, _i64, _i64p]
        _f32p = ctypes.POINTER(ctypes.c_float)
        _f64p = ctypes.POINTER(ctypes.c_double)
        lib.vctpu_vcf_parse.restype = _i64
        lib.vctpu_vcf_parse.argtypes = [
            _u8p, _i64, _i64, _i64, ctypes.c_int32,
            _i64p, _i64p, _i64p, _i64p, _i64p, _i64p, _i64p,
            _i64p, _f64p,
            _i32p, _u8p, _i32p,
            _i8p, _u8p, _f32p, _f32p, _f32p,
            _u8p, _i32p, _i32p, _i32p, _i32p, _i32p, _i32p,
            _u8p, _i32p, ctypes.c_int32, _f64p,
        ]
        lib.vctpu_bin_features.restype = _i64
        lib.vctpu_bin_features.argtypes = [
            _f32p, _i64, ctypes.c_int32, _f32p, ctypes.c_int32, _u8p,
        ]
        lib.vctpu_gather_windows.restype = _i64
        lib.vctpu_gather_windows.argtypes = [
            _u8p, _i64, _i64p, _i64, ctypes.c_int32, _u8p,
        ]
        lib.vctpu_format_float_info.restype = _i64
        lib.vctpu_format_float_info.argtypes = [
            _f64p, _i64, _u8p, _i64, _u8p, _i64, _i64p,
        ]
        lib.vctpu_featurize_windows.restype = _i64
        lib.vctpu_featurize_windows.argtypes = [
            _u8p, _i64, ctypes.c_int32, ctypes.c_int32,
            _u8p, _i32p, _i32p, _i32p, _u8p, _i32p,
            _i32p, _i32p, _f32p, _i32p, _i32p, _i32p,
        ]
        lib.vctpu_featurize_gather.restype = _i64
        lib.vctpu_featurize_gather.argtypes = [
            _u8p, _i64, _i64p, _i64, ctypes.c_int32,
            _u8p, _i32p, _i32p, _i32p, _u8p, _i32p,
            _i32p, _i32p, _f32p, _i32p, _i32p, _i32p,
        ]
        lib.vctpu_build_matrix.restype = _i64
        lib.vctpu_build_matrix.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), _i32p, _i64, ctypes.c_int32, _f32p,
        ]
        lib.vctpu_fused_chunk_score.restype = _i64
        lib.vctpu_fused_chunk_score.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), _i64p, _i64p, ctypes.c_int32,
            _i64p, _i64, ctypes.c_int32,
            _u8p, _i32p, _i32p, _i32p, _u8p, _i32p,
            ctypes.POINTER(ctypes.c_void_p), _i32p, ctypes.c_int32, _i32p,
            _i32p, _f32p, _i32p, _i32p, _f32p, _u8p,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_float,
            _f32p,
        ]
        lib.vctpu_forest_predict.restype = _i64
        lib.vctpu_forest_predict.argtypes = [
            _f32p, _i64, ctypes.c_int32,
            _i32p, _f32p, _i32p, _i32p, _f32p, _u8p,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_float,
            _f32p,
        ]
        lib.vctpu_matrix_forest_predict.restype = _i64
        lib.vctpu_matrix_forest_predict.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), _i32p, _i64, ctypes.c_int32,
            _i32p, ctypes.POINTER(ctypes.c_float), _i32p, _i32p,
            ctypes.POINTER(ctypes.c_float), _u8p,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_float,
            ctypes.POINTER(ctypes.c_float),
        ]
        lib.vctpu_fasta_encode.restype = _i64
        lib.vctpu_fasta_encode.argtypes = [
            _u8p, _i64, _i64, _i64, _i64, _u8p,
        ]
        lib.vctpu_coverage_stats.restype = _i64
        lib.vctpu_coverage_stats.argtypes = [
            _i32p, _i64, _i64, ctypes.c_int32, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_float), _i64p,
        ]
        lib.vctpu_gbt_fit.restype = _i64
        lib.vctpu_gbt_fit.argtypes = [
            _u8p, _f32p, _f32p,
            _i64, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_int32,
            ctypes.c_float, ctypes.c_float, ctypes.c_float, ctypes.c_float,
            _i32p, _i32p, _f32p,
        ]
        _vp = ctypes.c_void_p  # addresses: the wire fill runs once a dispatch
        lib.vctpu_wire_fill.restype = _i64
        lib.vctpu_wire_fill.argtypes = [
            _vp, _i64, _i64, _i64, _i64, _vp, _i64,
            _vp, _vp, _vp, _vp, _i64, _i64, ctypes.c_uint32,
            _vp, _vp, _vp, _vp,
            _vp, _i64, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            _vp, _vp, _vp, _vp, _vp, _vp,
            _vp, _i64, ctypes.c_int32, _vp,
        ]
        lib.vctpu_tabix_chunk_facts.restype = _i64
        lib.vctpu_tabix_chunk_facts.argtypes = [
            _vp, _i64, _i64, _vp, _vp, _vp,
            _vp, _vp, _vp, _vp, _vp, _vp, _vp, _vp, _i64, _vp]
        _LIB = lib
        return _LIB


def available() -> bool:
    return get_lib() is not None


def _u8view(data) -> np.ndarray:
    """Zero-copy uint8 view over bytes / bytearray / ndarray."""
    return np.frombuffer(data, dtype=np.uint8) if not isinstance(data, np.ndarray) else data


def bgzf_decompress_array(data) -> np.ndarray | None:
    """Inflate a whole BGZF/gzip buffer to a uint8 array (no extra copies)."""
    lib = get_lib()
    if lib is None or len(data) == 0:
        return None
    src_arr = np.ascontiguousarray(_u8view(data))
    src = src_arr.ctypes.data_as(_u8p)
    with native_span("bgzf_inflate"):
        size = lib.vctpu_bgzf_uncompressed_size(src, len(src_arr))
        if size < 0:
            # not BGZF-framed: inflate with geometric capacity growth
            cap = max(4 * len(src_arr), 1 << 16)
            for _ in range(8):
                dst = np.empty(cap, dtype=np.uint8)
                n = lib.vctpu_gzip_inflate(src, len(src_arr), dst.ctypes.data_as(_u8p), cap)
                if n >= 0:
                    return dst[:n]
                cap *= 4
            return None
        dst = np.empty(max(int(size), 1), dtype=np.uint8)
        # block-parallel path first (per-member raw inflate at
        # prefix-summed offsets); -2 means the payload itself is corrupt
        # — the serial gzip walk would fail on it too, so fall back only
        # on -1 (framing)
        n = lib.vctpu_bgzf_inflate(src, len(src_arr), dst.ctypes.data_as(_u8p), int(size))
        if n == -1:
            n = lib.vctpu_gzip_inflate(src, len(src_arr), dst.ctypes.data_as(_u8p), int(size))
    if n != size:
        return None
    return dst[:n]


def bgzf_decompress(data: bytes) -> bytes | None:
    """Inflate a whole BGZF/gzip byte string; None → use the Python fallback."""
    out = bgzf_decompress_array(data)
    return None if out is None else out.tobytes()


#: the deflate engines of ``vctpu_bgzf_compress``
BGZF_ZLIB, BGZF_LIBDEFLATE = 0, 1


def bgzf_engine() -> int | None:
    """The engine :func:`bgzf_compress` deflates with: ``BGZF_LIBDEFLATE``
    where the host's ``libdeflate.so.0`` loaded, else ``BGZF_ZLIB``; None
    without the native library."""
    lib = get_lib()
    return None if lib is None else lib.vctpu_bgzf_engine()


def bgzf_compress(data, level: int = 6, *, engine: int | None = None) -> bytes | None:
    """Deflate a bytes-like buffer into BGZF blocks (+EOF sentinel);
    None → Python fallback. Zero-copy on the way in: the engine deflates
    straight from the caller's buffer (bytes, memoryview, uint8 array) —
    the streaming writeback hands multi-MB chunk bodies through here and
    an extra materialization would double the write path's memory
    traffic. ``engine`` is the loaded one (:func:`bgzf_engine`); tests
    pass ``BGZF_ZLIB`` to hold the other path. Counts the members it
    deflated (``bgzf.deflate_members``) and those libdeflate deflated
    (``bgzf.libdeflate_members``; live only under obs)."""
    lib = get_lib()
    if lib is None:
        return None
    if engine is None:
        engine = lib.vctpu_bgzf_engine()
    src_arr = np.ascontiguousarray(_u8view(data))
    n_in = len(src_arr)
    src = src_arr.ctypes.data_as(_u8p) if n_in else \
        (ctypes.c_uint8 * 1).from_buffer_copy(b"\x00")
    n_blocks = n_in // 65280 + 1
    cap = n_in + n_blocks * 128 + 64
    dst = np.empty(cap, dtype=np.uint8)
    fallbacks = _i64(0)
    with native_span("bgzf_deflate"):
        n = lib.vctpu_bgzf_compress(src, n_in, dst.ctypes.data_as(_u8p),
                                    cap, level, engine, ctypes.byref(fallbacks))
    if n < 0:
        return None
    if obs.active():
        members = -(-n_in // 65280)
        obs.counter("bgzf.deflate_members").add(members)
        if engine == BGZF_LIBDEFLATE:
            obs.counter("bgzf.libdeflate_members").add(members - fallbacks.value)
    return dst[:n].tobytes()


def bam_depth(
    records,
    contig_starts: np.ndarray,
    contig_lens: np.ndarray,
    diff_flat: np.ndarray,
    *,
    min_bq: int = 0,
    min_mapq: int = 0,
    min_read_length: int = 0,
    include_deletions: bool = True,
    exclude_flags: int = 0x704,
) -> int | None:
    """Accumulate depth diffs over raw BAM records (bytes or uint8 array view);
    None → Python fallback."""
    lib = get_lib()
    if lib is None:
        return None
    starts = np.ascontiguousarray(contig_starts, dtype=np.int64)
    lens = np.ascontiguousarray(contig_lens, dtype=np.int64)
    assert diff_flat.dtype == np.int32 and diff_flat.flags["C_CONTIGUOUS"]
    src_arr = np.ascontiguousarray(_u8view(records))
    n = lib.vctpu_bam_depth(
        src_arr.ctypes.data_as(_u8p), len(src_arr),
        starts.ctypes.data_as(_i64p), lens.ctypes.data_as(_i64p), len(starts),
        diff_flat.ctypes.data_as(_i32p),
        min_bq, min_mapq, min_read_length, int(include_deletions), exclude_flags,
    )
    return None if n < 0 else int(n)


# INFO keys extracted during the native VCF scan; info_field() serves these
# from the cache without touching the INFO strings (filter/featurize hot set)
VCF_INFO_KEYS = ("DP", "SOR", "AF", "QD", "FS", "MQ", "TLOD", "AS_SOR", "DB", "END")


def vcf_parse(buf, n_samples: int) -> dict | None:
    """One-pass columnar parse of an uncompressed VCF text buffer.

    Returns a dict of flat arrays (see vctpu_vcf_parse in src) or None when
    the native library is unavailable / input malformed — caller falls back
    to the Python line parser.
    """
    lib = get_lib()
    if lib is None:
        return None
    src_arr = np.ascontiguousarray(_u8view(buf))
    src = src_arr.ctypes.data_as(_u8p)
    first_off = _i64(0)
    n = lib.vctpu_vcf_count(src, len(src_arr), ctypes.byref(first_off))
    if n < 0:
        return None
    n = int(n)
    uniq_cap = 4096
    f32, f64, i64, i32 = np.float32, np.float64, np.int64, np.int32
    # every span column is its own contiguous (n, 2) buffer: downstream
    # consumers (NativeAux, the assemble call) use them directly with no
    # strided-slice copies (round-4 writeback profile: 1.2s at 5M records)
    out = {
        "n": n,
        "line_spans": np.empty((n, 2), dtype=i64),
        "id_spans": np.empty((n, 2), dtype=i64),
        "ref_spans": np.empty((n, 2), dtype=i64),
        "alt_spans": np.empty((n, 2), dtype=i64),
        "filter_spans": np.empty((n, 2), dtype=i64),
        "info_spans": np.empty((n, 2), dtype=i64),
        "tail_spans": np.empty((n, 2), dtype=i64),
        "pos": np.empty(n, dtype=i64),
        "qual": np.empty(n, dtype=f64),
        "chrom_codes": np.empty(n, dtype=i32),
        "gt": np.empty((n, 2), dtype=np.int8),
        "gt_phased": np.empty(n, dtype=np.uint8),
        "gq": np.empty(n, dtype=f32),
        "dp_fmt": np.empty(n, dtype=f32),
        "ad": np.empty((n, 3), dtype=f32),
        "aclass": np.empty(n, dtype=np.uint8),
        "indel_length": np.empty(n, dtype=i32),
        "indel_nuc": np.empty(n, dtype=i32),
        "ref_code": np.empty(n, dtype=i32),
        "alt_code": np.empty(n, dtype=i32),
        "n_alts": np.empty(n, dtype=i32),
        "ref_len": np.empty(n, dtype=i32),
        "info_vals": np.empty((n, len(VCF_INFO_KEYS)), dtype=f64),
    }
    if n == 0:
        out["chroms"] = []
        return out
    uniq_buf = np.zeros(uniq_cap * 64, dtype=np.uint8)
    uniq_n = (ctypes.c_int32 * 1)(uniq_cap)
    keys_b = "".join(VCF_INFO_KEYS).encode()
    keys_arr = np.frombuffer(keys_b, dtype=np.uint8)
    key_lens = np.asarray([len(k) for k in VCF_INFO_KEYS], dtype=i32)

    def p(a, typ):
        return a.ctypes.data_as(typ)

    _f32p = ctypes.POINTER(ctypes.c_float)
    _f64p = ctypes.POINTER(ctypes.c_double)
    _i8p = ctypes.POINTER(ctypes.c_int8)
    rc = lib.vctpu_vcf_parse(
        src, len(src_arr), first_off.value, n, int(n_samples),
        p(out["line_spans"], _i64p), p(out["id_spans"], _i64p),
        p(out["ref_spans"], _i64p), p(out["alt_spans"], _i64p),
        p(out["filter_spans"], _i64p), p(out["info_spans"], _i64p),
        p(out["tail_spans"], _i64p),
        p(out["pos"], _i64p), p(out["qual"], _f64p),
        p(out["chrom_codes"], _i32p), p(uniq_buf, _u8p), uniq_n,
        p(out["gt"], _i8p), p(out["gt_phased"], _u8p),
        p(out["gq"], _f32p), p(out["dp_fmt"], _f32p), p(out["ad"], _f32p),
        p(out["aclass"], _u8p), p(out["indel_length"], _i32p), p(out["indel_nuc"], _i32p),
        p(out["ref_code"], _i32p), p(out["alt_code"], _i32p), p(out["n_alts"], _i32p),
        p(out["ref_len"], _i32p),
        p(np.ascontiguousarray(keys_arr), _u8p), p(key_lens, _i32p), len(VCF_INFO_KEYS),
        p(out["info_vals"], _f64p),
    )
    if rc != n:
        return None
    n_uniq = uniq_n[0]
    out["chroms"] = [
        bytes(uniq_buf[i * 64 : (i + 1) * 64]).rstrip(b"\x00").decode() for i in range(n_uniq)
    ]
    return out


def vcf_assemble(
    buf: np.ndarray,
    line_spans: np.ndarray,
    filter_spans: np.ndarray,
    info_spans: np.ndarray,
    tail_spans: np.ndarray,
    filt_blob: bytes,
    filt_offs: np.ndarray,
    sfx_blob: bytes,
    sfx_offs: np.ndarray,
    out: np.ndarray | None = None,
) -> np.ndarray | None:
    """Assemble writeback record lines from parse-buffer spans + new FILTER/INFO.

    Returns the uint8 output buffer (a view of ``out`` when provided and
    large enough — chunked writers reuse one buffer so each call touches
    warm pages), or None -> Python fallback.
    """
    lib = get_lib()
    if lib is None:
        return None
    n = len(line_spans)
    src = np.ascontiguousarray(_u8view(buf))
    # bytes OR uint8 ndarray blobs (ndarray: no copy, no bool ambiguity)
    fb = np.ascontiguousarray(_u8view(filt_blob)) if len(filt_blob) else np.zeros(1, np.uint8)
    sb = np.ascontiguousarray(_u8view(sfx_blob)) if len(sfx_blob) else np.zeros(1, np.uint8)
    cap = int(
        (line_spans[:, 1] - line_spans[:, 0]).sum() + len(filt_blob) + len(sfx_blob) + 4 * n + 64
    )
    if out is None or len(out) < cap or out.dtype != np.uint8 or not out.flags["C_CONTIGUOUS"]:
        out = np.empty(cap, dtype=np.uint8)

    # keep contiguous copies referenced for the duration of the call
    arrs = [
        np.ascontiguousarray(a, dtype=np.int64)
        for a in (line_spans, filter_spans, info_spans, tail_spans, filt_offs, sfx_offs)
    ]
    w = lib.vctpu_vcf_assemble(
        src.ctypes.data_as(_u8p), len(src), n,
        arrs[0].ctypes.data_as(_i64p), arrs[1].ctypes.data_as(_i64p),
        arrs[2].ctypes.data_as(_i64p), arrs[3].ctypes.data_as(_i64p),
        fb.ctypes.data_as(_u8p), arrs[4].ctypes.data_as(_i64p),
        sb.ctypes.data_as(_u8p), arrs[5].ctypes.data_as(_i64p),
        out.ctypes.data_as(_u8p), cap,
    )
    if w < 0:
        return None
    return out[:w]


def cram_header(buf) -> str | None:
    """SAM header text of a CRAM 3.0 buffer; None when unavailable/unsupported."""
    lib = get_lib()
    if lib is None:
        return None
    src = np.ascontiguousarray(_u8view(buf))
    cap = 1 << 20
    for _ in range(4):
        out = np.empty(cap, dtype=np.uint8)
        n = lib.vctpu_cram_header(src.ctypes.data_as(_u8p), len(src), out.ctypes.data_as(_u8p), cap)
        if n == -3:
            cap *= 8
            continue
        if n < 0:
            return None
        return out[:n].tobytes().decode("utf-8", "replace")
    return None


def cram_count(buf) -> int | None:
    """Exact record count from the container headers (no block decode)."""
    lib = get_lib()
    if lib is None:
        return None
    src = np.ascontiguousarray(_u8view(buf))
    n = lib.vctpu_cram_count(src.ctypes.data_as(_u8p), len(src))
    return None if n < 0 else int(n)


def cram_scan(buf, max_records: int) -> dict | None:
    """Per-record alignment arrays from a CRAM 3.0 buffer.

    Returns {ref_id, pos (1-based), span, mapq, flags, read_len} or None on
    unsupported input (caller raises a clear error — there is no Python
    fallback for CRAM decoding).
    """
    lib = get_lib()
    if lib is None:
        return None
    src = np.ascontiguousarray(_u8view(buf))
    out = {
        "ref_id": np.empty(max_records, dtype=np.int32),
        "pos": np.empty(max_records, dtype=np.int64),
        "span": np.empty(max_records, dtype=np.int32),
        "mapq": np.empty(max_records, dtype=np.int32),
        "flags": np.empty(max_records, dtype=np.int32),
        "read_len": np.empty(max_records, dtype=np.int32),
    }
    n = lib.vctpu_cram_scan(
        src.ctypes.data_as(_u8p), len(src), max_records,
        out["ref_id"].ctypes.data_as(_i32p), out["pos"].ctypes.data_as(_i64p),
        out["span"].ctypes.data_as(_i32p), out["mapq"].ctypes.data_as(_i32p),
        out["flags"].ctypes.data_as(_i32p), out["read_len"].ctypes.data_as(_i32p),
    )
    if n == -4:
        return "grow"  # capacity exceeded — caller retries with more room
    if n < 0:
        return None
    return {k: v[:n] for k, v in out.items()}


def cram_depth(
    buf,
    contig_starts: np.ndarray,
    contig_lens: np.ndarray,
    diff_flat: np.ndarray,
    *,
    min_bq: int = 0,
    min_mapq: int = 0,
    min_read_length: int = 0,
    include_deletions: bool = True,
    exclude_flags: int = 0x704,
) -> int | None:
    """Accumulate samtools-depth-semantics diffs over a CRAM buffer (the
    CRAM twin of :func:`bam_depth`, including the per-base ``-q`` filter);
    None when unavailable, negative handled by the caller."""
    lib = get_lib()
    if lib is None:
        return None
    starts = np.ascontiguousarray(contig_starts, dtype=np.int64)
    lens = np.ascontiguousarray(contig_lens, dtype=np.int64)
    assert diff_flat.dtype == np.int32 and diff_flat.flags["C_CONTIGUOUS"]
    src_arr = np.ascontiguousarray(_u8view(buf))
    n = lib.vctpu_cram_depth(
        src_arr.ctypes.data_as(_u8p), len(src_arr),
        starts.ctypes.data_as(_i64p), lens.ctypes.data_as(_i64p), len(starts),
        diff_flat.ctypes.data_as(_i32p),
        min_bq, min_mapq, min_read_length, int(include_deletions), exclude_flags,
    )
    return int(n)


def cram_pileup(buf, target_ref: int, start0: int, end0: int, ref_seq: str) -> np.ndarray | None:
    """(end0-start0, 4) aligned base counts over one contig window.

    ``ref_seq`` is the FULL target contig sequence (bases between CRAM
    features are reference matches; X features go through the SM matrix).
    """
    lib = get_lib()
    if lib is None:
        return None
    src = np.ascontiguousarray(_u8view(buf))
    ref = np.frombuffer(ref_seq.encode("ascii", "replace"), dtype=np.uint8)
    counts = np.zeros((max(end0 - start0, 0), 4), dtype=np.int32)
    n = lib.vctpu_cram_pileup(
        src.ctypes.data_as(_u8p), len(src), target_ref, start0, end0,
        ref.ctypes.data_as(_u8p), len(ref), counts.ctypes.data_as(_i32p),
    )
    if n < 0:
        return None
    return counts




def _pack(items):
    """(uint8 blob, (n+1) int64 offsets) over concatenated strings."""
    blob = "".join(items).encode("latin-1")
    offs = np.zeros(len(items) + 1, dtype=np.int64)
    np.cumsum(np.fromiter(map(len, items), dtype=np.int64, count=len(items)), out=offs[1:])
    return np.frombuffer(blob or b"\x00", dtype=np.uint8), offs


def match_contig_native(ref_seq: str, c_pos, c_ref, c_alt, c_gt,
                        t_pos, t_ref, t_alt, t_gt, haplotype_rescue: bool = True):
    """Native haplotype matcher; None -> Python fallback.

    ``c_ref``/``t_ref`` are per-record REF strings, ``c_alt``/``t_alt`` the
    comma-joined ALT strings; returns (call_tp, call_tp_gt, truth_tp,
    truth_tp_gt, call_truth_idx) as the Python matcher does.
    """
    lib = get_lib()
    if lib is None:
        return None
    nc, nt = len(c_pos), len(t_pos)
    seq = np.frombuffer(ref_seq.encode("latin-1") or b"\x00", dtype=np.uint8)
    crb, cro = _pack(list(c_ref))
    cab, cao = _pack(list(c_alt))
    trb, tro = _pack(list(t_ref))
    tab, tao = _pack(list(t_alt))
    cp = np.ascontiguousarray(c_pos, dtype=np.int64)
    tp = np.ascontiguousarray(t_pos, dtype=np.int64)
    cg = np.ascontiguousarray(c_gt, dtype=np.int8)
    tg = np.ascontiguousarray(t_gt, dtype=np.int8)
    call_tp = np.zeros(max(nc, 1), dtype=np.uint8)
    call_tp_gt = np.zeros(max(nc, 1), dtype=np.uint8)
    truth_tp = np.zeros(max(nt, 1), dtype=np.uint8)
    truth_tp_gt = np.zeros(max(nt, 1), dtype=np.uint8)
    idx = np.full(max(nc, 1), -1, dtype=np.int64)
    stats = np.zeros(2, dtype=np.int64)  # capped clusters, variants in them
    rc = lib.vctpu_match_contig(
        seq.ctypes.data_as(_u8p), len(ref_seq),
        nc, cp.ctypes.data_as(_i64p), crb.ctypes.data_as(_u8p), cro.ctypes.data_as(_i64p),
        cab.ctypes.data_as(_u8p), cao.ctypes.data_as(_i64p), cg.ctypes.data_as(_i8p),
        nt, tp.ctypes.data_as(_i64p), trb.ctypes.data_as(_u8p), tro.ctypes.data_as(_i64p),
        tab.ctypes.data_as(_u8p), tao.ctypes.data_as(_i64p), tg.ctypes.data_as(_i8p),
        1 if haplotype_rescue else 0,
        call_tp.ctypes.data_as(_u8p), call_tp_gt.ctypes.data_as(_u8p),
        truth_tp.ctypes.data_as(_u8p), truth_tp_gt.ctypes.data_as(_u8p),
        idx.ctypes.data_as(_i64p), stats.ctypes.data_as(_i64p),
    )
    if rc != 0:
        return None
    return (call_tp[:nc].astype(bool), call_tp_gt[:nc].astype(bool),
            truth_tp[:nt].astype(bool), truth_tp_gt[:nt].astype(bool), idx[:nc], stats)


def interval_membership(starts: np.ndarray, ends: np.ndarray, pos: np.ndarray) -> np.ndarray | None:
    """1/0 membership of each pos in sorted non-overlapping [start, end)."""
    lib = get_lib()
    if lib is None:
        return None
    s = np.ascontiguousarray(starts, dtype=np.int64)
    e = np.ascontiguousarray(ends, dtype=np.int64)
    p = np.ascontiguousarray(pos, dtype=np.int64)
    out = np.zeros(len(p), dtype=np.uint8)
    lib.vctpu_interval_membership(
        s.ctypes.data_as(_i64p), e.ctypes.data_as(_i64p), len(s),
        p.ctypes.data_as(_i64p), len(p), out.ctypes.data_as(_u8p),
    )
    return out


def bin_features(x: np.ndarray, edges: np.ndarray) -> np.ndarray | None:
    """searchsorted-left quantile binning (NaN -> last bin), uint8 out;
    exact match for the numpy/jnp binning in models/boosting."""
    lib = get_lib()
    if lib is None or edges.shape[1] > 255:
        return None
    _f32p = ctypes.POINTER(ctypes.c_float)
    xx = np.ascontiguousarray(x, dtype=np.float32)
    ee = np.ascontiguousarray(edges, dtype=np.float32)
    n, f = xx.shape
    out = np.empty((n, f), dtype=np.uint8)
    rc = lib.vctpu_bin_features(
        xx.ctypes.data_as(_f32p), n, f,
        ee.ctypes.data_as(_f32p), ee.shape[1], out.ctypes.data_as(_u8p),
    )
    return out if rc == 0 else None


def featurize_windows(windows: np.ndarray, center: int,
                      is_indel: np.ndarray, indel_nuc: np.ndarray,
                      ref_code: np.ndarray, alt_code: np.ndarray,
                      is_snp: np.ndarray, flow_order: np.ndarray) -> dict | None:
    """Native window featurization (ops/features.py device-kernel twin);
    returns the DEVICE_FEATURES columns dict or None when unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    _f32p = ctypes.POINTER(ctypes.c_float)
    ww = np.ascontiguousarray(windows, dtype=np.uint8)
    n, w = ww.shape
    ii = np.ascontiguousarray(is_indel, dtype=np.uint8)
    nu = np.ascontiguousarray(indel_nuc, dtype=np.int32)
    rc_ = np.ascontiguousarray(ref_code, dtype=np.int32)
    ac = np.ascontiguousarray(alt_code, dtype=np.int32)
    sn = np.ascontiguousarray(is_snp, dtype=np.uint8)
    fo = np.ascontiguousarray(flow_order, dtype=np.int32)
    hl = np.empty(n, dtype=np.int32)
    hn = np.empty(n, dtype=np.int32)
    gc = np.empty(n, dtype=np.float32)
    cy = np.empty(n, dtype=np.int32)
    lm = np.empty(n, dtype=np.int32)
    rm = np.empty(n, dtype=np.int32)
    rc = lib.vctpu_featurize_windows(
        ww.ctypes.data_as(_u8p), n, w, center,
        ii.ctypes.data_as(_u8p), nu.ctypes.data_as(_i32p),
        rc_.ctypes.data_as(_i32p), ac.ctypes.data_as(_i32p),
        sn.ctypes.data_as(_u8p), fo.ctypes.data_as(_i32p),
        hl.ctypes.data_as(_i32p), hn.ctypes.data_as(_i32p),
        gc.ctypes.data_as(_f32p), cy.ctypes.data_as(_i32p),
        lm.ctypes.data_as(_i32p), rm.ctypes.data_as(_i32p),
    )
    if rc != 0:
        return None
    return {"hmer_indel_length": hl, "hmer_indel_nuc": hn, "gc_content": gc,
            "cycleskip_status": cy, "left_motif": lm, "right_motif": rm}


def featurize_gather(seq: np.ndarray, pos0: np.ndarray, radius: int,
                     is_indel, indel_nuc, ref_code, alt_code, is_snp,
                     flow_order: np.ndarray,
                     outs: tuple[np.ndarray, ...]) -> bool:
    """Fused gather+featurize over one contig (no window tensor): writes
    the six DEVICE_FEATURES columns into ``outs`` = (hmer_len, hmer_nuc,
    gc, cyc, left_motif, right_motif) — contiguous views so callers
    featurize per-contig row ranges in place. Returns False when the
    native library is unavailable or arguments are rejected."""
    lib = get_lib()
    if lib is None:
        return False
    _f32p = ctypes.POINTER(ctypes.c_float)
    s = np.ascontiguousarray(seq, dtype=np.uint8)
    p = np.ascontiguousarray(pos0, dtype=np.int64)
    ii = np.ascontiguousarray(is_indel, dtype=np.uint8)
    nu = np.ascontiguousarray(indel_nuc, dtype=np.int32)
    rc_ = np.ascontiguousarray(ref_code, dtype=np.int32)
    ac = np.ascontiguousarray(alt_code, dtype=np.int32)
    sn = np.ascontiguousarray(is_snp, dtype=np.uint8)
    fo = np.ascontiguousarray(flow_order, dtype=np.int32)
    hl, hn, gc, cy, lm, rm = outs
    for a, dt in zip(outs, (np.int32, np.int32, np.float32, np.int32, np.int32, np.int32)):
        if a.dtype != dt or not a.flags["C_CONTIGUOUS"] or len(a) != len(p):
            return False
    rc = lib.vctpu_featurize_gather(
        s.ctypes.data_as(_u8p), len(s), p.ctypes.data_as(_i64p), len(p), radius,
        ii.ctypes.data_as(_u8p), nu.ctypes.data_as(_i32p),
        rc_.ctypes.data_as(_i32p), ac.ctypes.data_as(_i32p),
        sn.ctypes.data_as(_u8p), fo.ctypes.data_as(_i32p),
        hl.ctypes.data_as(_i32p), hn.ctypes.data_as(_i32p),
        gc.ctypes.data_as(_f32p), cy.ctypes.data_as(_i32p),
        lm.ctypes.data_as(_i32p), rm.ctypes.data_as(_i32p),
    )
    return rc == 0


def gather_windows_contig(seq: np.ndarray, pos0: np.ndarray, radius: int,
                          out: np.ndarray | None = None) -> np.ndarray | None:
    """(n, 2r+1) uint8 windows over one encoded contig (out-of-range -> N).

    ``out`` lets callers gather straight into a slice of a larger window
    tensor (contiguous uint8, right shape) — no intermediate copy."""
    lib = get_lib()
    if lib is None:
        return None
    s = np.ascontiguousarray(seq, dtype=np.uint8)
    p = np.ascontiguousarray(pos0, dtype=np.int64)
    shape = (len(p), 2 * radius + 1)
    if out is None or out.shape != shape or out.dtype != np.uint8 \
            or not out.flags["C_CONTIGUOUS"]:
        out = np.empty(shape, dtype=np.uint8)
    rc = lib.vctpu_gather_windows(
        s.ctypes.data_as(_u8p), len(s), p.ctypes.data_as(_i64p), len(p),
        radius, out.ctypes.data_as(_u8p),
    )
    return out if rc == 0 else None


def format_float_info(vals: np.ndarray, prefix: bytes) -> tuple[np.ndarray, np.ndarray] | None:
    """Render b";KEY=<%g>" per non-NaN value (empty for NaN); returns
    (byte buffer, (n+1,) offsets) or None when unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    _f64p = ctypes.POINTER(ctypes.c_double)
    v = np.ascontiguousarray(vals, dtype=np.float64)
    n = len(v)
    cap = n * (len(prefix) + 32) + 64
    buf = np.empty(cap, dtype=np.uint8)
    offs = np.empty(n + 1, dtype=np.int64)
    p = np.frombuffer(prefix, dtype=np.uint8) if prefix else np.zeros(0, np.uint8)
    total = lib.vctpu_format_float_info(
        v.ctypes.data_as(_f64p), n, p.ctypes.data_as(_u8p), len(p),
        buf.ctypes.data_as(_u8p), cap, offs.ctypes.data_as(_i64p),
    )
    if total < 0:
        return None
    return buf[:total], offs


_MATRIX_DTYPES = {np.dtype(np.float32): 0, np.dtype(np.int32): 1,
                  np.dtype(np.float64): 2, np.dtype(np.uint8): 3,
                  np.dtype(np.bool_): 4}


def _marshal_cols(cols: list[np.ndarray]):
    """(void* array, dtype codes, n, keep-alive refs) for typed column
    arrays; None when any dtype/shape is unsupported. Shared by every
    column-consuming kernel so they cannot diverge on what they accept."""
    if not cols:
        return None
    arrs = []
    codes = np.empty(len(cols), dtype=np.int32)
    n = len(cols[0])
    for j, c in enumerate(cols):
        a = np.ascontiguousarray(c)
        code = _MATRIX_DTYPES.get(a.dtype)
        if code is None or a.ndim != 1 or len(a) != n:
            return None
        arrs.append(a)
        codes[j] = code
    ptrs = (ctypes.c_void_p * len(arrs))(*[a.ctypes.data for a in arrs])
    return ptrs, codes, n, arrs


def _marshal_forest(feat, thr, left, right, value, default_left):
    """Contiguous typed copies of the packed-forest arrays (shared by the
    forest-walk entry points)."""
    return (np.ascontiguousarray(feat, dtype=np.int32),
            np.ascontiguousarray(thr, dtype=np.float32),
            np.ascontiguousarray(left, dtype=np.int32),
            np.ascontiguousarray(right, dtype=np.int32),
            np.ascontiguousarray(value, dtype=np.float32),
            None if default_left is None
            else np.ascontiguousarray(default_left, dtype=np.uint8))


def build_matrix(cols: list[np.ndarray]) -> np.ndarray | None:
    """(n, f) float32 matrix from per-column arrays without numpy's
    per-column temporaries; None -> numpy fallback."""
    lib = get_lib()
    if lib is None:
        return None
    marshalled = _marshal_cols(cols)
    if marshalled is None:
        return None
    ptrs, codes, n, _arrs = marshalled
    out = np.empty((n, len(cols)), dtype=np.float32)
    _f32p = ctypes.POINTER(ctypes.c_float)
    rc = lib.vctpu_build_matrix(ptrs, codes.ctypes.data_as(_i32p), n, len(cols),
                                out.ctypes.data_as(_f32p))
    return out if rc == 0 else None


def forest_predict(x: np.ndarray, feat: np.ndarray, thr: np.ndarray,
                   left: np.ndarray, right: np.ndarray, value: np.ndarray,
                   default_left: np.ndarray | None, max_depth: int,
                   aggregation: str, base_score: float) -> np.ndarray | None:
    """Native gather-walk forest inference (models/forest.predict_score
    semantics); returns (n,) float32 scores or None when unavailable.
    ``aggregation="sum"`` returns the RAW canonical-order leaf sums
    (no mean/sigmoid) — the engine-parity path finalizes on the host."""
    lib = get_lib()
    if lib is None or aggregation not in ("mean", "logit_sum", "sum"):
        return None
    _f32p = ctypes.POINTER(ctypes.c_float)
    xx = np.ascontiguousarray(x, dtype=np.float32)
    ff, tt, ll, rr, vv, dl = _marshal_forest(feat, thr, left, right, value, default_left)
    n, f = xx.shape
    t, m = ff.shape
    out = np.empty(n, dtype=np.float32)
    rc = lib.vctpu_forest_predict(
        xx.ctypes.data_as(_f32p), n, f,
        ff.ctypes.data_as(_i32p), tt.ctypes.data_as(_f32p),
        ll.ctypes.data_as(_i32p), rr.ctypes.data_as(_i32p),
        vv.ctypes.data_as(_f32p),
        None if dl is None else dl.ctypes.data_as(_u8p),
        t, m, max_depth, {"mean": 0, "logit_sum": 1, "sum": 2}[aggregation], base_score,
        out.ctypes.data_as(_f32p),
    )
    return out if rc == 0 else None


def matrix_forest_predict(cols: list[np.ndarray], feat: np.ndarray, thr: np.ndarray,
                          left: np.ndarray, right: np.ndarray, value: np.ndarray,
                          default_left: np.ndarray | None, max_depth: int,
                          aggregation: str, base_score: float) -> np.ndarray | None:
    """Fused column->matrix->forest inference: L2-resident row tiles are
    built from the typed column pointers and walked immediately, so the
    full (n, f) float32 matrix never exists. Bit-identical scores to
    build_matrix + forest_predict; None -> caller uses the two-step path.
    ``aggregation="sum"`` returns raw canonical-order leaf sums (the
    engine-parity path finalizes on the host)."""
    lib = get_lib()
    if lib is None or aggregation not in ("mean", "logit_sum", "sum"):
        return None
    marshalled = _marshal_cols(cols)
    if marshalled is None:
        return None
    ptrs, codes, n, _arrs = marshalled
    _f32p = ctypes.POINTER(ctypes.c_float)
    ff, tt, ll, rr, vv, dl = _marshal_forest(feat, thr, left, right, value, default_left)
    t, m = ff.shape
    out = np.empty(n, dtype=np.float32)
    rc = lib.vctpu_matrix_forest_predict(
        ptrs, codes.ctypes.data_as(_i32p), n, len(cols),
        ff.ctypes.data_as(_i32p), tt.ctypes.data_as(_f32p),
        ll.ctypes.data_as(_i32p), rr.ctypes.data_as(_i32p),
        vv.ctypes.data_as(_f32p),
        None if dl is None else dl.ctypes.data_as(_u8p),
        t, m, max_depth, {"mean": 0, "logit_sum": 1, "sum": 2}[aggregation], base_score,
        out.ctypes.data_as(_f32p),
    )
    return out if rc == 0 else None


def fused_chunk_score(run_seqs: list[np.ndarray], run_bounds: np.ndarray,
                      pos0: np.ndarray, radius: int,
                      is_indel, indel_nuc, ref_code, alt_code, is_snp,
                      flow_order: np.ndarray,
                      cols: list, dev_cols: np.ndarray,
                      feat: np.ndarray, thr: np.ndarray, left: np.ndarray,
                      right: np.ndarray, value: np.ndarray,
                      default_left: np.ndarray | None, max_depth: int,
                      aggregation: str, base_score: float) -> np.ndarray | None:
    """ONE native call per chunk: contig-run window gather -> featurize ->
    L2-tiled matrix fill -> forest walk, margins out (ROADMAP item 4).

    ``run_seqs`` holds the encoded contig of each contiguous row run
    (``run_bounds``, (n_runs+1,)); a contig missing from the FASTA passes
    an empty array (all-N windows). ``cols`` lists the HOST feature
    columns in feature order with ``None`` at the six window-derived
    slots; ``dev_cols`` (6,) names each device feature's column index
    (DEVICE_FEATURES order). ``aggregation="sum"`` returns raw
    canonical-order leaf sums — the engine-parity path finalizes on the
    host, exactly like :func:`matrix_forest_predict`. Margins are
    bit-identical to the unfused reference (shared row featurize, shared
    tile fill, shared walk). None -> caller uses the unfused path."""
    lib = get_lib()
    if lib is None or aggregation not in ("mean", "logit_sum", "sum"):
        return None
    n = len(pos0)
    _f32p = ctypes.POINTER(ctypes.c_float)
    # columns: typed pointers with dtype -1 at device-feature slots
    arrs = []
    codes = np.empty(len(cols), dtype=np.int32)
    for j, c in enumerate(cols):
        if c is None:
            arrs.append(None)
            codes[j] = -1
            continue
        a = np.ascontiguousarray(c)
        code = _MATRIX_DTYPES.get(a.dtype)
        if code is None or a.ndim != 1 or len(a) != n:
            return None
        arrs.append(a)
        codes[j] = code
    col_ptrs = (ctypes.c_void_p * len(cols))(
        *[None if a is None else a.ctypes.data for a in arrs])
    # contig runs: zero-copy pointers into the encoded contigs
    seqs = [np.ascontiguousarray(_u8view(s), dtype=np.uint8) for s in run_seqs]
    seq_ptrs = (ctypes.c_void_p * max(len(seqs), 1))(
        *([s.ctypes.data for s in seqs] or [None]))
    seq_lens = np.asarray([len(s) for s in seqs], dtype=np.int64)
    bounds = np.ascontiguousarray(run_bounds, dtype=np.int64)
    p = np.ascontiguousarray(pos0, dtype=np.int64)
    ii = np.ascontiguousarray(is_indel, dtype=np.uint8)
    nu = np.ascontiguousarray(indel_nuc, dtype=np.int32)
    rc_ = np.ascontiguousarray(ref_code, dtype=np.int32)
    ac = np.ascontiguousarray(alt_code, dtype=np.int32)
    sn = np.ascontiguousarray(is_snp, dtype=np.uint8)
    fo = np.ascontiguousarray(flow_order, dtype=np.int32)
    dc = np.ascontiguousarray(dev_cols, dtype=np.int32)
    ff, tt, ll, rr, vv, dl = _marshal_forest(feat, thr, left, right, value,
                                             default_left)
    t, m = ff.shape
    out = np.empty(n, dtype=np.float32)
    with native_span("fused_chunk_score"):
        rc = lib.vctpu_fused_chunk_score(
            seq_ptrs, seq_lens.ctypes.data_as(_i64p),
            bounds.ctypes.data_as(_i64p), len(seqs),
            p.ctypes.data_as(_i64p), n, radius,
            ii.ctypes.data_as(_u8p), nu.ctypes.data_as(_i32p),
            rc_.ctypes.data_as(_i32p), ac.ctypes.data_as(_i32p),
            sn.ctypes.data_as(_u8p), fo.ctypes.data_as(_i32p),
            col_ptrs, codes.ctypes.data_as(_i32p), len(cols),
            dc.ctypes.data_as(_i32p),
            ff.ctypes.data_as(_i32p), tt.ctypes.data_as(_f32p),
            ll.ctypes.data_as(_i32p), rr.ctypes.data_as(_i32p),
            vv.ctypes.data_as(_f32p),
            None if dl is None else dl.ctypes.data_as(_u8p),
            t, m, max_depth,
            {"mean": 0, "logit_sum": 1, "sum": 2}[aggregation],
            base_score,
            out.ctypes.data_as(_f32p),
        )
    return out if rc == 0 else None


def wire_fill(dst: np.ndarray, dst_row0: int, lo: int, hi: int,
              fields: np.ndarray, *, pos, chrom_codes, contig_off, contig_len,
              radius: int, pos_fill: int, qual, gt, gq, ad, info_vals,
              info_cols: tuple[int, int, int], aclass, indel_length, indel_nuc,
              ref_code, alt_code, n_alts, extras: list[np.ndarray],
              keep_nan: bool) -> int | None:
    """Rows ``[lo, hi)`` of the native scan's arrays into rows
    ``[dst_row0, dst_row0 + hi - lo)`` of ``dst`` (``uint32[rows, W/4]``, a
    staging buffer of :mod:`variantcalling_tpu.wire`), in ONE call with the
    interpreter released. ``fields`` is ``int32[k, 3]``: kind, byte offset,
    argument (``src/vctpu_wire.cc``); ``info_cols`` the DP, SOR and AF
    columns of ``info_vals``. Returns the float32 cells written as NaN,
    counted in the same pass where ``keep_nan`` (0 otherwise); None when the
    library is missing."""
    lib = get_lib()
    if lib is None:
        return None

    keep: list[np.ndarray] = []  # alive until the call returns

    def c(a, dtype):
        a = np.ascontiguousarray(a, dtype=dtype)
        keep.append(a)
        return a.ctypes.data

    if not (dst.dtype == np.uint32 and dst.ndim == 2 and dst.flags.c_contiguous
            and 0 <= dst_row0 and dst_row0 + (hi - lo) <= dst.shape[0]
            and hi <= len(pos)):
        raise ValueError("wire_fill: rows outside the staging buffer or the table")
    ex = (ctypes.c_void_p * max(len(extras), 1))(
        *[c(e, np.float32) for e in extras])
    nans = ctypes.c_int64(0)
    rc = lib.vctpu_wire_fill(
        dst.ctypes.data, dst_row0, 4 * dst.shape[1], lo, hi,
        c(fields, np.int32), len(fields),
        c(pos, np.int64), c(chrom_codes, np.int32),
        c(contig_off, np.int64), c(contig_len, np.int64), len(contig_off),
        radius, pos_fill,
        c(qual, np.float64), c(gt, np.int8), c(gq, np.float32), c(ad, np.float32),
        c(info_vals, np.float64), info_vals.shape[1], *info_cols,
        c(aclass, np.uint8), c(indel_length, np.int32), c(indel_nuc, np.int32),
        c(ref_code, np.int32), c(alt_code, np.int32), c(n_alts, np.int32),
        ctypes.addressof(ex), len(extras), int(keep_nan), ctypes.addressof(nans))
    if rc != hi - lo:
        raise ValueError(f"wire_fill: the native fill refused its arguments ({rc})")
    return nans.value


def tabix_chunk_facts(body, chrom_codes, pos, ref_len):
    """A rendered chunk's share of a tabix index (``src/vctpu_tabix.cc``;
    :func:`variantcalling_tpu.io.tabix.chunk_index_facts` names the parts),
    in ONE call with the interpreter released: ``(ends, contig_first,
    run_first, run_contig, run_bin, win_contig, win, win_start)``, all
    int64. None when the library is missing or the chunk cannot vouch for
    the index (``body`` is not one line a record, records out of order)."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(pos)
    text = np.ascontiguousarray(_u8view(body))
    codes = np.ascontiguousarray(chrom_codes, dtype=np.int32)
    pos = np.ascontiguousarray(pos, dtype=np.int64)
    ref_len = np.ascontiguousarray(ref_len, dtype=np.int32)
    if not len(codes) == len(ref_len) == n:
        raise ValueError("tabix_chunk_facts: columns of different lengths")
    # a window a record and its REF's further ones: a chunk whose REFs
    # reach over more than this is left to the second pass
    win_cap = 2 * n + 64
    out = [np.empty(n, dtype=np.int64) for _ in range(5)] + \
          [np.empty(win_cap, dtype=np.int64) for _ in range(3)]
    counts = np.zeros(3, dtype=np.int64)
    rc = lib.vctpu_tabix_chunk_facts(
        text.ctypes.data, len(text), n, codes.ctypes.data, pos.ctypes.data,
        ref_len.ctypes.data, *[a.ctypes.data for a in out], win_cap,
        counts.ctypes.data)
    if rc != 0:
        return None
    n_contig, n_run, n_win = counts.tolist()
    sizes = (n, n_contig, n_run, n_run, n_run, n_win, n_win, n_win)
    return tuple(a[:k].copy() if k < len(a) else a for a, k in zip(out, sizes))


def fasta_encode(raw: np.ndarray, line_bases: int, line_width: int,
                 length: int, out: np.ndarray | None = None) -> np.ndarray | None:
    """Threaded FASTA body encode (newline strip + ACGT->0..3 table, else 4).

    ``raw`` is the contig's byte region starting at its .fai offset; the
    result is byte-identical to the numpy reshape+lookup fallback in
    io/fasta._encode_contig. ``out`` lets callers encode into a slice of a
    preallocated whole-genome buffer. None -> numpy fallback."""
    lib = get_lib()
    if lib is None:
        return None
    src = np.ascontiguousarray(_u8view(raw))
    if out is None or len(out) != length or out.dtype != np.uint8 \
            or not out.flags["C_CONTIGUOUS"]:
        out = np.empty(length, dtype=np.uint8)
    rc = lib.vctpu_fasta_encode(
        src.ctypes.data_as(_u8p), len(src),
        int(line_bases), int(line_width), int(length),
        out.ctypes.data_as(_u8p),
    )
    return out if rc == 0 else None


def coverage_stats(data: np.ndarray, window: int, max_bin: int = 1000,
                   from_diffs: bool = False) -> tuple[np.ndarray, np.ndarray] | None:
    """Fused single-pass coverage reduce: (per-window f32 means,
    (max_bin+1,) int64 clipped histogram). ``from_diffs`` treats ``data``
    as a difference array (running cumsum = depth) so the bam/cram depth
    path reduces without materializing the depth vector. None -> fallback."""
    lib = get_lib()
    if lib is None:
        return None
    d = np.ascontiguousarray(data, dtype=np.int32)
    n = len(d)
    n_win = -(-n // window) if n else 0
    means = np.empty(n_win, dtype=np.float32)
    hist = np.empty(max_bin + 1, dtype=np.int64)
    _f32p = ctypes.POINTER(ctypes.c_float)
    rc = lib.vctpu_coverage_stats(
        d.ctypes.data_as(_i32p), n, int(window), int(max_bin),
        int(bool(from_diffs)),
        means.ctypes.data_as(_f32p), hist.ctypes.data_as(_i64p),
    )
    if rc != 0:
        return None
    return means, hist


def gbt_fit(binned: np.ndarray, y: np.ndarray, w: np.ndarray | None,
            n_trees: int, depth: int, n_bins: int,
            learning_rate: float, reg_lambda: float, min_child_weight: float,
            base_score: float):
    """Native histogram-GBT fit (src/vctpu_gbt.cc) — the CPU-fallback twin
    of models/boosting's jitted trainer (partitioned samples + sibling-
    subtraction histograms). Returns (feats, bins, leaves) shaped exactly
    like the jitted trainer's outputs, or None when the library is
    unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    _f32p = ctypes.POINTER(ctypes.c_float)
    bn = np.ascontiguousarray(binned, dtype=np.uint8)
    yy = np.ascontiguousarray(y, dtype=np.float32)
    ww = None if w is None else np.ascontiguousarray(w, dtype=np.float32)
    n, f = bn.shape
    leaves = 1 << depth
    feats = np.empty((n_trees, depth, leaves), dtype=np.int32)
    bins = np.empty((n_trees, depth, leaves), dtype=np.int32)
    vals = np.empty((n_trees, leaves), dtype=np.float32)
    rc = lib.vctpu_gbt_fit(
        bn.ctypes.data_as(_u8p), yy.ctypes.data_as(_f32p),
        None if ww is None else ww.ctypes.data_as(_f32p),
        n, f, n_bins, n_trees, depth,
        learning_rate, reg_lambda, min_child_weight, base_score,
        feats.ctypes.data_as(_i32p), bins.ctypes.data_as(_i32p),
        vals.ctypes.data_as(_f32p),
    )
    if rc != 0:
        return None
    return feats, bins, vals
