"""Columnar VCF/gVCF reader and writer (host-side ingest layer).

The reference reads VCFs through pysam/htslib one record at a time
(e.g. compress_gvcf.py:19, convert_haploid_regions.py:3) and through
``ugbio_core.vcfbed.vcftools.get_vcf_df`` into pandas. This framework's
ingest instead produces a **columnar** :class:`VariantTable` — numpy arrays
over all records — which featurization turns into padded device tensors.
The original tab-separated fields are retained so writers can rewrite only
the columns a pipeline touched (FILTER/INFO/FORMAT), keeping untouched
bytes identical to the input.

BGZF-compressed inputs (``.gz``) are readable via Python's gzip (BGZF is a
gzip-compatible framing); a C++ BGZF codec accelerates this path when built
(variantcalling_tpu/native).
"""

from __future__ import annotations

import gzip
import io as _io
import os
from dataclasses import dataclass, field

import numpy as np

from variantcalling_tpu import knobs, obs
from variantcalling_tpu.utils.trace import stage

MISSING = "."


# above this compressed size, keep constant-memory streaming via gzip
# rather than whole-file native inflation (shared by io/bed.py)
NATIVE_INFLATE_MAX_BYTES = 512 << 20


def _open_text(path: str):
    if str(path).endswith(".gz") or str(path).endswith(".bgz"):
        from variantcalling_tpu import native

        if native.available() and os.path.getsize(path) <= NATIVE_INFLATE_MAX_BYTES:
            with open(path, "rb") as fh:
                raw = fh.read()
            data = native.bgzf_decompress(raw)
            if data is not None:
                return _io.TextIOWrapper(_io.BytesIO(data), encoding="utf-8")
        return _io.TextIOWrapper(gzip.open(path, "rb"), encoding="utf-8")
    return open(path, "rt", encoding="utf-8")


@dataclass
class VcfHeader:
    """Parsed VCF header: meta lines (verbatim), contigs, field definitions, samples."""

    lines: list[str] = field(default_factory=list)  # '##...' lines, no newline
    samples: list[str] = field(default_factory=list)
    contigs: list[str] = field(default_factory=list)
    contig_lengths: dict[str, int] = field(default_factory=dict)
    infos: dict[str, dict] = field(default_factory=dict)  # id -> {Number, Type, Description}
    formats: dict[str, dict] = field(default_factory=dict)
    filters: dict[str, str] = field(default_factory=dict)

    @staticmethod
    def _parse_structured(line: str) -> dict:
        # ##INFO=<ID=DP,Number=1,Type=Integer,Description="...">
        inner = line[line.index("<") + 1 : line.rindex(">")]
        out: dict[str, str] = {}
        key = ""
        val = ""
        in_quotes = False
        target = "key"
        for ch in inner:
            if target == "key":
                if ch == "=":
                    target = "val"
                else:
                    key += ch
            else:
                if ch == '"':
                    in_quotes = not in_quotes
                    val += ch
                elif ch == "," and not in_quotes:
                    out[key] = val.strip('"')
                    key, val, target = "", "", "key"
                else:
                    val += ch
        if key:
            out[key] = val.strip('"')
        return out

    def add_meta_line(self, line: str) -> None:
        line = line.rstrip("\n")
        self.lines.append(line)
        if line.startswith("##contig="):
            d = self._parse_structured(line)
            name = d.get("ID", "")
            self.contigs.append(name)
            if "length" in d:
                try:
                    self.contig_lengths[name] = int(d["length"])
                except ValueError:
                    pass
        elif line.startswith("##INFO="):
            d = self._parse_structured(line)
            self.infos[d.get("ID", "")] = d
        elif line.startswith("##FORMAT="):
            d = self._parse_structured(line)
            self.formats[d.get("ID", "")] = d
        elif line.startswith("##FILTER="):
            d = self._parse_structured(line)
            self.filters[d.get("ID", "")] = d.get("Description", "")

    def ensure_info(self, info_id: str, number: str, info_type: str, description: str) -> None:
        if info_id not in self.infos:
            line = f'##INFO=<ID={info_id},Number={number},Type={info_type},Description="{description}">'
            self.add_meta_line(line)

    def ensure_format(self, fmt_id: str, number: str, fmt_type: str, description: str) -> None:
        if fmt_id not in self.formats:
            line = f'##FORMAT=<ID={fmt_id},Number={number},Type={fmt_type},Description="{description}">'
            self.add_meta_line(line)

    def ensure_filter(self, filter_id: str, description: str) -> None:
        if filter_id not in self.filters:
            self.add_meta_line(f'##FILTER=<ID={filter_id},Description="{description}">')

    def column_header(self) -> str:
        cols = ["#CHROM", "POS", "ID", "REF", "ALT", "QUAL", "FILTER", "INFO"]
        if self.samples:
            cols += ["FORMAT", *self.samples]
        return "\t".join(cols)


@dataclass
class NativeAux:
    """Products of the native one-pass VCF scan (native/src vctpu_vcf_parse).

    Row-aligned with the owning :class:`VariantTable`; ``buf`` is the shared
    uncompressed text buffer, spans are [start, end) byte offsets into it.
    Serves three purposes: (1) numeric caches (FORMAT GT/GQ/DP/AD, hot INFO
    keys, allele classification) so featurization never re-parses strings,
    (2) lazy FORMAT/sample materialization, (3) byte-slice VCF writeback.
    """

    buf: np.ndarray  # uint8 text
    line_spans: np.ndarray  # (n, 2)
    tail_spans: np.ndarray  # (n, 2): FORMAT..line-end (empty span if no samples)
    info_spans: np.ndarray  # (n, 2)
    filter_spans: np.ndarray  # (n, 2)
    gt: np.ndarray  # (n, 2) int8
    gt_phased: np.ndarray  # (n,) uint8
    gq: np.ndarray  # (n,) float32, NaN missing
    dp_fmt: np.ndarray  # (n,) float32
    ad: np.ndarray  # (n, 3) float32: ref, alt1, positive-total
    info_vals: np.ndarray  # (n, len(info_keys)) float64
    info_keys: tuple
    alle: dict  # aclass/indel_length/indel_nuc/ref_code/alt_code/n_alts/ref_len
    has_format: bool = True  # False after drop_format: no sample data, no buffer

    def take(self, keep: np.ndarray) -> "NativeAux":
        return NativeAux(
            buf=self.buf,
            has_format=self.has_format,
            line_spans=self.line_spans[keep],
            tail_spans=self.tail_spans[keep],
            info_spans=self.info_spans[keep],
            filter_spans=self.filter_spans[keep],
            gt=self.gt[keep],
            gt_phased=self.gt_phased[keep],
            gq=self.gq[keep],
            dp_fmt=self.dp_fmt[keep],
            ad=self.ad[keep],
            info_vals=self.info_vals[keep],
            info_keys=self.info_keys,
            alle={k: v[keep] for k, v in self.alle.items()},
        )


class FactorizedColumn:
    """Low-cardinality string column held as (codes, uniques).

    The filter pipeline's FILTER column has <=6 distinct values over 5M
    records; carrying integer codes end to end skips the ~1.3s
    pd.factorize of an object array on the writeback hot path. Quacks
    enough like an object array (len/iter/getitem/== str/np.asarray) that
    report code and tests can treat it as one.
    """

    __slots__ = ("codes", "uniques")

    def __init__(self, codes: np.ndarray, uniques: list[str]):
        self.codes = np.ascontiguousarray(codes, dtype=np.int32)
        self.uniques = list(uniques)

    def __len__(self) -> int:
        return len(self.codes)

    def __iter__(self):
        u = self.uniques
        return (u[c] for c in self.codes)

    def __getitem__(self, i):
        if isinstance(i, (int, np.integer)):
            return self.uniques[self.codes[i]]
        return FactorizedColumn(self.codes[i], self.uniques)

    def __eq__(self, other):  # vectorized `filters == "PASS"`
        if isinstance(other, str):
            try:
                return self.codes == self.uniques.index(other)
            except ValueError:
                return np.zeros(len(self.codes), dtype=bool)
        return NotImplemented

    def __ne__(self, other):
        eq = self.__eq__(other)
        return NotImplemented if eq is NotImplemented else ~eq

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.uniques, dtype=object)[self.codes]

    def to_object(self) -> np.ndarray:
        return self.__array__()


class _LazyCols:
    """Deferred string columns: (name -> (n,2) span array) into a shared buffer.

    Materialization decodes buffer slices once per column on first access;
    row subsets just subset the span arrays, so a pipeline that never
    touches REF/ALT/INFO strings never pays for them. The backing is the
    SAME bytes object the NativeAux buffer views (np.frombuffer) — or a
    uint8 array/memmap on the chunked-ingest path — so laziness adds no
    memory beyond the span arrays.
    """

    __slots__ = ("buf", "spans")

    def __init__(self, buf, spans: dict):
        self.buf = buf
        self.spans = spans

    def take(self, keep) -> "_LazyCols":
        return _LazyCols(self.buf, {k: v[keep] for k, v in self.spans.items()})

    def materialize(self, name: str) -> np.ndarray:
        spans = self.spans[name].tolist()
        buf = self.buf
        if isinstance(buf, np.ndarray):
            # one decode-side copy per chunk, cached so sibling columns
            # (vid/ref/alt/filters/info) don't re-copy the same buffer
            self.buf = buf = bytes(memoryview(buf))
        out = np.empty(len(spans), dtype=object)
        for i, (a, b) in enumerate(spans):
            out[i] = buf[a:b].decode("latin-1")
        return out


class VariantTable:
    """Columnar view of a VCF: one numpy array per column over all records.

    String-ish columns are object arrays; ragged per-record structures
    (ALTs, per-sample fields) stay host-side until featurization pads them
    into device tensors. ``aux`` (native ingest only) carries pre-parsed
    numeric caches + raw byte spans; use :meth:`subset` for row filtering so
    it stays aligned. ``fmt_keys``/``sample_cols`` are lazy on the native
    path: reading them materializes the strings from the raw buffer.
    """

    def __init__(
        self,
        header: VcfHeader,
        chrom: np.ndarray,
        pos: np.ndarray,
        vid: np.ndarray,
        ref: np.ndarray,
        alt: np.ndarray,
        qual: np.ndarray,
        filters: np.ndarray,
        info: np.ndarray,
        fmt_keys: np.ndarray | None = None,
        sample_cols: np.ndarray | None = None,
        aux: NativeAux | None = None,
        lazy_cols: "_LazyCols | None" = None,
        chrom_codes: np.ndarray | None = None,
        chrom_names: np.ndarray | None = None,
    ):
        self.header = header
        self.chrom = chrom
        #: native-ingest bonus: the scan's integer CHROM dictionary codes
        #: (+ name table), so per-chunk contig grouping (featurize
        #: _contig_runs) never re-factorizes 1M Python strings on the
        #: scoring hot path
        self.chrom_codes = chrom_codes
        self.chrom_names = chrom_names
        self.pos = pos
        self._vid = vid
        self._ref = ref
        self._alt = alt
        self.qual = qual
        self._filters = filters
        self._info = info
        self._fmt_keys = fmt_keys
        self._sample_cols = sample_cols
        self.aux = aux
        self._lazy = lazy_cols

    def __len__(self) -> int:
        return len(self.pos)

    def _col(self, slot: str):
        v = getattr(self, "_" + slot)
        if v is None and self._lazy is not None:
            v = self._lazy.materialize(slot)
            setattr(self, "_" + slot, v)
        return v

    # The five record string columns are lazy on the native-ingest path:
    # spans into the shared text buffer until first touched (the filter
    # pipeline never touches REF/ALT/INFO strings — allele classes come from
    # the native numeric cache and writeback splices byte spans).
    vid = property(lambda s: s._col("vid"), lambda s, v: setattr(s, "_vid", v))
    ref = property(lambda s: s._col("ref"), lambda s, v: setattr(s, "_ref", v))
    alt = property(lambda s: s._col("alt"), lambda s, v: setattr(s, "_alt", v))
    filters = property(lambda s: s._col("filters"), lambda s, v: setattr(s, "_filters", v))
    info = property(lambda s: s._col("info"), lambda s, v: setattr(s, "_info", v))

    @property
    def n_samples(self) -> int:
        return len(self.header.samples)

    @property
    def fmt_keys(self) -> np.ndarray | None:
        if self._fmt_keys is None and self.aux is not None and self.n_samples > 0:
            self.materialize_format()
        return self._fmt_keys

    @fmt_keys.setter
    def fmt_keys(self, v) -> None:
        self._fmt_keys = v

    @property
    def sample_cols(self) -> np.ndarray | None:
        if self._sample_cols is None and self.aux is not None and self.n_samples > 0:
            self.materialize_format()
        return self._sample_cols

    @sample_cols.setter
    def sample_cols(self, v) -> None:
        self._sample_cols = v

    @property
    def format_materialized(self) -> bool:
        """True when FORMAT/sample strings exist in memory (possibly edited)."""
        return self._fmt_keys is not None

    def subset(self, keep: np.ndarray) -> "VariantTable":
        """Row-subset every column (and aux) by a boolean/index array."""
        lazy_pending = self._lazy is not None and any(
            getattr(self, "_" + f) is None for f in ("vid", "ref", "alt", "filters", "info")
        )
        return VariantTable(
            header=self.header,
            chrom=self.chrom[keep],
            chrom_codes=self.chrom_codes[keep] if self.chrom_codes is not None else None,
            chrom_names=self.chrom_names,
            pos=self.pos[keep],
            vid=self._vid[keep] if self._vid is not None else None,
            ref=self._ref[keep] if self._ref is not None else None,
            alt=self._alt[keep] if self._alt is not None else None,
            qual=self.qual[keep],
            filters=self._filters[keep] if self._filters is not None else None,
            info=self._info[keep] if self._info is not None else None,
            lazy_cols=self._lazy.take(keep) if lazy_pending else None,
            fmt_keys=self._fmt_keys[keep] if self._fmt_keys is not None else None,
            sample_cols=self._sample_cols[keep] if self._sample_cols is not None else None,
            aux=self.aux.take(keep) if self.aux is not None else None,
        )

    def materialize_format(self) -> None:
        """Fill fmt_keys/sample_cols from the native tail spans (lazy path)."""
        if self._fmt_keys is not None or self.aux is None or self.n_samples == 0:
            return
        if not self.aux.has_format or self.aux.buf is None:
            return  # drop_format ingest: no sample data, mirroring the Python path
        text = self.aux.buf.tobytes().decode("latin-1")
        spans = self.aux.tail_spans.tolist()
        n = len(self)
        k = self.n_samples
        fmt = np.empty(n, dtype=object)
        sc = np.empty((n, k), dtype=object)
        for i, (a, b) in enumerate(spans):
            parts = text[a:b].split("\t") if b > a else [MISSING]
            fmt[i] = parts[0]
            for s in range(k):
                sc[i, s] = parts[1 + s] if 1 + s < len(parts) else MISSING
        self._fmt_keys = fmt
        self._sample_cols = sc

    # -- derived columnar views ------------------------------------------------

    def alt_lists(self) -> list[list[str]]:
        return [[] if a in (MISSING, "") else a.split(",") for a in self.alt]

    def n_alts(self) -> np.ndarray:
        if self.aux is not None:
            return self.aux.alle["n_alts"].copy()
        return np.fromiter(
            (0 if a in (MISSING, "") else a.count(",") + 1 for a in self.alt),
            dtype=np.int32,
            count=len(self),
        )

    def filter_sets(self) -> list[set[str]]:
        return [set() if f in (MISSING, "", "PASS") else set(f.split(";")) for f in self.filters]

    def info_field(self, name: str, dtype=np.float64, missing=np.nan, index: int = 0) -> np.ndarray:
        """Vectorized extraction of one INFO key (scalar or ``index``-th element)."""
        if self.aux is not None and index == 0 and name in self.aux.info_keys:
            vals = self.aux.info_vals[:, self.aux.info_keys.index(name)]
            if np.issubdtype(np.dtype(dtype) if not isinstance(dtype, type) else dtype, np.floating) or dtype is float:
                out = vals.astype(dtype)
                if not (isinstance(missing, float) and np.isnan(missing)):
                    out = np.where(np.isnan(vals), missing, out)
                return out
            out = np.full(len(self), missing, dtype=dtype)
            ok = ~np.isnan(vals)
            out[ok] = vals[ok].astype(dtype)
            return out
        out = np.full(len(self), missing, dtype=dtype)
        key_eq = name + "="
        for i, s in enumerate(self.info):
            if s is None or s == MISSING:
                continue
            for part in s.split(";"):
                if part.startswith(key_eq):
                    v = part[len(key_eq) :]
                    if "," in v:
                        v = v.split(",")[index]
                    if v != MISSING and v != "":
                        try:
                            out[i] = dtype(v) if not isinstance(dtype, type) else np.dtype(dtype).type(v)
                        except (ValueError, TypeError):
                            pass
                    break
        return out

    def info_flag(self, name: str) -> np.ndarray:
        out = np.zeros(len(self), dtype=bool)
        for i, s in enumerate(self.info):
            if s is None or s == MISSING:
                continue
            for part in s.split(";"):
                if part == name or part.startswith(name + "="):
                    out[i] = True
                    break
        return out

    def format_field(self, name: str, sample: int = 0) -> list[str | None]:
        """Raw string of one FORMAT key for one sample, per record (None if absent)."""
        if self.fmt_keys is None or self.sample_cols is None:  # property materializes lazily
            return [None] * len(self)
        out: list[str | None] = []
        for i in range(len(self)):
            keys = self.fmt_keys[i]
            if not keys or keys == MISSING:
                out.append(None)
                continue
            try:
                idx = keys.split(":").index(name)
            except ValueError:
                out.append(None)
                continue
            vals = self.sample_cols[i][sample].split(":")
            out.append(vals[idx] if idx < len(vals) else None)
        return out

    def genotypes(self, sample: int = 0) -> np.ndarray:
        """(n, 2) int8 diploid genotype; -1 for missing/haploid-second slot; phasing dropped."""
        if sample == 0 and self.aux is not None:
            return self.aux.gt.copy()  # cache stays pristine if callers edit
        gt_strs = self.format_field("GT", sample)
        out = np.full((len(self), 2), -1, dtype=np.int8)
        for i, g in enumerate(gt_strs):
            if not g:
                continue
            parts = g.replace("|", "/").split("/")
            for j, p in enumerate(parts[:2]):
                if p not in (MISSING, ""):
                    out[i, j] = int(p)
        return out

    def format_numeric(self, name: str, sample: int = 0, max_len: int | None = None, missing=-1) -> np.ndarray:
        """Padded (n, max_len) numeric tensor of a comma-listed FORMAT field (e.g. PL, AD)."""
        if sample == 0 and self.aux is not None and name in ("GQ", "DP") and max_len in (None, 1):
            vals = self.aux.gq if name == "GQ" else self.aux.dp_fmt
            out = vals.astype(np.float64)[:, None]
            if not (isinstance(missing, float) and np.isnan(missing)):
                out = np.where(np.isnan(out), missing, out)
            return out
        raw = self.format_field(name, sample)
        split = [r.split(",") if r not in (None, MISSING, "") else [] for r in raw]
        if max_len is None:
            max_len = max((len(s) for s in split), default=0)
        out = np.full((len(self), max_len), missing, dtype=np.float64)
        for i, vals in enumerate(split):
            for j, v in enumerate(vals[:max_len]):
                if v not in (MISSING, ""):
                    try:
                        out[i, j] = float(v)
                    except ValueError:
                        pass
        return out


def parse_header_bytes(bufb: bytes) -> tuple[VcfHeader, int]:
    """Parse the '#' header region of a VCF byte buffer.

    Returns (header, offset of the first record line). Shared by the
    whole-file native ingest and the chunked streaming reader so the two
    can never disagree on header handling.
    """
    header = VcfHeader()
    off, n = 0, len(bufb)
    while off < n:
        nl = bufb.find(b"\n", off)
        end = nl if nl >= 0 else n
        if end > off and bufb[off : off + 1] != b"#":
            break
        line = bufb[off:end].decode("utf-8", "replace")
        if line.startswith("##"):
            header.add_meta_line(line)
        elif line.startswith("#"):
            cols = line.rstrip("\r").split("\t")
            if len(cols) > 9:
                header.samples = cols[9:]
        off = end + 1
    return header, min(off, n)


def _read_vcf_native(path: str, drop_format: bool = False) -> VariantTable | None:
    """Whole-file ingest through the C++ one-pass scanner (native/src).

    Numeric columns, sample-0 FORMAT numerics, hot INFO keys and allele
    classes come out of the scan as flat arrays; only the short string
    columns are materialized here. FORMAT/sample strings stay lazy
    (NativeAux spans). Returns None when the native library is unavailable
    (caller uses the streaming Python parser).
    """
    from variantcalling_tpu import native

    if not native.available():
        return None
    if str(path).endswith((".gz", ".bgz")):
        if os.path.getsize(path) > NATIVE_INFLATE_MAX_BYTES:
            return None
        with open(path, "rb") as fh:
            raw = fh.read()
        arr = native.bgzf_decompress_array(raw)
        if arr is None:
            return None
        bufb = arr.tobytes()
    else:
        with open(path, "rb") as fh:
            bufb = fh.read()
    buf_np = np.frombuffer(bufb, dtype=np.uint8)

    header, _ = parse_header_bytes(bufb)

    parsed = native.vcf_parse(buf_np, len(header.samples))
    if parsed is None:
        return None
    return _table_from_parsed(parsed, header, bufb, buf_np, drop_format)


def _table_from_parsed(parsed: dict, header: VcfHeader, bufb, buf_np: np.ndarray,
                       drop_format: bool) -> VariantTable:
    """Assemble a VariantTable from a native scan result over ``buf_np``.

    ``bufb`` backs the lazy string columns (bytes for whole-file ingest, a
    uint8 view for chunked ingest). Shared by :func:`_read_vcf_native` and
    :class:`VcfChunkReader` so whole-file and chunked tables are built
    identically.
    """
    nrec = parsed["n"]

    # the five record string columns stay lazy (spans into the shared byte
    # buffer): the hot pipelines never touch them, so ingest skips ~70% of
    # its old wallclock and laziness costs no extra buffer copy
    lazy = _LazyCols(
        bufb,
        {
            "vid": parsed["id_spans"],
            "ref": parsed["ref_spans"],
            "alt": parsed["alt_spans"],
            "filters": parsed["filter_spans"],
            "info": parsed["info_spans"],
        },
    )

    from variantcalling_tpu import native

    chrom_names = np.array(parsed["chroms"] + [""], dtype=object)
    if drop_format:
        # mirror the Python path: no sample data retained, and release the
        # text buffer (numeric/INFO/allele caches are kept — they are small)
        aux = NativeAux(
            buf=None,
            has_format=False,
            line_spans=np.zeros((nrec, 2), dtype=np.int64),
            tail_spans=np.zeros((nrec, 2), dtype=np.int64),
            info_spans=np.zeros((nrec, 2), dtype=np.int64),
            filter_spans=np.zeros((nrec, 2), dtype=np.int64),
            gt=np.full((nrec, 2), -1, dtype=np.int8),
            gt_phased=np.zeros(nrec, dtype=np.uint8),
            gq=np.full(nrec, np.nan, dtype=np.float32),
            dp_fmt=np.full(nrec, np.nan, dtype=np.float32),
            ad=np.full((nrec, 3), np.nan, dtype=np.float32),
            info_vals=parsed["info_vals"],
            info_keys=tuple(native.VCF_INFO_KEYS),
            alle={
                k: parsed[k]
                for k in ("aclass", "indel_length", "indel_nuc", "ref_code", "alt_code", "n_alts", "ref_len")
            },
        )
    else:
        aux = NativeAux(
            buf=buf_np,
            line_spans=parsed["line_spans"],
            tail_spans=parsed["tail_spans"],
            info_spans=parsed["info_spans"],
            filter_spans=parsed["filter_spans"],
            gt=parsed["gt"],
            gt_phased=parsed["gt_phased"],
            gq=parsed["gq"],
            dp_fmt=parsed["dp_fmt"],
            ad=parsed["ad"],
            info_vals=parsed["info_vals"],
            info_keys=tuple(native.VCF_INFO_KEYS),
            alle={
                k: parsed[k]
                for k in ("aclass", "indel_length", "indel_nuc", "ref_code", "alt_code", "n_alts", "ref_len")
            },
        )
    if drop_format:
        # drop_format's contract is "release the buffer": materialize the
        # five string columns eagerly so nothing pins the uncompressed text
        eager = {k: lazy.materialize(k) for k in ("vid", "ref", "alt", "filters", "info")}
        lazy = None
    else:
        eager = dict.fromkeys(("vid", "ref", "alt", "filters", "info"))
    return VariantTable(
        header=header,
        chrom=chrom_names[parsed["chrom_codes"]] if nrec else np.empty(0, dtype=object),
        chrom_codes=np.ascontiguousarray(parsed["chrom_codes"]) if nrec else None,
        chrom_names=chrom_names,
        pos=parsed["pos"],
        vid=eager["vid"],
        ref=eager["ref"],
        alt=eager["alt"],
        qual=parsed["qual"],
        filters=eager["filters"],
        info=eager["info"],
        lazy_cols=lazy,
        aux=aux,
    )


def read_vcf(
    path: str,
    region: tuple[str, int, int] | None = None,
    drop_format: bool = False,
) -> VariantTable:
    """Parse a VCF/gVCF (.vcf or .vcf.gz) into a :class:`VariantTable`.

    Whole-file reads go through the native C++ scanner when built
    (:func:`_read_vcf_native`); ``region`` is (chrom, start_1based,
    end_inclusive), served from the sibling ``.tbi`` index when present
    (io/tabix — only covering BGZF blocks are inflated), streaming filter
    otherwise.
    """
    if region is None:
        table = _read_vcf_native(path, drop_format=drop_format)
        if table is not None:
            return table
    header = VcfHeader()
    chrom: list[str] = []
    pos: list[int] = []
    vid: list[str] = []
    ref: list[str] = []
    alt: list[str] = []
    qual: list[float] = []
    filt: list[str] = []
    info: list[str] = []
    fmt_keys: list[str] = []
    sample_cols: list[tuple[str, ...]] = []
    n_samples = 0

    indexed_lines = None
    if region is not None and str(path).endswith(".gz") and os.path.exists(str(path) + ".tbi"):
        from variantcalling_tpu.io.tabix import read_region_lines

        indexed_lines = read_region_lines(str(path), region[0], region[1] - 1, region[2])

    def _indexed_source(fh):
        # header from the file head, records straight from covering blocks
        for line in fh:
            if not line.startswith("#"):
                break
            yield line
        for line in indexed_lines:
            yield line + "\n"

    if indexed_lines is not None:
        # stream just the header (stops at the first record); the records
        # themselves come from the index's covering blocks only
        opener = _io.TextIOWrapper(gzip.open(path, "rb"), encoding="utf-8")
    else:
        opener = _open_text(path)
    with opener as fh:
        source = _indexed_source(fh) if indexed_lines is not None else fh
        for line in source:
            if line.startswith("##"):
                header.add_meta_line(line)
                continue
            if line.startswith("#"):
                cols = line.rstrip("\n").split("\t")
                if len(cols) > 9:
                    header.samples = cols[9:]
                n_samples = len(header.samples)
                continue
            parts = line.rstrip("\n").split("\t")
            if region is not None:
                if parts[0] != region[0]:
                    continue
                p = int(parts[1])
                if p < region[1] or p > region[2]:
                    continue
            chrom.append(parts[0])
            pos.append(int(parts[1]))
            vid.append(parts[2])
            ref.append(parts[3])
            alt.append(parts[4])
            qual.append(float(parts[5]) if parts[5] != MISSING else np.nan)
            filt.append(parts[6])
            info.append(parts[7] if len(parts) > 7 else MISSING)
            if n_samples and not drop_format:
                fmt_keys.append(parts[8] if len(parts) > 8 else MISSING)
                sample_cols.append(tuple(parts[9 : 9 + n_samples]))

    def obj(x):
        a = np.empty(len(x), dtype=object)
        a[:] = x
        return a

    table = VariantTable(
        header=header,
        chrom=obj(chrom),
        pos=np.asarray(pos, dtype=np.int64),
        vid=obj(vid),
        ref=obj(ref),
        alt=obj(alt),
        qual=np.asarray(qual, dtype=np.float64),
        filters=obj(filt),
        info=obj(info),
    )
    if n_samples and not drop_format:
        table.fmt_keys = obj(fmt_keys)
        sc = np.empty((len(sample_cols), n_samples), dtype=object)
        for i, tup in enumerate(sample_cols):
            sc[i, :] = tup
        table.sample_cols = sc
    return table


#: default streaming chunk size (bytes of VCF text per pipeline item);
#: ~8 MB is ~40-120K records of a typical callset. The parallel host-IO
#: layout re-tuned this down from 16 MB: chunks are now the fan-out
#: granularity of the worker pool, and finer chunks pack the ordered
#: window better (1M leg: 8 MB ≈ 1.19M v/s vs 16 MB ≈ 1.08M; 5M leg:
#: 1.15M vs 1.13M on the 2-core container) while a few in-flight chunks
#: still bound pipeline memory at O(100 MB)
STREAM_CHUNK_BYTES = 8 << 20


class _ParallelBgzfStream:
    """File-like ``read(n)`` over a BGZF file, inflated shard-parallel.

    BGZF members are independent deflate streams, so the compressed file
    splits at block boundaries (:func:`bgzf.scan_block_spans`) into
    shards of ~``VCTPU_IO_SHARD_BYTES`` decompressed bytes each, inflated
    on the IO worker pool and reassembled strictly in file order — the
    decompressed byte stream is identical to a serial ``gzip.open`` read,
    so chunk boundaries (and therefore journal resume identity) cannot
    depend on the worker count. Raises ``ValueError`` when the file is
    not cleanly BGZF-framed (plain gzip): callers fall back to the serial
    stream.
    """

    def __init__(self, path: str, pool, spans=None):
        from variantcalling_tpu.io import bgzf as bgzf_mod

        size = os.path.getsize(path)
        self.path = str(path)
        self._mm = (np.memmap(path, dtype=np.uint8, mode="r")
                    if size else np.empty(0, dtype=np.uint8))
        if spans is None:
            spans = bgzf_mod.scan_block_spans(self._mm) if size else []
            if spans is None:
                raise ValueError(f"{path}: not BGZF-framed")
        # ``spans`` given: a SUBSET of the member chain — the rank-span
        # window (docs/scaleout.md) inflates only its share of the file
        groups = bgzf_mod.group_spans(spans,
                                      knobs.get_int("VCTPU_IO_SHARD_BYTES"))
        from variantcalling_tpu.parallel.pipeline import imap_ordered

        self._shards = imap_ordered(pool, self._inflate, groups,
                                    window=pool.threads + 2)
        self._buf = bytearray()
        self._eof = False

    def _inflate(self, spans) -> bytes:
        from variantcalling_tpu.io import bgzf as bgzf_mod
        from variantcalling_tpu.parallel.pipeline import retry_transient
        from variantcalling_tpu.utils import faults

        def attempt() -> bytes:
            # injection point "io.shard_decompress": inflate is a pure
            # function of the mapped bytes, so a transient error here is
            # always safely retryable; a persistent one propagates through
            # the future and fails the run cleanly
            faults.check("io.shard_decompress")
            return bgzf_mod.inflate_spans(self._mm, spans)

        # the pooled worker's ``inflate.w<idx>`` row and its span on the
        # profiler trace's clock (a no-op with obs off, like the counters)
        with stage("inflate", bytes_in=sum(s[1] for s in spans)) as sp:
            out = retry_transient(attempt, f"bgzf shard inflate ({self.path})")
            sp.set(bytes_out=len(out))
        obs.counter("bgzf.inflate_shards").add(1)
        return out

    def read(self, n: int) -> bytes:
        while len(self._buf) < n and not self._eof:
            nxt = next(self._shards, None)
            if nxt is None:
                self._eof = True
                break
            self._buf += nxt
        out = bytes(self._buf[:n])
        del self._buf[:n]
        return out

    def close(self) -> None:
        self._shards.close()
        self._buf.clear()
        self._mm = None


class _MemberStream:
    """Serial ``read(n)`` over a run of BGZF members (one rank's suffix
    of the member chain) — the ``VCTPU_IO_THREADS=1`` sibling of
    :class:`_ParallelBgzfStream` for the rank-span window."""

    def __init__(self, mm, spans):
        self._mm = mm
        self._spans = spans
        self._i = 0
        self._buf = bytearray()

    def read(self, n: int) -> bytes:
        from variantcalling_tpu.io import bgzf as bgzf_mod

        while len(self._buf) < n and self._i < len(self._spans):
            j = min(self._i + 16, len(self._spans))
            self._buf += bgzf_mod.inflate_spans(self._mm,
                                                self._spans[self._i:j])
            self._i = j
        out = bytes(self._buf[:n])
        del self._buf[:n]
        return out

    def close(self) -> None:
        self._buf.clear()
        self._i = len(self._spans)


class _SpanGzWindow:
    """File-like ``read(n)`` serving ONE rank's line-aligned window of a
    BGZF file's decompressed stream (the docs/scaleout.md partition
    rule).

    The window is ``[cut(t_lo), cut(t_hi))`` where ``cut(u)`` is the
    smallest line-start position >= ``u``: the position after the first
    newline at offset >= ``u - 1``, clamped to the record region
    ``[h, total]`` (the header always ends at a line start, so rank 0's
    window begins exactly at ``h``). Adjacent ranks compute the SAME cut
    for their shared target, so the windows partition the record region
    exactly — no record is lost or duplicated, whatever the BGZF block
    layout. The inner stream starts at the member holding the first
    byte the window needs, so a rank inflates only ~its share (plus the
    members its boundary lines straddle).
    """

    def __init__(self, inner, base: int, t_lo: int, t_hi: int,
                 h: int, total: int):
        self._inner = inner
        self._buf = bytearray()
        self._buf_abs = base  # absolute offset of _buf[0]
        self._inner_eof = False
        self._t_lo, self._t_hi = t_lo, t_hi
        self._h, self._total = h, total
        self._start: int | None = None  # cut(t_lo), resolved lazily
        self._end: int | None = None  # cut(t_hi)

    def start(self) -> int:
        """Absolute decompressed offset of the window's first byte —
        ``cut(t_lo)`` — resolving (and discarding the pre-window prefix)
        eagerly. The reader uses it as the base for the per-chunk input
        end offsets the elastic re-cut consumes."""
        self._resolve_start()
        return self._start

    def _resolve_start(self) -> None:
        if self._start is not None:
            return
        self._start = self._cut(self._t_lo)
        if self._t_hi >= self._total:
            self._end = self._total
        while self._buf_abs < self._start:
            if not self._buf:
                if not self._more():
                    break
                continue
            self._drop(min(len(self._buf),
                           self._start - self._buf_abs))

    def _more(self) -> bool:
        if self._inner_eof:
            return False
        block = self._inner.read(4 << 20)
        if not block:
            self._inner_eof = True
            return False
        self._buf += block
        return True

    def _drop(self, n: int) -> None:
        del self._buf[:n]
        self._buf_abs += n

    def _cut(self, t: int) -> int:
        """``cut(t)``, buffering inner bytes as needed; inner EOF clamps
        to the end of the stream."""
        if t <= self._h:
            return self._h
        if t >= self._total:
            return self._total
        probe = t - 1
        if probe < self._buf_abs:
            # the probe byte is already consumed — only possible when
            # this cut coincides with the (already resolved) start cut:
            # no newline separates the two targets, or cut(t_lo) would
            # have stopped earlier
            return self._start if self._start is not None else self._buf_abs
        while True:
            start_idx = probe - self._buf_abs
            if start_idx < len(self._buf):
                nl = self._buf.find(b"\n", start_idx)
                if nl >= 0:
                    return self._buf_abs + nl + 1
                probe = self._buf_abs + len(self._buf)
            if not self._more():
                return self._buf_abs + len(self._buf)  # EOF mid-final-line

    def read(self, n: int) -> bytes:
        self._resolve_start()
        out = bytearray()
        while len(out) < n:
            if self._end is not None and self._buf_abs >= self._end:
                break
            if not self._buf and not self._more():
                break
            avail = len(self._buf)
            if self._end is None:
                # end unknown: everything strictly before t_hi - 1 is
                # in-window; once the buffer reaches the probe byte,
                # resolve the end cut (which may buffer further — the
                # final line can straddle members)
                if self._buf_abs + avail > self._t_hi - 1:
                    self._end = self._cut(self._t_hi)
                    continue
                take = avail
            else:
                take = min(avail, self._end - self._buf_abs)
            take = min(take, n - len(out))
            if take <= 0:
                break
            out += self._buf[:take]
            self._drop(take)
        return bytes(out)

    def close(self) -> None:
        self._inner.close()


class VcfChunkReader:
    """Line-aligned chunked native VCF ingest for the streaming executor.

    Iterating yields :class:`VariantTable` chunks in file order, each
    parsed by the same native scanner + table assembly the whole-file path
    uses (so per-chunk tables are indistinguishable from row-slices of the
    whole-file table). Sources:

    - plain ``.vcf``: a memory map, sliced at line boundaries — the file
      never fully materializes in anonymous memory, so peak RSS does not
      scale with input size;
    - ``.gz``/``.bgz``: streamed decompression (zlib releases the GIL), one
      independent bytes buffer per chunk with partial-line carry — again
      O(chunk) resident, not O(file).

    With ``VCTPU_IO_THREADS`` > 1 (default: cpu count) ingest goes
    PARALLEL (docs/streaming_executor.md "Parallel host IO"): BGZF input
    inflates shard-parallel (:class:`_ParallelBgzfStream`) and chunk
    PARSE — the dominant ingest cost on plain text too — fans out over
    the IO worker pool, reassembled into canonical sequence order before
    the tables leave the iterator. Chunk boundaries are computed by the
    same serial rules either way, so the yielded chunk sequence (and the
    journal resume identity) is byte-identical at every worker count.

    One-shot: the underlying stream is consumed by iteration. Requires
    the native library (callers gate on ``native.available()``); a
    mid-stream scan failure raises rather than silently degrading.
    """

    def __init__(self, path: str, chunk_bytes: int = 0,
                 io_threads: int | None = None, profiler=None,
                 rank_span: tuple[int, int] | None = None,
                 span_targets: tuple[int, int] | None = None):
        from variantcalling_tpu import native
        from variantcalling_tpu.parallel.pipeline import resolve_io_threads

        if not native.available():
            raise RuntimeError("VcfChunkReader requires the native engine")
        self.path = str(path)
        # rank-partitioned ingest (docs/scaleout.md): ``(rank, ranks)``
        # restricts this reader to ONE contiguous line-aligned span of
        # the record region — the deterministic cut rule shared with
        # every other rank, so the spans partition the file exactly
        self._rank_span: tuple[int, int] | None = None
        if rank_span is not None and int(rank_span[1]) > 1:
            r, nr = int(rank_span[0]), int(rank_span[1])
            if not 0 <= r < nr:
                raise ValueError(f"rank_span {rank_span!r} out of range")
            self._rank_span = (r, nr)
        # elastic spans (docs/scaleout.md "Elastic membership"): absolute
        # decompressed-byte targets ``[t_lo, t_hi)``. The rank fractions
        # above are the special case ``t = h + body*r//n``; the SAME cut
        # rule maps ANY monotone target sequence to an exact line-aligned
        # partition, so re-cut/stolen spans keep the byte-parity contract
        self._span_targets: tuple[int, int] | None = None
        if span_targets is not None:
            lo, hi = int(span_targets[0]), int(span_targets[1])
            if hi < lo:
                raise ValueError(f"span_targets {span_targets!r} inverted")
            self._span_targets = (lo, hi)
            if self._rank_span is not None:
                raise ValueError("rank_span and span_targets are exclusive")
        #: decompressed bytes of this reader's span (None: whole file) —
        #: the heartbeat's progress denominator for rank runs
        self.span_bytes: int | None = None
        #: absolute decompressed END offset of every chunk boundary this
        #: reader computed so far (skipped chunks included, indexed by
        #: chunk sequence number) — the committer journals it as
        #: ``in_end`` so an elastic re-cut can split a dead span at the
        #: last journaled boundary (parallel/elastic.py)
        self.chunk_ends: list[int] = []
        # arg beats the env knob beats the (test-patchable) module
        # default; resolved here, not at import, so a malformed value is
        # caught by run()'s up-front knobs.validate_all() instead of an
        # import-time traceback
        env_chunk = knobs.get_int("VCTPU_STREAM_CHUNK_BYTES") \
            if knobs.raw("VCTPU_STREAM_CHUNK_BYTES") is not None else None
        self.chunk_bytes = int(chunk_bytes) or env_chunk or STREAM_CHUNK_BYTES
        self.io_threads = (resolve_io_threads() if io_threads is None
                          else max(1, int(io_threads)))
        self.profiler = profiler
        self._pool = None
        self._pool_shared = False
        #: chunks to advance WITHOUT parsing (journal resume: their output
        #: bytes are already committed). Boundaries are computed exactly as
        #: for parsed chunks, so the continuation is byte-faithful.
        self._skip = 0
        self._gz = self.path.endswith((".gz", ".bgz"))
        self._mm: np.ndarray | None = None
        self._fh = None
        self._pending = b""
        if self._gz and (self._rank_span is not None
                         or self._span_targets is not None):
            # rank-span gz ingest: member-mapped window (BGZF only)
            try:
                self._init_gz_span()
            except BaseException:
                self.close()
                raise
        elif self._gz:
            # a failing header scan (e.g. a persistent shard-inflate error
            # surfacing through the parallel stream) must release the
            # already-started pool workers — close() is unreachable from
            # callers when the constructor itself raises
            try:
                self._fh = self._open_gz_stream()
                self.header, first_off, head = self._scan_gz_header(self._fh)
                self._pending = head[first_off:]
                self._gz_base = first_off  # chunk-end offset base
            except BaseException:
                self.close()
                raise
        else:
            size = os.path.getsize(self.path)
            self._mm = (np.memmap(self.path, dtype=np.uint8, mode="r")
                        if size else np.empty(0, dtype=np.uint8))
            cap = 1 << 20
            while True:
                head = bytes(memoryview(self._mm[: min(cap, size)]))
                header, first_off = parse_header_bytes(head)
                if (first_off < len(head) and head[first_off : first_off + 1] != b"#") \
                        or cap >= size:
                    break
                cap *= 8
            self.header = header
            self._first_off = first_off
            self._span_lo, self._span_hi = first_off, size
            if self._rank_span is not None:
                self._span_lo, self._span_hi = self._mm_span_bounds(size)
                self.span_bytes = self._span_hi - self._span_lo
            elif self._span_targets is not None:
                lo, hi = self._span_targets
                self._span_lo = self._mm_newline_cut(lo, size)
                self._span_hi = max(self._span_lo,
                                    self._mm_newline_cut(hi, size))
                self.span_bytes = self._span_hi - self._span_lo

    def _scan_gz_header(self, fh) -> tuple:
        """Read the VCF header off a decompressed-byte stream — the ONE
        gz header-scan rule (read ``chunk_bytes`` windows until a record
        line begins or the stream ends), shared by the whole-file and
        rank-span constructors so the two can never parse different
        headers for the same file. Returns ``(header, first_off, head)``
        — ``head[first_off:]`` is the already-read record remainder."""
        head = b""
        while True:
            block = fh.read(self.chunk_bytes)
            head += block
            header, first_off = parse_header_bytes(head)
            if not block or (first_off < len(head)
                             and head[first_off:first_off + 1] != b"#"):
                break
        return header, first_off, head

    def _mm_newline_cut(self, u: int, size: int) -> int:
        """The smallest line-start position >= ``u`` (the rank-span cut
        rule): the position after the first newline at index >= u - 1,
        clamped to the record region. The SAME rule every rank applies,
        so adjacent spans meet exactly."""
        if u <= self._first_off:
            return self._first_off
        if u >= size:
            return size
        pos = u - 1
        probe = 1 << 16
        while pos < size:
            w = self._mm[pos: min(pos + probe, size)]
            hits = np.flatnonzero(w == 0x0A)
            if len(hits):
                return min(pos + int(hits[0]) + 1, size)
            pos += len(w)
            probe *= 8
        return size

    def _mm_span_bounds(self, size: int) -> tuple[int, int]:
        r, n_ranks = self._rank_span
        body = size - self._first_off
        lo = self._mm_newline_cut(self._first_off + body * r // n_ranks,
                                  size)
        hi = self._mm_newline_cut(
            self._first_off + body * (r + 1) // n_ranks, size)
        return lo, max(lo, hi)

    def _init_gz_span(self) -> None:
        """Rank-span ingest of a BGZF input: map the member chain, parse
        the header with a short serial inflate from the file start, then
        serve this rank's line-aligned window of the decompressed stream
        (:class:`_SpanGzWindow`) starting at the member that holds the
        window's first needed byte. Plain single-member gzip has no
        member split points — rank partitioning refuses it loudly
        (EngineError, exit 2) rather than silently re-inflating the
        whole prefix per rank."""
        from variantcalling_tpu.engine import EngineError
        from variantcalling_tpu.io import bgzf as bgzf_mod

        size = os.path.getsize(self.path)
        mm = (np.memmap(self.path, dtype=np.uint8, mode="r")
              if size else np.empty(0, dtype=np.uint8))
        spans = bgzf_mod.scan_block_spans(mm) if size else []
        if spans is None:
            raise EngineError(
                f"{self.path}: rank-partitioned ingest needs BGZF-framed "
                "input (plain gzip is one indivisible deflate stream) — "
                "re-compress with bgzip/the BGZF writer, or run "
                "single-rank (docs/scaleout.md)")
        with gzip.open(self.path, "rb") as fh:
            self.header, first_off, _ = self._scan_gz_header(fh)
        h = first_off
        total = int(sum(s[2] for s in spans))
        if self._span_targets is not None:
            # elastic span: explicit absolute targets, clamped to the
            # record region — the rank fractions below are the special
            # case the coordinator's initial plan reproduces exactly
            t_lo = max(h, min(self._span_targets[0], total))
            t_hi = max(t_lo, min(self._span_targets[1], total))
        else:
            r, n_ranks = self._rank_span
            body = max(0, total - h)
            t_lo = h + body * r // n_ranks
            t_hi = h + body * (r + 1) // n_ranks
        self.span_bytes = max(0, t_hi - t_lo)
        # first decompressed byte the window needs: the line-start probe
        # at t_lo - 1 (or the header end, for rank 0's window)
        probe = t_lo - 1 if t_lo > h else h
        probe = max(0, min(probe, max(total - 1, 0)))
        cum = 0
        m_lo = len(spans)
        for i, s in enumerate(spans):
            if cum + s[2] > probe:
                m_lo = i
                break
            cum += s[2]
        tail = spans[m_lo:]
        if self.io_threads > 1 and tail:
            inner = _ParallelBgzfStream(self.path, self._ensure_pool(),
                                        spans=tail)
        else:
            inner = _MemberStream(mm, tail)
        self._fh = _SpanGzWindow(inner, cum, t_lo, t_hi, h, total)
        self._pending = b""

    def _open_gz_stream(self):
        """The decompressed-byte source for ``.gz`` input: shard-parallel
        BGZF inflate when the IO pool is on and the file is BGZF-framed,
        the serial gzip stream otherwise (plain single-member gzip has no
        split points). Both yield the identical byte stream."""
        if self.io_threads > 1:
            try:
                return _ParallelBgzfStream(self.path, self._ensure_pool())
            except ValueError:
                pass  # not BGZF-framed: one deflate stream, serial inflate
        return gzip.open(self.path, "rb")

    def _ensure_pool(self):
        if self._pool is None:
            from variantcalling_tpu.parallel.pipeline import IoPool

            self._pool = IoPool(self.io_threads)
        return self._pool

    def shared_pool(self):
        """The run-scoped IO pool, marked EXTERNALLY SHARED: the streaming
        executor hands it to work that outlives ingest (the chunk_worker
        fan-out and the writeback compress stage), so iteration exhaustion
        must no longer shut it down — a tail-chunk compress submitted to a
        dead pool would block forever. The run owner's :meth:`close` (in
        its teardown finally, after the pipeline drains) tears it down."""
        self._pool_shared = True
        return self._ensure_pool()

    def _close_stream(self) -> None:
        """Release the input stream only (idempotent)."""
        if self._fh is not None:
            try:
                self._fh.close()
            except OSError:
                pass
            self._fh = None

    def close(self) -> None:
        """Release the IO pool and the input stream (idempotent). Full
        unshared iteration closes implicitly; error paths and pool-sharing
        run owners call this so abandoned runs never accumulate idle pool
        workers."""
        if self._pool is not None:
            self._pool.shutdown()
            self._pool = None
        self._close_stream()

    def skip(self, n_chunks: int) -> None:
        """Advance the first ``n_chunks`` chunk boundaries without parsing
        them (journal resume — their rendered bytes are already on disk).
        Must be called before iteration starts."""
        self._skip = max(0, int(n_chunks))

    def chunk_end(self, seq: int) -> int | None:
        """Absolute decompressed end offset of chunk ``seq`` (``None``
        before its boundary is computed). Boundaries are computed during
        ingest, which strictly precedes the chunk's commit, so the
        committer's lookup for the chunk it just wrote always lands."""
        return self.chunk_ends[seq] if 0 <= seq < len(self.chunk_ends) \
            else None

    def _parse_chunk(self, buf_np: np.ndarray, lazy_buf) -> VariantTable:
        from variantcalling_tpu import native
        from variantcalling_tpu.parallel.pipeline import retry_transient
        from variantcalling_tpu.utils import faults

        def attempt() -> VariantTable:
            # injection point "io.chunk_read": a transient IO error here is
            # retried (parse is a pure function of the already-read buffer,
            # so a retry is always safe)
            faults.check("io.chunk_read")
            parsed = native.vcf_parse(buf_np, len(self.header.samples))
            if parsed is None:
                raise RuntimeError(f"native VCF scan failed mid-stream in {self.path}")
            return _table_from_parsed(parsed, self.header, lazy_buf, buf_np,
                                      drop_format=False)

        return retry_transient(attempt, f"chunk read ({self.path})")

    def iter_raw(self):
        """Raw ``(buf_np, lazy_buf)`` chunk buffers in canonical chunk
        order, WITHOUT parsing — the zero-wait chunk feed (ROADMAP item
        4). The streaming executor's pooled layout maps its whole
        per-chunk body (parse -> fused featurize+score -> render) over
        these on the IO pool, so a chunk is parsed immediately before it
        scores inside ONE task: no parsed table ever sits in a queue
        between a parse worker and a score worker (the
        ``score_stage.wait`` critical-path edge that dominated the p95
        of the layout before it). Boundaries are the same serial rule as
        :meth:`__iter__` — byte parity and the journal resume identity
        are unchanged. gz inputs still inflate shard-parallel inside the
        raw generator. One-shot, like iteration; the same close
        semantics apply (shared pools outlive exhaustion).
        """
        raw = self._raw_gz() if self._gz else self._raw_mm()
        try:
            yield from raw
        finally:
            if self._pool_shared:
                self._close_stream()
            else:
                self.close()

    def parse_chunk(self, buf_np: np.ndarray, lazy_buf) -> VariantTable:
        """Parse one raw chunk buffer (``iter_raw``) into a
        :class:`VariantTable` — the same native scan + per-worker
        ``parse.wN`` attribution the internal pooled parse uses, exposed
        for the executor's fused per-chunk body."""
        return self._parse_worker((buf_np, lazy_buf))

    def __iter__(self):
        raw = self._raw_gz() if self._gz else self._raw_mm()
        if self.io_threads <= 1:
            for buf_np, lazy_buf in raw:
                yield self._parse_chunk(buf_np, lazy_buf)
            return
        # parallel chunk parse: the native scan releases the GIL, so
        # chunks genuinely parse concurrently on the IO pool; the ordered
        # window reassembles them into canonical sequence order before
        # they leave the iterator, so downstream consumers (the stage
        # pipeline, the journal) see exactly the serial chunk stream
        from variantcalling_tpu.parallel.pipeline import imap_ordered

        try:
            yield from imap_ordered(self._ensure_pool(), self._parse_worker,
                                    raw, window=self.io_threads + 1)
        finally:
            if self._pool_shared:
                # the pool outlives ingest (shared with the compress stage
                # and the chunk fan-out); the run owner shuts it down
                self._close_stream()
            else:
                self.close()

    def _parse_worker(self, raw: tuple) -> VariantTable:
        buf_np, lazy_buf = raw
        if self.profiler is None:
            return self._parse_chunk(buf_np, lazy_buf)
        # the pooled worker's ``parse.w<idx>`` row (the run's profiler is
        # this reader's), and the span on the profiler trace's clock
        with stage("parse", bytes_in=len(buf_np)) as sp:
            table = self._parse_chunk(buf_np, lazy_buf)
            sp.set(records=len(table))
        return table

    def _raw_mm(self):
        """(buf_np, lazy_buf) chunk buffers in file order (plain text):
        the SAME boundary rule at every ``VCTPU_IO_THREADS`` setting.
        A rank-span reader iterates only its line-aligned span — the
        chunk rule applies to the span's bytes exactly as it would to a
        standalone file (chunk boundaries never change output bytes;
        they only shape the rank-local journal)."""
        mm = self._mm
        n = self._span_hi
        off = self._span_lo
        while off < n:
            end = min(off + self.chunk_bytes, n)
            if end < n:
                # align to the next newline (probe window grows for the
                # pathological all-one-line case)
                probe = 1 << 16
                while True:
                    w = mm[end: min(end + probe, n)]
                    hits = np.flatnonzero(w == 0x0A)
                    if len(hits):
                        end = end + int(hits[0]) + 1
                        break
                    if end + probe >= n:
                        end = n
                        break
                    probe *= 8
            self.chunk_ends.append(end)
            if self._skip > 0:
                self._skip -= 1
            else:
                view = mm[off:end]
                yield view, view
            off = end

    def _raw_gz(self):
        """(buf_np, lazy_buf) chunk buffers from the decompressed stream —
        the boundary rule reads fixed-size windows off ``self._fh``, so it
        is identical whether the stream is the serial gzip reader or the
        shard-parallel BGZF inflater."""
        # absolute offset of the next unconsumed decompressed byte: the
        # header end for whole-file ingest, cut(t_lo) for a span window —
        # chunk_ends advances from it by each chunk's raw length
        pos = (self._fh.start() if isinstance(self._fh, _SpanGzWindow)
               else self._gz_base)
        carry = self._pending
        self._pending = b""
        while True:
            block = self._fh.read(self.chunk_bytes)
            if not block:
                break
            block = carry + block
            cut = block.rfind(b"\n")
            if cut < 0:
                carry = block
                continue
            carry = block[cut + 1 :]
            chunk = block[: cut + 1]
            pos += len(chunk)
            self.chunk_ends.append(pos)
            if self._skip > 0:
                self._skip -= 1
                continue
            yield np.frombuffer(chunk, dtype=np.uint8), chunk
        if carry:
            pos += len(carry)
            self.chunk_ends.append(pos)
            if self._skip > 0:
                self._skip -= 1
            else:
                yield np.frombuffer(carry, dtype=np.uint8), carry
        self._fh.close()


def scan_record_region(path: str) -> tuple[int, int]:
    """``(header_end, total_size)`` of a VCF in DECOMPRESSED bytes — the
    target domain the elastic coordinator cuts spans over
    (``parallel/elastic.py``). The header-end rule matches the chunk
    readers' (``parse_header_bytes`` over a growing prefix), so the
    coordinator's span targets and every worker's cuts agree byte for
    byte. BGZF totals come from the member index (``scan_block_spans``
    isize sum) without inflating the file; plain single-member gzip has
    no split points and is refused loudly, exactly like rank-span
    ingest."""
    path = str(path)
    if path.endswith((".gz", ".bgz")):
        from variantcalling_tpu.engine import EngineError
        from variantcalling_tpu.io import bgzf as bgzf_mod

        size = os.path.getsize(path)
        mm = (np.memmap(path, dtype=np.uint8, mode="r") if size
              else np.empty(0, dtype=np.uint8))
        spans = bgzf_mod.scan_block_spans(mm) if size else []
        if spans is None:
            raise EngineError(
                f"{path}: span-partitioned ingest needs BGZF-framed "
                "input (plain gzip is one indivisible deflate stream) — "
                "re-compress with bgzip/the BGZF writer, or run "
                "single-rank (docs/scaleout.md)")
        total = int(sum(s[2] for s in spans))
        head = b""
        with gzip.open(path, "rb") as fh:
            while True:
                block = fh.read(STREAM_CHUNK_BYTES)
                head += block
                _header, first_off = parse_header_bytes(head)
                if not block or (first_off < len(head)
                                 and head[first_off:first_off + 1] != b"#"):
                    break
        return first_off, total
    size = os.path.getsize(path)
    mm = (np.memmap(path, dtype=np.uint8, mode="r") if size
          else np.empty(0, dtype=np.uint8))
    cap = 1 << 20
    while True:
        head = bytes(memoryview(mm[: min(cap, size)]))
        _header, first_off = parse_header_bytes(head)
        if (first_off < len(head) and head[first_off:first_off + 1] != b"#") \
                or cap >= size:
            break
        cap *= 8
    return first_off, size


def format_qual(q: float) -> str:
    if q is None or (isinstance(q, float) and np.isnan(q)):
        return MISSING
    if float(q) == int(q):
        return str(int(q))
    return f"{q:g}"


def write_vcf(
    path: str,
    table: VariantTable,
    new_filters: np.ndarray | None = None,
    extra_info: dict[str, np.ndarray] | None = None,
    sample_overrides: dict[int, np.ndarray] | None = None,
    fmt_override: np.ndarray | None = None,
    index: bool = True,
    verbatim_core: bool = False,
) -> None:
    """Write a VariantTable back to VCF, rewriting only the requested columns.

    - ``new_filters``: object array replacing the FILTER column.
    - ``extra_info``: info-key -> per-record value (np.nan/None skips a record;
      ``True`` writes a bare flag). Appended to the existing INFO string.
    - ``sample_overrides``: sample index -> object array of replacement
      sample strings; ``fmt_override`` replaces the FORMAT column.
    - ``index``: for ``.gz`` outputs, also build the sibling ``.tbi``
      (io/tabix) so htslib tools can consume the file directly.
    - ``verbatim_core``: caller asserts CHROM..QUAL were NOT edited since
      read; record assembly then runs in the native engine by splicing new
      FILTER/INFO between byte spans of the original buffer (the filter
      pipeline's writeback hot path). Ignored when the native library or
      parse buffer is unavailable.
    """
    if str(path).endswith(".gz"):
        from variantcalling_tpu.io.bgzf import BgzfWriter

        opener = lambda p, _mode: BgzfWriter(p)  # noqa: E731 — tabix-compatible blocks
    else:
        opener = open
    # tail fast path: FORMAT/sample columns come verbatim from the original
    # byte buffer (never materialized => never edited); all eight core
    # columns are rebuilt from the (possibly caller-edited) column arrays,
    # so in-place edits to chrom/pos/qual/... are always honored.
    fast = (
        table.aux is not None
        and table.aux.buf is not None
        and fmt_override is None
        and sample_overrides is None
        and not table.format_materialized
    )
    if not fast:
        table.materialize_format()  # slow path renders FORMAT/sample strings per record
    if fast:
        with opener(path, "wb") as out:
            for line in table.header.lines:
                out.write((line + "\n").encode())
            out.write((table.header.column_header() + "\n").encode())
            done = _write_assembled_native(out, table, new_filters, extra_info) \
                if verbatim_core else False
            if not done:
                _write_records_fast(out, table, new_filters, extra_info)
        if index and str(path).endswith(".gz"):
            from variantcalling_tpu.io.tabix import build_tabix_index

            try:
                build_tabix_index(str(path))
            except (ValueError, OSError):
                pass
        return
    with opener(path, "wt") as out:
        for line in table.header.lines:
            out.write(line + "\n")
        out.write(table.header.column_header() + "\n")
        n = len(table)
        for i in range(n):
            info_s = table.info[i]
            if extra_info:
                parts = [] if info_s in (MISSING, "", None) else [info_s]
                for k, vals in extra_info.items():
                    v = vals[i]
                    if v is None or (isinstance(v, float) and np.isnan(v)):
                        continue
                    if v is True:
                        parts.append(k)
                    elif isinstance(v, (float, np.floating)):
                        parts.append(f"{k}={float(v):g}")
                    else:
                        parts.append(f"{k}={v}")
                info_s = ";".join(parts) if parts else MISSING
            filt_s = new_filters[i] if new_filters is not None else table.filters[i]
            cols = [
                table.chrom[i],
                str(table.pos[i]),
                table.vid[i],
                table.ref[i],
                table.alt[i],
                format_qual(table.qual[i]),
                filt_s,
                info_s,
            ]
            if table.fmt_keys is not None:
                cols.append(fmt_override[i] if fmt_override is not None else table.fmt_keys[i])
                for s in range(table.n_samples):
                    if sample_overrides and s in sample_overrides:
                        cols.append(sample_overrides[s][i])
                    else:
                        cols.append(table.sample_cols[i][s])
            out.write("\t".join(cols) + "\n")
    if index and str(path).endswith(".gz"):
        from variantcalling_tpu.io.tabix import build_tabix_index

        try:
            build_tabix_index(str(path))
        except (ValueError, OSError):
            pass  # unsorted/odd inputs: the VCF itself is still valid


def _format_extra_info_bytes(n: int, extra_info: dict) -> list[bytes]:
    """Per-record b";K=V" suffixes in dict key order (float columns vectorized)."""
    acc = np.full(n, b"", dtype="S1")
    for k, vals in (extra_info or {}).items():
        arr = np.asarray(vals)
        if arr.dtype.kind == "f":
            f64 = arr.astype(np.float64)
            joined = np.char.add((";" + k + "=").encode(), np.char.mod(b"%g", f64))
            acc = np.where(~np.isnan(f64), np.char.add(acc, joined), acc)
        else:
            kb = k.encode()
            part = []
            for i in range(n):
                v = vals[i]
                if v is None or (isinstance(v, float) and np.isnan(v)):
                    part.append(b"")
                elif v is True:
                    part.append(b";" + kb)
                else:
                    part.append(b";" + kb + b"=" + str(v).encode())
            acc = np.char.add(acc, np.asarray(part, dtype="S"))
    return acc.tolist()


def _format_qual_column(qual: np.ndarray) -> np.ndarray:
    """Vectorized format_qual over the whole column (object array of str)."""
    q = np.asarray(qual, dtype=np.float64)
    out = np.full(len(q), MISSING, dtype=object)
    ok = ~np.isnan(q)
    is_int = ok & (q == np.floor(q))
    out[is_int] = np.char.mod("%d", q[is_int].astype(np.int64))
    frac = ok & ~is_int
    out[frac] = np.char.mod("%g", q[frac])
    return out


def _encode_column_factorized(values, n: int) -> tuple[np.ndarray, np.ndarray]:
    """(byte buffer, (n+1,) offsets) for a low-cardinality string column.

    FILTER columns repeat a handful of values (PASS/LOW_SCORE/...), so a
    hash factorize + per-unique vectorized byte fill beats 1M per-record
    Python encodes ~10x on the writeback hot path. A
    :class:`FactorizedColumn` skips the factorize entirely."""
    if isinstance(values, FactorizedColumn):
        codes, uniques = values.codes, values.uniques
    else:
        import pandas as pd

        codes, uniques = pd.factorize(np.asarray(values, dtype=object), use_na_sentinel=False)
    # factorize normalizes None to float NaN — both mean "missing" (.)
    enc = [(MISSING if u is None or u == "" or (isinstance(u, float) and np.isnan(u))
            else str(u)).encode() for u in uniques]
    lens = np.fromiter((len(e) for e in enc), dtype=np.int64, count=len(enc))
    offs = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lens[codes], out=offs[1:])
    buf = np.empty(int(offs[-1]), dtype=np.uint8)
    starts = offs[:-1]
    for ui, e in enumerate(enc):
        s = starts[codes == ui]
        eb = np.frombuffer(e, dtype=np.uint8)
        for j in range(len(e)):  # per-unique per-char: a few dozen fills total
            buf[s + j] = eb[j]
    return buf, offs


def _filter_info_blobs(table: VariantTable, new_filters, extra_info):
    """(filt_buf, filt_offs, sfx_buf, sfx_offs) for native record assembly.

    Shared by the whole-table writeback and the per-chunk streaming
    renderer so the two produce identical bytes by construction."""
    from variantcalling_tpu import native

    n = len(table)
    filters = new_filters if new_filters is not None else table.filters
    filt_buf, filt_offs = _encode_column_factorized(filters, n)

    # single float INFO column (the pipeline's TREE_SCORE writeback):
    # render ';KEY=%g' in the native engine; anything else falls back to
    # the generic per-record formatter
    sfx = None
    if extra_info and len(extra_info) == 1:
        (k, vals), = extra_info.items()
        arr = np.asarray(vals)
        if arr.dtype.kind == "f":
            sfx = native.format_float_info(arr, b";" + k.encode() + b"=")
    if sfx is not None:
        sfx_buf, sfx_offs = sfx
    else:
        suffix = _format_extra_info_bytes(n, extra_info) if extra_info else [b""] * n
        sfx_offs = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(np.fromiter(map(len, suffix), dtype=np.int64, count=n), out=sfx_offs[1:])
        sfx_buf = np.frombuffer(b"".join(suffix), dtype=np.uint8)
    return filt_buf, filt_offs, sfx_buf, sfx_offs


def assemble_table_bytes(table: VariantTable, new_filters=None, extra_info=None,
                         out: np.ndarray | None = None) -> np.ndarray | None:
    """Render one table's record body as a uint8 array via the native
    engine (the streaming executor's per-chunk writeback stage). Returns
    None when the native engine or the parse buffer is unavailable —
    callers fall back to :func:`render_table_bytes_python`."""
    from variantcalling_tpu import native

    aux = table.aux
    if aux is None or aux.buf is None or not native.available():
        return None
    filt_buf, filt_offs, sfx_buf, sfx_offs = _filter_info_blobs(table, new_filters, extra_info)
    return native.vcf_assemble(
        aux.buf, aux.line_spans, aux.filter_spans, aux.info_spans, aux.tail_spans,
        filt_buf, filt_offs, sfx_buf, sfx_offs, out=out)


def render_table_bytes_python(table: VariantTable, new_filters=None,
                              extra_info=None) -> bytes:
    """Python twin of :func:`assemble_table_bytes` (same bytes as the
    per-record writer path), for engines without the native library."""
    sink = _io.BytesIO()
    _write_records_fast(sink, table, new_filters, extra_info)
    return sink.getvalue()


def _write_assembled_native(out, table: VariantTable, new_filters, extra_info) -> bool:
    """Native record assembly (verbatim CHROM..QUAL head; see write_vcf),
    streamed in record chunks through ONE reused output buffer — a
    whole-callset buffer would touch ~400 MB of fresh pages at 5M records
    and then sweep them again for the file write; chunking keeps the
    working set page-warm. Returns False (nothing written) when the
    native engine is unavailable."""
    from variantcalling_tpu import native

    aux = table.aux
    if aux is None or aux.buf is None or not native.available():
        return False
    n = len(table)
    filt_buf, filt_offs, sfx_buf, sfx_offs = _filter_info_blobs(table, new_filters, extra_info)

    # blob offsets are absolute, so chunk slices pass the full blobs with
    # an offsets window; spans slice to contiguous row ranges
    chunk = 1 << 20
    scratch: np.ndarray | None = None
    for lo in range(0, n, chunk):
        hi = min(lo + chunk, n)
        body = native.vcf_assemble(
            aux.buf,
            aux.line_spans[lo:hi],
            aux.filter_spans[lo:hi],
            aux.info_spans[lo:hi],
            aux.tail_spans[lo:hi],
            filt_buf,
            filt_offs[lo : hi + 1],
            sfx_buf,
            sfx_offs[lo : hi + 1],
            out=scratch,
        )
        if body is None:
            if lo == 0:
                return False  # nothing written yet: Python fallback
            # mid-stream failure (alloc/thread exhaustion in the engine):
            # finish rows [lo, n) through the per-record Python writer so
            # the output file is still complete and correct
            rest = np.arange(lo, n)
            _write_records_fast(
                out, table.subset(rest),
                new_filters[rest] if new_filters is not None else None,
                {k: np.asarray(v)[rest] for k, v in extra_info.items()} if extra_info else None)
            return True
        out.write(memoryview(body))
        base = body.base if isinstance(body.base, np.ndarray) else body
        scratch = base if base.ndim == 1 else None
    return True


def _write_records_fast(out, table: VariantTable, new_filters, extra_info) -> None:
    """Record writeback with the FORMAT/sample tail copied verbatim from the
    original buffer (NativeAux spans); the eight core columns are rebuilt
    from the live column arrays so caller edits are always written."""
    aux = table.aux
    bufb = aux.buf.tobytes()
    n = len(table)
    tails = aux.tail_spans.tolist()
    suffix = _format_extra_info_bytes(n, extra_info) if extra_info else None
    filters = new_filters if new_filters is not None else table.filters
    pos_s = np.char.mod("%d", table.pos)  # vectorized int formatting
    qual_s = _format_qual_column(table.qual)
    chrom, vid, ref, alt, info_col = table.chrom, table.vid, table.ref, table.alt, table.info
    chunks: list[bytes] = []
    for i in range(n):
        info = info_col[i]
        if suffix is not None and suffix[i]:
            sfx = suffix[i].decode()
            info = sfx[1:] if info == MISSING else info + sfx
        ta, tb = tails[i]
        tail = b"\t" + bufb[ta:tb] if tb > ta else b""
        line = "\t".join(
            (chrom[i], pos_s[i], vid[i], ref[i], alt[i], qual_s[i], filters[i], info)
        )
        chunks.append(line.encode() + tail + b"\n")
        if len(chunks) >= 16384:
            out.write(b"".join(chunks))
            chunks.clear()
    if chunks:
        out.write(b"".join(chunks))
