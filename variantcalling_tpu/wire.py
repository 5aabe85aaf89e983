"""The wire: the ONE buffer a fused dispatch sends the device.

A dispatch of the fused featurize+score program
(``pipelines/filter_variants._dispatch_fused``) used to hand the device 19
one-dimensional arrays — 13 host columns, the packed position, five allele
arrays, four of them twice — each narrowed, sliced, padded and copied by a
numpy or jax call of its own: about 160 short interpreter-held calls a chunk,
a serial section every pooled worker queued on (PERF.md, PR 33). Now a
dispatch sends ``uint32[rows, W/4]``: one row per variant in a STATIC layout
(:class:`WireLayout`, a function of the program's host columns and of whether
the genome is resident — never of a chunk's contents, so no data can cause a
trace), filled in one pass into pooled staging memory (:class:`StagingPool`)
and unpacked inside the program (:func:`unpack`), where time is free.

Two fills write the same bytes (``tests/unit/test_wire.py`` holds them
equal): :func:`fill_native` — one call of ``native.wire_fill`` over the
arrays the native VCF scan left on the table, interpreter released — and
:func:`fill_numpy`, for a table that did not come through the native parser
or a machine without the library. No knob chooses: the caller takes the
native fill whenever :func:`native_fillable` says the table allows it.

| column | bytes | on the wire | pad value |
| --- | --- | --- | --- |
| ``pos`` (resident genome only) | 4 | uint32, the anchor's byte in the device genome | ``packed_position_fill`` (past the genome's end: an all-N window) |
| ``qual`` ``dp`` ``sor`` ``af`` ``gq``, every extra INFO or interval column | 4 each | float32 | 0 |
| ``indel_length`` ``n_alts`` | 4 each | int32 (exact for every value) | 0 |
| ``is_het`` ``is_snp`` ``is_indel`` ``is_ins`` | 1 each | uint8 flag | 0 |
| ``ref_code`` ``alt_code`` ``indel_nuc`` | 1 each | uint8 base code 0..4 | 4 |

32-bit columns come first in the program's host-column order, then the
bytes; the row is padded to a multiple of four (40 bytes for the base
feature list). The host-windows layout (``resident=False``) has no ``pos``
and sends the ``(rows, 41)`` windows as a second array of the same staging
buffer.
"""

from __future__ import annotations

import functools
import sys
import threading

import numpy as np

from variantcalling_tpu.featurize import WINDOW_RADIUS
from variantcalling_tpu.utils.trace import stage

assert sys.byteorder == "little", "the wire packs little-endian words"

WINDOW = 2 * WINDOW_RADIUS + 1

#: column -> (kind of native/src/vctpu_wire.cc, dtype on the wire, pad value);
#: a host column with no entry is an ``_EXTRA``
COLUMNS = {
    "pos": (0, np.uint32, None),  # pad: featurize.packed_position_fill
    "qual": (1, np.float32, 0),
    "dp": (2, np.float32, 0),
    "sor": (3, np.float32, 0),
    "af": (4, np.float32, 0),
    "gq": (5, np.float32, 0),
    "is_het": (6, np.uint8, 0),
    "is_snp": (7, np.uint8, 0),
    "is_indel": (8, np.uint8, 0),
    "is_ins": (9, np.uint8, 0),
    "indel_length": (10, np.int32, 0),
    "ref_code": (11, np.uint8, 4),
    "alt_code": (12, np.uint8, 4),
    "n_alts": (13, np.int32, 0),
    "indel_nuc": (14, np.uint8, 4),
}
#: any other host column: made by Python (interval membership, extra INFO keys)
_EXTRA = (15, np.float32, 0)
#: what the window kernels take besides the windows (device_feature_dict)
ALLELE_COLUMNS = ("is_indel", "indel_nuc", "ref_code", "alt_code", "is_snp")


class WireLayout:
    """The row of one program: ``dtype`` is the row as a numpy structured
    dtype (name -> wire dtype at its byte offset, itemsize ``width``),
    ``fields`` the same as ``int32[k, 3]`` (kind, offset, extra's number) for
    the native fill, ``extras`` the host columns only Python makes."""

    __slots__ = ("host_names", "resident", "dtype", "width", "fields", "extras",
                 "floats")

    def __init__(self, host_names: tuple[str, ...], resident: bool):
        self.host_names = host_names
        self.resident = resident
        names = (["pos"] if resident else []) + list(host_names) \
            + [c for c in ALLELE_COLUMNS if c not in host_names]
        self.extras = tuple(n for n in host_names if n not in COLUMNS)
        kinds = {n: COLUMNS.get(n, _EXTRA) for n in names}
        # 32-bit columns first: each is one whole word of the row
        order = sorted(names, key=lambda n: np.dtype(kinds[n][1]).itemsize == 1)
        offsets, off = [], 0
        for n in order:
            offsets.append(off)
            off += np.dtype(kinds[n][1]).itemsize
        self.width = -(-off // 4) * 4
        self.dtype = np.dtype({"names": order,
                               "formats": [kinds[n][1] for n in order],
                               "offsets": offsets, "itemsize": self.width})
        self.fields = np.asarray(
            [(kinds[n][0], o, self.extras.index(n) if n in self.extras else 0)
             for n, o in zip(order, offsets)], dtype=np.int32).reshape(-1, 3)
        #: the float32 columns: where a missing value is NaN under keep_nan
        self.floats = tuple(n for n in order if kinds[n][1] == np.float32)

    @property
    def words(self) -> int:
        return self.width // 4

    def pad_row(self, pos_fill: int) -> np.ndarray:
        """One row of pad values (``pos_fill``: the resident genome's)."""
        row = np.zeros(1, dtype=self.dtype)
        for n in self.dtype.names:
            pad = COLUMNS.get(n, _EXTRA)[2]
            row[n] = pos_fill if pad is None else pad
        return row


@functools.lru_cache(maxsize=64)
def layout_for(host_names: tuple[str, ...], resident: bool) -> WireLayout:
    """The layout of the program with these host columns: one object per
    (columns, resident), so layouts compare by identity."""
    return WireLayout(tuple(host_names), bool(resident))


def unpack(layout: WireLayout, words) -> dict:
    """Inside the program: ``uint32[rows, W/4]`` -> column name -> 1-D array
    in its wire dtype (slices, shifts and same-width bitcasts only)."""
    import jax.numpy as jnp
    from jax import lax

    out = {}
    for name in layout.dtype.names:
        dtype, off = layout.dtype.fields[name][:2]
        word = words[:, off // 4]
        if dtype.itemsize == 1:
            out[name] = ((word >> (8 * (off % 4))) & 0xFF).astype(jnp.uint8)
        elif dtype == np.uint32:
            out[name] = word
        else:
            out[name] = lax.bitcast_convert_type(word, dtype)
    return out


class Staging:
    """Bucket-sized host memory of one layout: ``words`` is what goes to the
    device, ``rec`` the same bytes as rows of the layout's dtype;
    ``windows`` (host-windows layout) the second array. Rows past the last
    fill hold pad values: written when the buffer is made, and again only
    over rows a fill left data in."""

    __slots__ = ("layout", "words", "rec", "windows", "_dirty", "_pos_fill")

    def __init__(self, rows: int, layout: WireLayout):
        self.layout = layout
        # zeros: the row's alignment bytes are never written again
        self.words = np.zeros((rows, layout.words), dtype=np.uint32)
        self.rec = self.words.view(layout.dtype).reshape(rows)
        self.windows = None if layout.resident \
            else np.empty((rows, WINDOW), dtype=np.uint8)
        self._dirty = rows  # nothing holds pad values yet
        self._pos_fill = None

    @property
    def rows(self) -> int:
        return len(self.rec)

    @property
    def nbytes(self) -> int:
        return self.words.nbytes + (0 if self.windows is None else self.windows.nbytes)

    def arrays(self) -> tuple:
        """What a dispatch hands the device, in the program's argument order."""
        return (self.words,) if self.windows is None else (self.windows, self.words)

    def pad_from(self, n: int, pos_fill: int) -> None:
        """Rows ``[0, n)`` were just filled: make rows ``[n, rows)`` pad."""
        hi = self.rows if pos_fill != self._pos_fill else max(self._dirty, n)
        if hi > n:
            self.rec[n:hi] = self.layout.pad_row(pos_fill)
            if self.windows is not None:
                self.windows[n:hi] = 4
        self._dirty, self._pos_fill = n, pos_fill


#: idle staging memory the pool keeps, in bytes (15 chunk bodies in flight
#: at 262,144 rows of 40 bytes are 157 MB)
POOL_MAX_BYTES = 256 << 20


class StagingPool:
    """Staging buffers by (rows, layout). A buffer handed to ``device_put``
    must not be refilled while the copy may still read it, so it comes back
    with the array whose readiness ends that (:meth:`give`'s ``until``: the
    TRANSFERRED array, or the program's result where the backend reads host
    memory in place) and is handed out again only once that array
    ``is_ready()``. Taking never blocks: with no free buffer it makes one."""

    def __init__(self, max_bytes: int = POOL_MAX_BYTES):
        self.max_bytes = max_bytes
        self._lock = threading.Lock()
        self._held: list[tuple[Staging, object]] = []  # oldest first

    def take(self, rows: int, layout: WireLayout) -> Staging:
        with self._lock:
            for i, (buf, until) in enumerate(self._held):
                if buf.rows == rows and buf.layout is layout and _ready(until):
                    del self._held[i]
                    return buf
        return Staging(rows, layout)

    def give(self, buf: Staging, until: tuple) -> None:
        """``until``: the arrays that must all be ready before ``buf`` may
        be filled again."""
        with self._lock:
            # let go of what has landed: an idle buffer keeps no device array alive
            self._held = [(b, () if _ready(u) else u) for b, u in self._held]
            self._held.append((buf, until))
            while sum(b.nbytes for b, _ in self._held) > self.max_bytes:
                self._held.pop(0)  # dropped, not reused: the copy keeps its memory alive

    def __len__(self) -> int:
        return len(self._held)


def _ready(until) -> bool:
    try:
        return all(a.is_ready() for a in until)
    except Exception:  # noqa: BLE001  # vctpu-lint: disable=VCT002 — a failed copy or program frees nothing: its buffer is never reused, and the failure surfaces where the dispatch fetches its result
        return False


POOL = StagingPool()

_ALIASES: dict[str, bool] = {}
_ALIASES_LOCK = threading.Lock()


def put_reads_host_memory(platform: str | None = None) -> bool:
    """Whether ``jax.device_put`` of an aligned numpy array may leave the
    device array reading the host's memory in place (the CPU client does,
    with or without ``may_alias``): found once per platform by writing to
    the host array after the copy. Where it does, a staging buffer is busy
    until the program that read it has finished, not until the transfer."""
    import jax

    platform = platform or jax.default_backend()
    if platform not in _ALIASES:
        # once a process and platform, inside a first wave's score_stage
        # (the read of ``dev[0]`` compiles a small program): the span holds
        # the wave's other threads too, which wait here for the one probing
        with stage("backend_probe", platform=platform), _ALIASES_LOCK:
            if platform not in _ALIASES:
                raw = np.zeros(4096 + 64, dtype=np.uint8)
                start = (-raw.ctypes.data) % 64
                host = raw[start:start + 4096].view(np.uint32)
                dev = jax.device_put(host,
                                     jax.local_devices(backend=platform)[0])
                dev.block_until_ready()
                host[:] = 1
                _ALIASES[platform] = bool(np.asarray(dev[0]) == 1)
    return _ALIASES[platform]


def native_fillable(table) -> bool:
    """Whether :func:`fill_native` can make every base column of ``table``:
    it came through the native scan (``aux`` and the parser's contig codes)
    and the library is loaded."""
    from variantcalling_tpu import native

    aux = table.aux
    return aux is not None and table.chrom_codes is not None \
        and all(k in aux.info_keys for k in ("DP", "SOR", "AF")) \
        and native.available()


def fill_native(buf: Staging, row0: int, table, lo: int, hi: int,
                extras: dict, genome, keep_nan: bool) -> int:
    """Rows ``[lo, hi)`` of ``table`` into rows ``[row0, ...)`` of ``buf``:
    what ``host_featurize``, ``classify_alleles``, ``_compute_af``,
    ``globalize_positions`` and the NaN -> 0 rule make of the scan's arrays,
    in one native pass. ``extras``: the layout's Python-made columns.
    Returns the float32 cells written as NaN where ``keep_nan`` (counted in
    the same pass), 0 otherwise."""
    from variantcalling_tpu import native
    from variantcalling_tpu.featurize import packed_position_fill

    if hi <= lo:
        return 0
    layout, aux = buf.layout, table.aux
    if layout.resident:
        names = table.chrom_names
        off = [genome.offsets.get(c, -1) for c in names]
        length = [genome.lengths.get(c, 0) for c in names]
        pos_fill = packed_position_fill(genome)
    else:
        off, length, pos_fill = [-1], [0], 0
    a = aux.alle
    return native.wire_fill(
        buf.words, row0, lo, hi, layout.fields,
        pos=table.pos, chrom_codes=table.chrom_codes,
        contig_off=off, contig_len=length, radius=WINDOW_RADIUS, pos_fill=pos_fill,
        qual=table.qual, gt=aux.gt, gq=aux.gq, ad=aux.ad, info_vals=aux.info_vals,
        info_cols=tuple(aux.info_keys.index(k) for k in ("DP", "SOR", "AF")),
        aclass=a["aclass"], indel_length=a["indel_length"], indel_nuc=a["indel_nuc"],
        ref_code=a["ref_code"], alt_code=a["alt_code"], n_alts=a["n_alts"],
        extras=[extras[n] for n in layout.extras], keep_nan=keep_nan)


def numpy_columns(layout: WireLayout, hf, gpos=None) -> dict:
    """Column name -> full-length array for :func:`fill_numpy`, from a
    complete ``HostFeatures`` (and the packed positions, resident layout)."""
    cols = {n: hf.cols[n] for n in layout.host_names}
    for n in ALLELE_COLUMNS:
        cols.setdefault(n, getattr(hf.alle, n))
    if layout.resident:
        cols["pos"] = gpos
    return cols


def fill_numpy(buf: Staging, row0: int, cols: dict, lo: int, hi: int,
               keep_nan: bool = False) -> int:
    """The same bytes as :func:`fill_native`, column by column: a cast into
    the column's place in the rows. Returns what :func:`fill_native` does:
    the float32 cells written as NaN where ``keep_nan``, 0 otherwise."""
    rec = buf.rec[row0:row0 + (hi - lo)]
    for name in buf.layout.dtype.names:
        rec[name] = cols[name][lo:hi]
    if not keep_nan:
        return 0
    return sum(int(np.isnan(rec[name]).sum()) for name in buf.layout.floats)
