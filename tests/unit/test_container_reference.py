"""The benchmark's container reference (``benchmarks/container_reference.py``)
against itself and against the program: what the program writes as
``.vcf.gz`` + ``.tbi`` passes every check, and a container that breaks one
guarantee fails the check that guards it. Tier-1: a reference that passes a
broken container would pass every later PR's."""

from __future__ import annotations

import ast
import gzip
import json
import os
import struct

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
N_CONTIGS, GENOME_LEN, N = 24, 24 * 400_000, 12_000


@pytest.fixture()
def ref(monkeypatch):
    monkeypatch.syspath_prepend(BENCH)
    import container_reference

    return container_reference


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    """A sorted 24-contig callset as plain text, as the program's ``.vcf.gz``
    with the program's ``.tbi``, and the contigs' names and lengths."""
    import sys

    sys.path.insert(0, BENCH)
    try:
        import fixtures
    finally:
        sys.path.remove(BENCH)
    from variantcalling_tpu.io.bgzf import BgzfWriter
    from variantcalling_tpu.io.tabix import build_tabix_index

    d = str(tmp_path_factory.mktemp("container"))
    plain = os.path.join(d, "calls.vcf")
    fixtures.write_callset(plain, 11, GENOME_LEN, N_CONTIGS, N, 5)
    gz = os.path.join(d, "calls.vcf.gz")
    with open(plain, "rb") as fh, BgzfWriter(gz) as w:
        w.write(fh.read())
    build_tabix_index(gz)
    return {"dir": d, "plain": plain, "gz": gz,
            "contigs": fixtures.contig_names(N_CONTIGS),
            "lengths": [GENOME_LEN // N_CONTIGS] * N_CONTIGS}


def tolerance() -> float:
    with open(os.path.join(BENCH, "configs", "forest-t40d6-hg38x2-vcfgz.json")) as fh:
        return json.load(fh)["container"]["size_tolerance"]


def test_it_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "container_reference.py")) as fh:
        tree = ast.parse(fh.read())
    names = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names}
    names |= {n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
    assert names == {"__future__", "gzip", "os", "random", "struct", "zlib"}


def test_the_writer_is_bgzf_by_the_specification(ref, world, tmp_path, monkeypatch):
    from variantcalling_tpu import native
    from variantcalling_tpu.io.bgzf import BgzfWriter, scan_block_spans

    out = str(tmp_path / "ref.vcf.gz")
    n = ref.compress_file(world["plain"], out)
    assert n == os.path.getsize(out) and ref.member(b"") == ref.EOF
    with open(world["plain"], "rb") as fh:
        text = fh.read()
    assert gzip.decompress(open(out, "rb").read()) == text
    got = ref.validate_container(out)
    assert got["text_bytes"] == len(text) and got["payload_max"] == ref.PAYLOAD
    assert got["blocks"] == -(-len(text) // ref.PAYLOAD) + 1
    # the program frames the same text the same way, at the same level: the
    # same members; under its zlib engine the same bytes (ISSUE 39: its
    # default is libdeflate where the host has it, which deflates them smaller)
    program = open(world["gz"], "rb").read()
    theirs = open(out, "rb").read()
    assert [s[2] for s in scan_block_spans(program)] == [s[2] for s in scan_block_spans(theirs)]
    assert len(program) <= len(theirs)
    if native.available():
        compress = native.bgzf_compress
        monkeypatch.setattr(native, "bgzf_compress",
                            lambda data, level=6: compress(data, level, engine=native.BGZF_ZLIB))
    with BgzfWriter(str(tmp_path / "zlib.vcf.gz")) as w:
        w.write(text)
    assert open(tmp_path / "zlib.vcf.gz", "rb").read() == theirs


def test_the_programs_container_passes_every_check(ref, world, tmp_path):
    got = ref.validate_container(world["gz"])
    plain = str(tmp_path / "inflated.vcf")
    assert ref.inflate_file(world["gz"], plain) == got["text_bytes"]
    size = ref.check_size(got["bytes"], plain, tolerance())
    # at or under the plain writer's zlib at the same level (ISSUE 39)
    assert 0.9 < size["size_ratio"] <= 1.0
    index = ref.check_index(world["gz"], plain, world["contigs"], world["lengths"],
                            64, "5:11")
    assert index == {"regions": 64, "regions_checked": 64}


def shifted_tbi(ref, world, gz):
    """The program's index with every linear offset moved on to the record
    after the one it names."""
    from variantcalling_tpu.io import tabix

    real = tabix._RefIndex.add

    def late(self, beg, end, v_start, v_end):
        real(self, beg, end, v_end, v_end)

    tabix._RefIndex.add = late
    try:
        tabix.build_tabix_index(gz)
    finally:
        tabix._RefIndex.add = real


def broken(ref, world, d, how):
    """A copy of the program's container broken in one way; what is left to
    check is ``(gz, plain)``."""
    gz, plain = os.path.join(d, "out.vcf.gz"), os.path.join(d, "out.vcf")
    data = open(world["gz"], "rb").read()
    tbi = open(world["gz"] + ".tbi", "rb").read()
    if how == "no_eof":
        data = data[:-len(ref.EOF)]
    elif how == "bytes_after_eof":
        data += b"\x00"
    elif how == "payload_70000":
        ref.compress_file(world["plain"], gz, payload=70_000)
        data = None
    elif how == "plain_gzip":
        data = gzip.compress(open(world["plain"], "rb").read(), 6)
    elif how == "level_1":
        ref.compress_file(world["plain"], gz, level=1)
        data = None
    elif how == "partial_left":
        open(gz + ".partial.123.abc", "wb").close()
    if data is not None:
        open(gz, "wb").write(data)
    open(gz + ".tbi", "wb").write(tbi)
    if how == "tbi_shifted":
        shifted_tbi(ref, world, gz)
    elif how == "tbi_bed_preset":
        body = bytearray(gzip.decompress(tbi))
        struct.pack_into("<i", body, 8, 0x10000)
        open(gz + ".tbi", "wb").write(ref.member(bytes(body)) + ref.EOF)
    elif how == "tbi_other_names":
        body = gzip.decompress(tbi).replace(b"chr24\x00", b"chrXX\x00")
        open(gz + ".tbi", "wb").write(ref.member(body) + ref.EOF)
    return gz, plain


@pytest.mark.parametrize("how, check, says", [
    ("no_eof", "container", "EOF member"),
    ("bytes_after_eof", "container", "no gzip member"),
    ("payload_70000", "container", "inflates to 70000"),
    ("plain_gzip", "container", "no gzip member with an extra field"),
    ("partial_left", "container", "partial files left"),
    ("level_1", "size", "at level 6"),
    ("tbi_shifted", "index", "the offset leads to"),
    ("tbi_bed_preset", "index", "preset"),
    ("tbi_other_names", "index", "names"),
])
def test_a_broken_container_fails_the_check_that_guards_it(ref, world, tmp_path,
                                                           how, check, says):
    gz, plain = broken(ref, world, str(tmp_path), how)
    steps = {
        "container": lambda: ref.validate_container(gz),
        "size": lambda: ref.check_size(os.path.getsize(gz), plain, tolerance()),
        "index": lambda: ref.check_index(gz, plain, world["contigs"],
                                         world["lengths"], 64, "5:11"),
    }
    for name in ("container", "size", "index"):
        if name == check:
            with pytest.raises(ValueError, match=says):
                steps[name]()
            return
        steps[name]()  # the checks before the guarding one still pass
        if name == "container":
            ref.inflate_file(gz, plain)
