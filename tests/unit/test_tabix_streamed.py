"""The ``.tbi`` gathered inside the run (ISSUE 37): ``io/tabix.chunk_index_facts``
on the workers and ``StreamedIndex`` where chunks pass in order, held byte for
byte (inflated) to ``build_tabix_index``'s second pass over the same committed
file: wherever record ends fall against the 65,280-byte members and the chunk
borders, for one and four contigs, REFs over two windows and bin levels, both
renderers; and the files the facts do not cover, which fall back to that pass.
Since ISSUE 39 a run's members are libdeflate's where it loaded, and the
streamed index follows their offsets."""

from __future__ import annotations

import gzip
import json
import os
import pickle

import numpy as np
import pytest

from variantcalling_tpu.io import bgzf as bgzf_mod
from variantcalling_tpu.io import tabix
from variantcalling_tpu.io import vcf as vcf_mod

native = pytest.importorskip("variantcalling_tpu.native")

MEMBER = bgzf_mod.MAX_BLOCK_DATA
LINE = 64  # every hand-made line is a multiple: 1,020 of them fill a member
assert MEMBER % LINE == 0


@pytest.fixture(autouse=True)
def _engine_cache_isolated():
    yield
    from variantcalling_tpu import engine as engine_mod

    engine_mod.reset_for_tests()


def inflated(path: str) -> bytes:
    with open(path, "rb") as fh:
        return gzip.decompress(fh.read())


def same_as_the_second_pass(vcf_gz: str, streamed_tbi: str) -> tabix.TabixIndex:
    """The streamed index against the oracle's for the same file: bytes,
    then lines through both for regions of every contig."""
    got = inflated(streamed_tbi)
    os.replace(streamed_tbi, streamed_tbi + ".streamed")
    tabix.build_tabix_index(vcf_gz)
    want = inflated(vcf_gz + ".tbi")
    assert got == want
    ours = tabix.TabixIndex.load(streamed_tbi + ".streamed")
    theirs = tabix.TabixIndex.load(vcf_gz + ".tbi")
    assert ours.names == theirs.names
    records = [ln for ln in inflated(vcf_gz).decode().splitlines() if not ln.startswith("#")]
    for name in ours.names:
        mine = [r.split("\t", 4) for r in records if r.startswith(name + "\t")]
        last = int(mine[-1][1])
        for beg, end in ((0, 1 << 29), (last // 2, last // 2 + 40_000), (last - 1, last)):
            lines = list(tabix.read_region_lines(vcf_gz, name, beg, end, index=ours))
            assert lines == list(tabix.read_region_lines(vcf_gz, name, beg, end, index=theirs))
            assert len(lines) == sum(
                int(f[1]) - 1 < end and int(f[1]) - 1 + len(f[3]) > beg for f in mine)
    return ours


# -- the two halves on hand-made files: where the record ends fall ---------------


def hand_made(path: str, contigs: int, shift: int, n: int = 5200, step: int = 150) -> bytes:
    """A sorted VCF whose every line is a multiple of 64 bytes and whose
    header is ``shift`` bytes past one: with 0 a record ends exactly with
    each member, with 1 one byte after, with -1 one byte before. Every
    400th REF is 20,000 bases: two or three 16 kb windows, a coarser bin.
    Returns the header's bytes."""
    names = [f"chr{c + 1}" for c in range(contigs)]
    head = "##fileformat=VCFv4.2\n" + "".join(
        f"##contig=<ID={c},length=100000000>\n" for c in names)
    cols = "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\tS1\n"
    room = (-(len(head) + len("##pad=\n") + len(cols)) + shift) % LINE
    head += "##pad=" + "p" * room + "\n" + cols
    assert len(head) % LINE == shift % LINE
    lines = []
    for i in range(n):
        chrom = names[i * contigs // n]
        ref = "A" * 20_000 if i % 400 == 7 else "ACG"[: 1 + i % 3]
        fixed = f"{chrom}\t{1000 + step * i}\t\t{ref}\t{ref[0] if len(ref) > 1 else 'T'}\t50\tPASS\tDP=9\tGT\t0/1\n"
        lines.append(fixed.replace("\t\t", "\t" + "r" * (-len(fixed) % LINE or LINE) + "\t", 1))
        assert len(lines[-1]) % LINE == 0
    with open(path, "w", encoding="ascii") as fh:
        fh.write(head + "".join(lines))
    return head.encode()


def cuts_for(scheme: str, ends: np.ndarray) -> list[int]:
    """Where the chunks end, in records: ``members`` after each record
    that ends within a byte of a member's end (a chunk border ON the
    border), ``mid`` every 333 records (those records mid-chunk), ``one``
    a single chunk."""
    n = len(ends)
    if scheme == "one":
        return [n]
    if scheme == "mid":
        return [*range(333, n, 333), n]
    near = np.flatnonzero(((ends + 1) % MEMBER) <= 2) + 1
    assert len(near) >= 3
    return sorted({*near.tolist(), n})


def render_native(table) -> np.ndarray:
    return vcf_mod.assemble_table_bytes(table)


@pytest.mark.parametrize("render", [render_native, vcf_mod.render_table_bytes_python],
                         ids=["native", "python"])
@pytest.mark.parametrize("contigs", [1, 4])
@pytest.mark.parametrize("scheme", ["members", "mid", "one"])
@pytest.mark.parametrize("shift", [0, 1, -1], ids=["on", "after", "before"])
def test_the_streamed_index_is_the_second_passs(tmp_path, shift, scheme, contigs, render):
    src, out = str(tmp_path / "in.vcf"), str(tmp_path / "out.vcf.gz")
    header = hand_made(src, contigs, shift)
    table = vcf_mod.read_vcf(src)
    spans = table.aux.line_spans
    ends = len(header) + np.cumsum(spans[:, 1] - spans[:, 0] + 1)
    on_a_border = int(np.sum(ends % MEMBER == 0))
    assert on_a_border >= 3 if shift == 0 else on_a_border == 0
    comp, index = bgzf_mod.BgzfChunkCompressor(), tabix.StreamedIndex()
    with open(out, "wb") as fh:
        def put(blob):
            index.add_blocks(blob)
            fh.write(blob)

        put(comp.add(header))
        lo = 0
        for hi in cuts_for(scheme, ends):
            part = table.subset(np.arange(lo, hi))
            body = render(part)
            facts = tabix.chunk_index_facts(body, part)
            assert facts is not None and len(facts.ends) == hi - lo
            index.add_chunk(facts, comp.bytes_in)
            put(comp.add(body))
            lo = hi
        put(comp.finish())
    assert inflated(out) == open(src, "rb").read()  # the renderers' bytes are the input's
    assert index.complete and index.records == len(table) and index.write(out + ".tbi")
    ours = same_as_the_second_pass(out, out + ".tbi")
    assert len(ours.names) == contigs
    bins = {b for per in ours.bins.values() for b in per}
    assert min(bins) < 4681 <= max(bins)  # the long REFs sit in coarser bins
    if shift == 0 and scheme != "one":
        # neighbours of one bin that meet on a member's end stay two chunks
        met = [(a, b) for per in ours.bins.values() for cs in per.values()
               for a, b in zip(cs, cs[1:]) if a[1] & 0xFFFF == MEMBER and b[0] & 0xFFFF == 0]
        assert met


def a_table(tmp_path, text: str):
    path = str(tmp_path / "t.vcf")
    with open(path, "w", encoding="ascii") as fh:
        fh.write("##fileformat=VCFv4.2\n#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\n" + text)
    return vcf_mod.read_vcf(path)


REFUSED = {
    "a position going back": "c1\t9\t.\tA\tT\t.\t.\t.\nc1\t8\t.\tA\tT\t.\t.\t.\n",
    "a contig twice": "c1\t5\t.\tA\tT\t.\t.\t.\nc2\t5\t.\tA\tT\t.\t.\t.\nc1\t6\t.\tA\tT\t.\t.\t.\n",
    "a start before 0": "c1\t0\t.\tA\tT\t.\t.\t.\n",
    "a name the dictionary cut": "c" * 70 + "\t5\t.\tA\tT\t.\t.\t.\n",
}


@pytest.mark.parametrize("why", sorted(REFUSED))
def test_a_chunk_that_cannot_vouch_gives_no_facts(tmp_path, why):
    table = a_table(tmp_path, REFUSED[why])
    assert tabix.chunk_index_facts(vcf_mod.render_table_bytes_python(table), table) is None


def test_facts_need_the_scans_columns_and_one_line_a_record(tmp_path):
    table = a_table(tmp_path, "c1\t5\t.\tA\tT\t.\t.\t.\nc1\t6\t.\tAC\tA\t.\t.\t.\n")
    body = vcf_mod.render_table_bytes_python(table)
    facts = tabix.chunk_index_facts(body, table)
    assert facts.names == ["c1"] and facts.ends.tolist() == [17, 35]
    assert (facts.first_beg, facts.last_beg) == (4, 5)
    assert tabix.chunk_index_facts(body[:-1], table) is None  # no last newline
    assert tabix.chunk_index_facts(body + body, table) is None  # lines of another table
    table.chrom_codes = None  # a table the native scan did not make
    assert tabix.chunk_index_facts(body, table) is None


def test_the_ordered_half_refuses_what_the_facts_do_not_cover(tmp_path):
    table = a_table(tmp_path, "c1\t5\t.\tA\tT\t.\t.\t.\nc2\t6\t.\tA\tT\t.\t.\t.\n")
    body = vcf_mod.render_table_bytes_python(table)
    facts = tabix.chunk_index_facts(body, table)

    def fed(*chunks, blobs=None):
        index, comp = tabix.StreamedIndex(), bgzf_mod.BgzfChunkCompressor()
        for f in chunks:
            index.add_chunk(f, comp.bytes_in)
            index.add_blocks(comp.add(body))
        for blob in blobs if blobs is not None else [comp.finish()]:
            index.add_blocks(blob)
        return index

    path = str(tmp_path / "x.tbi")
    assert fed(facts).write(path) and os.path.exists(path)
    os.remove(path)
    for index in (fed(facts, None),  # a body with no facts: a replayed cached chunk
                  fed(facts, facts),  # the same contigs again
                  fed(facts, blobs=[b"not BGZF"]),
                  # a short member before the last: not the compressor's framing
                  fed(facts, blobs=[bgzf_mod.compress_block(body[:7]),
                                    bgzf_mod.compress_block(body[7:]), bgzf_mod.BGZF_EOF]),
                  fed(facts, blobs=[bgzf_mod.BGZF_EOF])):  # fewer bytes than the records'
        assert not index.write(path) and not os.path.exists(path)


# -- whole runs --------------------------------------------------------------------

N = 6000


@pytest.fixture(scope="module", params=[1, 4], ids=["1contig", "4contigs"])
def world(request, tmp_path_factory):
    from variantcalling_tpu.synthetic import make_fixtures_fast, synthetic_forest

    d = str(tmp_path_factory.mktemp("tbistream"))
    make_fixtures_fast(d, n=N, genome_len=400_000, n_contigs=request.param)
    with open(f"{d}/calls.vcf", "rb") as fh:
        text = fh.read()
    with bgzf_mod.BgzfWriter(f"{d}/calls.vcf.gz") as w:
        w.write(text)
    # two records of one contig change places: no longer sorted
    lines = text.split(b"\n")
    k = next(i for i, ln in enumerate(lines) if ln and not ln.startswith(b"#")) + N // 2
    assert lines[k].split(b"\t")[0] == lines[k + 1].split(b"\t")[0]
    assert lines[k].split(b"\t")[1] != lines[k + 1].split(b"\t")[1]
    lines[k], lines[k + 1] = lines[k + 1], lines[k]
    with open(f"{d}/unsorted.vcf", "wb") as fh:
        fh.write(b"\n".join(lines))
    with open(f"{d}/model.pkl", "wb") as fh:
        pickle.dump({"m": synthetic_forest(np.random.default_rng(0), n_trees=8, depth=4)}, fh)
    return d


def run(world, monkeypatch, inp, out, io_threads=2, **env) -> dict:
    """One CLI run under obs; its exit code and final counters."""
    from variantcalling_tpu import engine as engine_mod
    from variantcalling_tpu.pipelines.filter_variants import run as fvp_run

    monkeypatch.setattr(vcf_mod, "STREAM_CHUNK_BYTES", 1 << 16)
    monkeypatch.setenv("VCTPU_THREADS", "2")
    monkeypatch.setenv("VCTPU_IO_THREADS", str(io_threads))
    monkeypatch.setenv("VCTPU_IO_SHARD_BYTES", str(1 << 17))
    monkeypatch.setenv("VCTPU_OBS", "1")
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    engine_mod.reset_for_tests()
    rc = fvp_run(["--input_file", f"{world}/{inp}", "--model_file", f"{world}/model.pkl",
                  "--model_name", "m", "--reference_file", f"{world}/ref.fa",
                  "--output_file", out])
    with open(out + ".obs.jsonl", encoding="utf-8") as fh:
        events = [json.loads(ln) for ln in fh if ln.strip()]
    final = [e for e in events if e["kind"] == "metrics" and e["name"] == "final"]
    c = final[-1]["counters"]
    return {"rc": rc, "records": c["tabix.records"], "counters": c,
            "chunks": sum(e["kind"] == "heartbeat" for e in events),
            "how": (c["tabix.index_streamed"], c["tabix.index_second_pass"],
                    c["tabix.index_skipped"])}


@pytest.mark.parametrize("renderer", ["native", "python"])
def test_a_run_writes_the_index_it_gathered(world, monkeypatch, tmp_path, renderer):
    if renderer == "python":  # the renderer a native hiccup falls to: same bytes
        monkeypatch.setattr(vcf_mod, "assemble_table_bytes", lambda *a, **k: None)
    out = str(tmp_path / "out.vcf.gz")
    got = run(world, monkeypatch, "calls.vcf.gz", out)
    assert (got["rc"], got["records"], got["how"]) == (0, N, (1, 0, 0)) and got["chunks"] > 3
    same_as_the_second_pass(out, out + ".tbi")


def test_the_streamed_index_follows_the_engines_member_offsets(world, monkeypatch, tmp_path):
    """ISSUE 39: under libdeflate the members' sizes, and so the index's
    virtual offsets, are not zlib's; the index gathered in the run follows
    the members the run wrote, under either engine, to the same text."""
    if native.bgzf_engine() != native.BGZF_LIBDEFLATE:
        pytest.skip("libdeflate.so.0 did not load on this host")
    ld_out, zl_out = str(tmp_path / "ld.vcf.gz"), str(tmp_path / "zl.vcf.gz")
    got = run(world, monkeypatch, "calls.vcf.gz", ld_out)
    assert (got["rc"], got["records"], got["how"]) == (0, N, (1, 0, 0))
    members = got["counters"]["bgzf.deflate_members"]
    assert members > 0 and got["counters"]["bgzf.libdeflate_members"] == members
    same_as_the_second_pass(ld_out, ld_out + ".tbi")
    compress = native.bgzf_compress
    monkeypatch.setattr(native, "bgzf_compress",
                        lambda data, level=6: compress(data, level, engine=native.BGZF_ZLIB))
    got = run(world, monkeypatch, "calls.vcf.gz", zl_out)
    assert (got["rc"], got["how"], got["counters"]["bgzf.libdeflate_members"]) == (0, (1, 0, 0), 0)
    same_as_the_second_pass(zl_out, zl_out + ".tbi")
    assert inflated(ld_out) == inflated(zl_out)
    assert open(ld_out, "rb").read() != open(zl_out, "rb").read()
    assert inflated(ld_out + ".tbi.streamed") != inflated(zl_out + ".tbi.streamed")


def test_an_unsorted_input_falls_back_to_the_second_pass(world, monkeypatch, tmp_path):
    out = str(tmp_path / "unsorted.vcf.gz")
    got = run(world, monkeypatch, "unsorted.vcf", out)
    assert (got["rc"], got["records"], got["how"]) == (0, N, (0, 1, 0))
    fell_back = inflated(out + ".tbi")
    tabix.build_tabix_index(out)
    assert fell_back == inflated(out + ".tbi")


def test_a_replayed_cached_chunk_falls_back_to_the_second_pass(world, monkeypatch, tmp_path):
    cache = {"VCTPU_CACHE": "1", "VCTPU_CACHE_DIR": str(tmp_path / "cache")}
    cold, warm = str(tmp_path / "cold.vcf.gz"), str(tmp_path / "warm.vcf.gz")
    got = run(world, monkeypatch, "calls.vcf.gz", cold, **cache)
    assert (got["rc"], got["records"], got["how"]) == (0, N, (1, 0, 0))  # misses render: facts
    got = run(world, monkeypatch, "calls.vcf.gz", warm, **cache)
    assert (got["rc"], got["records"], got["how"]) == (0, N, (0, 1, 0))  # hits replay: none
    assert open(cold, "rb").read() == open(warm, "rb").read()
    assert inflated(cold + ".tbi") == inflated(warm + ".tbi")


def test_a_quarantined_chunk_adds_no_record_and_the_run_still_streams(
        world, monkeypatch, tmp_path):
    from variantcalling_tpu.utils import faults

    out = str(tmp_path / "quar.vcf.gz")
    faults.reset()
    faults.arm("pipeline.chunk", times=2)  # chunk 0: an attempt, a re-dispatch, then diverted
    try:
        got = run(world, monkeypatch, "calls.vcf.gz", out, io_threads=1, VCTPU_QUARANTINE="1")
    finally:
        faults.reset()
    diverted = open(out + ".quarantine", "rb").read().count(b"\n")
    assert got["rc"] == 0 and got["how"] == (1, 0, 0)
    assert 0 < diverted < N and got["records"] == N - diverted
    written = [ln for ln in inflated(out).split(b"\n") if ln and not ln.startswith(b"#")]
    assert len(written) == N - diverted
    same_as_the_second_pass(out, out + ".tbi")
