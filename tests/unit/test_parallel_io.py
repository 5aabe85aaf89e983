"""Parallel host IO (ISSUE 7 tentpole): sharded BGZF ingest + ordered
parallel writeback.

Locks the three contracts the parallel paths must keep:

- **Byte parity**: streaming output is byte-identical across every
  ``VCTPU_IO_THREADS`` setting, both input containers (plain / BGZF) and
  both engines (native / jit) — parallelism changes WHO does the work,
  never the bytes.
- **Boundary identity**: the chunk sequence (and therefore the journal
  resume identity) is the same at every worker count.
- **Framing identity**: the compress stage's BGZF block framing is
  byte-identical to a serial :class:`BgzfWriter`, at any chunk split and
  worker count.
"""

from __future__ import annotations

import gzip
import itertools
import os
import pickle

import numpy as np
import pytest

from variantcalling_tpu.io import bgzf as bgzf_mod
from variantcalling_tpu.parallel.pipeline import IoPool, imap_ordered

native = pytest.importorskip("variantcalling_tpu.native")


@pytest.fixture(autouse=True)
def _engine_cache_isolated():
    """The engine decision is cached per process; tests here pin it via
    VCTPU_ENGINE, so drop the cache on the way out — a later test file
    must re-resolve under ITS environment, not ours."""
    yield
    from variantcalling_tpu import engine as engine_mod

    engine_mod.reset_for_tests()


#: directories the leak sentinel sweeps after every test (chaos
#: invariant on the regular suite — tests/conftest.assert_no_stream_leaks)
_WATCHED_DIRS: list[str] = []


@pytest.fixture(autouse=True)
def _leak_sentinel():
    yield
    from tests.conftest import assert_no_stream_leaks

    assert_no_stream_leaks(_WATCHED_DIRS)


# ---------------------------------------------------------------------------
# BGZF layer: block scan, shard inflate, chunk compressor framing
# ---------------------------------------------------------------------------


def _bgzf_file(tmp_path, payload: bytes) -> str:
    path = str(tmp_path / "x.gz")
    with bgzf_mod.BgzfWriter(path) as w:
        w.write(payload)
    return path


def test_scan_block_spans_roundtrip(tmp_path):
    payload = b"".join(b"line %d with some filler text\n" % i
                       for i in range(120_000))
    path = _bgzf_file(tmp_path, payload)
    raw = open(path, "rb").read()
    spans = bgzf_mod.scan_block_spans(raw)
    assert spans is not None and len(spans) > 2
    # spans tile the compressed file exactly; isizes tile the payload
    assert spans[0][0] == 0
    assert all(a[0] + a[1] == b[0] for a, b in zip(spans, spans[1:]))
    assert spans[-1][0] + spans[-1][1] == len(raw)
    assert sum(s[2] for s in spans) == len(payload)
    assert bgzf_mod.inflate_spans(raw, spans) == payload


def test_scan_block_spans_rejects_plain_gzip(tmp_path):
    path = str(tmp_path / "plain.gz")
    with gzip.open(path, "wb") as fh:
        fh.write(b"not bgzf\n" * 1000)
    assert bgzf_mod.scan_block_spans(open(path, "rb").read()) is None


@pytest.mark.parametrize("pooled", [False, True])
def test_chunk_compressor_matches_serial_writer(tmp_path, pooled, monkeypatch):
    """The compress stage's framing is byte-identical to BgzfWriter no
    matter how the byte stream is split into add() calls."""
    if pooled:
        # force the per-block pool fan-out: with the native compressor
        # built, _compress_full_blocks never consults the pool and both
        # parametrizations would exercise the identical native path —
        # the branch this case exists to cover would ship untested
        monkeypatch.setattr(native, "bgzf_compress", lambda *a, **k: None)
    rng = np.random.default_rng(3)
    payload = bytes(rng.integers(32, 127, size=400_000, dtype=np.uint8))
    serial = _bgzf_file(tmp_path, payload)
    want = open(serial, "rb").read()

    pool = IoPool(3) if pooled else None
    cuts = sorted(rng.integers(0, len(payload), size=7).tolist())
    pieces = [payload[a:b] for a, b in
              zip([0, *cuts], [*cuts, len(payload)])]
    cc = bgzf_mod.BgzfChunkCompressor(pool=pool)
    got = b"".join(cc.add(p) for p in pieces) + cc.finish()
    if pool is not None:
        pool.shutdown()
    assert got == want
    assert gzip.decompress(got) == payload


def test_chunk_compressor_empty_stream():
    cc = bgzf_mod.BgzfChunkCompressor()
    assert cc.add(b"") == b""
    assert cc.finish() == bgzf_mod.BGZF_EOF


# ---------------------------------------------------------------------------
# pool primitives
# ---------------------------------------------------------------------------


def test_imap_ordered_preserves_order_and_bounds_window():
    pool = IoPool(4)
    in_flight = []

    def work(x):
        in_flight.append(x)
        return x * x

    out = list(imap_ordered(pool, work, range(50), window=3))
    assert out == [x * x for x in range(50)]
    pool.shutdown()
    assert pool.unjoined == []


def test_imap_ordered_reraises_at_ordinal_position():
    pool = IoPool(2)

    def work(x):
        if x == 3:
            raise OSError("boom")
        return x

    it = imap_ordered(pool, work, range(10), window=4)
    assert [next(it) for _ in range(3)] == [0, 1, 2]
    with pytest.raises(OSError, match="boom"):
        next(it)
    pool.shutdown()


def test_io_pool_worker_names_feed_attribution():
    pool = IoPool(2, name="vctpu-io")
    import threading

    names = sorted({pool.submit(
        lambda: threading.current_thread().name).result(5)
        for _ in range(8)})
    assert all(n.startswith("vctpu-io-w") for n in names)
    pool.shutdown()


# ---------------------------------------------------------------------------
# the chunk reader: identical chunk sequence at every worker count
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def vcf_world(tmp_path_factory):
    from variantcalling_tpu.synthetic import make_fixtures

    d = str(tmp_path_factory.mktemp("pario"))
    make_fixtures(d, n=5000, genome_len=250_000)
    with open(f"{d}/calls.vcf", "rb") as fh:
        text = fh.read()
    with bgzf_mod.BgzfWriter(f"{d}/calls.vcf.gz") as w:
        w.write(text)
    _WATCHED_DIRS.append(d)
    return {"dir": d, "n": 5000}


def _chunk_signature(reader) -> list[tuple]:
    out = []
    for t in reader:
        out.append((len(t), int(t.pos[0]), int(t.pos[-1]), t.chrom[0]))
    return out


@pytest.mark.parametrize("suffix", ["", ".gz"])
def test_reader_chunk_boundaries_identical_across_io_threads(vcf_world, suffix):
    from variantcalling_tpu.io.vcf import VcfChunkReader

    path = f"{vcf_world['dir']}/calls.vcf{suffix}"
    ref = _chunk_signature(VcfChunkReader(path, chunk_bytes=1 << 15,
                                          io_threads=1))
    assert len(ref) > 3
    assert sum(s[0] for s in ref) == vcf_world["n"]
    for io_threads in (2, 4):
        sig = _chunk_signature(VcfChunkReader(path, chunk_bytes=1 << 15,
                                              io_threads=io_threads))
        assert sig == ref


def test_parallel_bgzf_stream_matches_gzip(vcf_world):
    from variantcalling_tpu.io.vcf import _ParallelBgzfStream

    path = f"{vcf_world['dir']}/calls.vcf.gz"
    want = gzip.open(path, "rb").read()
    pool = IoPool(3)
    stream = _ParallelBgzfStream(path, pool)
    got = b""
    while True:
        b = stream.read(37_123)  # deliberately unaligned reads
        if not b:
            break
        got += b
    stream.close()
    pool.shutdown()
    assert got == want


# ---------------------------------------------------------------------------
# acceptance: streaming byte parity across IO threads x container x engine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def stream_world(vcf_world, tmp_path_factory):
    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.synthetic import synthetic_forest

    d = vcf_world["dir"]
    model = synthetic_forest(np.random.default_rng(0), n_trees=8, depth=4)
    with open(f"{d}/model.pkl", "wb") as fh:
        pickle.dump({"m": model}, fh)
    return dict(vcf_world, model=model, fasta=FastaReader(f"{d}/ref.fa"))


def _stream(w, inp, out, monkeypatch, io_threads, engine):
    import argparse

    from variantcalling_tpu import engine as engine_mod
    from variantcalling_tpu.io import vcf as vcf_mod
    from variantcalling_tpu.pipelines.filter_variants import run_streaming

    monkeypatch.setattr(vcf_mod, "STREAM_CHUNK_BYTES", 1 << 15)
    monkeypatch.setenv("VCTPU_IO_THREADS", str(io_threads))
    monkeypatch.setenv("VCTPU_ENGINE", engine)
    engine_mod.reset_for_tests()  # re-resolve under the patched env
    args = argparse.Namespace(
        input_file=inp, output_file=out, runs_file=None,
        hpol_filter_length_dist=[10, 10], blacklist=None,
        blacklist_cg_insertions=False, annotate_intervals=[],
        flow_order="TGCA", is_mutect=False, limit_to_contig=None)
    return run_streaming(args, w["model"], w["fasta"], {}, None)


@pytest.mark.flakehunt
@pytest.mark.parametrize("engine", ["native", "jit"])
def test_streaming_byte_parity_io_threads_matrix(stream_world, monkeypatch,
                                                 engine):
    """Acceptance: output byte-identical across VCTPU_IO_THREADS={1,2,4}
    x {plain, BGZF} input x {plain, BGZF} output, per engine (ordering-
    sensitive: flakehunt repeats it)."""
    w = stream_world
    d = w["dir"]
    oracle: dict[str, bytes] = {}
    for io_threads, in_sfx, out_sfx in itertools.product(
            (1, 2, 4), ("", ".gz"), ("", ".gz")):
        inp = f"{d}/calls.vcf{in_sfx}"
        out = f"{d}/out_{engine}_{io_threads}{in_sfx.replace('.', '_')}.vcf{out_sfx}"
        stats = _stream(w, inp, out, monkeypatch, io_threads, engine)
        assert stats is not None and stats["n"] == w["n"], (io_threads, in_sfx)
        by = open(out, "rb").read()
        key = out_sfx
        if key not in oracle:
            oracle[key] = by
        else:
            assert by == oracle[key], (engine, io_threads, in_sfx, out_sfx)
    # the BGZF container holds exactly the plain bytes
    assert gzip.decompress(oracle[".gz"]) == oracle[""]


@pytest.mark.flakehunt
def test_streaming_parity_engines_agree_modulo_header(stream_world,
                                                      monkeypatch):
    """Cross-engine: the records are byte-identical (PR 2 contract);
    only the ##vctpu_engine=/##vctpu_forest_strategy= header lines name
    the scoring configuration."""
    w = stream_world
    d = w["dir"]
    outs = {}
    for engine in ("native", "jit"):
        out = f"{d}/out_x_{engine}.vcf"
        assert _stream(w, f"{d}/calls.vcf", out, monkeypatch, 2,
                       engine) is not None
        outs[engine] = open(out, "rb").read()
    assert outs["native"].replace(
        b"##vctpu_engine=native", b"##vctpu_engine=jit").replace(
        b"##vctpu_forest_strategy=native-cpp",
        b"##vctpu_forest_strategy=gather") == outs["jit"]


def test_streaming_gz_python_block_fallback_tail_compress(stream_world,
                                                          monkeypatch):
    """gz writeback WITHOUT the native compressor: chunk bodies deflate
    per-block on the shared IO pool. Tail chunks compress AFTER ingest
    exhausts, so the pool must outlive iteration (it is shared with the
    compress stage; the run owner shuts it down at teardown) — the
    regression here was a tail submit landing on a pool that ingest
    exhaustion had already shut down, blocking until the watchdog."""
    import argparse

    from variantcalling_tpu import engine as engine_mod
    from variantcalling_tpu.io import vcf as vcf_mod
    from variantcalling_tpu.pipelines.filter_variants import run_streaming

    monkeypatch.setattr(native, "bgzf_compress", lambda *a, **k: None)
    # chunks must span >1 BGZF block or the per-block fan-out is skipped
    monkeypatch.setattr(vcf_mod, "STREAM_CHUNK_BYTES", 1 << 17)
    monkeypatch.setenv("VCTPU_IO_THREADS", "4")
    monkeypatch.setenv("VCTPU_ENGINE", "native")
    monkeypatch.setenv("VCTPU_STAGE_TIMEOUT_S", "60")  # a regression fails, never wedges CI
    engine_mod.reset_for_tests()
    w = stream_world
    d = w["dir"]

    def run(out):
        args = argparse.Namespace(
            input_file=f"{d}/calls.vcf", output_file=out, runs_file=None,
            hpol_filter_length_dist=[10, 10], blacklist=None,
            blacklist_cg_insertions=False, annotate_intervals=[],
            flow_order="TGCA", is_mutect=False, limit_to_contig=None)
        return run_streaming(args, w["model"], w["fasta"], {}, None)

    stats = run(f"{d}/fb.vcf.gz")
    assert stats is not None and stats["n"] == w["n"]
    assert run(f"{d}/fb.vcf")["n"] == w["n"]
    assert gzip.decompress(open(f"{d}/fb.vcf.gz", "rb").read()) == \
        open(f"{d}/fb.vcf", "rb").read()


def test_streaming_gz_output_matches_serial_write_vcf(stream_world,
                                                      monkeypatch):
    """The parallel compress stage's .gz container is byte-identical to
    the serial whole-table writer's (same framing, same deflate)."""
    from variantcalling_tpu.io.vcf import read_vcf, write_vcf
    from variantcalling_tpu.pipelines.filter_variants import (
        FilterContext, _ensure_output_header)

    w = stream_world
    d = w["dir"]
    out_s = f"{d}/serial_out.vcf.gz"
    stats = _stream(w, f"{d}/calls.vcf", f"{d}/stream_out.vcf.gz",
                    monkeypatch, 4, "native")
    assert stats is not None
    table = read_vcf(f"{d}/calls.vcf")
    ctx = FilterContext(w["model"], w["fasta"])
    score, filters = ctx.score_table(table)
    _ensure_output_header(table.header, engine=ctx.engine,
                          strategy=ctx.forest_strategy)
    write_vcf(out_s, table, new_filters=filters,
              extra_info={"TREE_SCORE": np.round(score, 4)},
              verbatim_core=True)
    assert open(out_s, "rb").read() == \
        open(f"{d}/stream_out.vcf.gz", "rb").read()
