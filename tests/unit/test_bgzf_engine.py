"""The native BGZF compressor's two deflate engines (ISSUE 39): zlib, and
libdeflate where the host's ``libdeflate.so.0`` loads (``vctpu_bgzf_engine``).
Both through the C entry's ``engine`` argument: the round trip, the framing,
thread-count invariance, the size against zlib's, the zlib engine pinned to
Python's ``compress_block`` (the fallback), and the members counted under obs.
The libdeflate cases skip on a host where the library did not load."""

from __future__ import annotations

import gzip

import numpy as np
import pytest

from variantcalling_tpu import native
from variantcalling_tpu.io import bgzf as bgzf_mod

pytestmark = pytest.mark.skipif(not native.available(), reason="native toolchain unavailable")

MEMBER = bgzf_mod.MAX_BLOCK_DATA
ENGINES = pytest.mark.parametrize("engine", [native.BGZF_ZLIB, native.BGZF_LIBDEFLATE],
                                  ids=["zlib", "libdeflate"])
DATA = pytest.mark.parametrize("kind", ["vcf", "random"])


def held(engine: int) -> int:
    if engine == native.BGZF_LIBDEFLATE and native.bgzf_engine() != engine:
        pytest.skip("libdeflate.so.0 did not load on this host")
    return engine


@pytest.fixture(scope="module")
def texts(tmp_path_factory):
    """VCF text of ``make_fixtures_fast`` (about 225 members: 13 threads of
    at least 16 members each), and 300,000 incompressible bytes."""
    from variantcalling_tpu.synthetic import make_fixtures_fast

    d = str(tmp_path_factory.mktemp("bgzfengine"))
    make_fixtures_fast(d, n=270_000, genome_len=3_000_000, n_contigs=4)
    with open(f"{d}/calls.vcf", "rb") as fh:
        vcf = fh.read()
    assert len(vcf) > 13 * 16 * MEMBER
    return {"vcf": vcf,
            "random": np.random.default_rng(5).integers(0, 256, 300_000, np.uint8).tobytes()}


@DATA
@ENGINES
def test_both_decoders_give_the_text_back(texts, kind, engine):
    blob = native.bgzf_compress(texts[kind], engine=held(engine))
    assert gzip.decompress(blob) == texts[kind]
    assert native.bgzf_decompress(blob) == texts[kind]


@DATA
@ENGINES
def test_every_member_is_framed_and_the_chain_ends_in_eof(texts, kind, engine):
    data = texts[kind]
    blob = native.bgzf_compress(data, engine=held(engine))
    spans = bgzf_mod.scan_block_spans(blob)
    assert spans is not None and blob.endswith(bgzf_mod.BGZF_EOF)
    assert spans[-1] == (len(blob) - 28, 28, 0)
    sizes = [isize for _, _, isize in spans[:-1]]
    assert sizes == [min(MEMBER, len(data) - i) for i in range(0, len(data), MEMBER)]
    assert max(bsize for _, bsize, _ in spans) <= 65536  # incompressible members fit too


@ENGINES
def test_the_bytes_are_the_same_at_every_thread_count(texts, monkeypatch, engine):
    held(engine)
    outs = {}
    for t in (1, 2, 5, 13):
        monkeypatch.setenv("VCTPU_NATIVE_THREADS", str(t))
        outs[t] = native.bgzf_compress(texts["vcf"], engine=engine)
    assert all(out == outs[1] for out in outs.values())


def test_libdeflate_is_no_larger_than_zlib_on_vcf_text(texts):
    ld = native.bgzf_compress(texts["vcf"], engine=held(native.BGZF_LIBDEFLATE))
    zl = native.bgzf_compress(texts["vcf"], engine=native.BGZF_ZLIB)
    assert len(ld) <= len(zl) and ld != zl


@DATA
def test_the_zlib_engine_is_pythons_compress_block_member_by_member(texts, kind):
    data = texts[kind]
    want = b"".join(bgzf_mod.compress_block(data[i:i + MEMBER])
                    for i in range(0, len(data), MEMBER))
    assert native.bgzf_compress(data, engine=native.BGZF_ZLIB) == want + bgzf_mod.BGZF_EOF


def test_the_default_is_the_engine_that_loaded(texts):
    assert native.bgzf_engine() in (native.BGZF_ZLIB, native.BGZF_LIBDEFLATE)
    data = texts["random"]
    assert native.bgzf_compress(data) == native.bgzf_compress(data, engine=native.bgzf_engine())


@ENGINES
def test_members_are_counted_under_obs(texts, tmp_path, engine):
    from variantcalling_tpu import obs

    data = texts["vcf"][:5 * MEMBER + 17]
    native.bgzf_compress(data, engine=held(engine))  # no run open: nothing to count
    run = obs.start_run("test_bgzf_engine", force_path=str(tmp_path / "obs.jsonl"))
    try:
        native.bgzf_compress(data, engine=engine)
        native.bgzf_compress(b"", engine=engine)
        counters = run.metrics.snapshot()["counters"]
    finally:
        obs.end_run(run, "ok")
    assert counters["bgzf.deflate_members"] == 6
    assert counters.get("bgzf.libdeflate_members", 0) == (
        6 if engine == native.BGZF_LIBDEFLATE else 0)
