"""Streaming pipelined filter executor: ordering, bounded queues, serial
fallback, byte-identity with the serial path, FASTA encode/cache, and the
host coverage reduce (ISSUE 1 tentpole + satellites)."""

import gzip
import os
import pickle
import threading
import time

import numpy as np
import pytest

from tests import fixtures
from variantcalling_tpu.parallel.pipeline import StagePipeline, resolve_threads


# ---------------------------------------------------------------------------
# StagePipeline mechanics
# ---------------------------------------------------------------------------


def test_stage_pipeline_ordering_and_results():
    pipe = StagePipeline([lambda x: x * 2, lambda x: x + 1], threads=4)
    assert pipe.parallel
    got = list(pipe.run(range(50)))
    assert got == [i * 2 + 1 for i in range(50)]


def test_stage_pipeline_serial_one_thread_same_results():
    stages = [lambda x: x * 3, lambda x: x - 1]
    serial = StagePipeline(stages, threads=1)
    assert not serial.parallel
    assert list(serial.run(range(20))) == list(
        StagePipeline(stages, threads=4).run(range(20)))


def test_resolve_threads_env(monkeypatch):
    monkeypatch.setenv("VCTPU_THREADS", "1")
    assert resolve_threads() == 1
    monkeypatch.setenv("VCTPU_THREADS", "7")
    assert resolve_threads() == 7
    # knob-registry contract (ISSUE 4): a malformed value is a
    # configuration error (EngineError, CLI exit 2) on every engine —
    # the old fall-back-to-auto behavior silently changed the executor
    from variantcalling_tpu.engine import EngineError

    monkeypatch.setenv("VCTPU_THREADS", "bogus")
    with pytest.raises(EngineError, match="not a positive integer"):
        resolve_threads()
    monkeypatch.delenv("VCTPU_THREADS")
    assert resolve_threads() == (os.cpu_count() or 1)


def test_stage_pipeline_exception_propagates():
    def boom(x):
        if x == 7:
            raise ValueError("chunk 7 is cursed")
        return x

    pipe = StagePipeline([boom, lambda x: x], queue_depth=1, threads=4)
    with pytest.raises(ValueError, match="cursed"):
        list(pipe.run(range(32)))


def test_stage_pipeline_source_exception_propagates():
    def source():
        yield 1
        raise RuntimeError("source died")

    with pytest.raises(RuntimeError, match="source died"):
        list(StagePipeline([lambda x: x], threads=2).run(source()))


def test_stage_pipeline_bounded_inflight():
    """Queue bound: in-flight items never approach the input size."""
    n_items = 40
    depth = 1
    live = 0
    peak = 0
    lock = threading.Lock()

    def source():
        nonlocal live, peak
        for i in range(n_items):
            with lock:
                live += 1
                peak = max(peak, live)
            yield i

    def slow_sink(x):
        time.sleep(0.002)
        return x

    pipe = StagePipeline([lambda x: x, slow_sink], queue_depth=depth, threads=4)
    done = 0
    for _ in pipe.run(source()):
        with lock:
            live -= 1
        done += 1
    assert done == n_items
    # 3 queues * depth + one item resident in each of 2 stages + consumer
    assert peak <= 3 * depth + 2 + 1 + 1
    assert peak < n_items // 2


# ---------------------------------------------------------------------------
# streaming vs serial pipeline byte-identity
# ---------------------------------------------------------------------------


#: directories the leak sentinel sweeps after every test (chaos
#: invariant on the regular suite — tests/conftest.assert_no_stream_leaks)
_WATCHED_DIRS: list[str] = []


@pytest.fixture(autouse=True)
def _leak_sentinel():
    yield
    from tests.conftest import assert_no_stream_leaks

    assert_no_stream_leaks(_WATCHED_DIRS)


@pytest.fixture(scope="module")
def stream_world(tmp_path_factory):
    """Shuffled multi-contig callset + trained model: contig runs are NOT
    contiguous, so chunk scoring exercises the mask path too."""
    rng = np.random.default_rng(17)
    tmp = tmp_path_factory.mktemp("stream")
    _WATCHED_DIRS.append(str(tmp))
    contigs = {"chr1": 24000, "chr2": 16000, "chr3": 9000}
    genome = fixtures.make_genome(rng, contigs)
    fasta_path = tmp / "ref.fa"
    fixtures.write_fasta(str(fasta_path), genome)
    recs = fixtures.synth_variants(rng, genome, 1500)
    order = rng.permutation(len(recs))
    recs = [recs[i] for i in order]
    vcf_path = tmp / "calls.vcf.gz"
    fixtures.write_vcf(str(vcf_path), recs, contigs)
    runs_bed = tmp / "runs.bed"
    runs_bed.write_text("chr1\t1000\t1015\nchr2\t2000\t2012\n")
    bl = [(recs[i]["chrom"], recs[i]["pos"]) for i in (3, 10, 50)]
    bl_path = tmp / "blacklist.pkl"
    with open(bl_path, "wb") as fh:
        pickle.dump(bl, fh)

    from sklearn.ensemble import RandomForestClassifier

    from variantcalling_tpu.featurize import featurize
    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.io.vcf import read_vcf
    from variantcalling_tpu.models import registry
    from variantcalling_tpu.models.forest import from_sklearn

    table = read_vcf(str(vcf_path))
    fasta = FastaReader(str(fasta_path))
    fs = featurize(table, fasta)
    x = fs.matrix()
    y = (x[:, fs.feature_names.index("qual")] > 50).astype(int)
    clf = RandomForestClassifier(n_estimators=8, max_depth=4, random_state=0).fit(x, y)
    model_path = tmp / "model.pkl"
    registry.save_models(str(model_path), {"m": from_sklearn(clf, feature_names=fs.feature_names)})
    return {"tmp": tmp, "vcf": str(vcf_path), "fasta": str(fasta_path),
            "model": str(model_path), "runs": str(runs_bed),
            "blacklist": str(bl_path), "n": len(recs)}


def _run_cli(w, out_name, extra_env, monkeypatch):
    from variantcalling_tpu.pipelines import filter_variants as fvp

    for k, v in extra_env.items():
        if v is None:
            monkeypatch.delenv(k, raising=False)
        else:
            monkeypatch.setenv(k, v)
    out = w["tmp"] / out_name
    rc = fvp.run([
        "--input_file", w["vcf"], "--model_file", w["model"], "--model_name", "m",
        "--runs_file", w["runs"], "--blacklist", w["blacklist"],
        "--reference_file", w["fasta"], "--output_file", str(out),
        "--backend", "cpu",
    ])
    assert rc == 0
    return out.read_bytes()


def test_streaming_byte_identical_to_serial_shuffled_multicontig(stream_world, monkeypatch):
    w = stream_world
    # many small chunks so the run crosses contig and chunk boundaries often
    streaming = _run_cli(w, "out_stream.vcf.gz",
                         {"VCTPU_STREAM_CHUNK_BYTES": str(1 << 14),
                          "VCTPU_THREADS": None}, monkeypatch)
    serial = _run_cli(w, "out_serial.vcf.gz",
                      {"VCTPU_THREADS": "1"}, monkeypatch)
    assert streaming == serial  # container bytes INCLUDING the BGZF framing
    text = gzip.decompress(streaming)
    records = [ln for ln in text.split(b"\n") if ln and not ln.startswith(b"#")]
    assert len(records) == w["n"]


def test_vctpu_threads_1_selects_serial(monkeypatch):
    from variantcalling_tpu.pipelines.filter_variants import streaming_eligible

    monkeypatch.setenv("VCTPU_THREADS", "1")
    assert not streaming_eligible()
    monkeypatch.setenv("VCTPU_THREADS", "4")
    monkeypatch.setenv("VCTPU_STREAM", "0")
    assert not streaming_eligible()
    monkeypatch.delenv("VCTPU_STREAM")
    assert not streaming_eligible("chr1")  # region-limited jobs stay serial


def test_chunk_reader_matches_whole_file(stream_world):
    """Chunked tables are row-slices of the whole-file table."""
    from variantcalling_tpu.io.vcf import VcfChunkReader, read_vcf

    w = stream_world
    whole = read_vcf(w["vcf"])
    rdr = VcfChunkReader(w["vcf"], chunk_bytes=1 << 13)
    assert rdr.header.contigs == whole.header.contigs
    lo = 0
    n_chunks = 0
    for chunk in rdr:
        k = len(chunk)
        n_chunks += 1
        np.testing.assert_array_equal(chunk.pos, whole.pos[lo:lo + k])
        np.testing.assert_array_equal(np.asarray(chunk.chrom), np.asarray(whole.chrom[lo:lo + k]))
        np.testing.assert_array_equal(chunk.aux.alle["aclass"], whole.aux.alle["aclass"][lo:lo + k])
        lo += k
    assert lo == len(whole)
    assert n_chunks > 3  # the chunking actually chunked


# ---------------------------------------------------------------------------
# FASTA: vectorized .fai, native encode, persistent cache
# ---------------------------------------------------------------------------


def _reference_build_fai(path):
    """The pre-vectorization per-line .fai builder (kept as the oracle)."""
    entries = {}
    with open(path, "rb") as fh:
        name, length, offset, line_bases, line_width, pos = None, 0, 0, 0, 0, 0
        for raw in fh:
            line_len = len(raw)
            line = raw.rstrip(b"\r\n")
            if line.startswith(b">"):
                if name is not None:
                    entries[name] = (length, offset, line_bases, line_width)
                name = line[1:].split()[0].decode()
                length, offset, line_bases, line_width = 0, pos + line_len, 0, 0
            else:
                if line_bases == 0:
                    line_bases = len(line)
                    line_width = line_len
                length += len(line)
            pos += line_len
        if name is not None:
            entries[name] = (length, offset, line_bases, line_width)
    return entries


def test_vectorized_fai_matches_reference(tmp_path):
    from variantcalling_tpu.io import fasta as F

    rng = np.random.default_rng(5)
    p = tmp_path / "mixed.fa"
    with open(p, "wb") as fh:
        for name, n, width in [("c1", 997, 60), ("empty", 0, 60), ("c2", 120, 40),
                               ("c3", 59, 60), ("exact", 120, 60)]:
            fh.write(f">{name} desc\n".encode())
            s = "".join("ACGTN"[c] for c in rng.integers(0, 5, n))
            for i in range(0, n, width):
                fh.write(s[i:i + width].encode() + b"\n")
    got = F.build_fai(str(p))
    ref = _reference_build_fai(str(p))
    assert set(got) == set(ref)
    for name, (length, offset, lb, lw) in ref.items():
        e = got[name]
        assert (e.length, e.offset, e.line_bases, e.line_width) == (length, offset, lb, lw), name


def test_native_fasta_encode_matches_numpy(tmp_path):
    from variantcalling_tpu import native
    from variantcalling_tpu.io import fasta as F

    rng = np.random.default_rng(6)
    length, lb, lw = 99_991, 73, 74
    codes = rng.integers(0, 5, length).astype(np.uint8)
    seq = np.frombuffer(b"ACGTN", dtype="S1")[codes]
    raw = b"\n".join(seq[i:i + lb].tobytes() for i in range(0, length, lb)) + b"\n"
    out = native.fasta_encode(np.frombuffer(raw, np.uint8), lb, lw, length)
    if out is None:
        pytest.skip("native engine unavailable")
    np.testing.assert_array_equal(out, codes)
    # and through the reader (threaded path)
    p = tmp_path / "enc.fa"
    p.write_bytes(b">c\n" + raw)
    fr = F.FastaReader(str(p))
    np.testing.assert_array_equal(fr.fetch_encoded("c"), codes)


def test_persistent_genome_cache_roundtrip_and_invalidation(tmp_path):
    from variantcalling_tpu.io import fasta as F

    rng = np.random.default_rng(7)
    p = tmp_path / "g.fa"
    contigs = {"a": 5000, "b": 1200}
    seqs = {}
    with open(p, "wb") as fh:
        for name, n in contigs.items():
            s = "".join("ACGT"[c] for c in rng.integers(0, 4, n))
            seqs[name] = s
            fh.write(f">{name}\n".encode())
            for i in range(0, n, 60):
                fh.write(s[i:i + 60].encode() + b"\n")
    fr = F.FastaReader(str(p))
    fr.encode_all()  # encodes + persists the sidecar
    assert os.path.exists(str(p) + ".venc")
    fr2 = F.FastaReader(str(p))
    assert fr2._venc is not None  # cache attached: no re-encode
    for name, s in seqs.items():
        assert F.decode_seq(np.asarray(fr2.fetch_encoded(name))) == s
    # key is (path, mtime, size): touching the FASTA invalidates
    os.utime(p, ns=(12345, 12345))
    fr3 = F.FastaReader(str(p))
    assert fr3._venc is None
    for name, s in seqs.items():  # and the encode path still serves
        assert F.decode_seq(np.asarray(fr3.fetch_encoded(name))) == s


def test_a_resident_reader_writes_its_sidecar_once(tmp_path, monkeypatch):
    """The reader that encoded and persisted the genome serves the sidecar
    from then on: a ``vctpu serve`` daemon keeps one reader for its life,
    and every request's prefetch calls ``encode_all`` on it again."""
    from variantcalling_tpu.io import fasta as F

    p = tmp_path / "r.fa"
    s = "ACGT" * 2000
    with open(p, "wb") as fh:
        fh.write(b">c\n")
        for i in range(0, len(s), 60):
            fh.write(s[i:i + 60].encode() + b"\n")
    fr = F.FastaReader(str(p))
    writes = []
    persist = fr._persist_encoded
    monkeypatch.setattr(fr, "_persist_encoded", lambda: writes.append(1) or persist())
    before = np.array(fr.fetch_encoded("c"))
    fr.encode_all()
    assert writes == [1] and fr._venc is not None and not fr._encoded
    fr.encode_all()  # the next request's prefetch
    fr.encode_all()
    assert writes == [1]
    np.testing.assert_array_equal(fr.fetch_encoded("c"), before)


def test_fetch_encoded_thread_safe_single_encode(tmp_path):
    from variantcalling_tpu.io import fasta as F

    rng = np.random.default_rng(8)
    p = tmp_path / "t.fa"
    n = 200_000
    s = "".join("ACGT"[c] for c in rng.integers(0, 4, n))
    with open(p, "wb") as fh:
        fh.write(b">c\n")
        for i in range(0, n, 60):
            fh.write(s[i:i + 60].encode() + b"\n")
    fr = F.FastaReader(str(p))
    encodes = []
    orig = fr._encode_contig

    def counting(chrom):
        encodes.append(chrom)
        return orig(chrom)

    fr._encode_contig = counting
    results = [None] * 8

    def worker(i):
        results[i] = fr.fetch_encoded("c")

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(encodes) == 1  # in-flight event dedupes concurrent encodes
    for r in results:
        assert r is not None and len(r) == n


# ---------------------------------------------------------------------------
# coverage: single-pass host reduce (satellite, VERDICT item 3/4)
# ---------------------------------------------------------------------------


def test_host_coverage_stats_matches_jitted_kernels():
    import jax.numpy as jnp

    from variantcalling_tpu.ops import coverage as cov

    rng = np.random.default_rng(9)
    depth = np.clip(rng.normal(30, 9, size=257_123), 0, 2000).astype(np.int32)
    qs = np.asarray([0.05, 0.25, 0.5, 0.75, 0.95])
    h = cov.host_coverage_stats(depth, 1000, qs=qs)
    np.testing.assert_array_equal(h["means"], np.asarray(cov.binned_mean(jnp.asarray(depth), 1000)))
    jh = np.asarray(cov.depth_histogram(jnp.asarray(depth)))
    np.testing.assert_array_equal(h["hist"], jh)
    np.testing.assert_array_equal(
        h["percentiles"],
        np.asarray(cov.percentiles_from_histogram(jnp.asarray(jh), jnp.asarray(qs))))


def test_host_coverage_stats_numpy_fallback_parity(monkeypatch):
    from variantcalling_tpu import native
    from variantcalling_tpu.ops import coverage as cov

    rng = np.random.default_rng(10)
    depth = rng.integers(0, 1500, size=123_457).astype(np.int32)
    qs = np.asarray([0.1, 0.5, 0.9])
    fast = cov.host_coverage_stats(depth, 512, qs=qs)
    monkeypatch.setattr(native, "coverage_stats", lambda *a, **k: None)
    slow = cov.host_coverage_stats(depth, 512, qs=qs)
    for k in ("means", "hist", "percentiles"):
        np.testing.assert_array_equal(fast[k], slow[k])


def test_host_coverage_stats_from_diffs():
    from variantcalling_tpu.ops import coverage as cov

    rng = np.random.default_rng(11)
    diffs = np.zeros(50_000, np.int32)
    idx = rng.integers(0, len(diffs) - 100, 2000)
    np.add.at(diffs, idx, 1)
    np.add.at(diffs, idx + rng.integers(1, 100, 2000), -1)
    depth = np.cumsum(diffs).astype(np.int32)
    a = cov.host_coverage_stats(diffs, 100, max_depth=50, from_diffs=True)
    b = cov.host_coverage_stats(depth, 100, max_depth=50)
    np.testing.assert_array_equal(a["means"], b["means"])
    np.testing.assert_array_equal(a["hist"], b["hist"])


# ---------------------------------------------------------------------------
# bounded memory (slow): streaming RSS does not scale with input size
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_streaming_peak_rss_flat_vs_input_size(tmp_path):
    """Peak RSS of a streaming run grows FAR slower than the input: the
    memmap ingest + bounded queues keep residency at O(chunk), while the
    input grows 8x."""
    import subprocess
    import sys

    from variantcalling_tpu.models import registry
    from variantcalling_tpu.synthetic import make_fixtures_fast, synthetic_forest

    sizes = {"small": 150_000, "big": 1_200_000}
    model = synthetic_forest(np.random.default_rng(0), n_trees=10, depth=5)
    rss = {}
    for name, n in sizes.items():
        d = tmp_path / name
        d.mkdir()
        make_fixtures_fast(str(d), n=n, genome_len=4_000_000, n_contigs=2)
        registry.save_models(str(d / "model.pkl"), {"m": model})
        code = f"""
import resource, sys
sys.path.insert(0, {str(os.getcwd())!r})
from variantcalling_tpu.pipelines import filter_variants as fvp
rc = fvp.run([
    "--input_file", {str(d / 'calls.vcf')!r}, "--model_file", {str(d / 'model.pkl')!r},
    "--model_name", "m", "--reference_file", {str(d / 'ref.fa')!r},
    "--output_file", {str(d / 'out.vcf')!r}, "--backend", "cpu"])
assert rc == 0
print("RSS_KB", resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)
"""
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("VCTPU_THREADS", None)
        proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                              text=True, env=env, timeout=600)
        assert proc.returncode == 0, proc.stderr[-2000:]
        rss[name] = int(proc.stdout.split("RSS_KB")[1].strip().split()[0])
    # 8x the records must cost well under 2x the peak RSS (interpreter +
    # genome dominate; the callset text/aux must NOT be resident at once)
    assert rss["big"] < 2.0 * rss["small"], rss
