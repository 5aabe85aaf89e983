"""Content-addressed chunk-result cache (perf_opt tentpole, ISSUE 16).

Locks the four contracts the cache must keep (docs/caching.md):

- **One identity spelling**: the resume journal's ``config`` sub-dict IS
  the cache fingerprint input (``io/identity.py``) — the two can never
  diverge, and a mismatch log names the exact field.
- **Byte parity**: warm-hit, mixed hit/miss, and cache-off outputs are
  byte-identical to a cold run, across IO layouts and engines, for both
  plain and BGZF containers (the compressor re-carries its block
  boundary across replayed bodies).
- **Invalidation is scoring-scoped**: a scoring knob change misses; an
  io-thread change still hits.
- **The cache can only degrade a run to cold, never corrupt it**:
  poisoned entries (CRC), torn tmp files (SIGKILL mid-write) and store
  write failures all recompute; cancelled sessions publish nothing.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import pickle

import numpy as np
import pytest

from variantcalling_tpu.io import chunk_cache, identity
from variantcalling_tpu.io import journal as journal_mod

native = pytest.importorskip("variantcalling_tpu.native")

_WATCHED_DIRS: list[str] = []


@pytest.fixture(autouse=True)
def _cache_isolated(monkeypatch, tmp_path):
    """Every test gets its own store dir and a clean resident index; the
    engine decision cache resets on the way out (tests pin VCTPU_ENGINE),
    and the leak sentinel sweeps the shared fixture dirs."""
    monkeypatch.setenv("VCTPU_CACHE_DIR", str(tmp_path / "store"))
    chunk_cache.reset_for_tests()
    yield
    chunk_cache.reset_for_tests()
    from variantcalling_tpu import engine as engine_mod

    engine_mod.reset_for_tests()
    from tests.conftest import assert_no_stream_leaks

    assert_no_stream_leaks(_WATCHED_DIRS)


def _args(**kw) -> argparse.Namespace:
    base = dict(input_file="in.vcf", output_file="out.vcf", runs_file=None,
                hpol_filter_length_dist=[10, 10], blacklist=None,
                blacklist_cg_insertions=False, annotate_intervals=[],
                flow_order="TGCA", is_mutect=False, limit_to_contig=None)
    base.update(kw)
    return argparse.Namespace(**base)


# ---------------------------------------------------------------------------
# identity: one spelling, field-named mismatches
# ---------------------------------------------------------------------------


def test_journal_and_cache_identity_can_never_diverge(tmp_path):
    """The single-source-of-truth lock: the journal's resume identity
    embeds the EXACT dict the cache fingerprints — same object, same
    spelling — and the journal's input_signature IS identity's."""
    cfg = identity.scoring_config(_args(), engine="native",
                                  forest_strategy="native-cpp",
                                  mesh_devices=1, rank=0, ranks=1)
    inp = tmp_path / "in.vcf"
    inp.write_bytes(b"##h\n")
    meta = identity.resume_meta(_args(input_file=str(inp)), chunk_bytes=1024,
                                header_bytes=b"##h\n", config=cfg)
    assert meta["config"] is cfg
    # the journal re-exports identity's spelling — not a private copy
    assert journal_mod.input_signature is identity.input_signature
    # a config round-tripped through the journal's JSON header
    # fingerprints identically (canonical sorted-keys encoding)
    assert identity.fingerprint(json.loads(json.dumps(cfg))) == \
        identity.fingerprint(cfg)


def test_invalidation_is_scoring_scoped():
    """Every scoring-relevant knob invalidates the fingerprint;
    execution-irrelevant knobs (io threads, obs) are simply NOT part of
    the identity — the docs/caching.md invalidation matrix."""
    def fp(args=None, **execution):
        ex = dict(engine="native", forest_strategy="native-cpp",
                  mesh_devices=1, rank=0, ranks=1)
        ex.update(execution)
        return identity.fingerprint(
            identity.scoring_config(args or _args(), **ex))

    base = fp()
    assert fp() == base  # deterministic
    assert fp(_args(model_name="other")) != base
    assert fp(_args(flow_order="ACGT")) != base
    assert fp(_args(is_mutect=True)) != base
    assert fp(_args(hpol_filter_length_dist=[12, 10])) != base
    assert fp(_args(blacklist_cg_insertions=True)) != base
    assert fp(engine="jit") != base
    assert fp(forest_strategy="gather") != base
    assert fp(mesh_devices=2) != base
    assert fp(ranks=2) != base
    # scoring_fields carries NO io/obs knob: the invalidation matrix is
    # closed over exactly these keys — adding one here means updating
    # docs/caching.md's table too
    assert set(identity.scoring_fields(_args())) == {
        "model_file", "model_name", "runs_file", "blacklist",
        "blacklist_cg_insertions", "hpol", "flow_order", "is_mutect",
        "annotate_intervals"}


def test_describe_mismatch_names_the_field():
    old = {"config": {"engine": "jit", "model_name": "m"}, "chunk_bytes": 1}
    new = {"config": {"engine": "native", "model_name": "m"},
           "chunk_bytes": 1}
    s = identity.describe_mismatch(old, new)
    assert "config.engine" in s and "'jit'" in s and "'native'" in s
    assert "model_name" not in s
    assert identity.describe_mismatch({"a": 1}, {"a": 1}) == \
        "no field-level difference (type/shape change)"


# ---------------------------------------------------------------------------
# entry codec + stores: atomic, CRC-verified, bounded
# ---------------------------------------------------------------------------


def test_entry_codec_rejects_everything_suspicious():
    blob = chunk_cache._encode(b"body-bytes", 7, 3)
    assert chunk_cache._decode(blob) == (b"body-bytes", 7, 3)
    assert chunk_cache._decode(blob[:-1]) is None          # truncated
    assert chunk_cache._decode(blob + b"x") is None        # trailing junk
    assert chunk_cache._decode(b"") is None                # empty
    assert chunk_cache._decode(b"XXXX" + blob[4:]) is None  # bad magic
    flipped = bytearray(blob)
    flipped[-1] ^= 0xFF                                    # poisoned body
    assert chunk_cache._decode(bytes(flipped)) is None


def test_disk_store_poisoned_entry_is_evicted_and_missed(tmp_path):
    store = chunk_cache.DiskStore(str(tmp_path / "s"), bound=1 << 20)
    store.put("k", b"payload", 5, 2)
    assert store.get("k") == (b"payload", 5, 2)
    path = store._path("k")
    data = bytearray(open(path, "rb").read())
    data[-3] ^= 0x40  # flip one body bit — the cache_poison fault class
    open(path, "wb").write(bytes(data))
    assert store.get("k") is None          # never served
    assert not os.path.exists(path)        # evicted for the recompute
    assert store.get("k") is None          # still a clean miss


def test_disk_store_sweeps_stale_tmp_keeps_fresh(tmp_path):
    d = tmp_path / "s"
    d.mkdir()
    torn = d / (chunk_cache._TMP_PREFIX + "dead")
    torn.write_bytes(b"half-an-entry")
    os.utime(torn, (10_000.0, 10_000.0))       # a long-dead writer's tmp
    fresh = d / (chunk_cache._TMP_PREFIX + "live")
    fresh.write_bytes(b"in-flight")
    chunk_cache.DiskStore(str(d), bound=1 << 20)
    assert not torn.exists()                   # swept
    assert fresh.exists()                      # a live writer survives


def test_disk_store_lru_bound_evicts_oldest(tmp_path):
    store = chunk_cache.DiskStore(str(tmp_path / "s"), bound=3000)
    body = b"x" * 900
    for i in range(4):
        store.put(f"k{i}", body, 1, 1)
        t = 1_000_000.0 + i
        os.utime(store._path(f"k{i}"), (t, t))
    store.put("k4", body, 1, 1)  # pushes past the bound
    assert store.get("k0") is None and store.get("k1") is None
    assert store.get("k4") == (body, 1, 1)
    assert store.stats()["bytes"] <= 3000


def test_memory_store_bounds_lru():
    mem = chunk_cache.MemoryStore(bound=2000)
    for i in range(3):
        mem.put(f"k{i}", b"y" * 900, 1, 1)
    assert mem.get("k0") is None               # evicted by the bound
    assert mem.get("k2") == (b"y" * 900, 1, 1)
    assert mem.stats()["bytes"] <= 2000


def test_session_disk_hit_warms_resident_index(tmp_path):
    """The serve warm path: a disk hit is promoted into the in-process
    index so the NEXT request never touches disk for that span."""
    disk = chunk_cache.DiskStore(str(tmp_path / "s"), bound=1 << 20)
    disk.put("key", b"rendered", 3, 1)
    chunk_cache.resident_mode(True)
    mem = chunk_cache._memory_store()
    sess = chunk_cache.CacheSession("f" * 64, [mem, disk])
    assert sess.get("key") == (b"rendered", 3, 1)
    assert mem.get("key") == (b"rendered", 3, 1)
    assert sess.stats()["hits"] == 1 and sess.stats()["bytes_saved"] == 8


def test_session_publishes_committed_prefix_only(tmp_path):
    store = chunk_cache.DiskStore(str(tmp_path / "s"), bound=1 << 20)
    sess = chunk_cache.CacheSession("a" * 64, [store])
    for seq in range(4):
        sess.stage(seq, sess.key_of(b"span%d" % seq), b"body%d" % seq, 1, 1)
    sess.publish_up_to(1)                      # chunks 0..1 committed
    assert store.stats()["entries"] == 2
    sess.discard()                             # the run fails here
    sess.publish_up_to(99)
    assert store.stats()["entries"] == 2       # 2..3 never published
    assert sess.stats()["published"] == 2


def test_session_write_failure_degrades_never_raises(tmp_path):
    from variantcalling_tpu.utils import faults

    store = chunk_cache.DiskStore(str(tmp_path / "s"), bound=1 << 20)
    sess = chunk_cache.CacheSession("b" * 64, [store])
    sess.stage(0, sess.key_of(b"span"), b"body", 1, 1)
    faults.arm("cache.entry_write", times=1)
    try:
        sess.publish_up_to(0)                  # ENOSPC inside the store
    finally:
        faults.reset()
    assert store.stats()["entries"] == 0       # dropped, tmp cleaned up
    assert not glob.glob(str(tmp_path / "s" / ".vcc_tmp_*"))
    sess.stage(1, sess.key_of(b"span2"), b"body2", 1, 1)
    sess.publish_up_to(1)                      # the session survives
    assert store.stats()["entries"] == 1


def test_open_session_rank_agnostic_two_rank_counts(monkeypatch):
    """The serving-fabric warm-hit property (docs/serving_fabric.md): a
    session opened as rank 0 of 1 and one opened as rank 1 of 2 share
    the fingerprint, the content keys, and the store — so a span
    rendered under one partitioning warm-hits under the other, which is
    what lets the router's contig-aware re-cut (and an elastic re-span
    after backend death) reuse a dead predecessor's work."""
    monkeypatch.setenv("VCTPU_CACHE", "1")
    cfg = {"engine": "native", "model_sig": "m" * 16}
    one = chunk_cache.open_session(dict(cfg, ranks=1), rank=0, ranks=1)
    two = chunk_cache.open_session(dict(cfg, ranks=2, span=(0, 512)),
                                   rank=1, ranks=2)
    assert one is not None and two is not None
    assert one.fingerprint == two.fingerprint
    raw = b"chr1\t100\t.\tA\tT\t.\tPASS\t.\n" * 64
    key = one.key_of(raw)
    assert two.key_of(raw) == key
    one.stage(0, key, b"rendered-bytes", 64, 31)
    one.publish_up_to(0)
    one.finish()
    assert two.get(key) == (b"rendered-bytes", 64, 31)
    assert two.stats()["hits"] == 1 and two.stats()["misses"] == 0
    two.finish()


# ---------------------------------------------------------------------------
# streaming byte parity: cold / warm / mixed / off, across layouts+engines
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def stream_world(tmp_path_factory):
    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.synthetic import make_fixtures, synthetic_forest

    d = str(tmp_path_factory.mktemp("cacheworld"))
    make_fixtures(d, n=3000, genome_len=200_000)
    model = synthetic_forest(np.random.default_rng(0), n_trees=8, depth=4)
    with open(f"{d}/model.pkl", "wb") as fh:
        pickle.dump({"m": model}, fh)
    _WATCHED_DIRS.append(d)
    return {"dir": d, "n": 3000, "model": model,
            "fasta": FastaReader(f"{d}/ref.fa")}


def _stream(w, out, monkeypatch, *, io_threads=1, engine="native",
            cache="1", cache_dir=None):
    from variantcalling_tpu import engine as engine_mod
    from variantcalling_tpu.io import vcf as vcf_mod
    from variantcalling_tpu.pipelines.filter_variants import run_streaming

    monkeypatch.setattr(vcf_mod, "STREAM_CHUNK_BYTES", 1 << 15)
    # VCTPU_THREADS=2 keeps streaming eligible on single-core CI hosts
    monkeypatch.setenv("VCTPU_THREADS", "2")
    monkeypatch.setenv("VCTPU_IO_THREADS", str(io_threads))
    monkeypatch.setenv("VCTPU_ENGINE", engine)
    monkeypatch.setenv("VCTPU_CACHE", cache)
    if cache_dir is not None:
        monkeypatch.setenv("VCTPU_CACHE_DIR", cache_dir)
    engine_mod.reset_for_tests()
    args = _args(input_file=f"{w['dir']}/calls.vcf", output_file=out)
    return run_streaming(args, w["model"], w["fasta"], {}, None)


def _strip_prov(data: bytes) -> bytes:
    from tools.chaoshunt.harness import normalize_output

    return normalize_output(data)


@pytest.mark.flakehunt
@pytest.mark.parametrize("engine", ["native", "jit"])
@pytest.mark.parametrize("io_threads", [1, 4])
def test_byte_parity_cold_warm_mixed_off(stream_world, monkeypatch,
                                         tmp_path, engine, io_threads):
    """Acceptance matrix: cold-populate, fully-warm, mixed hit/miss
    (half the store evicted) and VCTPU_CACHE=0 all produce IDENTICAL
    bytes — per engine, per IO layout. Warm legs must actually hit."""
    w = stream_world
    cache_dir = str(tmp_path / "store")

    def leg(name, cache="1"):
        out = str(tmp_path / f"{name}.vcf")
        stats = _stream(w, out, monkeypatch, io_threads=io_threads,
                        engine=engine, cache=cache, cache_dir=cache_dir)
        assert stats is not None and stats["n"] == w["n"], name
        return stats, open(out, "rb").read()

    off_stats, off_bytes = leg("off", cache="0")
    assert off_stats["cache"] is None
    cold_stats, cold_bytes = leg("cold")
    assert cold_bytes == off_bytes
    assert cold_stats["cache"]["hits"] == 0
    assert cold_stats["cache"]["misses"] > 0
    assert cold_stats["cache"]["published"] == cold_stats["cache"]["misses"]

    warm_stats, warm_bytes = leg("warm")
    assert warm_bytes == cold_bytes
    assert warm_stats["cache"]["misses"] == 0
    assert warm_stats["cache"]["hits"] == cold_stats["cache"]["misses"]
    assert warm_stats["cache"]["bytes_saved"] > 0

    entries = sorted(glob.glob(os.path.join(cache_dir, "*.vcc")))
    assert len(entries) == cold_stats["cache"]["published"]
    for p in entries[::2]:
        os.remove(p)                          # evict half: mixed leg
    mixed_stats, mixed_bytes = leg("mixed")
    assert mixed_bytes == cold_bytes
    assert mixed_stats["cache"]["hits"] > 0
    assert mixed_stats["cache"]["misses"] > 0


@pytest.mark.flakehunt
def test_warm_hit_replay_through_bgzf_carry(stream_world, monkeypatch,
                                            tmp_path):
    """BGZF framing identity: a fully-warm .gz run recompresses replayed
    bodies through the live block carry — container bytes identical to
    the cold run's, and the payload identical to the plain output."""
    w = stream_world
    cache_dir = str(tmp_path / "store")
    outs = {}
    for name in ("cold", "warm"):
        out = str(tmp_path / f"{name}.vcf.gz")
        stats = _stream(w, out, monkeypatch, io_threads=4, engine="native",
                        cache_dir=cache_dir)
        assert stats is not None and stats["n"] == w["n"]
        outs[name] = open(out, "rb").read()
        if name == "warm":
            assert stats["cache"]["hits"] > 0
            assert stats["cache"]["misses"] == 0
    assert outs["warm"] == outs["cold"]
    plain = str(tmp_path / "plain.vcf")
    _stream(w, plain, monkeypatch, io_threads=4, engine="native",
            cache_dir=cache_dir)
    assert gzip.decompress(outs["warm"]) == open(plain, "rb").read()


@pytest.mark.flakehunt
def test_io_layout_change_still_hits_engine_change_misses(stream_world,
                                                          monkeypatch,
                                                          tmp_path):
    """The invalidation matrix, live: io_threads is NOT identity (the
    4-thread store serves the 1-thread run warm); the engine IS (a jit
    run over the native store runs cold — and stays byte-identical
    modulo the provenance headers)."""
    w = stream_world
    cache_dir = str(tmp_path / "store")
    out1 = str(tmp_path / "t4.vcf")
    _stream(w, out1, monkeypatch, io_threads=4, engine="native",
            cache_dir=cache_dir)
    out2 = str(tmp_path / "t1.vcf")
    stats = _stream(w, out2, monkeypatch, io_threads=1, engine="native",
                    cache_dir=cache_dir)
    assert stats["cache"]["hits"] > 0 and stats["cache"]["misses"] == 0
    assert open(out2, "rb").read() == open(out1, "rb").read()
    out3 = str(tmp_path / "jit.vcf")
    stats = _stream(w, out3, monkeypatch, io_threads=1, engine="jit",
                    cache_dir=cache_dir)
    assert stats["cache"]["hits"] == 0 and stats["cache"]["misses"] > 0
    assert _strip_prov(open(out3, "rb").read()) == \
        _strip_prov(open(out1, "rb").read())


@pytest.mark.flakehunt
def test_poisoned_store_recomputes_byte_identical(stream_world, monkeypatch,
                                                  tmp_path):
    """cache_poison at the pipeline level: flip one body bit in EVERY
    entry — the warm run detects each (CRC), recomputes cold, and the
    output is still byte-identical. Wrong bytes are impossible; the
    failure mode is only lost speedup."""
    w = stream_world
    cache_dir = str(tmp_path / "store")
    out1 = str(tmp_path / "cold.vcf")
    _stream(w, out1, monkeypatch, cache_dir=cache_dir)
    entries = glob.glob(os.path.join(cache_dir, "*.vcc"))
    assert entries
    for p in entries:
        data = bytearray(open(p, "rb").read())
        data[len(data) // 2] ^= 0x01
        open(p, "wb").write(bytes(data))
    out2 = str(tmp_path / "poisoned.vcf")
    stats = _stream(w, out2, monkeypatch, cache_dir=cache_dir)
    assert stats["cache"]["hits"] == 0         # nothing poisoned served
    assert stats["cache"]["misses"] > 0
    assert open(out2, "rb").read() == open(out1, "rb").read()


def test_read_fault_degrades_to_recompute(stream_world, monkeypatch,
                                          tmp_path):
    """cache.entry_read EIO (a dying disk under the store): every read
    degrades to a miss; the run completes byte-identical."""
    from variantcalling_tpu.utils import faults

    w = stream_world
    cache_dir = str(tmp_path / "store")
    out1 = str(tmp_path / "cold.vcf")
    _stream(w, out1, monkeypatch, cache_dir=cache_dir)
    out2 = str(tmp_path / "eio.vcf")
    faults.arm("cache.entry_read", times=None)
    try:
        stats = _stream(w, out2, monkeypatch, cache_dir=cache_dir)
    finally:
        faults.reset()
    assert stats["cache"]["hits"] == 0
    assert open(out2, "rb").read() == open(out1, "rb").read()


# ---------------------------------------------------------------------------
# serve tier: resident warm index, request-scoped publication
# ---------------------------------------------------------------------------


def test_resident_warm_index_serves_across_requests(stream_world,
                                                    monkeypatch, tmp_path):
    """The serve tier: with resident_mode on (daemon startup), request 1
    warms the in-process index; request 2 hits it. resident_stats()
    (the /status payload) reports the traffic."""
    w = stream_world
    chunk_cache.resident_mode(True)
    cache_dir = str(tmp_path / "store")
    out1 = str(tmp_path / "r1.vcf")
    _stream(w, out1, monkeypatch, cache_dir=cache_dir)
    st = chunk_cache.resident_stats()
    assert st["resident"] and st["sessions"] == 1
    assert st["memory"]["entries"] > 0         # publication warmed it
    out2 = str(tmp_path / "r2.vcf")
    stats = _stream(w, out2, monkeypatch, cache_dir=cache_dir)
    assert stats["cache"]["hits"] > 0 and stats["cache"]["misses"] == 0
    assert open(out2, "rb").read() == open(out1, "rb").read()
    st = chunk_cache.resident_stats()
    assert st["sessions"] == 2 and st["hits"] == stats["cache"]["hits"]


def test_cancelled_request_never_publishes(stream_world, monkeypatch,
                                           tmp_path):
    """Per-request scoping: a cancelled request discards its staged
    entries — the warm index and the disk store hold only entries whose
    bytes some output carried."""
    from variantcalling_tpu.utils import cancellation

    w = stream_world
    chunk_cache.resident_mode(True)
    cache_dir = str(tmp_path / "store")
    token = cancellation.CancelToken()
    token.cancel("client disconnected")
    out = str(tmp_path / "cancelled.vcf")
    with pytest.raises(cancellation.CancelledError), \
            cancellation.scope(token):
        _stream(w, out, monkeypatch, cache_dir=cache_dir)
    assert chunk_cache.resident_stats()["memory"]["entries"] == 0
    assert not glob.glob(os.path.join(cache_dir, "*.vcc"))
    assert not os.path.exists(out)


# ---------------------------------------------------------------------------
# chaoshunt integration: the cache fault classes draw + shrink
# ---------------------------------------------------------------------------


def test_chaos_cache_schedules_draw_and_round_trip():
    from tools.chaoshunt import harness

    drawn = [harness.draw_schedule(s) for s in range(80)]
    cache_scheds = [s for s in drawn if s.cache is not None]
    assert cache_scheds, "no cache schedule drawn in 80 seeds"
    assert {s.cache["mode"] for s in cache_scheds} == {"poison", "torn"}
    for s in cache_scheds:
        assert s.layout != "mesh2"  # the mesh megabatch bypasses the cache
        again = harness.Schedule.from_json(json.loads(json.dumps(
            s.to_json())))
        assert again.to_json() == s.to_json()
        assert "cache_" in s.describe()
        # the shrinker can degrade a cache schedule to the plain flow
        assert any(c.cache is None for c in harness._simplifications(s))
