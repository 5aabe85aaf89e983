"""Elastic pod membership (docs/scaleout.md "Elastic membership"): the
mobile-span partition, the single-claimant lease, the re-cut journal
handoff, the span-plan committer, and the coordinator state machine.

The contracts under lock:

- **Any monotone target plan tiles the record body** — not just the
  classic rank fractions. Re-cut plans (a span split at a journal
  watermark) concatenate to the serial record stream exactly.
- **Leases are single-claimant**: however many workers race one (span,
  generation) offer, exactly one O_EXCL open wins.
- **Journals are portable**: a journal written by worker A is adopted
  by worker B (``handoff_journal``) and resumes byte-identically —
  including under ``VCTPU_RESUME_VERIFY=full`` — recomputing nothing.
- **The merged elastic output is literally byte-identical** to the
  single-rank run (span workers carry no ``##vctpu_ranks=`` header),
  for never-re-cut and mid-span-re-cut plans alike.
- **The coordinator never hangs**: every death is re-offered, every
  straggler stolen, every hopeless span fails loudly with exit 7.
"""

from __future__ import annotations

import argparse
import gzip
import itertools
import os
import pickle
import threading
import time

import numpy as np
import pytest

from variantcalling_tpu.engine import EngineError
from variantcalling_tpu.io import bgzf as bgzf_mod
from variantcalling_tpu.parallel import elastic
from variantcalling_tpu.parallel import rank_plan as rank_plan_mod
from variantcalling_tpu.utils import faults

native = pytest.importorskip("variantcalling_tpu.native")


@pytest.fixture(autouse=True)
def _engine_cache_isolated():
    yield
    from variantcalling_tpu import engine as engine_mod

    engine_mod.reset_for_tests()


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


_WATCHED_DIRS: list[str] = []


@pytest.fixture(autouse=True)
def _leak_sentinel():
    yield
    from tests.conftest import assert_no_stream_leaks

    assert_no_stream_leaks(_WATCHED_DIRS)


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.synthetic import make_fixtures, synthetic_forest

    d = str(tmp_path_factory.mktemp("elastic"))
    make_fixtures(d, n=2500, genome_len=150_000)
    with open(f"{d}/calls.vcf", "rb") as fh:
        text = fh.read()
    with bgzf_mod.BgzfWriter(f"{d}/calls.vcf.gz") as w:
        w.write(text)
    model = synthetic_forest(np.random.default_rng(0), n_trees=8, depth=4)
    with open(f"{d}/model.pkl", "wb") as fh:
        pickle.dump({"m": model}, fh)
    _WATCHED_DIRS.append(d)
    return {"dir": d, "n": 2500, "model": model,
            "fasta": FastaReader(f"{d}/ref.fa")}


# ---------------------------------------------------------------------------
# spans, the env wire format, plan resolution
# ---------------------------------------------------------------------------


def test_initial_spans_match_classic_rank_fractions():
    """The seed plan uses EXACTLY the classic ``i/n`` body fractions, is
    contiguous, and covers ``[header_end, total)`` — a never-re-cut
    elastic pod is the static pod."""
    h, total, n = 366, 64195, 3
    spans = elastic.initial_spans(h, total, n)
    assert spans[0].lo == h and spans[-1].hi == total
    for a, b in zip(spans, spans[1:]):
        assert a.hi == b.lo
    body = total - h
    for i, s in enumerate(spans):
        assert s.lo == h + body * i // n
        assert s.gen == 0
    with pytest.raises(ValueError):
        elastic.initial_spans(h, total, 0)
    # an empty body still yields n well-formed (empty) spans
    assert all(s.lo == s.hi == 10 for s in elastic.initial_spans(10, 10, 2))


def test_span_env_roundtrip_and_rejects_malformed():
    s = elastic.Span(366, 64195, 2)
    assert elastic.parse_span_env(elastic.span_env(s)) == (366, 64195, 2)
    for bad in ("", "1:2", "a:b:c", "1:2:3:4", "5:4:0", "-1:2:0", "1:2:-1"):
        with pytest.raises(EngineError):
            elastic.parse_span_env(bad)


def test_resolve_span_plan(monkeypatch):
    """``VCTPU_SPAN`` resolves to a single-rank span plan: no pod
    provenance header (the byte-parity contract), the worker computes
    as rank 0 of 1 over its leased targets."""
    monkeypatch.delenv("VCTPU_RANK", raising=False)
    monkeypatch.delenv("VCTPU_NUM_PROCESSES", raising=False)
    monkeypatch.setenv("VCTPU_SPAN", "366:64195:1")
    plan = rank_plan_mod.resolve()
    assert (plan.rank, plan.ranks, plan.source) == (0, 1, "span")
    assert plan.span == (366, 64195) and plan.gen == 1
    # ranks == 1 means the provenance emitter writes NO ##vctpu_ranks=
    # line (literal byte parity with the single-rank run, not modulo)
    assert plan.ranks == 1


def test_resolve_rejects_span_and_rank_together(monkeypatch):
    monkeypatch.setenv("VCTPU_SPAN", "0:10:0")
    monkeypatch.setenv("VCTPU_RANK", "0")
    monkeypatch.setenv("VCTPU_NUM_PROCESSES", "2")
    with pytest.raises(EngineError, match="VCTPU_SPAN and VCTPU_RANK"):
        rank_plan_mod.resolve()


# ---------------------------------------------------------------------------
# the single-claimant lease
# ---------------------------------------------------------------------------


def test_claim_lease_exactly_one_winner(tmp_path):
    """N threads race one (span, generation) offer: exactly one O_EXCL
    open succeeds; the next generation is a fresh offer."""
    seg = str(tmp_path / "out.vcf.span0-100.seg")
    wins: list[bool] = []
    barrier = threading.Barrier(8)

    def race():
        barrier.wait()
        wins.append(elastic.claim_lease(seg, 0))

    threads = [threading.Thread(target=race) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert sum(wins) == 1 and len(wins) == 8
    assert os.path.exists(elastic.lease_path(seg, 0))
    assert elastic.claim_lease(seg, 1)  # re-offer = new generation
    assert not elastic.claim_lease(seg, 1)


def test_run_scaleout_lease_loss_raises_before_compute(tmp_path):
    """A worker offered an already-claimed (span, generation) raises
    LeaseLost BEFORE touching the model or the input — the coordinator
    treats its exit 6 as benign."""
    out = str(tmp_path / "out.vcf")
    seg = elastic.span_segment_path(out, 10, 20)
    assert elastic.claim_lease(seg, 0)
    plan = rank_plan_mod.RankPlan(ranks=1, rank=0, source="span",
                                  reason="test", span=(10, 20), gen=0)
    ns = argparse.Namespace(input_file="/nonexistent", output_file=out)
    with pytest.raises(elastic.LeaseLost, match="lease already claimed"):
        rank_plan_mod.run_scaleout(ns, None, None, {}, None, plan=plan)


# ---------------------------------------------------------------------------
# arbitrary monotone target plans tile the record body
# ---------------------------------------------------------------------------


def _raw_bytes(reader) -> bytes:
    return b"".join(bytes(memoryview(b)) if isinstance(b, np.ndarray)
                    else bytes(b) for b, _ in reader.iter_raw())


@pytest.mark.parametrize("suffix", ["", ".gz"])
def test_span_targets_tile_serial_for_recut_plans(world, suffix):
    """Concatenating the raw bytes of ANY contiguous monotone target
    plan — classic fractions, an uneven re-cut, targets mid-line —
    reproduces the serial record stream exactly. This is the property
    that makes re-cutting free: the merge never cares how the
    membership history arrived at the final plan."""
    from variantcalling_tpu.io.vcf import VcfChunkReader, scan_record_region

    path = f"{world['dir']}/calls.vcf{suffix}"
    h, total = scan_record_region(path)
    serial = _raw_bytes(VcfChunkReader(path, chunk_bytes=1 << 15,
                                       io_threads=1))
    body = total - h
    plans = [
        [s for s in elastic.initial_spans(h, total, 3)],
        # an uneven "re-cut" plan: one span split at arbitrary targets
        # that land mid-line, plus an EMPTY span
        [elastic.Span(h, h + 1234), elastic.Span(h + 1234, h + 1234),
         elastic.Span(h + 1234, h + body // 2 + 17),
         elastic.Span(h + body // 2 + 17, total)],
    ]
    for spans in plans:
        got = b"".join(
            _raw_bytes(VcfChunkReader(path, chunk_bytes=1 << 15,
                                      io_threads=1,
                                      span_targets=(s.lo, s.hi)))
            for s in spans)
        assert got == serial, [s.label() for s in spans]


def test_chunk_ends_are_recut_points(world):
    """Every chunk's recorded ``in_end`` is an absolute line start, and
    re-reading the prefix ``[lo, chunk_end(k))`` as its own span
    reproduces the first k+1 chunks byte-for-byte — the re-cut rule's
    correctness in miniature (the adopter's chunk boundaries are the
    dead worker's)."""
    from variantcalling_tpu.io.vcf import VcfChunkReader, scan_record_region

    path = f"{world['dir']}/calls.vcf"
    h, total = scan_record_region(path)
    span = (h, h + (total - h) * 2 // 3)
    r = VcfChunkReader(path, chunk_bytes=1 << 14, io_threads=1,
                       span_targets=span)
    chunks = [bytes(memoryview(b)) for b, _ in r.iter_raw()]
    assert len(chunks) >= 3
    ends = [r.chunk_end(i) for i in range(len(chunks))]
    assert all(e is not None for e in ends)
    assert ends == sorted(ends)
    assert r.chunk_end(len(chunks)) is None  # out of range -> None
    data = open(path, "rb").read()
    for i, e in enumerate(ends):
        assert e == ends[0] - len(chunks[0]) + sum(map(len, chunks[:i + 1]))
        assert e == len(data) or data[e - 1:e] == b"\n"  # a line start
    k = len(chunks) // 2
    prefix = VcfChunkReader(path, chunk_bytes=1 << 14, io_threads=1,
                            span_targets=(span[0], ends[k]))
    got = [bytes(memoryview(b)) for b, _ in prefix.iter_raw()]
    assert got == chunks[:k + 1]


# ---------------------------------------------------------------------------
# in-process elastic pod: literal byte parity + the re-cut handoff
# ---------------------------------------------------------------------------


def _ns(inp, out):
    return argparse.Namespace(
        input_file=inp, output_file=out, runs_file=None,
        hpol_filter_length_dist=[10, 10], blacklist=None,
        blacklist_cg_insertions=False, annotate_intervals=[],
        flow_order="TGCA", is_mutect=False, limit_to_contig=None)


def _span_plan(span: elastic.Span) -> rank_plan_mod.RankPlan:
    return rank_plan_mod.RankPlan(ranks=1, rank=0, source="span",
                                  reason="test", span=(span.lo, span.hi),
                                  gen=span.gen)


def _prep(monkeypatch):
    from variantcalling_tpu import engine as engine_mod
    from variantcalling_tpu.io import vcf as vcf_mod

    monkeypatch.setattr(vcf_mod, "STREAM_CHUNK_BYTES", 1 << 14)
    monkeypatch.setenv("VCTPU_THREADS", "2")
    monkeypatch.setenv("VCTPU_IO_THREADS", "2")
    monkeypatch.setenv("VCTPU_ENGINE", "native")
    engine_mod.reset_for_tests()


def _run_span(world, inp, out, span, *, write_marker=True):
    """One span worker's body, in-process (the subprocess e2e is
    tests/system/test_elastic.py): compute the segment, seal it."""
    from variantcalling_tpu.pipelines.filter_variants import run_streaming

    plan = _span_plan(span)
    seg = elastic.span_segment_path(out, span.lo, span.hi)
    stats = run_streaming(_ns(inp, seg), world["model"], world["fasta"],
                          {}, None, rank_plan=plan)
    assert stats is not None
    if write_marker:
        rank_plan_mod.write_marker(
            seg, rank_plan_mod.segment_identity(_ns(inp, out), plan), stats)
    return stats


@pytest.mark.parametrize("out_sfx", ["", ".gz"])
def test_elastic_pod_literally_byte_identical(world, monkeypatch, out_sfx):
    """Acceptance: the merged elastic output equals the single-rank run
    BYTE FOR BYTE — not merely modulo headers — because span workers
    run as single-rank plans, for plain and BGZF output alike."""
    from variantcalling_tpu.io.vcf import scan_record_region
    from variantcalling_tpu.pipelines.filter_variants import run_streaming

    d = world["dir"]
    inp = f"{d}/calls.vcf"
    _prep(monkeypatch)
    single = f"{d}/esingle{out_sfx.replace('.', '_')}.vcf{out_sfx}"
    assert run_streaming(_ns(inp, single), world["model"], world["fasta"],
                         {}, None) is not None
    want = open(single, "rb").read()

    h, total = scan_record_region(inp)
    out = f"{d}/epod{out_sfx.replace('.', '_')}.vcf{out_sfx}"
    spans = elastic.initial_spans(h, total, 3)
    n = sum(_run_span(world, inp, out, s)["n"] for s in spans)
    assert n == world["n"]
    stats = elastic.merge_spans(out, spans)
    assert stats["n"] == world["n"] and stats["spans"] == 3
    assert open(out, "rb").read() == want
    raw = open(out, "rb").read()
    text = gzip.decompress(raw) if out_sfx else raw
    assert b"##vctpu_ranks=" not in text
    # the sweep left nothing behind
    assert not [p for p in os.listdir(d)
                if p.startswith(os.path.basename(out) + ".span")]
    os.remove(out)
    os.remove(single)


def test_recut_handoff_adoption_is_byte_identical(world, monkeypatch):
    """Satellite (journal portability): worker A dies mid-span leaving a
    journal + partial; the coordinator's re-cut splits the span at the
    last ``in_end``; worker B adopts the handed-off journal under
    ``VCTPU_RESUME_VERIFY=full`` and resumes — skipping every journaled
    chunk — while a third worker takes the unstarted suffix. The merged
    plan is byte-identical to the single-rank run."""
    from variantcalling_tpu.io import journal as journal_mod
    from variantcalling_tpu.io.vcf import scan_record_region
    from variantcalling_tpu.pipelines.filter_variants import run_streaming

    d = world["dir"]
    inp = f"{d}/calls.vcf"
    _prep(monkeypatch)
    monkeypatch.setenv("VCTPU_IO_BACKOFF_S", "0.01")
    single = f"{d}/hsingle.vcf"
    assert run_streaming(_ns(inp, single), world["model"], world["fasta"],
                         {}, None) is not None
    want = open(single, "rb").read()

    h, total = scan_record_region(inp)
    out = f"{d}/hpod.vcf"
    left, right = elastic.initial_spans(h, total, 2)
    # worker A: header + 2 chunks land, then every writeback fails
    faults.arm("io.writeback", times=None, after=3)
    with pytest.raises(OSError):
        _run_span(world, inp, out, left, write_marker=False)
    faults.reset()
    seg_a = elastic.span_segment_path(out, left.lo, left.hi)
    chunks, end = elastic.journal_progress(seg_a)
    assert chunks >= 1 and end is not None and left.lo < end < left.hi

    # the coordinator's re-cut: adopt [lo, end), fresh [end, hi)
    adopt = elastic.Span(left.lo, end, left.gen + 1)
    rest = elastic.Span(end, left.hi, 0)
    seg_b = elastic.span_segment_path(out, adopt.lo, adopt.hi)
    assert elastic.handoff_journal(seg_a, seg_b, (adopt.lo, adopt.hi))
    assert not os.path.exists(journal_mod.journal_path(seg_a))
    assert not journal_mod.list_partials(seg_a)

    # worker B adopts under FULL prefix verification: every journaled
    # chunk re-read, CRC-checked and skipped — zero recompute
    monkeypatch.setenv("VCTPU_RESUME_VERIFY", "full")
    stats_b = _run_span(world, inp, out, adopt)
    assert stats_b["resumed_chunks"] == chunks
    monkeypatch.delenv("VCTPU_RESUME_VERIFY")
    n = stats_b["n"]
    n += _run_span(world, inp, out, rest)["n"]
    n += _run_span(world, inp, out, right)["n"]
    assert n == world["n"]
    elastic.merge_spans(out, [adopt, rest, right])
    assert open(out, "rb").read() == want
    os.remove(out)
    os.remove(single)


def test_handoff_refuses_missing_or_unsafe_journals(tmp_path):
    """``handoff_journal`` degrades to whole-span re-assignment (returns
    False) rather than guess: no journal, an empty journal, or a
    journal whose partial is gone."""
    from variantcalling_tpu.io import journal as journal_mod

    old = str(tmp_path / "o.vcf.span0-100.seg")
    new = str(tmp_path / "o.vcf.span0-50.seg")
    assert not elastic.handoff_journal(old, new, (0, 50))  # no journal
    j = journal_mod.ChunkJournal(old)
    token = journal_mod.new_partial_token()
    j.begin({"config": {"span": [0, 100]}, "partial": token})
    j.close()
    assert not elastic.handoff_journal(old, new, (0, 50))  # no entries
    j = journal_mod.ChunkJournal(old)
    j.begin({"config": {"span": [0, 100]}, "partial": token})
    j.append(0, 10, 5, 64, 123, in_end=40)
    j.close()
    assert not elastic.handoff_journal(old, new, (0, 50))  # partial gone
    with open(journal_mod.partial_path(old, token), "wb") as fh:
        fh.write(b"x" * 64)
    assert elastic.handoff_journal(old, new, (0, 50))
    loaded = journal_mod.ChunkJournal.load(new)
    assert loaded is not None
    meta, entries = loaded
    assert meta["config"]["span"] == [0, 50]  # pinned to the NEW lease
    assert entries[0]["in_end"] == 40
    os.remove(journal_mod.partial_path(new, token))
    os.remove(journal_mod.journal_path(new))


def test_journal_progress_reads_in_end_watermark(tmp_path):
    from variantcalling_tpu.io import journal as journal_mod

    seg = str(tmp_path / "x.vcf.span0-100.seg")
    assert elastic.journal_progress(seg) == (0, None)
    j = journal_mod.ChunkJournal(seg)
    j.begin({"config": {}})
    j.append(0, 10, 5, 64, 1, in_end=40)
    j.append(1, 10, 5, 64, 2, in_end=77)
    j.close()
    assert elastic.journal_progress(seg) == (2, 77)
    os.remove(journal_mod.journal_path(seg))


# ---------------------------------------------------------------------------
# the chunk cache across a steal seam (rank-agnostic keys)
# ---------------------------------------------------------------------------


def test_cache_warm_hits_across_steal_seam(world, monkeypatch, tmp_path):
    """Satellite (rank-agnostic cache keys): chunks computed under one
    partition are served to ANY partition. A cold 2-span run populates
    the shared store; a re-cut plan whose seam lands at a chunk
    boundary replays every chunk as a hit — including the chunks
    straddling the steal seam — and commits byte-identically."""
    from variantcalling_tpu.io.vcf import VcfChunkReader, scan_record_region

    d = world["dir"]
    inp = f"{d}/calls.vcf"
    _prep(monkeypatch)
    monkeypatch.setenv("VCTPU_CACHE", "1")
    monkeypatch.setenv("VCTPU_CACHE_DIR", str(tmp_path / "store"))
    h, total = scan_record_region(inp)
    left, right = elastic.initial_spans(h, total, 2)

    cold_out = f"{d}/ccold.vcf"
    cold = [_run_span(world, inp, cold_out, s) for s in (left, right)]
    assert all(s["cache"]["hits"] == 0 and s["cache"]["misses"] > 0
               for s in cold)
    elastic.merge_spans(cold_out, [left, right])
    want = open(cold_out, "rb").read()

    # re-cut the left span at one of ITS chunk boundaries — the warm
    # plan's seam is exactly where a mid-run steal would have cut
    r = VcfChunkReader(inp, chunk_bytes=1 << 14, io_threads=1,
                       span_targets=(left.lo, left.hi))
    n_chunks = sum(1 for _ in r.iter_raw())
    assert n_chunks >= 2
    seam = r.chunk_end(n_chunks // 2 - 1)
    assert left.lo < seam < left.hi
    warm_out = f"{d}/cwarm.vcf"
    plan = [elastic.Span(left.lo, seam), elastic.Span(seam, left.hi),
            elastic.Span(right.lo, right.hi)]
    warm = [_run_span(world, inp, warm_out, s) for s in plan]
    for s in warm:
        assert s["cache"]["misses"] == 0 and s["cache"]["hits"] > 0
    assert sum(s["cache"]["hits"] for s in warm) == \
        sum(s["cache"]["misses"] for s in cold)
    elastic.merge_spans(warm_out, plan)
    assert open(warm_out, "rb").read() == want
    os.remove(cold_out)
    os.remove(warm_out)


# ---------------------------------------------------------------------------
# the span-plan committer's preconditions
# ---------------------------------------------------------------------------


def test_merge_spans_refuses_gapped_or_overlapping_plans(tmp_path):
    out = str(tmp_path / "m.vcf")
    for bad in ([elastic.Span(0, 10), elastic.Span(20, 30)],
                [elastic.Span(0, 15), elastic.Span(10, 30)]):
        with pytest.raises(rank_plan_mod.MergeError,
                           match="not contiguous"):
            elastic.merge_spans(out, bad)
    with pytest.raises(rank_plan_mod.MergeError):
        elastic.merge_spans(out, [])  # an empty plan commits nothing


# ---------------------------------------------------------------------------
# the coordinator state machine (fake workers — the subprocess e2e is
# tests/system/test_elastic.py)
# ---------------------------------------------------------------------------


class _FakeProc:
    _pids = itertools.count(40_000)

    def __init__(self, rc=0, delay=0.0, on_exit=None):
        self.pid = next(self._pids)
        self._rc = rc
        self._t0 = time.monotonic()
        self._delay = delay
        self._on_exit = on_exit
        self._fired = False
        self.killed = False

    def poll(self):
        if self.killed:
            return -9
        if time.monotonic() - self._t0 < self._delay:
            return None
        if not self._fired:
            self._fired = True
            if self._on_exit is not None:
                self._on_exit()
        return self._rc

    def kill(self):
        self.killed = True

    def wait(self, timeout=None):
        return self.poll()


def _seal(out, span):
    """What a successful span worker leaves behind: the segment + its
    completion marker (all the coordinator's done-check reads)."""
    seg = elastic.span_segment_path(out, span.lo, span.hi)
    with open(seg, "wb") as fh:
        fh.write(b"#h\n")
    rank_plan_mod.write_marker(seg, {"k": 1}, {"n": 0, "n_pass": 0})


def _coord(out, spans, spawn, **kw):
    kw.setdefault("poll_s", 0.005)
    kw.setdefault("steal_check_s", 0.01)
    kw.setdefault("grace_s", 0.05)
    return elastic.Coordinator(out, spans, spawn, **kw)


def test_coordinator_completes_clean_pod(tmp_path):
    out = str(tmp_path / "p.vcf")
    spans = [elastic.Span(0, 50), elastic.Span(50, 100)]

    def spawn(span, slot):
        return _FakeProc(on_exit=lambda: _seal(out, span))

    c = _coord(out, spans, spawn)
    assert c.run() == 0
    assert c.spans == spans
    assert c.transitions.count("join") == 2
    assert c.transitions.count("leave") == 2


def test_coordinator_reoffers_death_under_next_generation(tmp_path):
    """A killed worker's span (no journal) is re-offered whole under
    gen+1; the replacement completes and the pod succeeds."""
    out = str(tmp_path / "p.vcf")
    seen: list[int] = []

    def spawn(span, slot):
        seen.append(span.gen)
        if span.gen == 0:
            return _FakeProc(rc=-9)  # died before any journal landed
        return _FakeProc(on_exit=lambda: _seal(out, span))

    c = _coord(out, [elastic.Span(0, 100)], spawn)
    assert c.run() == 0
    assert seen == [0, 1]
    assert "reassign" in c.transitions


def test_coordinator_gives_up_with_distinct_exit(tmp_path):
    """A span that dies every time fails the pod with EXIT_SPAN_FAILED
    after bounded attempts — loud and distinct, never a hang."""
    out = str(tmp_path / "p.vcf")
    c = _coord(out, [elastic.Span(0, 100)],
               lambda span, slot: _FakeProc(rc=1), max_attempts=2)
    assert c.run() == elastic.EXIT_SPAN_FAILED
    assert "give_up" in c.transitions
    assert c.transitions.count("join") == 3  # initial + 2 re-offers


def test_coordinator_config_error_fails_fast(tmp_path):
    """Worker exit 2 is deterministic — re-offering would die the same
    way, so the pod propagates 2 immediately and kills the rest."""
    out = str(tmp_path / "p.vcf")
    other = _FakeProc(delay=999)

    def spawn(span, slot):
        return _FakeProc(rc=2) if span.lo == 0 else other

    c = _coord(out, [elastic.Span(0, 50), elastic.Span(50, 100)], spawn)
    assert c.run() == elastic.EXIT_USAGE
    assert other.killed


def test_coordinator_treats_markerless_exit_as_death(tmp_path):
    """Exit 0 without a .done marker is a death, not a success — the
    marker is the completion contract."""
    out = str(tmp_path / "p.vcf")
    calls = itertools.count()

    def spawn(span, slot):
        if next(calls) == 0:
            return _FakeProc(rc=0)  # clean exit, no marker sealed
        return _FakeProc(on_exit=lambda: _seal(out, span))

    c = _coord(out, [elastic.Span(0, 100)], spawn)
    assert c.run() == 0
    assert "reassign" in c.transitions


def test_coordinator_deadline_exits_timeout(tmp_path):
    out = str(tmp_path / "p.vcf")
    proc = _FakeProc(delay=999)
    c = _coord(out, [elastic.Span(0, 100)], lambda span, slot: proc,
               timeout_s=0.15)
    assert c.run() == elastic.EXIT_TIMEOUT
    assert proc.killed


def test_coordinator_steals_stuck_straggler(tmp_path):
    """Two siblings finish; the third shows zero journal progress long
    past what the sibling rates predict — the coordinator kills it,
    re-offers the span, and the replacement finishes the pod."""
    out = str(tmp_path / "p.vcf")
    spans = [elastic.Span(0, 50), elastic.Span(50, 100),
             elastic.Span(100, 150)]
    stole: list[elastic.Span] = []

    def spawn(span, slot):
        if span.lo == 100 and span.gen == 0:
            return _FakeProc(delay=999)  # the straggler: no progress
        if span.gen > 0:
            stole.append(span)
        return _FakeProc(delay=0.02, on_exit=lambda: _seal(out, span))

    c = _coord(out, spans, spawn, steal_factor=2.0)
    assert c.run() == 0
    assert "steal" in c.transitions
    assert stole and stole[0].gen == 1
    # no journal -> whole-span re-offer: same intervals, bumped gen
    assert [(s.lo, s.hi) for s in c.spans] == \
        [(s.lo, s.hi) for s in spans]


def test_coordinator_sheds_under_host_pressure(tmp_path):
    """With the load average pinned above max_load, the pool sheds to
    min_ranks: spans run one at a time, the shed transition lands in
    the ledger, and the pod still completes."""
    out = str(tmp_path / "p.vcf")
    alive = {"n": 0, "peak": 0}

    def spawn(span, slot):
        alive["n"] += 1
        alive["peak"] = max(alive["peak"], alive["n"])

        def done():
            alive["n"] -= 1
            _seal(out, span)

        return _FakeProc(delay=0.03, on_exit=done)

    spans = [elastic.Span(i * 10, i * 10 + 10) for i in range(3)]
    c = _coord(out, spans, spawn, max_load=4.0, min_ranks=1,
               load_fn=lambda: (16.0, 0.0, 0.0))
    assert c.run() == 0
    assert "shed" in c.transitions
    assert alive["peak"] == 1


def test_coordinator_promotes_winning_shadow_claimant(tmp_path):
    """steal_race chaos: the duplicate claimant that WINS the lease
    becomes the span's worker when the original exits 6 — the pod
    completes with claim_lost counted, never with two renderers."""
    out = str(tmp_path / "p.vcf")
    span0 = elastic.Span(0, 100)
    procs: list[_FakeProc] = []

    def spawn(span, slot):
        if slot is None:  # the shadow duplicate — wins the lease
            p = _FakeProc(delay=0.03, on_exit=lambda: _seal(out, span))
        else:  # the original — loses the race
            p = _FakeProc(rc=elastic.EXIT_LEASE_LOST, delay=0.01)
        procs.append(p)
        return p

    c = _coord(out, [span0], spawn, chaos="steal_race")
    assert c.run() == 0
    assert c.claim_lost == 1
    assert len(procs) == 2  # no third spawn: the shadow was promoted
    assert "claim_lost" in c.transitions
