"""Rank-partitioned scale-out of the streaming filter (docs/scaleout.md).

PR 8 sharded scoring over a single-process mesh; this module promotes
the whole filter hot path — sharded BGZF ingest, fused scoring, render,
journal, recovery ladder — from one process to N cooperating processes,
the way the GPU-cluster pipeline work (arXiv 2509.09058) scales the same
post-alignment workload across machines: partition the input, run a full
pipeline per rank, merge ordered results.

The pieces:

- :class:`RankPlan` — the run-level rank layout, resolved ONCE next to
  the MeshPlan in ``FilterContext`` (``parallel/distributed.rank`` is
  the one rank spelling: ``VCTPU_RANK`` under the local launcher —
  before any jax init — or ``jax.process_index()`` under a real
  ``jax.distributed`` cluster), recorded as ``##vctpu_ranks=`` output
  provenance and pinned into every rank's journal resume identity.
- **Partition rule**: every rank processes a CONTIGUOUS span of the
  record region, split at line boundaries by one deterministic rule
  (``VcfChunkReader`` ``rank_span`` — byte targets at ``r/N`` of the
  record body, advanced to the next line start), so ranks share no state
  and the concatenation of rank outputs is exactly the serial record
  stream. BGZF inputs split at member boundaries (``scan_block_spans``)
  and each rank inflates only ~its share.
- **Rank segments**: rank ``r`` runs the UNCHANGED streaming executor
  against ``<out>.rank{r}of{N}.seg`` — plain text even for ``.gz``
  outputs (compression is deferred to the seam-aware commit), with its
  own chunk journal, so a SIGKILLed rank resumes from ITS journal while
  finished ranks skip via their ``.done`` markers.
- **Rank-sequenced commit** (:func:`merge_ranks`): verifies every
  segment + marker, streams ``header + body_0 + body_1 + ...`` through
  the atomic ``.partial`` + ``os.replace`` protocol; ``.gz`` outputs
  re-compress through ONE :class:`~variantcalling_tpu.io.bgzf.
  BgzfChunkCompressor` so the 65280-byte block carry is re-carried
  deterministically across rank seams — the framing is byte-identical
  to a serial writer of the same stream by the PR 7 carry contract,
  never new framing invented at the seam.

Byte contract: the merged output is identical to the single-rank run
modulo the ``##vctpu_*`` provenance headers (the ``##vctpu_ranks=``
line exists only when N > 1) — locked by the parity matrix in
``tests/unit/test_rank_plan.py`` / ``tests/system/test_scaleout.py``.

Launchers: ``tools/podrun`` spawns N local workers with
``VCTPU_RANK``/``VCTPU_NUM_PROCESSES`` set and commits the merge;
``vctpu merge-ranks <out>`` is the standalone commit step; under a real
``jax.distributed`` cluster rank 0 commits after a collective barrier.
"""

from __future__ import annotations

import json
import os
import zlib
from dataclasses import dataclass

from variantcalling_tpu import knobs, logger, obs
from variantcalling_tpu.engine import EngineError

RANKS_HEADER_KEY = "vctpu_ranks"

#: decompressed bytes per merge copy block (bounds merge memory)
_MERGE_BLOCK = 8 << 20


class MergeError(RuntimeError):
    """A rank-merge precondition failed (missing/invalid/mismatched
    segments) — CLI exit 3, distinct from config errors (2)."""


@dataclass(frozen=True)
class RankPlan:
    """The run-level rank layout (docs/scaleout.md).

    Elastic pods (docs/scaleout.md "Elastic membership") add the
    ``span`` spelling: a worker leased an absolute target interval
    ``[lo, hi)`` of the decompressed record region runs as a
    single-rank plan (``ranks=1``) whose reader is span-bounded —
    so its segment header carries NO ``##vctpu_ranks=`` line and the
    merged bytes stay identical to the serial run whatever the final
    span plan looks like. ``gen`` is the lease generation the
    coordinator offered this span under (``parallel/elastic.py``)."""

    ranks: int
    rank: int
    source: str  # "env" (local launcher) | "distributed" | "span" | "single"
    reason: str
    span: tuple | None = None  # absolute (lo, hi) byte targets
    gen: int = 0  # lease generation of an elastic offer

    def header_line(self) -> str:
        # n only — never the rank id: every rank's segment must emit
        # byte-identical header bytes or the seam commit cannot verify
        # cross-rank config agreement
        return f"##{RANKS_HEADER_KEY}=n={self.ranks}"


def resolve() -> RankPlan:
    """Resolve THIS process's rank layout, once per run.

    ``VCTPU_SPAN`` (``lo:hi:gen``, the elastic launcher's spelling —
    ``parallel/elastic.py``) wins first: the worker is one leased span
    of an elastic pod, running as a single-rank plan with a
    span-bounded reader. ``VCTPU_RANK`` (+ ``VCTPU_NUM_PROCESSES``) is
    the classic local launcher's spelling and is read BEFORE any jax
    init; without it, an initialized ``jax.distributed`` runtime
    (coordinator/auto mode) supplies the layout; everything else is the
    single plan. An out-of-range rank is a configuration error
    (exit 2), never a clamp."""
    s = knobs.get_str("VCTPU_SPAN")
    r = knobs.get_int("VCTPU_RANK")
    if s:
        if r is not None:
            raise EngineError(
                "VCTPU_SPAN and VCTPU_RANK are both set — a worker is "
                "either one leased span of an elastic pod or one rank "
                "of a static pod, never both (docs/scaleout.md)")
        from variantcalling_tpu.parallel import elastic

        lo, hi, gen = elastic.parse_span_env(s)
        return RankPlan(ranks=1, rank=0, source="span",
                        reason="VCTPU_SPAN (elastic launcher)",
                        span=(lo, hi), gen=gen)
    if r is not None:
        n = knobs.get_int("VCTPU_NUM_PROCESSES")
        if n is None:
            raise EngineError(
                "VCTPU_RANK is set but VCTPU_NUM_PROCESSES is not — a "
                "rank-partitioned launch needs both (tools/podrun sets "
                "them; see docs/scaleout.md)")
        if r >= n:
            raise EngineError(
                f"VCTPU_RANK={r} is out of range for "
                f"VCTPU_NUM_PROCESSES={n} (ranks are 0-based)")
        return RankPlan(ranks=n, rank=r, source="env",
                        reason="VCTPU_RANK/VCTPU_NUM_PROCESSES (local "
                               "launcher)")
    try:
        import jax

        n = jax.process_count()
        if n > 1:
            return RankPlan(ranks=n, rank=jax.process_index(),
                            source="distributed",
                            reason="jax.distributed runtime")
    except Exception as e:  # noqa: BLE001 — uninitialized backend == single process
        from variantcalling_tpu.utils import degrade

        degrade.record("rank_plan.process_count_probe", e,
                       fallback="single-rank plan")
    return RankPlan(ranks=1, rank=0, source="single",
                    reason="single process")


def log_plan(plan: RankPlan) -> None:
    """Announce a resolved multi-rank plan (obs ``resolve`` event + log);
    single-rank plans stay silent, like the mesh plan. Elastic span
    plans announce their leased interval instead of a rank id."""
    if plan.span is not None:
        logger.info("span plan: [%d,%d) gen %d (%s)", plan.span[0],
                    plan.span[1], plan.gen, plan.reason)
        if obs.active():
            obs.event("resolve", "span_plan",
                      value=f"[{plan.span[0]},{plan.span[1]})",
                      gen=plan.gen, source=plan.source, reason=plan.reason)
        return
    if plan.ranks <= 1:
        return
    logger.info("rank plan: rank %d of %d (%s)", plan.rank, plan.ranks,
                plan.reason)
    if obs.active():
        obs.event("resolve", "rank_plan", value=plan.ranks, rank=plan.rank,
                  source=plan.source, reason=plan.reason)


# ---------------------------------------------------------------------------
# rank segments: paths, completion markers
# ---------------------------------------------------------------------------


def segment_path(out_path: str, rank: int, ranks: int) -> str:
    """Rank ``rank``'s output segment next to the final destination.
    Plain text whatever the destination container — compression happens
    once, at the seam-aware merge."""
    return f"{out_path}.rank{rank}of{ranks}.seg"


def marker_path(seg_path: str) -> str:
    return seg_path + ".done"


def discover_ranks(out_path: str) -> int | None:
    """Infer N from the ``<out>.rank*of*.seg`` siblings on disk (the
    ``vctpu merge-ranks`` no-flag path); None when no segments exist,
    :class:`MergeError` when siblings disagree on N."""
    import glob
    import re

    ns = set()
    for p in glob.glob(glob.escape(str(out_path)) + ".rank*of*.seg"):
        m = re.search(r"\.rank(\d+)of(\d+)\.seg$", p)
        if m:
            ns.add(int(m.group(2)))
    if not ns:
        return None
    if len(ns) > 1:
        raise MergeError(
            f"segments next to {out_path} disagree on the rank count "
            f"({sorted(ns)}) — stale leftovers of a different launch; "
            "remove them or pass --ranks explicitly")
    return ns.pop()


def contig_spans(path: str, n: int, header_end: int | None = None,
                 total: int | None = None,
                 slack: float = 0.2) -> list[tuple[int, int]]:
    """Contig-aware span plan over the record region of a PLAIN-text
    VCF: cut at ~equal byte targets advanced to the next line start
    (the rank-partition rule), then — when a contig boundary lies
    within ``slack`` of the span size past the cut — snap the cut to
    that boundary, so a contig's records land on ONE worker and its
    reference-genome cache stays hot (the serving-fabric placement
    rule, docs/serving_fabric.md). The snap only ever moves a cut
    forward to another line start, so the spans still tile the record
    region exactly and the concatenation of span outputs remains the
    serial record stream whatever the snaps did."""
    if header_end is None or total is None:
        from variantcalling_tpu.io import vcf as vcf_mod

        header_end, total = vcf_mod.scan_record_region(path)
    body = total - header_end
    if body <= 0 or n <= 1:
        return [(header_end, total)]
    n = min(n, body)
    budget = max(1, int(body / n * slack))
    cuts: list[int] = []
    with open(path, "rb") as fh:
        for i in range(1, n):
            cut = _line_start(fh, header_end + (body * i) // n, total)
            cuts.append(_snap_to_contig(fh, cut, total, budget))
    edges = [header_end] + sorted(set(cuts)) + [total]
    return [(lo, hi) for lo, hi in zip(edges, edges[1:]) if hi > lo]


def _line_start(fh, target: int, total: int) -> int:
    """Advance ``target`` to the next line start at or after it (the
    VcfChunkReader rank_span rule: a cut never tears a record)."""
    if target <= 0:
        return 0
    fh.seek(target - 1)
    off = target - 1
    while off < total:
        block = fh.read(min(1 << 16, total - off))
        if not block:
            break
        nl = block.find(b"\n")
        if nl >= 0:
            return min(off + nl + 1, total)
        off += len(block)
    return total


def _snap_to_contig(fh, cut: int, total: int, budget: int) -> int:
    """Move a line-start cut forward to the first contig change within
    ``budget`` bytes; keep the plain cut when the contig runs past the
    budget (locality is best effort, balance is not negotiable)."""
    fh.seek(cut)
    scanned = 0
    first_contig = None
    pos = cut
    while pos < total and scanned <= budget:
        line = fh.readline()
        if not line:
            break
        contig = line.split(b"\t", 1)[0]
        if first_contig is None:
            first_contig = contig
        elif contig != first_contig:
            return pos  # the boundary: records of the next contig start here
        pos += len(line)
        scanned += len(line)
    return cut


def segment_identity(args, plan: RankPlan,
                     engine_name: str | None = None) -> dict:
    """The identity a completed segment is valid FOR: input + model +
    every scoring flag + the rank layout + the engine-selection env.
    Built from the SAME ``io/identity.scoring_fields`` dict the
    streaming resume journal and the chunk cache use (one source of
    truth for "what makes scored bytes a pure function of input") — a
    relaunch under any changed configuration recomputes instead of
    reusing a stale segment."""
    from variantcalling_tpu.io import identity as identity_mod

    ident = identity_mod.scoring_fields(args)
    ident["input"] = identity_mod.file_sig(args.input_file)
    ident["ranks"] = [plan.rank, plan.ranks]
    if plan.span is not None:
        # elastic span workers: the segment is valid for exactly the
        # leased target interval — a re-cut span recomputes (or adopts
        # the handed-off journal), never reuses a different interval's
        # bytes. The splice masks BOTH partition fields when checking
        # cross-segment config agreement.
        ident["span"] = [int(plan.span[0]), int(plan.span[1])]
    # engine-selection env: resolved engine name + the raw strategy/
    # mesh requests — they change the segment's provenance HEADER
    # bytes, so a stale segment under a different selection must
    # recompute (the merge's header equality check backstops this
    # across ranks; identity catches the all-ranks-stale case)
    ident["engine"] = engine_name
    ident["forest_strategy"] = knobs.raw("VCTPU_FOREST_STRATEGY") or "auto"
    ident["mesh_devices"] = knobs.raw("VCTPU_MESH_DEVICES")
    return ident


def write_marker(seg_path: str, identity: dict, stats: dict) -> None:
    """Atomically record a segment's completion: identity + byte length
    + whole-segment CRC + the run stats (for skip-path logging)."""
    doc = {
        "identity": identity,
        "bytes": os.path.getsize(seg_path),
        "crc32": _file_crc(seg_path),
        "stats": {k: stats.get(k) for k in ("n", "n_pass", "chunks")},
    }
    tmp = marker_path(seg_path) + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, sort_keys=True)
        fh.write("\n")
    os.replace(tmp, marker_path(seg_path))


def _file_crc(path: str) -> int:
    crc = 0
    with open(path, "rb") as fh:
        while True:
            block = fh.read(_MERGE_BLOCK)
            if not block:
                return crc & 0xFFFFFFFF
            crc = zlib.crc32(block, crc)


def load_marker(seg_path: str) -> dict | None:
    try:
        with open(marker_path(seg_path), encoding="utf-8") as fh:
            doc = json.load(fh)
    except (OSError, ValueError):
        return None
    return doc if isinstance(doc, dict) else None


def valid_segment(seg_path: str, identity: dict) -> dict | None:
    """The completed-segment skip check (a relaunch after a partial pod
    failure must not recompute finished ranks): marker present, identity
    equal, segment length matching; ``VCTPU_RESUME_VERIFY=full``
    additionally re-reads and CRC-checks the whole segment (the journal
    v2 rule). Returns the recorded stats, or None → recompute."""
    doc = load_marker(seg_path)
    if doc is None or doc.get("identity") != identity:
        return None
    try:
        size = os.path.getsize(seg_path)
    except OSError:
        return None
    if size != doc.get("bytes"):
        return None
    if knobs.get_str("VCTPU_RESUME_VERIFY") == "full" \
            and _file_crc(seg_path) != doc.get("crc32"):
        logger.info("rank segment %s: CRC mismatch (full verify) — "
                    "recomputing", seg_path)
        return None
    stats = doc.get("stats")
    return stats if isinstance(stats, dict) else {}


def discard_segments(out_path: str) -> None:
    """Remove every rank segment + marker next to ``out_path`` (the
    post-commit sweep, and the chaos harness's between-leg cleanup)."""
    import glob

    for p in glob.glob(glob.escape(str(out_path)) + ".rank*of*.seg*"):
        try:
            os.remove(p)
        except OSError:
            pass


# ---------------------------------------------------------------------------
# the rank-sequenced committer
# ---------------------------------------------------------------------------


def _header_len(path: str) -> int:
    """Byte length of the VCF header (every leading ``#``-prefixed
    line) of ``path`` — the split point between a segment's header copy
    and its record body."""
    size = os.path.getsize(path)
    cap = 1 << 20
    with open(path, "rb") as fh:
        while True:
            fh.seek(0)
            head = fh.read(min(cap, size))
            end = 0
            torn = False
            while end < len(head):
                if head[end:end + 1] != b"#":
                    return end
                nl = head.find(b"\n", end)
                if nl < 0:
                    torn = True  # header line crosses the read window
                    break
                end = nl + 1
            if not torn and cap >= size:
                return end  # header-only segment (an empty rank span)
            if cap >= size:
                raise MergeError(
                    f"{path}: unterminated header line — truncated segment")
            cap *= 8


def merge_ranks(out_path: str, ranks: int | None = None,
                cleanup: bool = True) -> dict:
    """The rank-sequenced commit: merge every rank's segment into the
    final destination, byte-identical to the single-rank run of the same
    header modulo nothing — the segments ARE the serial record stream in
    rank order.

    Plain destinations concatenate ``header + body_0 + ... + body_{N-1}``;
    ``.gz`` destinations stream the same bytes through ONE
    :class:`~variantcalling_tpu.io.bgzf.BgzfChunkCompressor`, so the
    65280-byte block carry crosses every rank seam exactly as a serial
    writer's would (the PR 7 framing contract — the carry is a pure
    function of cumulative stream length, and the committer re-carries
    it at the seams rather than inventing new framing). The write rides
    the run-unique ``.partial`` + atomic ``os.replace`` protocol, so a
    killed merge never tears the destination.

    Raises :class:`MergeError` when a segment is missing, its marker is
    absent/stale, or rank headers disagree (cross-rank config drift).
    """
    out_path = str(out_path)
    if ranks is None:
        ranks = discover_ranks(out_path)
        if ranks is None:
            raise MergeError(f"no rank segments found next to {out_path}")
    segs = [(f"rank {r}/{ranks}", segment_path(out_path, r, ranks))
            for r in range(ranks)]
    total, markers = splice_segments(out_path, segs)
    stats = {
        "ranks": ranks,
        "bytes": total,
        "n": sum(int((m.get("stats") or {}).get("n") or 0)
                 for m in markers),
        "n_pass": sum(int((m.get("stats") or {}).get("n_pass") or 0)
                      for m in markers),
    }
    if obs.active():
        obs.event("journal", "rank_merge", ranks=ranks, bytes=total,
                  records=stats["n"])
    if cleanup:
        discard_segments(out_path)
    logger.info("merged %d rank segments -> %s (%d records, %d bytes "
                "uncompressed)", ranks, out_path, stats["n"], total)
    return stats


def splice_segments(out_path: str,
                    segs: list[tuple[str, str]]) -> tuple[int, list[dict]]:
    """The seam-aware splice core shared by :func:`merge_ranks` and the
    elastic span committer (``parallel/elastic.merge_spans``): verify
    every ``(label, path)`` segment — present, sealed by a ``.done``
    marker, length-consistent with it, produced under ONE configuration
    modulo the partition fields (``ranks``/``span`` are exactly what may
    legitimately differ across a plan), identical header bytes — then
    stream ``header + body_0 + ... + body_{k-1}`` into ``out_path``
    through the run-unique ``.partial`` + atomic ``os.replace``
    protocol. ``.gz`` destinations re-compress through ONE
    :class:`~variantcalling_tpu.io.bgzf.BgzfChunkCompressor` so the
    65280-byte block carry is re-carried across however many seams the
    final plan has. Returns ``(uncompressed_bytes, markers)``."""
    if not segs:
        raise MergeError(f"empty segment plan for {out_path}")
    markers = []
    for label, seg in segs:
        if not os.path.exists(seg):
            raise MergeError(
                f"{label} segment missing: {seg} — that worker has not "
                "completed (relaunch it; finished workers skip via their "
                ".done markers)")
        doc = load_marker(seg)
        if doc is None:
            raise MergeError(
                f"{label} completion marker missing/unreadable "
                f"({marker_path(seg)}) — the segment may be mid-write")
        if os.path.getsize(seg) != doc.get("bytes"):
            raise MergeError(
                f"{label} segment length disagrees with its "
                "marker — torn or concurrently-written segment")
        markers.append(doc)
    idents = {json.dumps(dict(m.get("identity") or {}, ranks=None,
                              span=None), sort_keys=True) for m in markers}
    if len(idents) > 1:
        raise MergeError(
            "segments were produced under DIFFERENT configurations "
            "(identity mismatch across markers) — refusing to splice them")

    header_lens = [_header_len(seg) for _, seg in segs]
    with open(segs[0][1], "rb") as fh:
        header = fh.read(header_lens[0])
    for i in range(1, len(segs)):
        with open(segs[i][1], "rb") as fh:
            if fh.read(header_lens[i]) != header:
                raise MergeError(
                    f"{segs[i][0]} segment header differs from "
                    f"{segs[0][0]}'s — cross-worker configuration drift; "
                    "refusing to splice")

    from variantcalling_tpu.io import journal as journal_mod

    gz = out_path.endswith(".gz")
    token = journal_mod.new_partial_token()
    journal_mod.claim_token(token)
    part = journal_mod.partial_path(out_path, token)
    total = 0
    try:
        with open(part, "wb") as sink:
            if gz:
                from variantcalling_tpu.io.bgzf import BgzfChunkCompressor

                comp = BgzfChunkCompressor()
                sink.write(comp.add(header))
            else:
                sink.write(header)
            total += len(header)
            for i, (_, seg) in enumerate(segs):
                with open(seg, "rb") as fh:
                    fh.seek(header_lens[i])
                    while True:
                        block = fh.read(_MERGE_BLOCK)
                        if not block:
                            break
                        total += len(block)
                        sink.write(comp.add(block) if gz else block)
            if gz:
                sink.write(comp.finish())
        os.replace(part, out_path)  # the one atomic commit of the merge
    except BaseException:
        journal_mod.release_token(token)
        try:
            os.remove(part)
        except OSError:
            pass
        raise
    journal_mod.release_token(token)
    if gz:
        from variantcalling_tpu.io.tabix import build_tabix_index

        try:
            build_tabix_index(out_path)
        except (ValueError, OSError):
            pass  # unsorted/odd inputs: the VCF itself is still valid
    return total, markers


# ---------------------------------------------------------------------------
# the per-rank scale-out driver
# ---------------------------------------------------------------------------


def scaleout_eligible(args) -> bool:
    """Can this job run rank-partitioned? Same gate as the streaming
    executor minus the single-process requirement (a rank IS one of N
    processes by design)."""
    from variantcalling_tpu.pipelines.filter_variants import \
        streaming_eligible

    return streaming_eligible(getattr(args, "limit_to_contig", None),
                              allow_multiprocess=True)


def run_scaleout(args, model, fasta, annotate, blacklist, engine=None,
                 plan: RankPlan | None = None) -> int:
    """One rank's worth of a rank-partitioned filter run: compute (or
    skip, when a valid ``.done`` marker proves a previous launch already
    did) this rank's segment, then commit per the plan's source —
    ``distributed`` runs barrier and rank 0 merges; under the local
    launcher the merge belongs to ``tools/podrun`` (or a standalone
    ``vctpu merge-ranks``), because env-launched workers share no
    collectives to barrier on."""
    from variantcalling_tpu.pipelines import filter_variants as fv

    plan = plan or resolve()
    out_path = str(args.output_file)
    if plan.span is not None:
        from variantcalling_tpu.parallel import elastic

        seg = elastic.span_segment_path(out_path, plan.span[0],
                                        plan.span[1])
        # single-claimant lease: claimed BEFORE any compute or skip
        # check, so two workers offered the same (span, generation) can
        # never render the same segment — the loser exits
        # EXIT_LEASE_LOST (6), benign to the coordinator
        if not elastic.claim_lease(seg, plan.gen):
            raise elastic.LeaseLost(
                f"span [{plan.span[0]},{plan.span[1]}) generation "
                f"{plan.gen}: lease already claimed "
                f"({elastic.lease_path(seg, plan.gen)})")
    else:
        seg = segment_path(out_path, plan.rank, plan.ranks)
    identity = segment_identity(args, plan,
                                engine.name if engine is not None else None)
    prior = valid_segment(seg, identity)
    if prior is not None:
        logger.info("rank %d/%d: segment already complete (%s records) — "
                    "skipping compute", plan.rank, plan.ranks,
                    prior.get("n", "?"))
        if obs.active():
            obs.event("journal", "segment_skip", rank=plan.rank,
                      ranks=plan.ranks, records=prior.get("n"))
        stats = prior
    else:
        import argparse

        args2 = argparse.Namespace(**vars(args))
        args2.output_file = seg
        stats = fv.run_streaming(args2, model, fasta, annotate, blacklist,
                                 engine=engine, rank_plan=plan)
        if stats is None:
            raise EngineError(
                "rank-partitioned scale-out requires the streaming "
                "executor (native engine built, VCTPU_STREAM=1, "
                "VCTPU_THREADS>1, no --limit_to_contig) — rerun "
                "single-rank or fix the configuration; docs/scaleout.md")
        write_marker(seg, identity, stats)
        logger.info("rank %d/%d: wrote segment %s (%d records, %d PASS)",
                    plan.rank, plan.ranks, seg, stats["n"], stats["n_pass"])
    if plan.source == "distributed":
        import numpy as np

        from variantcalling_tpu.parallel import distributed as dist

        # pod-wide completion barrier: the gather returns only when every
        # rank's segment landed, so rank 0's merge can never read a
        # mid-write sibling
        dist.allgather_concat(np.asarray([plan.rank], dtype=np.int32))
        if plan.rank == 0:
            merged = merge_ranks(out_path, plan.ranks)
            logger.info("wrote %s: %d variants, %d PASS (%d ranks)",
                        out_path, merged["n"], merged["n_pass"],
                        plan.ranks)
        else:
            logger.info("rank %d/%d: commit delegated to rank 0",
                        plan.rank, plan.ranks)
    else:
        logger.info("rank %d/%d: segment staged; the launcher commits the "
                    "merge (tools/podrun, or `vctpu merge-ranks %s`)",
                    plan.rank, plan.ranks, out_path)
    return 0


# ---------------------------------------------------------------------------
# ``vctpu merge-ranks`` — the standalone commit step
# ---------------------------------------------------------------------------


def run(argv: list[str]) -> int:
    """CLI: merge staged rank segments into the final output.

    Exit 0 on a committed merge, 2 on usage/config errors, 3 when the
    segments are not mergeable (missing rank, stale marker, cross-rank
    drift) — distinct so a launcher can tell "relaunch the ranks" from
    "fix the invocation"."""
    import argparse
    import sys

    ap = argparse.ArgumentParser(
        prog="vctpu merge-ranks",
        description="rank-sequenced commit: merge <out>.rankNofM.seg "
                    "segments into the final output (docs/scaleout.md)")
    ap.add_argument("output_file",
                    help="the FINAL destination path the workers targeted")
    ap.add_argument("--ranks", type=int, default=None,
                    help="expected rank count (default: inferred from the "
                         "segments on disk)")
    ap.add_argument("--keep-segments", action="store_true",
                    help="keep the per-rank segments + markers after the "
                         "merge (default: swept)")
    args = ap.parse_args(argv)
    if args.ranks is not None and args.ranks <= 0:
        print("error: --ranks must be positive", file=sys.stderr)
        return 2
    try:
        stats = merge_ranks(args.output_file, ranks=args.ranks,
                            cleanup=not args.keep_segments)
    except MergeError as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    print(f"wrote {args.output_file}: {stats['n']} variants, "
          f"{stats['n_pass']} PASS from {stats['ranks']} rank segments")
    return 0
