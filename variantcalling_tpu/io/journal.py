"""Chunk journal for resumable, atomic streaming writeback.

The streaming filter executor writes its output through a two-file
protocol so an interrupted run (crash, OOM-kill, SIGKILL) never leaves a
partial file at the destination and can RESUME instead of recomputing:

- ``<out>.partial``  — the output bytes as they accumulate; renamed onto
  the destination (``os.replace``, atomic on POSIX) only after the last
  chunk landed. The destination path either holds a previous complete
  file or nothing — never a torn write.
- ``<out>.journal``  — one JSON line per committed chunk (sequence
  number, record/pass counts, body length, CRC32), after a header line
  binding the journal to the exact input file (size + mtime_ns), chunk
  size and output header bytes. Appended and flushed after the chunk's
  bytes are in the partial file, so the journal never claims more than
  the partial file holds (the reverse — partial ahead of journal — is
  healed by truncation on resume).

Resume contract (``pipelines/filter_variants.run_streaming``): chunk
boundaries are a pure function of (input bytes, chunk_bytes), every
per-variant product is row-local, and the journal pins both — so
"skip the journaled chunks, truncate the partial file to the journaled
watermark, continue" reproduces the uninterrupted output byte for byte
(locked by ``tests/unit/test_streaming_faults.py``).

Anything suspicious — signature mismatch, truncated journal line, CRC
mismatch, partial file shorter than the watermark — degrades to a fresh
run; resume is an optimization, never a correctness risk.

Rank-partitioned scale-out runs (docs/scaleout.md) ride this protocol
PER RANK: each rank's streaming run targets its own segment path
(``<out>.rank{r}of{N}.seg``), so every rank keeps its own journal +
partial pair and a SIGKILLed rank resumes from ITS journal while its
siblings are untouched — the resume identity additionally pins the rank
layout (``config.ranks``), because a journal written by rank r of N
describes r's chunk span only. Completed segments are sealed by a
``.done`` marker (``parallel/rank_plan.py``) the relaunch skip-path and
the rank-sequenced committer both verify.
"""

from __future__ import annotations

import json
import os
import zlib
from dataclasses import dataclass, field

from variantcalling_tpu import logger

JOURNAL_SUFFIX = ".journal"
PARTIAL_SUFFIX = ".partial"
_VERSION = 1


def fsync_enabled() -> bool:
    """Journal v2 durability knob: fsync partial + journal per chunk."""
    from variantcalling_tpu import knobs

    return knobs.get_bool("VCTPU_JOURNAL_FSYNC")


def partial_path(out_path: str, token: str | None = None) -> str:
    """The in-flight output path. ``token`` (``new_partial_token``)
    makes it run-unique — two concurrent runs targeting the same output
    then accumulate INDEPENDENT partials and the atomic ``os.replace``
    commit makes the destination last-complete-writer-wins, where the
    old fixed ``<out>.partial`` let them silently clobber each other's
    bytes mid-write. ``None`` keeps the legacy fixed name (journals
    written before the token field resume through it)."""
    base = str(out_path) + PARTIAL_SUFFIX
    return f"{base}.{token}" if token else base


def open_partial(out_path: str, token: str | None, mode: str = "wb"):
    """Open the in-flight partial for ``out_path`` — the ONE sanctioned
    partial-open (VCT011 run-state ownership): the streaming sink's
    binary handle comes from here, so the ``.partial`` naming scheme has
    exactly one writer-side spelling and a rename of the scheme cannot
    leave a pipeline opening the old name."""
    return open(partial_path(out_path, token), mode)


def remove_partial(out_path: str, token: str | None) -> None:
    """Best-effort removal of the in-flight partial (failure-exit
    cleanup of a non-resumable run) — the sanctioned spelling of the
    unlink, so droppings-removal tracks the naming scheme."""
    try:
        os.remove(partial_path(out_path, token))
    except OSError:
        pass


def commit_partial(out_path: str, token: str | None) -> None:
    """Atomically commit the partial onto its destination. The source
    is a ``.partial`` sibling by construction (the tmp-sibling idiom
    VCT011 requires), so an interrupted commit never exposes a torn
    destination — either the old bytes or the complete new ones."""
    os.replace(partial_path(out_path, token), out_path)


def list_partials(out_path: str) -> list[str]:
    """Every partial next to ``out_path`` — the legacy fixed name plus
    all unique-suffix partials. The ONE spelling of that glob, shared by
    the chaos/load harnesses and the test sentinels,
    so a future change to the naming scheme cannot strand a copy."""
    import glob

    base = str(out_path) + PARTIAL_SUFFIX
    found = [base] if os.path.exists(base) else []
    return found + sorted(glob.glob(glob.escape(base) + ".*"))


def new_partial_token() -> str:
    """A fresh run-unique partial suffix. The leading pid is load-
    bearing: :func:`cleanup_stale_partials` only sweeps partials whose
    owning process is DEAD, so a concurrent live run's partial is never
    collected."""
    return f"{os.getpid()}-{os.urandom(4).hex()}"


def _token_pid(token: str) -> int | None:
    head = token.split("-", 1)[0]
    return int(head) if head.isdigit() else None


#: partial tokens with an OPEN sink in THIS process — pid liveness alone
#: cannot distinguish a serve daemon's in-flight request from its own
#: finished-and-failed one (same pid), so the streaming writer claims
#: its token for the sink's lifetime (set add/discard are GIL-atomic)
_ACTIVE_TOKENS: set[str] = set()


def claim_token(token: str) -> None:
    _ACTIVE_TOKENS.add(token)


def release_token(token: str) -> None:
    _ACTIVE_TOKENS.discard(token)


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except OSError:
        return True  # EPERM: alive under another uid
    return True


def token_in_use(token: str) -> bool:
    """Does a RUNNING process/request own this partial? Another live pid
    always counts as in use (conservative: a recycled pid keeps a stale
    file rather than risking a live one); our own pid counts only while
    the token is claimed by an open sink in this process."""
    pid = _token_pid(token)
    if pid is None or not _pid_alive(pid):
        return False
    if pid != os.getpid():
        return True
    return token in _ACTIVE_TOKENS


def cleanup_stale_partials(out_path: str) -> None:
    """Sweep ABANDONED unique-suffix partials next to ``out_path``: any
    ``<out>.partial.<pid>-<hex>`` no running process/request owns
    (:func:`token_in_use` — dead owner pid, or this process's pid with
    no open sink claiming the token). A live FOREIGN pid's partial is
    left strictly alone; unclaimed own-pid orphans must go, or a
    long-lived serve daemon slowly accretes them."""
    import glob

    prefix = str(out_path) + PARTIAL_SUFFIX + "."
    for p in glob.glob(glob.escape(str(out_path) + PARTIAL_SUFFIX) + ".*"):
        token = p[len(prefix):]
        if _token_pid(token) is None:
            continue  # not our naming scheme — leave it
        if token_in_use(token):
            continue
        try:
            os.remove(p)
            logger.info("swept stale partial %s (no live owner)", p)
        except OSError:
            pass


def journal_path(out_path: str) -> str:
    return str(out_path) + JOURNAL_SUFFIX


# the (size, mtime_ns) input pin moved to io/identity.py — the ONE
# spelling shared with the segment markers and the chunk cache; this
# re-export keeps the journal's historical import surface working
from variantcalling_tpu.io.identity import input_signature  # noqa: F401


@dataclass
class ResumeState:
    """What a valid journal + partial file pair lets us skip."""

    chunks: int  # complete chunks already in the partial file
    watermark: int  # byte offset in the partial file after those chunks
    n_records: int
    n_pass: int
    #: unique partial suffix the journal recorded (None: legacy fixed
    #: ``<out>.partial`` written before the token field)
    partial_token: str | None = None


@dataclass
class ChunkJournal:
    """Writer/loader for the ``<out>.journal`` sidecar."""

    out_path: str
    _fh: object | None = field(default=None, repr=False)

    # -- writing -----------------------------------------------------------

    def begin(self, meta: dict) -> None:
        """Start a FRESH journal with the run-identity header line."""
        meta = dict(meta, version=_VERSION)
        self._fh = open(journal_path(self.out_path), "w", encoding="utf-8")
        self._fh.write(json.dumps(meta, sort_keys=True) + "\n")
        self._fh.flush()

    def reopen(self) -> None:
        """Append to an existing journal (resume path)."""
        self._fh = open(journal_path(self.out_path), "a", encoding="utf-8")

    def append(self, seq: int, records: int, passed: int, body_len: int,
               crc: int, in_end: int | None = None) -> None:
        assert self._fh is not None, "journal not started"
        entry = {"seq": seq, "records": records, "pass": passed,
                 "body_len": body_len, "crc": crc}
        if in_end is not None:
            # absolute decompressed END offset of the chunk's INPUT span
            # — the elastic re-cut rule (parallel/elastic.py) splits a
            # dead rank's span at the last journaled in_end, so the
            # journaled prefix is adoptable as a complete sub-span and
            # the remainder re-cuts fresh. Optional: journals without it
            # (older writers) degrade to whole-span re-assignment.
            entry["in_end"] = int(in_end)
        self._fh.write(json.dumps(entry) + "\n")
        self._fh.flush()
        if fsync_enabled():
            # durability knob (VCTPU_JOURNAL_FSYNC): the journal line
            # reaches the platter before the next chunk starts — a power
            # cut can then cost at most the in-flight chunk. Default off:
            # flush ordering alone already survives process death, and
            # per-chunk fsync costs real throughput on the 5M path.
            os.fsync(self._fh.fileno())

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    def finish(self) -> None:
        """Successful completion: the journal has served its purpose."""
        self.close()
        try:
            os.remove(journal_path(self.out_path))
        except OSError:
            pass

    # -- loading -----------------------------------------------------------

    @staticmethod
    def load(out_path: str) -> tuple[dict, list[dict]] | None:
        """(meta, entries) from an existing journal; None when absent or
        unreadable. A truncated/corrupt LAST line (killed mid-append) is
        dropped; corruption earlier than that invalidates the journal."""
        try:
            with open(journal_path(out_path), encoding="utf-8") as fh:
                lines = fh.read().splitlines()
        except OSError:
            return None
        if not lines:
            return None
        try:
            meta = json.loads(lines[0])
        except ValueError:
            return None
        if not isinstance(meta, dict) or meta.get("version") != _VERSION:
            return None
        entries: list[dict] = []
        for i, line in enumerate(lines[1:]):
            try:
                e = json.loads(line)
            except ValueError:
                if i == len(lines) - 2:  # torn tail line: drop it
                    break
                return None
            if not isinstance(e, dict) or e.get("seq") != len(entries):
                return None  # out-of-order / duplicated entries: distrust all
            entries.append(e)
        return meta, entries


def try_resume(out_path: str, meta: dict,
               claim: bool = False) -> ResumeState | None:
    """Validate journal + partial file against this run's identity ``meta``
    and prepare the partial file for continuation.

    On success the partial file is TRUNCATED to the journaled watermark
    (healing a torn final chunk), RE-TOKENED under this process's pid,
    and a :class:`ResumeState` is returned; ANY mismatch or malformation
    returns None (fresh run) — a corrupt journal must never be able to
    crash every subsequent run. ``claim=True`` (the streaming writer)
    additionally claims the new token ATOMICALLY with the rename, so no
    concurrent discard/sweep can take the partial in the gap before the
    writer opens it — the caller then owns :func:`release_token`.
    """
    try:
        return _try_resume(out_path, meta, claim=claim)
    except (KeyError, ValueError, TypeError, OSError):
        # journal parses as JSON but is structurally wrong (missing
        # fields, non-numeric values): suspicious -> fresh run
        logger.info("streaming resume: malformed journal — fresh run")
        return None


def _try_resume(out_path: str, meta: dict,
                claim: bool = False) -> ResumeState | None:
    loaded = ChunkJournal.load(out_path)
    if loaded is None:
        return None
    jmeta, entries = loaded
    expect = dict(meta, version=_VERSION)
    if {k: jmeta.get(k) for k in expect} != expect:
        # say WHICH field invalidated the journal (old vs new value):
        # resume/cache invalidation must be debuggable from production
        # logs, not reproducible-only (io/identity.describe_mismatch)
        from variantcalling_tpu.io import identity as identity_mod

        logger.info("streaming resume: journal identity mismatch (%s) — "
                    "fresh run",
                    identity_mod.describe_mismatch(
                        {k: jmeta.get(k) for k in expect}, expect))
        return None
    if not entries:
        return None
    token = jmeta.get("partial") or None
    if token is not None and token_in_use(token):
        # the journal's partial belongs to a RUNNING process/request —
        # truncating/appending a live writer's file would interleave two
        # runs' bytes. Same-output concurrency is served by the unique
        # partials + atomic commit (last complete writer wins); resume
        # is only for DEAD runs.
        logger.info("streaming resume: the journal's partial is owned by "
                    "a running process — fresh run")
        return None
    part = partial_path(out_path, token)
    try:
        size = os.path.getsize(part)
    except OSError:
        return None
    watermark = int(meta["header_len"]) + sum(int(e["body_len"]) for e in entries)
    if size < watermark:
        logger.info("streaming resume: partial file behind the journal — fresh run")
        return None
    from variantcalling_tpu import knobs

    if knobs.get_str("VCTPU_RESUME_VERIFY") == "full":
        # journal v2 opt-in (VCTPU_RESUME_VERIFY=full): re-read and
        # CRC-check EVERY journaled chunk plus the header bytes before
        # trusting the prefix — for operators who suspect the partial
        # file itself (bad disk, concurrent writer) and will pay a full
        # sequential read to know. Any mismatch degrades to a fresh run.
        try:
            with open(part, "rb") as fh:
                head = fh.read(int(meta["header_len"]))
                if zlib.crc32(head) != int(meta["header_crc"]):
                    logger.info("streaming resume: header CRC mismatch "
                                "(full verify) — fresh run")
                    return None
                for e in entries:
                    body = fh.read(int(e["body_len"]))
                    if len(body) != int(e["body_len"]) \
                            or zlib.crc32(body) != int(e["crc"]):
                        logger.info("streaming resume: chunk %d CRC mismatch "
                                    "(full verify) — fresh run",
                                    int(e["seq"]))
                        return None
        except OSError:
            return None
    else:
        # default: spot-verify the LAST journaled chunk's bytes (cheap;
        # whole-prefix verification re-reads everything a resume is
        # meant to skip — VCTPU_RESUME_VERIFY=full opts into that)
        last = entries[-1]
        try:
            with open(part, "rb") as fh:
                fh.seek(watermark - int(last["body_len"]))
                tail = fh.read(int(last["body_len"]))
        except OSError:
            return None
        if zlib.crc32(tail) != int(last["crc"]):
            logger.info("streaming resume: chunk CRC mismatch — fresh run")
            return None
    if size > watermark:  # torn final chunk beyond the journal: heal it
        with open(part, "r+b") as fh:
            fh.truncate(watermark)
    # RE-TOKEN on resume: the resumed run must own its partial under ITS
    # pid — keeping the dead run's token would let a concurrent fresh
    # run's stale-partial sweep (dead owner pid) delete the file out
    # from under the live resumer. Legacy fixed-name partials adopt the
    # token scheme here the same way.
    new_token = new_partial_token()
    if claim:
        claim_token(new_token)  # before the file exists: no sweep gap
    try:
        os.rename(part, partial_path(out_path, new_token))
        # heal the journal itself too: a SIGKILL mid-append can leave a
        # torn (newline-less) tail line that load() dropped — appending
        # after it would glue valid JSON onto garbage and poison the
        # NEXT resume. Rewriting meta (with the NEW partial token) +
        # the validated entries makes reopen()-append safe.
        j = ChunkJournal(out_path)
        j.begin(dict(jmeta, partial=new_token))
        for e in entries:
            j.append(int(e["seq"]), int(e["records"]), int(e["pass"]),
                     int(e["body_len"]), int(e["crc"]),
                     in_end=e.get("in_end"))
        j.close()
    except BaseException:
        if claim:
            release_token(new_token)  # a failed resume owns nothing
        raise
    return ResumeState(
        chunks=len(entries), watermark=watermark,
        n_records=sum(int(e["records"]) for e in entries),
        n_pass=sum(int(e["pass"]) for e in entries),
        partial_token=new_token,
    )


def discard(out_path: str) -> None:
    """Remove journal + its partial file (non-resumable failure, or a
    fresh run superseding stale leftovers), then sweep abandoned
    partials of dead runs. The journal is read FIRST so the unique-
    suffix partial it names is removed with it — but ONLY when no
    running process/request owns that partial (:func:`token_in_use`): a
    concurrent live run to the same output keeps its data plane intact
    and commits last-complete-writer-wins (its journal/resume
    bookkeeping IS superseded — two journals cannot share one path;
    bytes are safe, a later resume of the loser degrades to fresh)."""
    loaded = ChunkJournal.load(out_path)
    token = loaded[0].get("partial") if loaded else None
    paths = [journal_path(out_path), partial_path(out_path)]
    if token and not token_in_use(token):
        paths.append(partial_path(out_path, token))
    for p in paths:
        try:
            os.remove(p)
        except OSError:
            pass
    cleanup_stale_partials(out_path)
