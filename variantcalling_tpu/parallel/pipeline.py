"""Bounded-queue ordered stage executor — the host-side streaming pipeline.

The flagship filter path used to run its three host stages strictly in
sequence (whole-file ingest -> featurize+score -> whole-file writeback),
so end-to-end wall time was the SUM of the stages even though each stage
leaves cores idle (ingest/writeback are I/O-and-glue heavy, scoring is
compute heavy). This executor runs the stages as a chunked pipeline over
sequence-numbered items: one worker thread per stage, bounded queues
between stages, results consumed strictly in submission order. Stage time
then hides behind the slowest stage instead of summing — the same
argument the GPU variant-calling pipeline literature makes for overlapping
I/O around the compute kernel (PAPERS.md, "Optimizing the Variant Calling
Pipeline Execution ... Using GPU-Enabled Machines"; GenPIP's stage fusion).

Design rules:

- one thread per stage, FIFO queues: per-stage order is preserved by
  construction, so output ordering needs no reorder buffer — items leave
  the last stage in exactly the order the source yielded them (each item
  carries its sequence number and the consumer asserts it);
- bounded queues (``queue_depth``): at most ``queue_depth`` items wait
  between any two stages, so peak memory is O(stages * queue_depth *
  chunk), never O(input);
- ``VCTPU_THREADS=1`` (or a single-core host) degrades to a plain serial
  loop through the same stage callables — byte-identical results, no
  threads, no queues;
- a stage exception cancels the whole pipeline promptly (stop event +
  queue drain) and re-raises in the consumer;
- a WATCHDOG (``timeout`` / ``VCTPU_STAGE_TIMEOUT_S``) bounds how long the
  consumer waits without any pipeline progress: a hung stage (wedged
  native call, dead filesystem) raises :class:`StageTimeoutError` naming
  the stuck stage instead of deadlocking the run, with queues drained and
  every joinable worker joined on the way out (failure semantics locked
  by ``tests/unit/test_streaming_faults.py``).

The GIL is not a problem here: stage bodies are native engine calls,
numpy, and file I/O, all of which release it.
"""

from __future__ import annotations

import contextvars
import os
import queue
import threading
import time
import zlib
from collections.abc import Callable, Iterable, Iterator

from variantcalling_tpu import knobs, logger, obs
from variantcalling_tpu.obs import sampler as obs_sampler
from variantcalling_tpu.utils import faults

_SENTINEL = object()


def _get_timed(q: queue.Queue, stats) -> tuple[bool, object]:
    """One bounded (0.1s) queue get, with the blocked time accounted to
    ``stats.wait_in`` when profiling — the ONE spelling of the wait-in
    attribution (stage workers and the consumer share it, so the
    accounting cannot drift between copies). Returns ``(ok, item)``."""
    if stats is None:
        try:
            return True, q.get(timeout=0.1)
        except queue.Empty:
            return False, None
    t0 = time.perf_counter()  # vctpu-lint: disable=VCT006 — obs queue-wait attribution
    try:
        return True, q.get(timeout=0.1)
    except queue.Empty:
        return False, None
    finally:
        stats.add_wait_in(time.perf_counter() - t0)  # vctpu-lint: disable=VCT006 — obs queue-wait attribution


def _put_timed(put: Callable, q: queue.Queue, item, stats) -> bool:
    """One bounded put through ``put`` (the pipeline's cancellable
    ``_put``), with the blocked time accounted to ``stats.wait_out``
    when profiling — the one spelling of backpressure attribution."""
    if stats is None:
        return put(q, item)
    t0 = time.perf_counter()  # vctpu-lint: disable=VCT006 — obs backpressure-wait attribution
    ok = put(q, item)
    stats.add_wait_out(time.perf_counter() - t0)  # vctpu-lint: disable=VCT006 — obs backpressure-wait attribution
    return ok

#: default per-run watchdog deadline (seconds of NO pipeline progress);
#: generous — chunks normally flow every few hundred ms, and a legitimate
#: slow stage still heartbeats by finishing items. 0 disables. The value
#: lives in the knob registry; this alias cannot drift from it.
DEFAULT_STAGE_TIMEOUT_S = knobs.REGISTRY["VCTPU_STAGE_TIMEOUT_S"].default


class StageTimeoutError(RuntimeError):
    """The pipeline made no progress within the watchdog deadline."""


class LadderEscalation(RuntimeError):
    """Base class for recovery-ladder escalation signals (e.g. the mesh
    dp-degrade restart): :func:`retry_chunk` passes these through
    untouched — re-dispatching the same chunk cannot answer a signal
    that says "change the run configuration"."""


def resolve_threads() -> int:
    """Pipeline thread policy: VCTPU_THREADS overrides, else cpu count.

    ``VCTPU_THREADS=1`` is the documented switch for "run the serial
    path". A malformed value is a configuration error (EngineError, CLI
    exit 2) like every other knob — the registry killed the old
    fall-back-to-auto behavior, where a typo silently changed the
    executor."""
    n = knobs.get_int("VCTPU_THREADS")
    return n if n is not None else (os.cpu_count() or 1)


def resolve_io_threads() -> int:
    """Host-IO worker policy for the parallel ingest/writeback paths
    (sharded BGZF inflate, chunk-parse fan-out, writeback block
    compress): ``VCTPU_IO_THREADS`` overrides, else cpu count. ``1``
    disables parallel IO — the serial code paths run inline, no pool.
    A malformed value is a configuration error (EngineError, exit 2;
    knob-registry contract)."""
    n = knobs.get_int("VCTPU_IO_THREADS")
    return n if n is not None else (os.cpu_count() or 1)


class _IoFuture:
    """Minimal future for :class:`IoPool` (result/exception + done event)."""

    __slots__ = ("_done", "_result", "_exc")

    def __init__(self):
        self._done = threading.Event()
        self._result = None
        self._exc: BaseException | None = None

    def result(self, timeout: float | None = None):
        if not self._done.wait(timeout):
            raise TimeoutError("IO task did not complete in time")
        if self._exc is not None:
            raise self._exc
        return self._result


class IoPool:
    """Tiny DAEMON-thread worker pool for the parallel host-IO paths.

    Unlike ``concurrent.futures.ThreadPoolExecutor`` (non-daemon workers,
    joined at interpreter exit), these workers are daemons: a truly
    wedged native/zlib call inside one cannot block process exit — the
    same policy the stage executor applies to its workers (the watchdog
    names the stuck stage; an unjoinable thread dies with the process).
    Worker threads are named ``<name>-w<idx>`` so the obs profiler can
    attribute per-worker work (docs/observability.md).
    """

    def __init__(self, threads: int, name: str = "vctpu-io"):
        self.threads = max(1, int(threads))
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self.unjoined: list[str] = []
        self._workers = [
            threading.Thread(target=self._loop, name=f"{name}-w{i}", daemon=True)
            for i in range(self.threads)
        ]
        for w in self._workers:
            w.start()

    def _loop(self) -> None:
        # (no sampler registration needed here: the obs v3 profiler's
        # name-based fallback already classifies "vctpu-io-wN"/"vctpu-
        # mesh-dispatch-wN" workers; explicit registration is for
        # threads whose NAME is not enough — pipeline stage workers and
        # the committer)
        while True:
            item = self._q.get()
            if item is None:
                return
            fut, ctx, fn, args = item
            try:
                # run in the SUBMITTER's context: request-scoped knob
                # overrides, scoped faults and cancel tokens
                # (knobs.scope / faults.scope / utils.cancellation)
                # follow the request's work onto the pool — the
                # per-request isolation contract of vctpu serve
                fut._result = ctx.run(fn, *args)
            # not a swallow: result() re-raises in the consumer
            except BaseException as e:  # noqa: BLE001  # vctpu-lint: disable=VCT002 — relayed through the future and re-raised at result()
                fut._exc = e
            finally:
                fut._done.set()

    def submit(self, fn: Callable, *args) -> _IoFuture:
        fut = _IoFuture()
        self._q.put((fut, contextvars.copy_context(), fn, args))
        return fut

    def shutdown(self, timeout: float = 5.0) -> None:
        """Stop the workers (bounded join — a wedged worker is recorded
        in ``unjoined`` and abandoned, mirroring StagePipeline)."""
        for _ in self._workers:
            self._q.put(None)
        self.unjoined = []
        for w in self._workers:
            w.join(timeout=timeout)
            if w.is_alive():
                self.unjoined.append(w.name)
        if self.unjoined:
            logger.warning("IO pool: %d worker(s) did not join: %s",
                           len(self.unjoined), ", ".join(self.unjoined))


def imap_ordered(pool: IoPool, fn: Callable, items: Iterable,
                 window: int) -> Iterator:
    """Map ``fn`` over ``items`` on ``pool``, yielding results strictly
    in submission order with at most ``window`` tasks in flight — the
    ordered-reassembly primitive of the parallel host-IO paths (shard
    inflate, chunk parse, block compress). The bounded window keeps peak
    memory at O(window × item); a failed task re-raises at its ordinal
    position (downstream consumers see the same exception order a serial
    loop would)."""
    from collections import deque

    pending: deque[_IoFuture] = deque()
    it = iter(items)
    exhausted = False
    while True:
        while not exhausted and len(pending) < max(1, window):
            try:
                item = next(it)
            except StopIteration:
                exhausted = True
                break
            pending.append(pool.submit(fn, item))
        if not pending:
            return
        yield pending.popleft().result()


def resolve_stage_timeout() -> float:
    """Watchdog deadline from ``VCTPU_STAGE_TIMEOUT_S`` (0 disables). A
    malformed value is a configuration error (EngineError, CLI exit 2;
    knob-registry contract) — it can neither disable the watchdog
    silently nor be silently ignored."""
    return knobs.get_float("VCTPU_STAGE_TIMEOUT_S")


def _retry_delay(attempt: int, backoff_s: float, who: str) -> float:
    """Exponential backoff with bounded DETERMINISTIC jitter, seeded by
    the retrying worker's identity: pool workers that hit the same
    transient fault in lockstep (one shared-disk hiccup fans the same
    error to every ``vctpu-io-w<N>``) would otherwise all sleep exactly
    ``backoff_s * 2^k`` and stampede the sink together on wake. The
    jitter spreads wakeups over [1x, 1.5x) of the base delay, is a pure
    function of (worker name, attempt) — reproducible runs stay
    reproducible, no RNG state — and is timing-only: output bytes can
    never depend on it."""
    base = backoff_s * (2 ** attempt)
    frac = (zlib.crc32(f"{who}:{attempt}".encode()) % 1024) / 1024.0
    return base * (1.0 + 0.5 * frac)


def retry_transient(fn: Callable, what: str, attempts: int | None = None,
                    backoff_s: float | None = None,
                    retry_on: tuple[type[BaseException], ...] = (OSError,)):
    """Run ``fn()`` with bounded retry + exponential backoff on transient
    IO errors — the streaming executor's recovery primitive for chunk
    reads and sink writes (docs/robustness.md failure matrix).

    ``attempts`` counts TOTAL tries (default ``VCTPU_IO_RETRIES``+1 = 3);
    backoff doubles from ``backoff_s`` (default ``VCTPU_IO_BACKOFF_S`` =
    0.05s) with deterministic per-worker jitter (:func:`_retry_delay`).
    Non-retryable exceptions propagate immediately; the last retryable
    failure propagates after the budget is spent.
    """
    if attempts is None:
        attempts = 1 + knobs.get_int("VCTPU_IO_RETRIES")
    if backoff_s is None:
        backoff_s = knobs.get_float("VCTPU_IO_BACKOFF_S")
    last: BaseException | None = None
    for k in range(max(1, attempts)):
        try:
            return fn()
        except retry_on as e:  # noqa: PERF203 — retry loop is the point
            last = e
            if k + 1 >= attempts:
                break
            delay = _retry_delay(k, backoff_s,
                                 threading.current_thread().name)
            if obs.active():
                obs.event("retry", what, attempt=k + 1, attempts=attempts,
                          error=f"{type(e).__name__}: {e}")
                obs.counter("io.retries").add(1)
            logger.warning("transient error in %s (attempt %d/%d): %s — retrying in %.2fs",
                           what, k + 1, attempts, e, delay)
            if delay:
                time.sleep(delay)
    raise last  # type: ignore[misc]


# -- supervised chunk recovery (docs/robustness.md "Recovery ladder") ------

#: per-thread re-dispatch context: quarantine guards divert a poison
#: chunk only on the FINAL attempt of the budget, and they learn which
#: attempt they are on through this cell (same thread by construction —
#: retry_chunk runs its body inline)
_RETRY_TLS = threading.local()


def on_final_attempt() -> bool:
    """True when the calling chunk body is on its LAST (or only) dispatch
    attempt. Code not running under :func:`retry_chunk` is always final —
    a guard outside the ladder quarantines on the first strike."""
    return getattr(_RETRY_TLS, "final", True)


def resolve_chunk_retries() -> int:
    """Chunk re-dispatch budget (``VCTPU_CHUNK_RETRIES``, default 1)."""
    return knobs.get_int("VCTPU_CHUNK_RETRIES")


def retry_chunk(fn: Callable, what: str, seq: int | None = None):
    """Task-level re-dispatch of a failed chunk body — the second rung of
    the supervised recovery ladder (docs/robustness.md).

    Chunk bodies (parse, featurize+score, render, the mesh megabatch
    dispatch) are pure functions of their input, so re-running one cannot
    change output bytes — it can only turn a transient failure (an IO
    worker death, a flaky allocator, a cosmic-ray exception) into a
    completed chunk instead of a dead run. Contract errors stay loud and
    unretried: ``EngineError`` (configuration) and
    :class:`StageTimeoutError` (watchdog) propagate immediately, as do
    interpreter-exit exceptions. The final failure re-raises unchanged,
    so callers — including the quarantine guards one rung up — see
    exactly the exception a retry-free run would have seen.
    """
    from variantcalling_tpu.engine import EngineError

    attempts = 1 + resolve_chunk_retries()
    last: BaseException | None = None
    prev = getattr(_RETRY_TLS, "final", True)
    try:
        for k in range(max(1, attempts)):
            if k:
                if obs.active():
                    fields = {"what": what, "attempt": k,
                              "retries": attempts - 1,
                              "chunk": -1 if seq is None else seq,
                              "error": f"{type(last).__name__}: {last}"}
                    # causal linkage: the re-dispatch names the trace of
                    # the chunk it is recovering (the body bound it via
                    # obs.trace_scope), so `obs critical-path`/triage can
                    # walk from the recovery event to the chunk's DAG
                    tid = obs.current_trace()
                    if tid is not None:
                        fields["trace_id"] = tid
                    obs.event("recovery", "chunk_retry", **fields)
                    obs.counter("recovery.chunk_retries").add(1)
                logger.warning(
                    "chunk failure in %s (attempt %d/%d): %s — re-dispatching",
                    what, k, attempts, last)
            _RETRY_TLS.final = k + 1 >= attempts  # vctpu-lint: disable=VCT010 — threading.local IS a per-thread cell (the obs/metrics pattern); no cross-thread visibility exists
            try:
                return fn()
            except (EngineError, StageTimeoutError, LadderEscalation):
                raise
            # the final failure re-raises below — never a swallow
            except Exception as e:  # noqa: BLE001  # vctpu-lint: disable=VCT002 — bounded re-dispatch; the last failure re-raises after the loop
                last = e
    finally:
        _RETRY_TLS.final = prev  # vctpu-lint: disable=VCT010 — threading.local IS a per-thread cell (the obs/metrics pattern); no cross-thread visibility exists
    raise last  # type: ignore[misc]


def record_quarantine(what: str, records: int, exc: BaseException,
                      trace_id: str | None = None) -> None:
    """The loud-divert bookkeeping EVERY quarantine site shares (the
    host-path guard in pipelines/filter_variants and the mesh dispatch
    ladder in parallel/shard_score): a sanctioned degradation with
    ``warn=True``, the ``recovery``/``quarantine`` obs event — carrying
    the diverted chunk's TRACE id so the event resolves to the chunk's
    span DAG — and the quarantined-chunks counter: one spelling, so the
    contract cannot drift between paths."""
    from variantcalling_tpu.utils import degrade

    degrade.record("stream.quarantine", exc, warn=True,
                   fallback=f"chunk of {records} records diverted to the "
                            ".quarantine sidecar")
    if obs.active():
        fields = {"what": what, "records": records,
                  "error": f"{type(exc).__name__}: {exc}"}
        tid = trace_id if trace_id is not None else obs.current_trace()
        if tid is not None:
            fields["trace_id"] = tid
        obs.event("recovery", "quarantine", **fields)
        obs.counter("recovery.quarantined_chunks").add(1)


def _dump_thread_stacks() -> str:
    """Every live thread's current Python stack (the same dump a fatal
    signal would print), captured to a string so the v2 watchdog can put
    it INTO the obs stream — a wedged production run's post-mortem then
    carries the exact frames that were stuck, not just the stage name."""
    import faulthandler
    import tempfile

    try:
        with tempfile.TemporaryFile(mode="w+") as fh:
            faulthandler.dump_traceback(file=fh, all_threads=True)
            fh.seek(0)
            return fh.read()
    except (OSError, ValueError):
        return "(thread-stack dump unavailable)"


class StagePipeline:
    """Run items through ``stages`` (list of callables) with stage overlap.

    ``run(source)`` yields ``stages[-1](...stages[0](item))`` for every
    item of ``source``, in source order. With >1 resolved threads each
    stage runs in its own worker thread connected by bounded queues; with
    1 thread the same callables run inline (the serial path).
    """

    def __init__(self, stages: list[Callable], queue_depth: int = 2,
                 threads: int | None = None, timeout: float | None = None,
                 profiler=None, source_name: str = "source",
                 consumer_name: str = "consume",
                 source_pooled: bool = False, recover: bool = False):
        if stages is None:
            raise ValueError("StagePipeline needs a stage list")
        # an EMPTY stage list is legal with a pooled source (parallel
        # host IO): the pipeline is then source -> bounded queue ->
        # consumer, and the watchdog/error/teardown contracts still hold
        self.stages = list(stages)
        self.queue_depth = max(1, int(queue_depth))
        self.threads = resolve_threads() if threads is None else max(1, int(threads))
        self.timeout = resolve_stage_timeout() if timeout is None else max(0.0, float(timeout))
        #: obs v2 attribution (obs/profile.StageProfiler) — the executor
        #: feeds work vs queue-wait vs backpressure-wait per stage into
        #: it; the CALLER owns emit() (it knows the run's wall clock and
        #: record count). ``source_name``/``consumer_name`` label the
        #: feed thread's reads and the consumer loop's waits (the filter
        #: passes "ingest"/"writeback").
        self.profiler = profiler
        self.source_name = source_name
        self.consumer_name = consumer_name
        #: True when the source is an ordered drain of a worker pool
        #: (parallel host IO): time blocked in next() is then QUEUE-WAIT
        #: on the pool, not work — the workers attribute the real work
        #: under their own ``<stage>.w<idx>`` profile rows
        self.source_pooled = source_pooled
        #: SUPERVISED mode — the streaming filter executor turns this on
        #: (docs/robustness.md "Recovery ladder"): a failed stage item
        #: re-dispatches through :func:`retry_chunk` before the failure
        #: is final; the watchdog's FIRST expiry dumps all thread stacks
        #: into the obs stream, releases injected hangs, re-dispatches
        #: the wedged chunk once on a one-shot thread and grants one
        #: more deadline (duplicate deliveries are dropped by sequence
        #: number — chunk bodies are pure, so duplicates are
        #: byte-identical). Off by default: bare pipelines keep the PR-2
        #: fail-loud-on-first-strike semantics.
        self.recover = bool(recover)
        #: True when the v2 watchdog spent its single retry on the most
        #: recent run (tests / post-mortem introspection)
        self.watchdog_retried = False
        #: threads that refused to join within the cleanup grace period on
        #: the most recent run (a truly wedged native call cannot be
        #: interrupted from Python; they are daemons and die with the
        #: process). Empty after a clean run.
        self.unjoined: list[str] = []

    @property
    def parallel(self) -> bool:
        return self.threads > 1

    # -- serial path -------------------------------------------------------

    def _stage_name(self, i: int) -> str:
        return getattr(self.stages[i], "__name__", None) or f"stage{i}"

    def _active_profiler(self):
        """The attribution sink for this run, or None (profiling rides
        the obs run: no stream, or ``VCTPU_OBS_PROFILE=0``, no cost)."""
        if self.profiler is None or not obs.active():
            return None
        return self.profiler if obs.profile_mod().enabled() else None

    def _record_stage_work(self, name: str, dt: float, seq: int, prof) -> None:
        """One stage item closed: span + latency histogram + attribution.
        A stage callable that measures itself through ``trace.stage``
        (attribute ``self_timed = True``: the filter's score, render and
        compress stages) is not recorded a second time here."""
        obs.span(name, dt, threading.current_thread().name, chunk=seq)
        obs.histogram(f"stage.{name}.s").observe(dt)
        if prof is not None:
            prof.stage(name).add_work(dt)

    def _next_timed(self, it: Iterator, seq: int, prof) -> tuple[bool, object]:
        """One source read, timed into the source stage when obs is on
        (shared by the serial loop and the feed thread). ``(ok, item)``."""
        if not obs.active():
            try:
                return True, next(it)
            except StopIteration:
                return False, None
        t0 = time.perf_counter()  # vctpu-lint: disable=VCT006 — obs span timing
        try:
            item = next(it)
        except StopIteration:
            return False, None
        dt = time.perf_counter() - t0  # vctpu-lint: disable=VCT006 — obs span timing
        if self.source_pooled:
            # pooled source: blocked-on-pool time is wait-in, not work
            obs.span(self.source_name, dt, threading.current_thread().name,
                     chunk=seq)
            obs.histogram(f"stage.{self.source_name}.s").observe(dt)
            if prof is not None:
                prof.stage(self.source_name).add_wait_in(dt, items=1)
        else:
            self._record_stage_work(self.source_name, dt, seq, prof)
        return True, item

    def _serial_stage_item(self, i: int, fn: Callable, seq: int, item, prof):
        """One stage applied to one item on the serial path — injection
        points fire PER STAGE, exactly like the threaded workers, so the
        recovery ladder sees the same unit in both modes."""
        faults.check("pipeline.stage")
        faults.check("pipeline.stage_hang")
        if not obs.active() or getattr(fn, "self_timed", False):
            return fn(item)
        t0 = time.perf_counter()  # vctpu-lint: disable=VCT006 — obs span timing
        out = fn(item)
        self._record_stage_work(
            self._stage_name(i),
            time.perf_counter() - t0, seq, prof)  # vctpu-lint: disable=VCT006 — obs span timing
        return out

    def _apply_stages(self, item, seq: int, prof):
        """One item through the serial stage chain, with PER-STAGE
        re-dispatch in supervised mode — mirroring the threaded path: a
        stage marked ``retry_safe = False`` (the stateful BGZF-carry
        compressor) runs exactly once while every other stage keeps its
        retry budget, so a single-thread .gz run still recovers
        transient scoring failures."""
        for i, fn in enumerate(self.stages):
            if self.recover and getattr(fn, "retry_safe", True):
                # bind the chunk's trace so the re-dispatch events the
                # ladder emits resolve to the chunk they recover (the
                # stage body's own scope has already unwound when the
                # failure reaches this supervisor)
                with obs.trace_scope(
                        obs.trace_of(item) if obs.tracing() else None):
                    item = retry_chunk(
                        lambda it_=item, i_=i, fn_=fn:
                        self._serial_stage_item(i_, fn_, seq, it_, prof),
                        self._stage_name(i), seq=seq)
            else:
                item = self._serial_stage_item(i, fn, seq, item, prof)
        return item

    def _run_serial(self, source: Iterable) -> Iterator:
        prof = self._active_profiler()
        it = iter(source)
        seq = 0
        while True:
            ok, item = self._next_timed(it, seq, prof)
            if not ok:
                break
            item = self._apply_stages(item, seq, prof)
            yield item
            seq += 1

    # -- threaded path -----------------------------------------------------

    def run(self, source: Iterable) -> Iterator:
        if obs.active():
            obs.event("stage", "pipeline_start",
                      stages=[self._stage_name(i) for i in range(len(self.stages))],
                      threads=self.threads, queue_depth=self.queue_depth,
                      mode="threaded" if self.parallel else "serial",
                      # the serial loop runs no watchdog — report 0 so the
                      # stream never claims a deadline that cannot fire
                      watchdog_s=self.timeout if self.parallel else 0)
            if self.timeout and self.parallel:
                obs.counter("watchdog.armed").add(1)
        if not self.parallel:
            done = 0
            try:
                for item in self._run_serial(source):
                    done += 1
                    yield item
            finally:
                if obs.active():  # lifecycle closes on EVERY exit path
                    obs.event("stage", "pipeline_end", chunks=done,
                              unjoined=[])
            return

        stop = threading.Event()
        queues = [queue.Queue(maxsize=self.queue_depth)
                  for _ in range(len(self.stages) + 1)]
        # per-stage heartbeat: monotonic time the stage last STARTED an
        # item, None while idle — lets the watchdog name the stuck stage
        busy_since: list[float | None] = [None] * len(self.stages)
        # the in-flight (seq, item) per stage — what the v2 watchdog
        # re-dispatches when the owning worker is wedged (recover mode)
        busy_item: list[tuple | None] = [None] * len(self.stages)

        def _put(q: queue.Queue, item) -> bool:
            # bounded put that stays responsive to cancellation
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        # NOTE error relay: a failing stage/source puts an (_SENTINEL, exc)
        # tuple downstream and exits — it does NOT set the stop event, or
        # the next stage could observe stop before draining the error and
        # the consumer would see a bare cancellation instead of the real
        # exception. Only the consumer sets stop (on error or completion);
        # upstream workers blocked on full queues unblock when it drains.

        prof = self._active_profiler()

        def _feed() -> None:
            obs_sampler.register_current("pipe.src")
            src = prof.stage(self.source_name) if prof is not None else None
            try:
                it = iter(source)
                seq = 0
                while True:
                    ok, item = self._next_timed(it, seq, prof)
                    if not ok:
                        break
                    if not _put_timed(_put, queues[0], (seq, item), src):
                        return
                    if obs.active():
                        # queue pressure at the pipeline head (with an
                        # empty stage list this is the ONLY queue)
                        obs.gauge("queue.source.depth").set(queues[0].qsize())
                    seq += 1
                _put(queues[0], _SENTINEL)
            # not a swallow: the consumer re-raises the relayed exception
            except BaseException as e:  # noqa: BLE001  # vctpu-lint: disable=VCT002 — relayed to the consumer and re-raised there
                _put(queues[0], (_SENTINEL, e))

        def _run_stage_item(i: int, fn: Callable, seq: int, item):
            """One stage item: injection points + timed stage body — the
            unit the recovery ladder re-dispatches (the watchdog/error
            contracts are proven against the injection points,
            tests/unit/test_streaming_faults.py)."""
            faults.check("pipeline.stage")
            faults.check("pipeline.stage_hang")
            if not obs.active() or getattr(fn, "self_timed", False):
                return fn(item)
            t0 = time.perf_counter()  # vctpu-lint: disable=VCT006 — obs span timing
            out = fn(item)
            self._record_stage_work(
                self._stage_name(i),
                time.perf_counter() - t0, seq, prof)  # vctpu-lint: disable=VCT006 — obs span timing
            return out

        def _stage(i: int, fn: Callable) -> None:
            # sampler attribution by STAGE name, not just thread index —
            # the flame then reads "pipe.compress_stage", not "pipe-stage0"
            obs_sampler.register_current(f"pipe.{self._stage_name(i)}")
            q_in, q_out = queues[i], queues[i + 1]
            stats = prof.stage(self._stage_name(i)) if prof is not None else None
            # stateful stages (a ``retry_safe = False`` attribute on the
            # callable — the BGZF compressor's carry is the one real
            # case) must see each item EXACTLY once: no re-dispatch, and
            # duplicates from an upstream watchdog re-dispatch dropped
            # HERE, before the stage body, not only at the consumer
            retryable = self.recover and getattr(fn, "retry_safe", True)
            last_seq = -1
            try:
                while not stop.is_set():
                    ok, got = _get_timed(q_in, stats)
                    if not ok:
                        continue
                    if got is _SENTINEL or (isinstance(got, tuple) and got[0] is _SENTINEL):
                        _put(q_out, got)
                        return
                    seq, item = got
                    if self.recover and seq <= last_seq:
                        # duplicate delivery from a watchdog re-dispatch
                        # of the upstream stage: already processed
                        continue
                    busy_since[i] = time.monotonic()
                    busy_item[i] = got
                    try:
                        if retryable:
                            # same trace binding as the serial supervisor:
                            # ladder events name the chunk they recover
                            with obs.trace_scope(
                                    obs.trace_of(item)
                                    if obs.tracing() else None):
                                out = retry_chunk(
                                    lambda: _run_stage_item(i, fn, seq, item),
                                    self._stage_name(i), seq=seq)
                        else:
                            out = _run_stage_item(i, fn, seq, item)
                        last_seq = seq
                        if obs.active():
                            # queue pressure AFTER this stage produced:
                            # depth ~= items waiting for the next stage
                            obs.gauge(f"queue.stage{i}.depth").set(q_out.qsize())
                    finally:
                        busy_since[i] = None
                        busy_item[i] = None
                    _put_timed(_put, q_out, (seq, out), stats)
            # not a swallow: the consumer re-raises the relayed exception
            except BaseException as e:  # noqa: BLE001  # vctpu-lint: disable=VCT002 — relayed to the consumer and re-raised there
                _put(q_out, (_SENTINEL, e))

        def _watchdog_recover() -> None:
            """Watchdog v2, first expiry (recover mode): dump every
            thread's stack into the obs stream, release injected hangs
            (a cancellable wait resumes its stage normally), and
            re-dispatch each wedged stage's in-flight chunk ONCE on a
            one-shot thread — a truly wedged daemon cannot be
            interrupted, but its chunk's result can still be delivered
            (chunk bodies are pure; the consumer drops duplicate
            sequence numbers). The run then gets one more full deadline
            before the abort path runs as before."""
            msg = self._watchdog_message(busy_since, workers)
            stacks = _dump_thread_stacks()
            logger.warning("stage pipeline watchdog: first deadline "
                           "expired — re-dispatching the wedged chunk "
                           "once before aborting. %s", msg)
            if obs.active():
                # causal linkage: the wedged in-flight chunks' trace ids
                # (the traced table / render tuple each stage holds), so
                # the re-dispatch resolves to the chunk DAGs it revives
                tids = []
                for got in busy_item:
                    if got is None:
                        continue
                    tid = obs.trace_of(got[1])
                    if tid is not None:
                        tids.append(tid)
                obs.event("recovery", "watchdog_retry", detail=msg,
                          stacks=stacks[:20000], trace_ids=tids)
                obs.counter("recovery.watchdog_retries").add(1)
            faults.cancel_hangs()
            for i, got in enumerate(busy_item):
                if got is None:
                    continue
                if not getattr(self.stages[i], "retry_safe", True):
                    # a stateful stage (BGZF carry) cannot absorb the
                    # same item twice: cancel+grace only, no re-dispatch
                    continue
                seq, item = got
                fn, q_out = self.stages[i], queues[i + 1]

                def _redispatch(i=i, fn=fn, seq=seq, item=item, q_out=q_out):
                    try:
                        out = _run_stage_item(i, fn, seq, item)
                    # not a swallow: the consumer re-raises the relay
                    except BaseException as e:  # noqa: BLE001  # vctpu-lint: disable=VCT002 — relayed to the consumer and re-raised there
                        _put(q_out, (_SENTINEL, e))
                        return
                    _put(q_out, (seq, out))

                w = threading.Thread(target=_in_ctx, args=(_redispatch,),
                                     name=f"pipe-stage{i}-retry", daemon=True)
                workers.append(w)
                w.start()

        # every worker runs in the CALLER's context (fresh copy per
        # thread — a Context object is single-threaded): request-scoped
        # knobs/faults/cancel tokens bound where run() was called follow
        # the stage bodies, the per-request isolation contract of
        # vctpu serve (docs/serving.md)
        run_ctx = contextvars.copy_context()

        def _in_ctx(fn: Callable, *args) -> None:
            run_ctx.copy().run(fn, *args)

        workers = [threading.Thread(target=_in_ctx, args=(_feed,),
                                    name="pipe-src", daemon=True)]
        workers += [
            threading.Thread(target=_in_ctx, args=(_stage, i, fn),
                             name=f"pipe-stage{i}", daemon=True)
            for i, fn in enumerate(self.stages)
        ]
        for w in workers:
            w.start()
        expect = 0
        last_progress = time.monotonic()
        self.watchdog_retried = False
        consume = prof.stage(self.consumer_name) if prof is not None else None
        try:
            while True:
                ok, got = _get_timed(queues[-1], consume)
                if not ok:
                    if stop.is_set():
                        # a failed stage may have died before relaying
                        raise RuntimeError("stage pipeline cancelled")
                    if self.timeout and time.monotonic() - last_progress > self.timeout:
                        if self.recover and not self.watchdog_retried:
                            # v2: one supervised retry before the abort
                            self.watchdog_retried = True
                            _watchdog_recover()
                            last_progress = time.monotonic()
                            continue
                        msg = self._watchdog_message(busy_since, workers)
                        if obs.active():
                            obs.event("stage", "watchdog_fire", detail=msg)
                            obs.counter("watchdog.fired").add(1)
                        raise StageTimeoutError(msg)
                    continue
                last_progress = time.monotonic()
                if got is _SENTINEL:
                    return
                if isinstance(got, tuple) and got[0] is _SENTINEL:
                    raise got[1]
                seq, item = got
                if self.recover and seq < expect:
                    # duplicate delivery: the wedged worker woke up after
                    # the watchdog's re-dispatch already delivered its
                    # chunk (both computed identical bytes — pure body)
                    continue
                # single-thread-per-stage FIFO makes this a hard invariant
                assert seq == expect, (seq, expect)
                expect += 1
                yield item
        finally:
            stop.set()
            # release any injected hang so its thread can observe stop and
            # join below (no-op outside fault-injection runs)
            faults.cancel_hangs()
            for q in queues:  # unblock any worker parked on a full queue
                try:
                    while True:
                        q.get_nowait()
                except queue.Empty:
                    pass
            self.unjoined = []
            for w in workers:
                w.join(timeout=5.0)
                if w.is_alive():
                    self.unjoined.append(w.name)
            if self.unjoined:
                # a wedged native call cannot be interrupted from Python;
                # the daemon thread dies with the process. Surface it —
                # silence here would hide a leak.
                logger.warning("stage pipeline: %d worker(s) did not join: %s",
                               len(self.unjoined), ", ".join(self.unjoined))
            if obs.active():
                obs.event("stage", "pipeline_end", chunks=expect,
                          unjoined=list(self.unjoined))

    def _watchdog_message(self, busy_since: list[float | None],
                          workers: list[threading.Thread]) -> str:
        now = time.monotonic()
        stuck = [
            f"stage {i} ({getattr(self.stages[i], '__name__', 'stage')}) busy {now - t:.1f}s"
            for i, t in enumerate(busy_since) if t is not None
        ]
        alive = [w.name for w in workers if w.is_alive()]
        detail = "; ".join(stuck) if stuck else "no stage reports busy (source stalled?)"
        return (f"stage pipeline watchdog: no progress for {self.timeout:.0f}s — "
                f"{detail}; alive workers: {', '.join(alive) or 'none'}. "
                "Raise VCTPU_STAGE_TIMEOUT_S for legitimately slow stages.")


def run_pipeline(source: Iterable, stages: list[Callable],
                 queue_depth: int = 2, threads: int | None = None) -> Iterator:
    """Convenience wrapper: ``StagePipeline(stages, ...).run(source)``."""
    return StagePipeline(stages, queue_depth=queue_depth, threads=threads).run(source)
