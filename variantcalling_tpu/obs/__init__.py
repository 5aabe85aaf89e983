"""obs — the runtime telemetry subsystem every pipeline run writes through.

The reference ``ugvc`` has essentially no observability (its one
profiling primitive is an unused decorator that prints a negative
duration); this repo's own stopgaps had fragmented the same way —
``utils/trace.py`` spans, ``degrade.record`` degradations, fault-injection
firings, journal/resume decisions and executor lifecycle each went to
their own unstructured log lines. This package unifies them into ONE
run-scoped, schema-versioned JSONL stream (docs/observability.md):

- a **run manifest** (resolved knob registry, topology, input identity,
  package version) opens every stream (:mod:`~variantcalling_tpu.obs.manifest`);
- a **typed metrics registry** (counters/gauges/histograms with lock-free
  recording from worker threads, :mod:`~variantcalling_tpu.obs.metrics`)
  snapshots into the stream at run end;
- **events** — trace spans, degradations, fault firings, retries,
  journal/resume decisions, engine/strategy resolutions, heartbeats —
  append in one globally ordered sequence (``seq``, monotonic ``ts``);
- exporters turn any stream into a Chrome trace-event file for Perfetto
  or a terminal roll-up (:mod:`~variantcalling_tpu.obs.export`,
  ``vctpu obs export`` / ``vctpu obs summary``).

Contract (locked by ``tests/unit/test_obs.py``):

- **output-neutral**: with ``VCTPU_OBS`` on or off, every pipeline's
  output bytes are identical — obs writes only its own sidecar;
- **cheap when off**: every hook bottoms out in one module-bool check
  (:func:`active`); what it costs when ON is measured on the chip
  (PERF.md section 5, "Cost of tracing");
- **one ordered stream**: events from any thread serialize through one
  lock that also takes the timestamp, so file order, ``seq`` order and
  ``ts`` order agree.

Knobs: ``VCTPU_OBS=1`` enables recording; ``VCTPU_OBS_PATH`` overrides
the sidecar path (default: ``<output_file>.obs.jsonl`` next to the
pipeline output); ``VCTPU_OBS_PROFILE`` (default on) adds the obs v2
performance-attribution layer (:mod:`~variantcalling_tpu.obs.profile`:
per-stage work/wait attribution, RSS/CPU watermark sampler);
``VCTPU_OBS_CPUPROF=1`` starts the obs v3 continuous
CPU sampling profiler (:mod:`~variantcalling_tpu.obs.sampler`:
whole-process stack samples + per-thread CPU clocks folded into a
``sample`` event stream at ``VCTPU_OBS_CPUPROF_HZ`` — ``vctpu obs
flame`` / ``cpuledger`` are the readers); ``VCTPU_OBS_JAXPROF=1``
additionally captures a
``jax.profiler`` device trace next to the run log so host and device
timelines load side by side in Perfetto.

The LIVE telemetry plane (docs/observability.md) rides the same gate:
``VCTPU_OBS_TRACE`` (default on) threads a causal trace through every
chunk's lifecycle — per-chunk trace ids, per-stage ``trace`` spans with
parent links, megabatch fan-in, recovery linkage — the walkable DAG
``vctpu obs critical-path`` consumes; ``VCTPU_OBS_SNAPSHOT_S`` emits
periodic in-run ``snapshot`` metrics (rolling-window quantiles from
``VCTPU_OBS_WINDOW_S``) on the event-flush cadence; ``VCTPU_OBS_MAX_MB``
rotates the log to ``.segN`` segments at the cap; and
``VCTPU_OBS_PROM_FILE`` atomically rewrites a Prometheus textfile on
every snapshot (``vctpu obs tail --follow`` / ``vctpu obs prom`` are
the reader-side faces).

Abnormal exits: the first ``start_run`` registers an ``atexit`` hook
plus SIGTERM and SIGINT handlers that flush the metrics snapshot and
``run_end`` event before the process dies (then re-deliver the signal
with the default disposition — the exit code still says killed-by-
signal), so only a SIGKILL can truncate a stream (the PR 2 SIGKILL
tests own that case — resume recovers the output, and every obs reader
tolerates the torn tail: ``vctpu obs summary``/``tail`` report such a
stream as ``in-flight``).
"""

from __future__ import annotations

import atexit
import collections
import contextvars
import itertools
import json
import os
import re
import signal
import sys
import threading
import time

from variantcalling_tpu import knobs, logger
from variantcalling_tpu.obs.layers import LAYER_OF
from variantcalling_tpu.obs.metrics import NOOP, MetricsRegistry
from variantcalling_tpu.obs.schema import SCHEMA_VERSION

OBS_ENV = "VCTPU_OBS"
OBS_PATH_ENV = "VCTPU_OBS_PATH"
JAXPROF_ENV = "VCTPU_OBS_JAXPROF"
TRACE_ENV = "VCTPU_OBS_TRACE"
SNAPSHOT_ENV = "VCTPU_OBS_SNAPSHOT_S"
WINDOW_ENV = "VCTPU_OBS_WINDOW_S"
MAX_MB_ENV = "VCTPU_OBS_MAX_MB"
PROM_FILE_ENV = "VCTPU_OBS_PROM_FILE"

#: the most ``trace.stage`` spans a run keeps for ``trace.report()``
SPAN_TABLE_MAX = 8192

#: flush the stream every this many events (plus manifest and run end) —
#: a crash loses at most one flush window, without per-event fsync cost
FLUSH_EVERY = 32

#: module fast flag — hot sites check this before doing ANY other work
_ACTIVE = False
#: causal-tracing fast flag: True while a run with tracing is open
#: (``VCTPU_OBS_TRACE``, default on) — the one check trace sites pay
_TRACING = False
_RUN: "ObsRun | None" = None
# re-entrant: the SIGTERM flush handler may fire while the main thread is
# already inside start_run/end_run — a plain Lock would self-deadlock the
# dying process
_LOCK = threading.RLock()

#: the StageProfiler of the pipeline run this context belongs to, and the
#: request it serves. Both ride the context the executor already copies
#: into its workers (``parallel/pipeline.py``: ``IoPool.submit``,
#: ``StagePipeline.run``), so two pipeline runs in flight under ONE obs run
#: (two ``vctpu serve`` requests) each keep their own.
_PROFILER: contextvars.ContextVar = contextvars.ContextVar(
    "vctpu_obs_profiler", default=None)
_REQUEST: "contextvars.ContextVar[request_scope | None]" = \
    contextvars.ContextVar("vctpu_obs_request", default=None)

#: trace-id spelling (``t<N>``, run-scoped) — obs.trace_of recognizes a
#: bare id threaded through a stage-item tuple by this shape
_TRACE_ID_RE = re.compile(r"^t\d+$")


def enabled() -> bool:
    """Is obs recording requested by the environment (``VCTPU_OBS``)?"""
    return knobs.get_bool(OBS_ENV)


def active() -> bool:
    """Is a run stream currently open? The ONE check every hot-path hook
    performs before paying any obs cost."""
    return _ACTIVE


class ObsRun:
    """One open run stream: file handle, ordered event writer, metrics."""

    def __init__(self, path: str, tool: str, opened_at: float | None = None):
        self.path = path
        self.tool = tool
        self.metrics = MetricsRegistry(window_s=knobs.get_float(WINDOW_ENV))
        #: obs v2 attachments, owned by start_run/end_run: the resource
        #: watermark sampler and the jax.profiler trace dir (if any)
        self.sampler = None
        #: obs v3: the continuous CPU sampling profiler
        #: (``VCTPU_OBS_CPUPROF``, obs/sampler.py), owned the same way
        self.cpu_sampler = None
        self.jaxprof_dir: str | None = None
        #: the run's closed ``trace.stage`` spans in close order
        #: (``trace.report()``); bounded, so a daemon-long run keeps the
        #: newest and never grows
        self.spans: collections.deque = collections.deque(maxlen=SPAN_TABLE_MAX)
        #: causal-tracing state (docs/observability.md "Causal chunk
        #: tracing"): run-scoped id counters plus the per-trace cursor —
        #: trace id -> last span id, so the next stage span of a chunk
        #: knows its parent. Cursor writes are GIL-atomic dict item
        #: assignments, and a chunk's stages execute strictly in
        #: sequence (megabatch fan-in goes through ONE dispatch thread),
        #: so no two threads ever race one trace's cursor.
        self.tracing = knobs.get_bool(TRACE_ENV)
        self.traces: dict[str, str] = {}
        self._trace_n = itertools.count()
        self._span_n = itertools.count()
        #: live-plane state: periodic snapshot throttle + segment
        #: rotation bookkeeping + the Prometheus textfile target
        self._snapshot_s = knobs.get_float(SNAPSHOT_ENV)
        self._last_snapshot = time.perf_counter()
        self._in_snapshot = False
        self._closing = False
        max_mb = knobs.get_int(MAX_MB_ENV)
        self._max_bytes = (max_mb or 0) << 20
        self._bytes = 0
        self._seg = 0
        self.prom_path = knobs.get_str(PROM_FILE_ENV) or None
        self._fh = open(path, "w", encoding="utf-8")
        # re-entrant for the same reason as the module _LOCK: the SIGTERM
        # flush can land while this thread is mid-_emit
        self._lock = threading.RLock()
        self._seq = 0
        self._since_flush = 0
        # ts is derived from ONE wall anchor plus the monotonic clock so
        # the stream's timestamps can never move backwards (NTP steps the
        # wall clock; perf_counter does not step). The clock's zero is
        # ``opened_at`` where the opener took one (a perf_counter reading
        # at the start of ``obs_open``, so that span starts at 0)
        now = time.perf_counter()
        self._t0_mono = now if opened_at is None else opened_at
        self._t0_wall = time.time() - (now - self._t0_mono)

    def now(self) -> float:
        """The stream's ``t`` clock: seconds since the run opened (a span's
        explicit ``start`` is this, taken outside the lock)."""
        return time.perf_counter() - self._t0_mono

    def _emit(self, kind: str, name: str, fields: dict, flush: bool = False) -> None:
        pid = os.getpid()
        tid = threading.get_ident()
        flushed = False
        with self._lock:
            # timestamped INSIDE the lock: file order == seq order == ts order
            t = self.now()
            event = dict(fields)  # extras first; the envelope wins on collision
            request = _REQUEST.get()
            if request is not None:
                # everything a request's threads emit says whose it is
                event.setdefault("req", request.req)
            event.update(v=SCHEMA_VERSION, seq=self._seq,
                         ts=round(self._t0_wall + t, 6), t=round(t, 6),
                         kind=kind, name=name, pid=pid, tid=tid)
            self._seq += 1
            try:
                line = json.dumps(event) + "\n"
                self._fh.write(line)
                self._bytes += len(line)
                self._since_flush += 1
                if flush or self._since_flush >= FLUSH_EVERY:
                    self._fh.flush()
                    self._since_flush = 0
                    flushed = True
                if self._max_bytes and self._bytes >= self._max_bytes:
                    self._rotate()
            except ValueError:
                # a straggler event racing end_run's file close: telemetry
                # must never throw into the recording (worker) thread
                pass
        if flushed:
            # the live plane rides the existing flush cadence: every
            # FLUSH_EVERY events the throttle below may emit an in-run
            # metrics snapshot (kind=snapshot) so an external tail/prom
            # reader sees fresh rolling quantiles without a new thread
            self._maybe_snapshot()

    def _rotate(self) -> None:
        """Segment rollover (``VCTPU_OBS_MAX_MB``): close the current
        file and continue the SAME ordered stream (seq keeps counting)
        in ``<path>.seg<N>`` — readers merge segments exactly like
        ``.rankN`` siblings. Called with the event lock held."""
        try:
            nxt = open(f"{self.path}.seg{self._seg + 1}", "w",
                       encoding="utf-8")
        except OSError as e:
            # rotation failing must never lose events: disable the cap
            # and keep writing the current segment
            self._max_bytes = 0
            logger.warning("obs: cannot open rotation segment for %s: %s — "
                           "size cap disabled for this run", self.path, e)
            return
        old, self._fh = self._fh, nxt
        self._seg += 1
        self._bytes = 0
        self._since_flush = 0
        try:
            old.close()
        except OSError:
            pass

    def _maybe_snapshot(self) -> None:
        """Throttled periodic in-run metrics snapshot (the live plane's
        heartbeat): at most one per ``VCTPU_OBS_SNAPSHOT_S``, emitted on
        the event-flush cadence — an idle stream emits none, a busy one
        emits on schedule. Also rewrites the Prometheus textfile when
        ``VCTPU_OBS_PROM_FILE`` is set."""
        if self._snapshot_s <= 0 or self._in_snapshot or self._closing:
            return
        now = time.perf_counter()
        if now - self._last_snapshot < self._snapshot_s:
            return
        self._in_snapshot = True
        try:
            self._last_snapshot = now
            snap = self.metrics.snapshot()
            self._emit("snapshot", "metrics", snap, flush=True)
            self._write_prom(snap, in_flight=True)
        finally:
            self._in_snapshot = False

    def _write_prom(self, snap: dict, in_flight: bool) -> None:
        if not self.prom_path:
            return
        from variantcalling_tpu.obs import prom
        from variantcalling_tpu.utils import degrade

        try:
            prom.write_textfile(
                self.prom_path,
                prom.snapshot_to_prom(snap, tool=self.tool,
                                      in_flight=in_flight))
        except OSError as e:
            degrade.record("obs.prom_write", e,
                           fallback="Prometheus textfile skipped")

    def close(self, status: str, closing: "_SelfSpan | None" = None) -> None:
        self._closing = True  # run_end must be the stream's last event
        with self._lock:
            dur = self.now()
        snap = self.metrics.snapshot()
        self._emit("metrics", "final", snap)
        if closing is not None:
            closing.emit(self)  # obs_close: what closing cost up to here
        self._emit("run_end", self.tool, {"status": status,
                                          "dur": round(dur, 6)}, flush=True)
        self._fh.close()
        self._write_prom(snap, in_flight=False)


class _SelfSpan:
    """What opening and closing a run stream costs, as a span like any
    other (``obs_open`` / ``obs_close``, layer ``tracing``): a ``vctpu:``
    profiler annotation around all of it and one ``span`` event, so that
    what tracing adds to a traced file is a number. ``utils.trace.stage``
    cannot do it: it needs the open run these two make and unmake."""

    __slots__ = ("name", "thread", "t0", "_ann")

    def __init__(self, name: str):
        self.name = name
        self.thread = threading.current_thread().name
        self._ann = annotate(name, trace="", thread=self.thread)
        self.t0 = time.perf_counter()

    def emit(self, run: ObsRun) -> None:
        run._emit("span", self.name, span_body(
            self.name, self.t0 - run._t0_mono, time.perf_counter() - self.t0,
            self.thread, 0, {}))

    def end(self) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)


def _rank_suffixed(path: str) -> str:
    """Multi-rank runs must not interleave one file: rank N > 0 writes
    ``<path>.rankN``. Rank resolution is the ONE shared spelling
    (``parallel/distributed.rank``): ``VCTPU_RANK`` first — a local
    scale-out launcher's worker (tools/podrun) must suffix correctly
    WITHOUT initializing a jax backend — then the guarded
    ``jax.process_index()`` fallback the coordinator mode uses."""
    from variantcalling_tpu.parallel.distributed import rank as _rank

    r = _rank()
    return f"{path}.rank{r}" if r else path


def start_run(tool: str, default_path: str | None = None,
              argv: list[str] | None = None,
              inputs: dict[str, str] | None = None,
              force_path: str | None = None) -> ObsRun | None:
    """Open a run stream and emit its manifest; returns None when obs is
    disabled or a run is already active (the caller that got the ObsRun
    back owns :func:`end_run`; joiners just record into the open stream).

    ``force_path`` bypasses the ``VCTPU_OBS`` gate — for the tier-0
    schema check and tests that must record regardless of environment.
    """
    if force_path is None and not enabled():
        return None
    with _LOCK:
        if _RUN is not None:
            return None  # join the open stream, don't nest
        path = force_path or knobs.get_str(OBS_PATH_ENV) or default_path
        if not path:
            return None  # nowhere to write (no output file context)
        opening = _SelfSpan("obs_open")
        try:
            return _open_run(tool, _rank_suffixed(path), argv, inputs, opening)
        finally:
            opening.end()


def _open_run(tool: str, path: str, argv, inputs,
              opening: _SelfSpan) -> ObsRun | None:
    """:func:`start_run`'s body, under its ``obs_open`` span and the
    module lock."""
    global _ACTIVE, _RUN, _TRACING
    from variantcalling_tpu.obs.manifest import build_manifest

    try:
        run = ObsRun(path, tool, opened_at=opening.t0)
    except OSError as e:
        logger.warning("obs: cannot open run log %s: %s — recording "
                       "disabled for this run", path, e)
        return None
    run._emit("manifest", tool, build_manifest(tool, argv=argv,
                                               inputs=inputs), flush=True)
    _RUN = run
    _ACTIVE = True
    _TRACING = run.tracing
    _register_flush_handlers()
    if _register_jax_listener():
        # declared up front: a snapshot that reads 0 says "JAX compiled
        # nothing in this run", an absent counter "nobody was counting"
        for name in JAX_COUNTERS:
            run.metrics.counter(name)
    if knobs.get_bool(profile_mod().PROFILE_ENV):
        # RSS/CPU watermark sampler (obs v2): daemon thread, stopped
        # (and its watermark event emitted) by end_run
        run.sampler = profile_mod().ResourceSampler(run)
        run.sampler.start()
    if knobs.get_bool(sampler_mod().CPUPROF_ENV):
        # continuous CPU sampling profiler (obs v3): daemon thread
        # folding whole-process stack samples into the stream;
        # stopped (final flush + cpuprof summary event) by end_run
        run.cpu_sampler = sampler_mod().CpuSampler(run)
        run.cpu_sampler.start()
    if knobs.get_bool(JAXPROF_ENV):
        _start_jaxprof(run)
    logger.info("obs: recording run telemetry to %s", path)
    opening.emit(run)
    return run


def end_run(run: ObsRun | None, status: str = "ok") -> None:
    """Close the stream opened by the matching :func:`start_run` (no-op
    for joiners, who were handed None)."""
    global _ACTIVE, _RUN, _TRACING
    if run is None:
        return
    with _LOCK:
        if _RUN is not run:
            return
        closing = _SelfSpan("obs_close")
        # attachments stop while the stream still accepts events (the
        # samplers' summary events must precede the metrics snapshot)
        if run.cpu_sampler is not None:
            try:
                run.cpu_sampler.stop()
            except RuntimeError:  # never started (racing interpreter exit)
                pass
            run.cpu_sampler = None
        if run.sampler is not None:
            try:
                run.sampler.stop()
            except RuntimeError:  # never started (racing interpreter exit)
                pass
            run.sampler = None
        if run.jaxprof_dir is not None:
            _stop_jaxprof(run)
        _ACTIVE = False
        _TRACING = False
        _RUN = None
    try:
        run.close(status, closing)
    except OSError as e:  # a full disk must not mask the run's own error
        logger.warning("obs: failed to finalize run log %s: %s", run.path, e)
    finally:
        closing.end()


def profile_mod():
    """The profiler module, imported lazily (it imports this package)."""
    from variantcalling_tpu.obs import profile

    return profile


def sampler_mod():
    """The continuous-profiler module, imported lazily (same reason)."""
    from variantcalling_tpu.obs import sampler

    return sampler


def _start_jaxprof(run: ObsRun) -> None:
    """``VCTPU_OBS_JAXPROF=1``: capture a ``jax.profiler`` device trace
    for the whole run into ``<run log>.jaxprof/``. The device trace and
    the Perfetto export of this stream share the host wall clock (the
    stream's ``ts`` is wall-anchored) and the pid/tid convention (real
    OS ids on both sides), so the two files load side by side in one
    Perfetto session ("Open trace file" twice)."""
    from variantcalling_tpu.utils import degrade

    logdir = run.path + ".jaxprof"
    try:
        import jax

        jax.profiler.start_trace(logdir)
    except Exception as e:  # noqa: BLE001 — profiling must not kill the run
        degrade.record("obs.jaxprof_start", e, fallback="no device trace")
        return
    run.jaxprof_dir = logdir
    run._emit("profile", "jaxprof_start", {"logdir": logdir})


def _stop_jaxprof(run: ObsRun) -> None:
    from variantcalling_tpu.utils import degrade

    logdir, run.jaxprof_dir = run.jaxprof_dir, None
    try:
        import jax

        jax.profiler.stop_trace()
        run._emit("profile", "jaxprof_stop", {"logdir": logdir})
        logger.info("obs: jax.profiler device trace written to %s", logdir)
    except Exception as e:  # noqa: BLE001 — a failed stop must not mask the run's exit
        degrade.record("obs.jaxprof_stop", e, fallback="device trace may be "
                       "incomplete")


# -- JAX's own compile / cache / trace durations ----------------------------
#
# ONE ``jax.monitoring`` duration listener for the life of the process,
# registered by the first ``start_run`` that finds jax imported (obs never
# imports it: a launcher that must stay off the chip opens runs too). It
# checks ``_ACTIVE`` first, so outside a run it costs one bool check per
# JAX event. JAX 0.9.0 times ``compile_or_get_cached`` as a whole under
# the backend-compile event, so a persistent-cache hit fires BOTH events
# on the compiling thread, retrieval first: the thread-local flag tells a
# load from a compile. A compile is counted whether or not the cache keeps
# it (the cache's own miss event skips compiles under
# ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS``).

JAX_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
JAX_CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
JAX_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"

JAX_COUNTERS = ("jax.backend_compiles", "jax.backend_compile_s",
                "jax.cache_loads", "jax.cache_load_s", "jax.trace_s")

_JAX_LISTENER_REGISTERED = False
_JAX_TLS = threading.local()


def _on_jax_duration(event: str, duration: float, **kw) -> None:
    if not _ACTIVE:
        return
    run = _RUN
    if run is None:
        return
    if event == JAX_TRACE_EVENT:
        run.metrics.counter("jax.trace_s").add(duration)
    elif event == JAX_CACHE_RETRIEVAL_EVENT:
        _JAX_TLS.loaded = True  # vctpu-lint: disable=VCT010 — threading.local IS a per-thread cell (the obs/metrics pattern); no cross-thread visibility exists
        run.metrics.counter("jax.cache_loads").add(1)
        run.metrics.counter("jax.cache_load_s").add(duration)
    elif event == JAX_BACKEND_COMPILE_EVENT:
        if getattr(_JAX_TLS, "loaded", False):
            _JAX_TLS.loaded = False  # vctpu-lint: disable=VCT010 — threading.local IS a per-thread cell (the obs/metrics pattern); no cross-thread visibility exists
            return
        run.metrics.counter("jax.backend_compiles").add(1)
        run.metrics.counter("jax.backend_compile_s").add(duration)
        # "which step recompiled": the innermost trace.stage open on the
        # compiling thread
        from variantcalling_tpu.utils import trace as trace_mod

        run._emit("profile", "backend_compile", {
            "span": trace_mod.current_span() or "",
            "thread": threading.current_thread().name,
            "dur": round(duration, 6),
            "fun_name": str(kw.get("fun_name", ""))})


def _register_jax_listener() -> bool:
    """Idempotent; retried by later ``start_run``s until jax is there.
    True once the listener is in place."""
    global _JAX_LISTENER_REGISTERED
    jax = sys.modules.get("jax")
    if not _JAX_LISTENER_REGISTERED and jax is not None:
        _JAX_LISTENER_REGISTERED = True
        jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
    return _JAX_LISTENER_REGISTERED


# -- abnormal-exit flush (satellite: no silently truncated streams) --------

_ATEXIT_REGISTERED = False
_SIGTERM_REGISTERED = False
_SIGINT_REGISTERED = False


def _flush_open_run(status: str) -> None:
    run = _RUN
    if run is not None:
        end_run(run, status)


def _atexit_flush() -> None:
    # a tool that crashed between start_run and its finally (or that
    # never had one) still gets its metrics snapshot and run_end written
    _flush_open_run("atexit")


def _register_flush_handlers() -> None:
    """Idempotent: atexit once; SIGTERM/SIGINT only when the process
    still has the default disposition (a host app's own handler must
    win; for SIGINT "default" is Python's ``default_int_handler``) and
    only from the main thread (signal.signal raises elsewhere). The
    signal attempts RETRY on later start_runs — a first run opened from
    a worker thread must not permanently forfeit the flush for runs the
    main thread opens afterwards."""
    global _ATEXIT_REGISTERED, _SIGTERM_REGISTERED, _SIGINT_REGISTERED
    if not _ATEXIT_REGISTERED:
        _ATEXIT_REGISTERED = True
        atexit.register(_atexit_flush)
    main = threading.current_thread() is threading.main_thread()
    if not _SIGTERM_REGISTERED:
        try:
            if main and signal.getsignal(signal.SIGTERM) is signal.SIG_DFL:
                signal.signal(signal.SIGTERM, _sigterm_flush)
                _SIGTERM_REGISTERED = True
        except (ValueError, OSError):  # exotic platform / embedded interp
            pass
    if not _SIGINT_REGISTERED:
        # Ctrl-C previously tore the stream mid-write (no metrics, no
        # run_end): Python's default SIGINT handler raises
        # KeyboardInterrupt wherever the main thread happens to be, and
        # a consumer loop blocked in a queue get dies without reaching
        # end_run. Same re-deliver pattern as SIGTERM below.
        try:
            if main and signal.getsignal(signal.SIGINT) \
                    is signal.default_int_handler:
                signal.signal(signal.SIGINT, _sigint_flush)
                _SIGINT_REGISTERED = True
        except (ValueError, OSError):
            pass


def _sigterm_flush(signum, frame) -> None:
    _flush_open_run("sigterm")
    # restore the default disposition and re-deliver so the exit code
    # still says "killed by SIGTERM" — obs observes, it never rescues
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    os.kill(os.getpid(), signal.SIGTERM)


def _sigint_flush(signum, frame) -> None:
    _flush_open_run("sigint")
    # same pattern as SIGTERM: default disposition + re-deliver, so the
    # parent still sees "killed by SIGINT" (WIFSIGNALED, exit -2) — obs
    # observes, it never rescues
    signal.signal(signal.SIGINT, signal.SIG_DFL)
    os.kill(os.getpid(), signal.SIGINT)


def event(kind: str, name: str, **fields) -> None:
    """Append one event to the open stream (no-op when inactive).

    ``fields`` must be JSON-serializable; keep them small — this is a
    telemetry stream, not a data channel."""
    if not _ACTIVE:
        return
    run = _RUN
    if run is not None:
        run._emit(kind, name, fields)


#: the profiler-trace name prefix of every program span (the benchmark's
#: ``program_spans.py`` finds them by it)
ANNOTATION_PREFIX = "vctpu:"


def annotate(name: str, **stats):
    """An ENTERED ``jax.profiler.TraceAnnotation("vctpu:<name>", **stats)``
    for the caller to ``__exit__``: the span on the device trace's clock.
    None where jax is not loaded (never the reason it gets imported)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    ann = jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name, **stats)
    ann.__enter__()
    return ann


def span_body(name: str, start: float, dur: float, thread: str, depth: int,
              fields: dict, cpu: float | None = None) -> dict:
    """The body of a ``span`` event, in its one spelling (``trace.stage``'s
    spans, the executor's generic stages and the stream's own ``obs_open`` /
    ``obs_close``): ``start`` on the stream's ``t`` clock, the name's layer
    (``obs.layers.LAYER_OF``) where it has one, ``cpu`` where it was read."""
    body = dict(fields, start=round(start, 6), dur=round(dur, 6),
                thread=thread, depth=depth)
    if cpu is not None:
        body["cpu"] = round(cpu, 6)
    layer = LAYER_OF.get(name)
    if layer is not None:
        body["layer"] = layer
    return body


def span(name: str, dur: float, thread: str, depth: int = 0, **fields) -> None:
    """Record one closed wall-clock span measured by the caller (the
    stage executor's generic stages and queue waits; everything else goes
    through ``utils.trace.stage``). ``dur`` in seconds; ``start`` is the
    close stamp less ``dur``, taken outside the stream's lock."""
    if not _ACTIVE:
        return
    run = _RUN
    if run is not None:
        request = _REQUEST.get()
        if request is not None and request.root is not None and not depth:
            fields.setdefault("parent", request.root)
        run._emit("span", name, span_body(name, max(0.0, run.now() - dur), dur,
                                          thread, depth, fields))


# -- causal chunk tracing (docs/observability.md "Causal chunk tracing") ---
#
# Every chunk gets a TRACE at ingest; every stage execution appends a
# trace span carrying (trace_id, span_id, parents) so the chunk's full
# history — including megabatch fan-in, retries and recovery actions —
# is a walkable DAG. `vctpu obs critical-path` consumes it; the Perfetto
# exporter renders the parent links as flow arrows.

_TRACE_TLS = threading.local()


def tracing() -> bool:
    """Is causal tracing recording (an open run with VCTPU_OBS_TRACE on)?
    The ONE check trace sites pay before any other work."""
    return _TRACING


def new_trace() -> str | None:
    """Allocate a fresh run-scoped trace id (one per chunk, at ingest);
    None when tracing is off."""
    run = _RUN if _TRACING else None
    if run is None:
        return None
    return f"t{next(run._trace_n)}"


def trace_span(tid: str | None, name: str, dur: float,
               parents: list[str] | None = None,
               traces: list[str] | None = None, **fields) -> str | None:
    """Record one causal span of trace ``tid`` and advance the trace's
    cursor so the chunk's NEXT span parents to this one.

    ``parents`` overrides the implicit parent (the trace's cursor);
    ``traces`` marks a FAN-IN span (one megabatch dispatch serving many
    chunks): the event lists every member trace id, its parents are each
    member's cursor, and every member's cursor advances to this span —
    the DAG edge set `vctpu obs critical-path` walks. Returns the new
    span id (None when tracing is off)."""
    run = _RUN if _TRACING else None
    if run is None or tid is None:
        return None
    sid = f"s{next(run._span_n)}"
    if parents is None:
        last = run.traces.get(tid)
        parents = [last] if last is not None else []
    body = dict(fields, trace_id=tid, span_id=sid, dur=round(dur, 6))
    if parents:
        body["parents"] = list(parents)
    if traces:
        body["traces"] = list(traces)
    run._emit("trace", name, body)
    for t in (traces if traces else (tid,)):
        run.traces[t] = sid
    return sid


def trace_cursor(tid: str | None) -> str | None:
    """The trace's current last-span id (fan-in callers collect these as
    the dispatch span's parents)."""
    run = _RUN if _TRACING else None
    if run is None or tid is None:
        return None
    return run.traces.get(tid)


def end_trace(tid: str | None) -> None:
    """Drop the trace's cursor (the chunk committed — its DAG is done);
    keeps the per-run cursor table bounded at in-flight chunks."""
    run = _RUN if _TRACING else None
    if run is not None and tid is not None:
        run.traces.pop(tid, None)


def set_current_trace(tid: str | None) -> None:
    """Bind ``tid`` as this thread's current chunk trace — recovery
    sites (retry_chunk, quarantine) read it to link their events to the
    chunk they are recovering."""
    _TRACE_TLS.tid = tid  # vctpu-lint: disable=VCT010 — threading.local IS a per-thread cell (the obs/metrics pattern); no cross-thread visibility exists


def current_trace() -> str | None:
    """This thread's current chunk trace id (None outside a chunk body
    or with tracing off)."""
    return getattr(_TRACE_TLS, "tid", None)


class trace_scope:
    """Context manager: bind a chunk's trace id to this thread for the
    duration of its stage body (restores the previous binding, so nested
    bodies and pool workers reusing a thread stay correct)."""

    __slots__ = ("tid", "_prev")

    def __init__(self, tid: str | None):
        self.tid = tid

    def __enter__(self):
        self._prev = getattr(_TRACE_TLS, "tid", None)
        _TRACE_TLS.tid = self.tid  # vctpu-lint: disable=VCT010 — threading.local IS a per-thread cell (the obs/metrics pattern); no cross-thread visibility exists
        return self.tid

    def __exit__(self, *exc):
        _TRACE_TLS.tid = self._prev  # vctpu-lint: disable=VCT010 — threading.local IS a per-thread cell (the obs/metrics pattern); no cross-thread visibility exists
        return False


def trace_of(item) -> str | None:
    """Best-effort trace id of a stage item: the ``_obs_trace`` attribute
    a traced chunk table carries, or — for the render/compress tuples —
    a bare ``t<N>`` id threaded through the tuple. The watchdog uses this
    to link its re-dispatch events to the wedged chunk's trace."""
    tid = getattr(item, "_obs_trace", None)
    if isinstance(tid, str):
        return tid
    if isinstance(item, tuple):
        for x in item:
            tid = getattr(x, "_obs_trace", None)
            if isinstance(tid, str):
                return tid
            if isinstance(x, str) and _TRACE_ID_RE.match(x):
                return x
    return None


def counter(name: str):
    """The named counter of the open run, or a shared no-op."""
    run = _RUN if _ACTIVE else None
    return run.metrics.counter(name) if run is not None else NOOP


def gauge(name: str):
    run = _RUN if _ACTIVE else None
    return run.metrics.gauge(name) if run is not None else NOOP


def histogram(name: str):
    run = _RUN if _ACTIVE else None
    return run.metrics.histogram(name) if run is not None else NOOP


def current() -> ObsRun | None:
    """The open run (``trace.stage``, tests, manifest introspection)."""
    return _RUN


def current_profiler():
    """The StageProfiler bound to this context (None outside a pipeline
    run, or with profiling off): where a ``trace.stage`` site deep in the
    dispatch finds its attribution row without a parameter."""
    return _PROFILER.get()


class bind_profiler:
    """Context manager: bind a pipeline run's StageProfiler to the current
    context for the pipeline's duration (restores what was bound). The
    run's worker threads inherit it with the rest of the context. No-op
    without a profiler."""

    __slots__ = ("prof", "_token")

    def __init__(self, prof):
        self.prof = prof
        self._token = None

    def __enter__(self):
        if self.prof is not None:
            self._token = _PROFILER.set(self.prof)
        return self.prof

    def __exit__(self, *exc):
        if self._token is not None:
            _PROFILER.reset(self._token)
            self._token = None
        return False


class request_scope:
    """Context manager: bind a request (``vctpu serve``) to the current
    context. While it is bound, every event the context's threads emit
    carries ``req``, and a ``trace.stage`` span that is the outermost on
    its thread names ``root`` (the request's root span) as its parent.
    ``notes`` is what the code under the request tells the front about
    it (:func:`request_note`); bound with obs on or off."""

    __slots__ = ("req", "root", "notes", "_token")

    def __init__(self, req: str, root: str | None = None):
        self.req = req
        self.root = root
        self.notes: dict = {}
        self._token = None

    def __enter__(self):
        self._token = _REQUEST.set(self)
        return self

    def __exit__(self, *exc):
        _REQUEST.reset(self._token)
        self._token = None
        return False


def current_request() -> "request_scope | None":
    """The request bound to this context (None outside ``vctpu serve``)."""
    return _REQUEST.get()


def request_note(**fields) -> None:
    """Tell the request this context serves (if any) something about its
    run (``genome_resident=True`` from the fused dispatch)."""
    request = _REQUEST.get()
    if request is not None:
        request.notes.update(fields)
