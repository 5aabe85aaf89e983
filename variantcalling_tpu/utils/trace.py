"""Tracing/profiling: the ONE span primitive, and the JAX device profiler.

The reference's only profiling primitive is an (unused, buggy — it prints
t_start - t_end, a negative duration) wall-clock decorator
(ugvc/utils/decorators.py:4-14) plus simppl's command echo. SURVEY §5.1
makes tracing first-class here:

- ``stage(name, **fields)`` / ``@timed``: a nested wall-clock span. It is
  live exactly when an obs run is (``obs.active()``): with obs off the
  call is one module-bool check returning a shared no-op — no object, no
  lock, no append, nothing kept. With obs on ONE measurement feeds every
  reader (docs/observability.md "Writing new instrumentation"):

  * a ``jax.profiler.TraceAnnotation("vctpu:<name>", trace=<chunk trace
    id>, thread=<python thread name>)`` (and ``req=<request id>`` under a
    ``vctpu serve`` request), so the span lands in the
    profiler's host plane on the DEVICE TRACE'S CLOCK whenever a
    ``jax.profiler`` trace is being taken (the keyword arguments arrive as
    event stats; all Python threads' lines are named ``python`` there, so
    the thread name has to ride as a stat);
  * one obs ``span`` event with an explicit ``start`` (the stream's ``t``
    clock, taken at entry, outside the stream's lock), ``dur``, ``layer``
    (:data:`LAYER_OF`), on a span named in ``obs.layers.CPU_SPANS`` ``cpu``
    (the calling thread's on-CPU seconds from entry to exit,
    ``time.thread_time()``, read just outside the wall clock's readings and
    the profiler annotation: native code that released the interpreter
    counts, a thread blocked on the interpreter, a lock, a queue, the
    device or the disk does not, so ``dur - cpu`` is waiting), ``thread``,
    ``depth``, ``parent`` (the enclosing ``stage`` on this thread; for a
    thread's outermost span under a request, the request's root span),
    ``trace_id`` (the chunk's causal trace) and, under a request, ``req``;
  * the :class:`~variantcalling_tpu.obs.profile.StageProfiler` row of the
    pipeline run this context belongs to (``obs.current_profiler()``),
    ``<name>.w<idx>`` on a pooled worker (``<name>`` elsewhere), with the
    parent's name, the layer and the summed ``cpu_s`` on the row, and the
    histogram ``stage.<name>.s``;
  * with ``causal=True`` the chunk's causal ``trace`` span, fed from the
    same measurement;
  * the run's span table (``ObsRun.spans``, bounded), which ``report()``
    renders per thread. Nothing process-global grows: the table lives
    and dies with the run.

- ``device_trace(logdir)``: context manager around ``jax.profiler`` —
  captures an XLA trace (HLO timelines, fusion views) viewable in
  TensorBoard/Perfetto; no-op if profiling is unavailable.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import re
import threading
import time
from dataclasses import dataclass

from variantcalling_tpu import logger, obs
from variantcalling_tpu.obs.layers import CPU_SPANS
from variantcalling_tpu.obs.layers import LAYER_OF as LAYER_OF  # re-exported
from variantcalling_tpu.utils import degrade

#: the profiler-trace name prefix of every program span
ANNOTATION_PREFIX = obs.ANNOTATION_PREFIX

#: a pooled worker's thread name ends in its index (``vctpu-io-w3``): its
#: attribution row is ``<name>.w3``, the family spelling
#: ``vctpu obs bottleneck`` merges
_WORKER_RE = re.compile(r"-(w\d+)$")

#: span fields that are also StageProfiler accumulators
_ROW_FIELDS = ("items", "records", "bytes_in", "bytes_out")


@dataclass
class Span:
    name: str
    seconds: float
    depth: int
    thread: str = "MainThread"
    parent: str | None = None


class _ThreadState(threading.local):
    def __init__(self):
        self.stack: list[_LiveSpan] = []  # this thread's open spans


_LOCAL = _ThreadState()


class _NoSpan:
    """What ``stage()`` hands out while no obs run is open: one shared,
    stateless context manager."""

    __slots__ = ()
    seconds = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **fields) -> None:
        pass


_NOOP = _NoSpan()


class _LiveSpan:
    __slots__ = ("name", "fields", "causal", "trace_id", "thread", "parent",
                 "request", "run", "start", "seconds", "_ann", "_cpu0")

    def __init__(self, run, name: str, trace: str | None, causal: bool,
                 fields: dict):
        self.run = run
        self.name = name
        self.fields = fields
        self.causal = causal
        self.trace_id = trace
        self.seconds = 0.0  # the measurement, once the span has closed

    def set(self, **fields) -> None:
        """Fields known only once the body ran (a parsed chunk's record
        count): they join the span event and the attribution row."""
        self.fields.update(fields)

    def __enter__(self):
        # the CPU clock is read OUTSIDE the wall clock's two readings: where
        # the kernel makes it a slow call (tens of microseconds on the chip's
        # host) a span's wall must not grow by it
        self._cpu0 = time.thread_time() if self.name in CPU_SPANS else None
        stack = _LOCAL.stack
        self.parent = stack[-1].name if stack else None
        stack.append(self)
        self.thread = threading.current_thread().name
        if self.trace_id is None:
            self.trace_id = obs.current_trace()
        request = self.request = obs.current_request()
        stats = {"trace": self.trace_id or "", "thread": self.thread}
        if request is not None:
            stats["req"] = request.req
        self._ann = obs.annotate(self.name, **stats)
        self.start = self.run.now()
        return self

    def __exit__(self, *exc):
        run, name = self.run, self.name
        dur = self.seconds = run.now() - self.start
        if self._ann is not None:
            self._ann.__exit__(*exc)
        cpu = None if self._cpu0 is None \
            else time.thread_time() - self._cpu0
        stack = _LOCAL.stack
        stack.pop()
        if exc[0] is not None:
            # a failed body records nothing (the recovery ladder re-runs
            # it, and the attempt that succeeds is the chunk's span)
            return False
        depth = len(stack)
        fields = self.fields
        body = obs.span_body(name, self.start, dur, self.thread, depth,
                             fields, cpu)
        request = self.request
        if self.parent is not None:
            body["parent"] = self.parent
        elif request is not None and request.root not in (None, name):
            # the outermost span of a worker thread: the request's root
            # span is its ancestor (the attribution row keeps no parent:
            # there it means "a part of another row's work")
            body["parent"] = request.root
        if self.trace_id is not None:
            body["trace_id"] = self.trace_id
        run._emit("span", name, body)
        run.metrics.histogram(f"stage.{name}.s").observe(dur)
        prof = obs.current_profiler()
        if prof is not None:
            worker = _WORKER_RE.search(self.thread)
            row = f"{name}.{worker.group(1)}" if worker else name
            prof.stage(row, parent=self.parent,
                       layer=body.get("layer")).add_work(
                dur, cpu=cpu,
                **{k: fields[k] for k in _ROW_FIELDS if k in fields})
        if self.causal and self.trace_id is not None:
            obs.trace_span(self.trace_id, name, dur, **fields)
        run.spans.append(Span(name, dur, depth, self.thread, self.parent))
        if logger.isEnabledFor(logging.DEBUG):
            logger.debug("stage %s: %.3fs", name, dur)
        return False


def stage(name: str, *, trace: str | None = None, causal: bool = False,
          **fields):
    """The span primitive (module docstring): ``with stage("render_stage",
    records=n, causal=True): ...``.

    ``trace`` names the chunk's causal trace where the thread has none
    bound (the committer); by default it is ``obs.current_trace()``.
    ``fields`` must be JSON-serializable; ``records`` / ``bytes_in`` /
    ``bytes_out`` also feed the attribution row. Names carry no dot: the
    row's family is everything before the first one. A new name goes into
    :data:`LAYER_OF` (a test fails on one that is not there)."""
    if not obs.active():
        return _NOOP
    run = obs.current()
    if run is None:  # the run closed between the two reads
        return _NOOP
    return _LiveSpan(run, name, trace, causal, fields)


def current_span() -> str | None:
    """The innermost ``stage`` open on this thread (None outside any)."""
    stack = _LOCAL.stack
    return stack[-1].name if stack else None


def note(**fields) -> None:
    """Add fields to the innermost ``stage`` open on this thread, from
    code below the site that opened it (``built=True`` from the
    predictor cache's miss path). Nothing to do outside a live span."""
    stack = _LOCAL.stack
    if stack:
        stack[-1].fields.update(fields)


def timed(fn=None, *, name: str | None = None):
    """Decorator form of ``stage`` (fixes the reference's negative-duration timer)."""

    def deco(f):
        label = name or f.__qualname__

        @functools.wraps(f)
        def wrapper(*args, **kwargs):
            with stage(label):
                return f(*args, **kwargs)

        return wrapper

    return deco(fn) if fn is not None else deco


def spans() -> list[Span]:
    """The open run's spans in close order (empty with obs off)."""
    run = obs.current()
    return list(run.spans) if run is not None else []


def report() -> str:
    """The open run's spans as per-thread groups: the main thread's first
    (unlabeled, the historical format), every worker thread after,
    labeled."""
    mine = spans()
    threads = ["MainThread"] + sorted({s.thread for s in mine} - {"MainThread"})
    lines = ["stage timings:"]
    for t in threads:
        pad = "  " if t == "MainThread" else "    "
        rows = [f"{pad}{'  ' * s.depth}{s.name}: {s.seconds:.3f}s"
                for s in mine if s.thread == t]
        if rows and t != "MainThread":
            lines.append(f"  [thread {t}]")
        lines += rows
    return "\n".join(lines)


@contextlib.contextmanager
def device_trace(logdir: str):
    """Capture a JAX/XLA device trace into ``logdir`` (TensorBoard-viewable)."""
    import jax

    try:
        jax.profiler.start_trace(logdir)
        started = True
    except Exception as e:  # profiling unsupported on this backend/build
        degrade.record("trace.device_trace_start", e, fallback="no device trace")
        logger.warning("device trace unavailable: %s", e)
        started = False
    if started and obs.active():
        obs.event("stage", "device_trace_start", logdir=logdir)
    try:
        yield
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
                logger.info("device trace written to %s", logdir)
                obs.event("stage", "device_trace_stop", logdir=logdir)
            except Exception as e:  # noqa: BLE001
                degrade.record("trace.device_trace_stop", e,
                               fallback="trace may be incomplete")
                logger.warning("device trace stop failed: %s", e)
