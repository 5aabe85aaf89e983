"""The ``vctpu serve`` HTTP daemon (docs/serving.md).

Transport: stdlib ``http.server`` over localhost TCP
(``VCTPU_SERVE_HOST``/``VCTPU_SERVE_PORT``) or a Unix-domain socket
(``VCTPU_SERVE_SOCKET``). Handler threads are daemons named
``vctpu-serve-h<N>`` so the leak sentinel and the obs thread-family
attribution see them like every other executor thread.

Endpoints (request lifecycle + failure matrix: docs/serving.md):

- ``POST /v1/filter``   — the full filter pipeline against the resident
  model/genome; writes the request's output file byte-identically to
  the cold CLI (same ``run_loaded`` code), returns the run stats.
- ``POST /v1/score``    — score a VCF in memory (no writeback), return
  score summary statistics.
- ``POST /v1/coverage`` — the single-pass coverage reduce over an
  inline depth vector.
- ``POST /v1/warm``     — make a model + reference resident: the host
  objects, the reference genome on the device and the model's fused
  program, so that a request of any size finds them there.
- ``GET /healthz`` ``GET /v1/status`` ``GET /v1/metrics`` — liveness,
  admission/cache introspection, Prometheus text exposition.

Every pipeline request runs under its own ``knobs.scope`` /
``faults.scope`` / cancellation token (per-request fault isolation —
the serve package docstring), behind the bounded admission controller.
"""

from __future__ import annotations

import argparse
import contextlib
import itertools
import json
import os
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from variantcalling_tpu import engine as engine_mod
from variantcalling_tpu import knobs, logger, obs
from variantcalling_tpu.engine import EngineError
from variantcalling_tpu.serve.admission import (AdmissionController,
                                                QueueDeadlineError, ShedError)
from variantcalling_tpu.serve.metrics import ServeMetrics
from variantcalling_tpu.serve.state import ResidentState
from variantcalling_tpu.utils import cancellation, faults
from variantcalling_tpu.utils.trace import stage

#: knob names a request may NOT override: scoping these per request
#: would change daemon-global machinery mid-flight (the serve topology
#: itself, obs stream identity) rather than the request's own run
#: (VCTPU_FAULTS is env-armed at import time, so a scoped override would
#: be silently inert — the request-level channel is the 'faults' field)
_UNSCOPABLE = frozenset(n for n in knobs.REGISTRY
                        if n.startswith(("VCTPU_SERVE_", "VCTPU_FABRIC_",
                                         "VCTPU_OBS"))) \
    | {"VCTPU_FAULTS"}

#: request fields accepted by the filter/score endpoints beyond the
#: required four, mirroring the CLI flags (docs/serving.md)
_OPTIONAL_ARGS = ("runs_file", "blacklist", "blacklist_cg_insertions",
                  "flow_order", "is_mutect", "annotate_intervals",
                  "limit_to_contig", "hpol_filter_length_dist")


class RequestError(Exception):
    """A malformed request (HTTP 400, ``status: bad_request``)."""


def _filter_namespace(body: dict, output_file: str | None) -> argparse.Namespace:
    """The pipeline args namespace a request body maps to — one builder
    for filter and score so the two cannot drift from the CLI surface."""
    for field in ("input", "model", "model_name", "reference"):
        if not body.get(field):
            raise RequestError(f"missing required field {field!r}")
    for field in ("input", "model", "reference"):
        if not os.path.exists(body[field]):
            raise RequestError(f"{field} path does not exist: {body[field]}")
    ns = argparse.Namespace(
        input_file=body["input"], model_file=body["model"],
        model_name=body["model_name"], reference_file=body["reference"],
        output_file=output_file, runs_file=body.get("runs_file"),
        blacklist=body.get("blacklist"),
        blacklist_cg_insertions=bool(body.get("blacklist_cg_insertions")),
        hpol_filter_length_dist=[int(v) for v in
                                 body.get("hpol_filter_length_dist",
                                          [10, 10])],
        flow_order=body.get("flow_order", "TGCA"),
        is_mutect=bool(body.get("is_mutect")),
        annotate_intervals=list(body.get("annotate_intervals") or []),
        limit_to_contig=body.get("limit_to_contig"),
    )
    return ns


class Server:
    """One resident daemon: warmed state + admission + HTTP front."""

    #: endpoint name -> unbound handler; subclasses (the fabric backend)
    #: extend with ``dict(Server.ENDPOINTS, ...)`` — bound at the bottom
    #: of this module once the methods exist
    ENDPOINTS: dict = {}
    #: path -> method name for endpoints that own their transport
    #: (streamed bodies instead of the JSON round trip); checked before
    #: the JSON routes
    STREAM_ROUTES: dict = {}

    def __init__(self, host: str | None = None, port: int | None = None,
                 socket_path: str | None = None,
                 obs_log: str | None = None):
        self.host = host if host is not None \
            else knobs.get_str("VCTPU_SERVE_HOST")
        self.port = port if port is not None \
            else knobs.get_int("VCTPU_SERVE_PORT")
        self.socket_path = socket_path if socket_path is not None \
            else (knobs.get_str("VCTPU_SERVE_SOCKET") or None)
        self.default_deadline_s = knobs.get_float("VCTPU_SERVE_DEADLINE_S")
        self.drain_s = knobs.get_float("VCTPU_SERVE_DRAIN_S")
        self.state = ResidentState()
        self.metrics = ServeMetrics()
        self.admission = AdmissionController(
            latency_p50=self.metrics.rolling_p50)
        self._req_n = itertools.count()
        self._started = time.monotonic()
        self._httpd: ThreadingHTTPServer | None = None
        self._serve_thread: threading.Thread | None = None
        #: deadline reaper registry: req id -> (deadline_monotonic, token)
        self._deadlines: dict[str, tuple[float, cancellation.CancelToken]] = {}
        self._deadline_lock = threading.Lock()
        self._reaper_stop = threading.Event()
        self._reaper: threading.Thread | None = None
        self.draining = threading.Event()
        self.stopped = threading.Event()
        #: the daemon-lifetime obs run (None when VCTPU_OBS=0 and no
        #: explicit log was requested)
        self._obs_log = obs_log
        self._obs_run = None

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> None:
        """Bind, warm the process-level caches, and serve on a
        background thread (the caller owns the foreground — CLI main
        loop or a test)."""
        from variantcalling_tpu.io import chunk_cache
        from variantcalling_tpu.utils.compile_cache import \
            enable_persistent_cache

        enable_persistent_cache()
        # opt this process into the chunk cache's in-memory warm index
        # (docs/caching.md): requests that repeat an input span under the
        # same scoring config replay rendered bytes without touching disk.
        # Resident mode only — a one-shot CLI would just duplicate every
        # rendered body in RAM. No-op until VCTPU_CACHE=1.
        chunk_cache.resident_mode(True)
        if self._obs_log:
            self._obs_run = obs.start_run("serve", force_path=self._obs_log)
        elif obs.enabled():
            self._obs_run = obs.start_run(
                "serve", default_path=os.path.abspath("vctpu_serve.obs.jsonl"))
        # declared up front, so that a daemon none of whose requests found
        # its genome on the device reads 0 and not "no such counter"
        self.metrics.add("serve.requests_genome_resident", 0)
        handler = _make_handler(self)
        if self.socket_path:
            with contextlib.suppress(OSError):
                os.remove(self.socket_path)
            self._httpd = _UnixHTTPServer(self.socket_path, handler)
            self.address = self.socket_path
        else:
            self._httpd = _NamedThreadingHTTPServer(
                (self.host, self.port), handler)
            self.port = self._httpd.server_address[1]
            self.address = f"http://{self.host}:{self.port}"
        self._reaper = threading.Thread(target=self._reap_deadlines,
                                        name="vctpu-serve-reaper",
                                        daemon=True)
        self._reaper.start()
        self._serve_thread = threading.Thread(
            target=self._httpd.serve_forever, kwargs={"poll_interval": 0.1},
            name="vctpu-serve-accept", daemon=True)
        self._serve_thread.start()
        if obs.active():
            obs.event("serve", "listening", address=self.address,
                      max_inflight=self.admission.max_inflight,
                      queue_depth=self.admission.queue_depth)
        logger.info("vctpu serve: listening on %s (max_inflight=%d, "
                    "queue_depth=%d)", self.address,
                    self.admission.max_inflight, self.admission.queue_depth)

    def drain(self, reason: str = "sigterm") -> None:
        """Graceful shutdown: refuse new work (503 ``draining``), let
        in-flight requests finish within ``VCTPU_SERVE_DRAIN_S``, cancel
        stragglers, flush the obs stream with status ``drain``."""
        if self.draining.is_set():
            return
        self.draining.set()
        self.admission.draining = True
        logger.info("vctpu serve: draining (%s) — refusing new requests, "
                    "waiting up to %.0fs for %d in flight", reason,
                    self.drain_s, self.admission.inflight)
        if obs.active():
            obs.event("serve", "drain_start", reason=reason,
                      inflight=self.admission.inflight,
                      queued=self.admission.queued)
        deadline = time.monotonic() + self.drain_s
        while not self.admission.idle() and time.monotonic() < deadline:
            time.sleep(0.05)
        if not self.admission.idle():
            # drain budget spent: cancel what is left so the request
            # threads unwind through their normal teardown
            with self._deadline_lock:
                stragglers = list(self._deadlines.values())
            for _, token in stragglers:
                token.cancel("daemon drain timeout")
            give_up = time.monotonic() + 10.0
            while not self.admission.idle() and time.monotonic() < give_up:
                time.sleep(0.05)
        self._reaper_stop.set()
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
        if self._serve_thread is not None:
            self._serve_thread.join(timeout=5.0)
        if self._reaper is not None:
            self._reaper.join(timeout=5.0)
        if self.socket_path:
            with contextlib.suppress(OSError):
                os.remove(self.socket_path)
        if obs.active():
            obs.event("serve", "drain_end",
                      clean=self.admission.idle())
        obs.end_run(self._obs_run, "drain")
        self._obs_run = None
        self.stopped.set()
        logger.info("vctpu serve: stopped")

    # -- deadlines ----------------------------------------------------------

    def _register_deadline(self, req: str, deadline_s: float | None,
                           token: cancellation.CancelToken) -> None:
        with self._deadline_lock:
            self._deadlines[req] = (
                time.monotonic() + deadline_s if deadline_s else float("inf"),
                token)

    def _unregister_deadline(self, req: str) -> None:
        with self._deadline_lock:
            self._deadlines.pop(req, None)

    def _reap_deadlines(self) -> None:
        """The deadline reaper: trips expired requests' cancel tokens so
        their streaming loops unwind at the next chunk boundary."""
        while not self._reaper_stop.wait(0.1):
            now = time.monotonic()
            with self._deadline_lock:
                expired = [(req, tok) for req, (at, tok)
                           in self._deadlines.items() if now > at]
            for req, token in expired:
                token.cancel("request deadline expired")
                self._unregister_deadline(req)

    # -- request execution --------------------------------------------------

    def execute(self, endpoint: str, body: dict,
                respond=None) -> tuple[int, dict]:
        """One pipeline request end to end: admission -> isolation scope
        -> pipeline -> (HTTP status, JSON payload), handed to ``respond``
        (the transport's writer) where one is given. Never raises — every
        failure maps to a per-request response; only the transport layer
        above can fail past this point.

        The request is bound to the context (``obs.request_scope``), so
        every event and span below carries its ``req``; ``serve_request``
        is the root span, ``serve_admit`` / ``serve_state`` /
        ``serve_respond`` and the pipeline's own spans lie under it."""
        req = f"r{next(self._req_n)}"
        with obs.request_scope(req, root="serve_request") as scope, \
                stage("serve_request", endpoint=endpoint) as root:
            code, payload = self._run_request(endpoint, body, req)
            if scope.notes.get("genome_resident"):
                self.metrics.add("serve.requests_genome_resident")
            root.set(status=payload.get("status"), code=code)
            if respond is not None:
                with stage("serve_respond", code=code):
                    respond(code, payload)
        return code, payload

    def _run_request(self, endpoint: str, body: dict,
                     req: str) -> tuple[int, dict]:
        deadline_s = body.get("deadline_s", self.default_deadline_s)
        try:
            deadline_s = float(deadline_s) if deadline_s else None
        except (TypeError, ValueError):
            # a client-side input error, not a daemon fault: 400, never
            # the internal-error path
            return 400, {"status": "bad_request", "req": req,
                         "error": f"deadline_s must be a number, got "
                                  f"{body.get('deadline_s')!r}"}
        t0 = time.perf_counter()  # vctpu-lint: disable=VCT006 — serve request-latency metric
        self.metrics.set_load(self.admission.inflight, self.admission.queued)
        try:
            release = self.admission.admit(endpoint, deadline_s)
        except ShedError as e:
            self.metrics.count(endpoint, "shed")
            if obs.active():
                obs.event("serve", "shed", req=req, endpoint=endpoint,
                          reason=e.reason)
            status = 503
            return status, {"status": "draining" if e.reason == "draining"
                            else "shed", "req": req, "reason": e.reason,
                            "retry_after_s": e.retry_after_s}
        except QueueDeadlineError as e:
            self.metrics.count(endpoint, "deadline")
            if obs.active():
                obs.event("serve", "deadline", req=req, endpoint=endpoint,
                          where="queued")
            return 504, {"status": "deadline", "req": req, "error": str(e)}
        self.metrics.count(endpoint, "accepted")
        self.metrics.set_load(self.admission.inflight, self.admission.queued)
        token = cancellation.CancelToken()
        queued_s = time.perf_counter() - t0  # vctpu-lint: disable=VCT006 — serve request-latency metric
        remaining = None if deadline_s is None \
            else max(0.1, deadline_s - queued_s)
        self._register_deadline(req, remaining, token)
        if obs.active():
            obs.event("serve", "request_start", req=req, endpoint=endpoint,
                      queued_s=round(queued_s, 6),
                      deadline_s=deadline_s or 0)
        try:
            code, payload = self._execute_isolated(endpoint, body, req, token)
        finally:
            self._unregister_deadline(req)
            release()
            self.metrics.set_load(self.admission.inflight,
                                  self.admission.queued)
        dur = time.perf_counter() - t0  # vctpu-lint: disable=VCT006 — serve request-latency metric
        self.metrics.observe_latency(endpoint, dur)
        # terminal counter from the payload's own status so every
        # documented family (metrics.STATUSES) is actually recorded —
        # a drain-cancelled request counts as 'cancelled', not 'failed'
        outcome = payload.get("status")
        self.metrics.count(
            endpoint, outcome if outcome in ("ok", "deadline", "cancelled")
            else "failed")
        payload.setdefault("req", req)
        payload["dur_s"] = round(dur, 6)
        if obs.active():
            obs.event("serve", "request_end", req=req, endpoint=endpoint,
                      status=payload.get("status"), code=code,
                      dur=round(dur, 6))
        return code, payload

    def _execute_isolated(self, endpoint: str, body: dict, req: str,
                          token: cancellation.CancelToken) -> tuple[int, dict]:
        """The per-request isolation envelope: scoped knobs, scoped
        faults, bound cancel token — then the endpoint body. Exceptions
        become per-request responses HERE, so nothing a request does
        propagates into the daemon."""
        overrides = dict(body.get("knobs") or {})
        for name in overrides:
            if name in _UNSCOPABLE:
                return 400, {"status": "config_error",
                             "error": f"knob {name} cannot be scoped "
                                      "per request"}
        try:
            knob_scope = knobs.scope(overrides)
        except KeyError as e:
            return 400, {"status": "config_error", "error": str(e)}
        try:
            with knob_scope, faults.scope(body.get("faults") or ""), \
                    cancellation.scope(token):
                # per-request knob validation: a malformed scoped value
                # is THIS request's configuration error (exit-2 moral
                # equivalent), never a daemon fault
                knobs.validate_all()
                handler = self.ENDPOINTS[endpoint]
                return handler(self, body, req)
        except RequestError as e:
            return 400, {"status": "bad_request", "error": str(e)}
        except EngineError as e:
            return 400, {"status": "config_error", "error": str(e)}
        except cancellation.CancelledError as e:
            reason = token.reason or str(e)
            if "drain" in reason:
                return 503, {"status": "cancelled", "error": reason}
            return 504, {"status": "deadline", "error": reason}
        # the fault-isolation boundary: ANY request failure — poison
        # chunk past its ladder budget, watchdog abort, IO error —
        # becomes this request's error response; the daemon, its warmed
        # state and concurrent requests are untouched (loadhunt proves
        # the byte-level half of that claim)
        except BaseException as e:  # noqa: BLE001  # vctpu-lint: disable=VCT002 — the per-request fault-isolation boundary: reported to the client with kind + recorded in obs, never swallowed into a fallback
            if obs.active():
                obs.event("serve", "request_error", req=req,
                          endpoint=endpoint, error_kind=type(e).__name__,
                          error=str(e)[:500])
            logger.warning("serve: request %s (%s) failed: %s: %s", req,
                           endpoint, type(e).__name__, e)
            return 500, {"status": "error", "kind": type(e).__name__,
                         "error": str(e)[:2000]}

    # -- endpoint bodies ----------------------------------------------------

    def _do_filter(self, body: dict, req: str) -> tuple[int, dict]:
        from variantcalling_tpu.pipelines import filter_variants as fv

        if not body.get("output"):
            raise RequestError("missing required field 'output'")
        with stage("serve_state"):
            args = _filter_namespace(body, output_file=body["output"])
            eng = engine_mod.resolve_request()
            model = self.state.get_model(args.model_file, args.model_name)
            fasta = self.state.get_fasta(args.reference_file)
            annotate = {fv._interval_name(p): _read_intervals(p)
                        for p in args.annotate_intervals}
            blacklist = fv.read_blacklist(args.blacklist) if args.blacklist \
                else None
        rc = fv.run_loaded(args, model, fasta, annotate, blacklist,
                           engine=eng)
        if rc != 0:
            return 500, {"status": "failed", "rc": rc}
        return 200, {"status": "ok", "output": args.output_file,
                     "engine": eng.name}

    def _do_score(self, body: dict, req: str) -> tuple[int, dict]:
        import numpy as np

        from variantcalling_tpu.io.vcf import read_vcf
        from variantcalling_tpu.pipelines import filter_variants as fv

        with stage("serve_state"):
            args = _filter_namespace(body, output_file=None)
            eng = engine_mod.resolve_request()
            model = self.state.get_model(args.model_file, args.model_name)
            fasta = self.state.get_fasta(args.reference_file)
            ctx = fv.FilterContext(model, fasta, flow_order=args.flow_order,
                                   is_mutect=args.is_mutect, engine=eng)
        table = read_vcf(args.input_file)
        cancellation.check("score request")
        score, filters = ctx.score_table(table)
        cancellation.check("score request")
        return 200, {"status": "ok", "n": int(len(table)),
                     "n_pass": int(np.sum(filters.codes == 0)),
                     "engine": eng.name,
                     "score_mean": round(float(np.mean(score)), 6),
                     "score_min": round(float(np.min(score)), 6),
                     "score_max": round(float(np.max(score)), 6)}

    def _do_coverage(self, body: dict, req: str) -> tuple[int, dict]:
        import numpy as np

        from variantcalling_tpu.ops.coverage import host_coverage_stats

        depth = body.get("depth")
        if not isinstance(depth, list) or not depth:
            raise RequestError("field 'depth' must be a non-empty list "
                               "of ints")
        window = int(body.get("window", 100))
        if window <= 0:
            raise RequestError("field 'window' must be positive")
        stats = host_coverage_stats(
            np.asarray(depth, dtype=np.int32), window,
            qs=np.asarray([0.05, 0.5, 0.95], dtype=np.float32))
        return 200, {
            "status": "ok", "n": len(depth), "window": window,
            "windows": int(len(stats["means"])),
            "mean": round(float(np.mean(stats["means"])), 6),
            "percentiles": {"p5": int(stats["percentiles"][0]),
                            "p50": int(stats["percentiles"][1]),
                            "p95": int(stats["percentiles"][2])}}

    def _do_warm(self, body: dict, req: str) -> tuple[int, dict]:
        """Make what the body names resident, and say what now is: a
        ``reference`` as host reader, ``.venc`` sidecar and — where
        requests score on the jit engine — the genome on the device, under
        the key ``featurize._genome_resident_worthwhile`` looks up, so a
        request of any size then gathers its windows there; a ``model``
        as host object and as the fused program a plain request on that
        reference asks for (its executables still compile or load at each
        bucket size's first call)."""
        from variantcalling_tpu import featurize
        from variantcalling_tpu.pipelines import filter_variants as fv

        for field in ("model", "reference"):
            if body.get(field) and not os.path.exists(body[field]):
                raise RequestError(f"{field} path does not exist: "
                                   f"{body[field]}")
        eng = engine_mod.resolve_request()
        warmed = []
        fasta = None
        if body.get("reference"):
            fasta = self.state.get_fasta(body["reference"])
            fasta.encode_all()  # persist/load the .venc sidecar now
            warmed.append("reference")
            if eng.name == "jit" and featurize.genome_packable(fasta):
                featurize.device_genome(
                    fasta, sharding=featurize.standard_genome_sharding())
                warmed.append("device_genome")
        if body.get("model") and body.get("model_name"):
            model = self.state.get_model(body["model"], body["model_name"])
            warmed.append("model")
            if fv.warm_program(model, fasta, engine=eng,
                               flow_order=body.get("flow_order", "TGCA")):
                warmed.append("program")
        if not warmed:
            raise RequestError("nothing to warm: pass model+model_name "
                               "and/or reference")
        return 200, {"status": "ok", "warmed": warmed,
                     "resident": self.resident_payload()}

    def resident_payload(self) -> dict:
        """What the daemon holds: host models and readers
        (``state.stats()``) and the genomes on the device."""
        resident = self.state.stats()
        self.metrics.set_gauge("serve.device_genomes",
                               resident["device_genomes"]["entries"])
        return resident

    # -- introspection payloads --------------------------------------------

    def status_payload(self) -> dict:
        per_endpoint = {}
        for ep in sorted(self.ENDPOINTS):
            p50, p99 = self.metrics.rolling_p50(ep), self.metrics.rolling_p99(ep)
            if p50 is not None or p99 is not None:
                per_endpoint[ep] = {
                    "rolling_p50_s": round(p50, 6) if p50 else None,
                    "rolling_p99_s": round(p99, 6) if p99 else None}
        return {
            "status": "draining" if self.draining.is_set() else "ok",
            "uptime_s": round(time.monotonic() - self._started, 1),
            "address": self.address,
            "in_flight": self.admission.inflight,
            "queued": self.admission.queued,
            "max_inflight": self.admission.max_inflight,
            "queue_depth": self.admission.queue_depth,
            "endpoints": per_endpoint,
            "resident": self.resident_payload(),
            "cache": _chunk_cache_stats(),
        }

    def metrics_payload(self) -> str:
        from variantcalling_tpu.obs import prom

        return prom.snapshot_to_prom(self.metrics.snapshot(), tool="serve",
                                     in_flight=not self.draining.is_set())


def _chunk_cache_stats() -> dict:
    from variantcalling_tpu.io import chunk_cache

    return chunk_cache.resident_stats()


def _read_intervals(path: str):
    from variantcalling_tpu.io import bed as bedio

    return bedio.read_intervals(path)


#: endpoint name -> bound method (the pipeline endpoints admission
#: guards; GET endpoints bypass admission — they must answer under
#: overload, that is their job)
_ENDPOINTS = {
    "filter": Server._do_filter,
    "score": Server._do_score,
    "coverage": Server._do_coverage,
    "warm": Server._do_warm,
}
Server.ENDPOINTS = _ENDPOINTS


# -- transport --------------------------------------------------------------

_HANDLER_N = itertools.count()


class _NamedThreadingHTTPServer(ThreadingHTTPServer):
    daemon_threads = True
    allow_reuse_address = True

    def process_request(self, request, client_address):
        """ThreadingMixIn.process_request with NAMED daemon threads
        (``vctpu-serve-h<N>``) so the leak sentinel and the obs
        thread-family attribution cover handler threads."""
        t = threading.Thread(target=self.process_request_thread,
                             args=(request, client_address),
                             name=f"vctpu-serve-h{next(_HANDLER_N)}",
                             daemon=True)
        t.start()


class _UnixHTTPServer(_NamedThreadingHTTPServer):
    """HTTP over an AF_UNIX socket (``VCTPU_SERVE_SOCKET``)."""

    address_family = socket.AF_UNIX

    def __init__(self, path: str, handler):
        super().__init__(path, handler, bind_and_activate=True)

    def server_bind(self):
        # HTTPServer.server_bind unpacks (host, port) — meaningless for
        # a filesystem address; bind directly and pin the name fields
        self.socket.bind(self.server_address)
        self.server_name = "unix"
        self.server_port = 0

    def get_request(self):
        request, _ = self.socket.accept()
        return request, ("unix", 0)


def _make_handler(server: Server):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        #: socket timeout: an idle keep-alive connection (or a client
        #: that sent half a request and walked away) releases its
        #: handler thread instead of pinning it forever
        timeout = 60
        #: argparse-free routing table: path -> endpoint name
        _POST_ROUTES = {f"/v1/{name}": name for name in server.ENDPOINTS}

        def log_message(self, fmt, *args):  # quiet: obs carries the events
            logger.debug("serve http: " + fmt, *args)

        def address_string(self):  # AF_UNIX: client_address is not a pair
            try:
                return super().address_string()
            except (TypeError, IndexError):
                return "unix"

        def _respond(self, code: int, payload: dict,
                     retry_after_s: float | None = None) -> None:
            data = (json.dumps(payload) + "\n").encode()
            try:
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                if retry_after_s is not None:
                    self.send_header("Retry-After",
                                     str(max(1, int(retry_after_s))))
                self.end_headers()
                self.wfile.write(data)
            except (BrokenPipeError, ConnectionResetError, OSError):
                # mid-request client disconnect: the work (if any) is
                # already done and committed/failed server-side; account
                # it and move on — the daemon never dies for a client
                server.metrics.registry.counter("serve.disconnects").add(1)
                obs.counter("serve.disconnects").add(1)
                logger.info("serve: client went away before the response")

        def _respond_text(self, code: int, text: str,
                          content_type: str) -> None:
            data = text.encode()
            try:
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)
            except (BrokenPipeError, ConnectionResetError, OSError):
                pass

        def do_GET(self):
            if self.path in ("/healthz", "/v1/healthz"):
                self._respond(200, {
                    "status": "draining" if server.draining.is_set()
                    else "ok"})
            elif self.path == "/v1/status":
                self._respond(200, server.status_payload())
            elif self.path == "/v1/metrics":
                self._respond_text(
                    200, server.metrics_payload(),
                    "text/plain; version=0.0.4; charset=utf-8")
            else:
                self._respond(404, {"status": "not_found",
                                    "error": f"unknown path {self.path}"})

        def do_POST(self):
            stream = server.STREAM_ROUTES.get(self.path)
            if stream is not None:
                # a streaming endpoint owns its whole transport exchange
                # (chunked upload in, chunked artifact out) — same
                # belt-and-braces rule: a serve-layer bug still answers
                try:
                    getattr(server, stream)(self)
                except BaseException as e:  # noqa: BLE001  # vctpu-lint: disable=VCT002 — transport-level last resort: reported to the client as a 500, logged; never silent
                    logger.warning("serve: internal error handling %s: "
                                   "%s: %s", self.path,
                                   type(e).__name__, e)
                    self._respond(500, {"status": "error",
                                        "kind": type(e).__name__,
                                        "error": str(e)[:2000]})
                return
            endpoint = self._POST_ROUTES.get(self.path)
            if endpoint is None:
                self._respond(404, {"status": "not_found",
                                    "error": f"unknown path {self.path}"})
                return
            try:
                length = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(length) or b"{}")
                if not isinstance(body, dict):
                    raise ValueError("request body must be a JSON object")
            except (ValueError, OSError) as e:
                self._respond(400, {"status": "bad_request",
                                    "error": f"malformed request: {e}"})
                return
            sent = []

            def respond(code: int, payload: dict) -> None:
                sent.append(code)
                self._respond(code, payload,
                              retry_after_s=payload.get("retry_after_s"))

            try:
                server.execute(endpoint, body, respond=respond)
            # belt and braces under the isolation boundary: a bug in the
            # serve layer itself must still produce a response — a
            # handler thread dying silently leaves the client hanging,
            # which is exactly the failure loadhunt's shed-not-hang
            # invariant exists to catch
            except BaseException as e:  # noqa: BLE001  # vctpu-lint: disable=VCT002 — transport-level last resort: reported to the client as a 500, logged; never silent
                logger.warning("serve: internal error handling %s: %s: %s",
                               endpoint, type(e).__name__, e)
                if not sent:
                    respond(500, {"status": "error",
                                  "kind": type(e).__name__,
                                  "error": str(e)[:2000]})

    return Handler
