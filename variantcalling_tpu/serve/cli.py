"""CLI: ``vctpu serve`` — run the resident daemon in the foreground.

Configuration comes from the ``VCTPU_SERVE_*`` knob registry (port,
socket, admission limits, deadlines, drain budget — ``vctpu knobs``
lists them); the flags here are the deployment conveniences a
supervisor/test harness needs:

- ``--ready-file PATH`` — written (JSON: address, port, pid) AFTER the
  listener is up; harnesses wait on it instead of polling the port.
- ``--status-file PATH`` — written at exit with the shutdown report
  (status, requests served, leaked threads) — the chaoshunt-driver
  convention, so loadhunt can assert the no-leak invariant.
- ``--obs-log PATH`` — force an obs stream for the daemon regardless of
  ``VCTPU_OBS`` (the tier-0/test spelling, like ``force_path``).

SIGTERM/SIGINT trigger the graceful drain (finish in-flight within
``VCTPU_SERVE_DRAIN_S``, refuse new work with 503 ``draining``, flush
obs with status ``drain``) and exit 0 — a drained daemon is a CLEAN
exit, supervisors must not see a crash. Exit 2 on configuration errors
(knob registry contract).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
import time


def get_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="vctpu serve",
        description="fault-isolated resident scoring daemon "
                    "(docs/serving.md)")
    ap.add_argument("--host", default=None,
                    help="bind address (default VCTPU_SERVE_HOST)")
    ap.add_argument("--port", type=int, default=None,
                    help="TCP port, 0 = ephemeral (default "
                         "VCTPU_SERVE_PORT)")
    ap.add_argument("--socket", default=None,
                    help="AF_UNIX socket path (overrides host/port; "
                         "default VCTPU_SERVE_SOCKET)")
    ap.add_argument("--ready-file", default=None,
                    help="write {address, port, pid} JSON once listening")
    ap.add_argument("--status-file", default=None,
                    help="write the shutdown report JSON at exit")
    ap.add_argument("--obs-log", default=None,
                    help="force an obs run stream at this path")
    ap.add_argument("--backend", default=None, choices=["tpu", "cpu"],
                    help="cpu pins JAX to the CPU platform; tpu requires "
                         "a TPU or exits 2; default: whatever JAX "
                         "initializes (serve resolves it once at startup)")
    ap.add_argument("--fabric", action="store_true",
                    help="run the fabric ROUTER tier: the scatter-gather "
                         "front door over the backends named by "
                         "VCTPU_FABRIC_BACKENDS / --backends "
                         "(docs/serving_fabric.md); never touches jax")
    ap.add_argument("--fabric-backend", action="store_true",
                    help="run a fabric BACKEND: the resident daemon plus "
                         "the streaming /v1/segment endpoint the router "
                         "fans spans out to")
    ap.add_argument("--backends", default=None,
                    help="router only: comma-separated backend addresses "
                         "(default VCTPU_FABRIC_BACKENDS)")
    return ap


def _leaked_threads() -> list[str]:
    """Executor/serve threads still alive at shutdown — the loadhunt
    no-leak invariant (the chaoshunt driver convention)."""
    deadline = time.time() + 3.0  # vctpu-lint: disable=VCT006 — bounded shutdown grace wait, not a measurement
    prefixes = ("vctpu-", "pipe-", "genome-prefetch", "obs-sampler")
    while time.time() < deadline:  # vctpu-lint: disable=VCT006 — bounded shutdown grace wait, not a measurement
        leaked = sorted(t.name for t in threading.enumerate()
                        if t.name.startswith(prefixes) and t.is_alive())
        if not leaked:
            return []
        time.sleep(0.05)
    return leaked


def run(argv: list[str]) -> int:
    args = get_parser().parse_args(argv)
    from variantcalling_tpu import knobs, logger
    from variantcalling_tpu.engine import EngineError

    if args.fabric and args.fabric_backend:
        logger.error("--fabric and --fabric-backend are different tiers; "
                     "pick one")
        return 2
    try:
        knobs.validate_all()
    except EngineError as e:
        logger.error("%s", e)
        return 2
    if args.fabric:
        # the router tier is pure placement + transport + splice: no
        # pipeline, no jax — cheap to restart, cheap to front-load
        from variantcalling_tpu.serve.router import Router

        server = Router(host=args.host, port=args.port,
                        socket_path=args.socket, obs_log=args.obs_log,
                        backends=[a.strip() for a in args.backends.split(",")
                                  if a.strip()]
                        if args.backends is not None else None)
    else:
        from variantcalling_tpu import engine

        try:
            engine.pin_backend(args.backend)
        except EngineError as e:
            logger.error("%s", e)
            return 2
        if args.fabric_backend:
            from variantcalling_tpu.serve.backend import Backend as _Cls
        else:
            from variantcalling_tpu.serve.daemon import Server as _Cls
        server = _Cls(host=args.host, port=args.port,
                      socket_path=args.socket, obs_log=args.obs_log)
    # graceful drain on SIGTERM/SIGINT: refuse new work, finish
    # in-flight, flush obs with status "drain", exit 0 — installed
    # BEFORE start() so obs's own flush handlers (which only bind to
    # default dispositions) defer to the daemon's drain
    stop_reason: dict = {}

    def _signal_drain(signum, frame):
        stop_reason["signal"] = signal.Signals(signum).name.lower()
        threading.Thread(target=server.drain,
                         args=(stop_reason["signal"],),
                         name="vctpu-serve-drain", daemon=True).start()

    signal.signal(signal.SIGTERM, _signal_drain)
    signal.signal(signal.SIGINT, _signal_drain)
    server.start()
    if args.ready_file:
        tmp = args.ready_file + ".tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump({"address": server.address, "port": server.port,
                       "pid": os.getpid()}, fh)
        os.replace(tmp, args.ready_file)
    server.stopped.wait()
    if args.status_file:
        snap = server.metrics.snapshot()
        with open(args.status_file, "w", encoding="utf-8") as fh:
            json.dump({"status": "drained",
                       "reason": stop_reason.get("signal", "stopped"),
                       "counters": snap.get("counters", {}),
                       "leaked": _leaked_threads()}, fh)
    return 0


if __name__ == "__main__":
    sys.exit(run(sys.argv[1:]))
