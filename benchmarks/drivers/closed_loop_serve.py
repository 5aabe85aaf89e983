"""Traffic driver: closed-loop clients against one resident ``vctpu serve``
daemon (an in-process ``serve.daemon.Server`` on a unix socket, as
``chip_smoke.Smoke.serve_leg`` runs one). Each client is a thread that posts
one request after another through ``serve.transport.request``, its next when
the last is answered, alternating between the configuration's references;
client ``c`` starts on reference ``c`` modulo their number.

``warm_up`` starts the daemon at its defaults (the configuration's ``daemon``
block states them, and a daemon that resolved others ends the run), posts
``/v1/warm`` for the model and each reference, then whole requests per
reference, so that every genome is where the daemon keeps it and every
program of the window is compiled. Any answer but 200 ``ok`` ends the run,
and so does a daemon whose ``/v1/warm`` does not report every reference
resident on the device: that is the deployment the configuration states.

``window`` leaves what ``closed_loop_cli`` leaves: one row per request in
``run.files`` (``rc`` 0 only for a 200 ``ok``, ``wall_s`` the client's clock
from send to response), ``run.window_s`` and, traced, ``run.traced``. The
window runs from the first send until every request sent before
``run.seconds`` have passed is answered. It drains the daemon before it
returns, so the daemon's obs log is whole when the readers open it.

A traced run puts the profiler around each client's first requests
(``trace_requests`` in all). A row is ``traced`` if its request began and
ended inside the traced interval, and the daemon's one obs log is dealt out
to the traced rows' ``<out>.obs.jsonl`` (where ``Run.trace_context`` looks):
an event that carries a request's ``req`` goes to that request's row; one
that carries none (the manifest, the end-of-run snapshot, and every row of a
program that does not mark its events) goes to the first traced row if it
falls inside the traced interval or closes the run. So the readers see the
traced requests' spans and rows and the daemon's final counters, and not the
warm-up's.

It reads from the traffic file: ``endpoint``, ``clients``, ``think_s``,
``warm``, ``warmup_requests_per_reference``, ``trace_requests``.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time

#: a request's or a warm's time limit on the client's side: the first warm
#: of a checkout encodes a 3.1 Gbp genome and uploads it
CLIENT_TIMEOUT_S = 1800.0
#: where a traced run leaves the daemon's whole obs log, for reading by hand
KEPT_LOG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "chiprun_out", "benchmarks", "serve.obs.jsonl")


def post(run, path: str, body: dict) -> tuple[int, dict, float]:
    """-> (HTTP status, JSON payload, seconds from send to response)."""
    from variantcalling_tpu.serve import transport

    t0 = time.perf_counter()
    with transport.request(run.server.address, "POST", path,
                           headers={"Content-Type": "application/json"},
                           body=json.dumps(body).encode(),
                           timeout=CLIENT_TIMEOUT_S) as r:
        return r.status, r.json(), time.perf_counter() - t0


def filter_body(run, ref: dict, out: str) -> dict:
    return {"input": ref["calls"], "model": run.models,
            "model_name": run.config["model_name"], "reference": ref["path"],
            "output": out}


def start_daemon(run):
    from variantcalling_tpu.serve.daemon import Server

    run.obs_log = os.path.join(run.dir, "serve.obs.jsonl") if run.trace else None
    server = Server(socket_path=os.path.join(run.dir, "serve.sock"),
                    obs_log=run.obs_log)
    server.start()
    want = run.config["daemon"]
    got = {"max_inflight": server.admission.max_inflight,
           "queue_depth": server.admission.queue_depth,
           "deadline_s": server.default_deadline_s}
    if any(got[k] != want[k] for k in got):
        server.drain("benchmark")
        raise SystemExit(f"the daemon resolved {got}, the configuration "
                         f"states {want}")
    return server


def warm_up(run) -> None:
    run.server = start_daemon(run)
    try:
        if run.traffic["warm"]:
            for ref in run.refs:
                code, payload, wall = post(run, "/v1/warm", {
                    "model": run.models, "model_name": run.config["model_name"],
                    "reference": ref["path"]})
                run.say(f"warm on ref {ref['seed']}: {code} {wall:.1f}s "
                        f"{json.dumps(payload)}")
                if code != 200 or payload.get("status") != "ok":
                    raise SystemExit(f"/v1/warm answered {code}: {payload}")
            # the deployment the configuration states: every reference stays
            # resident on the device. A daemon that cannot bring that about
            # (or say so) cannot run it; what it would time is another
            # deployment, with an empty device
            held = payload.get("resident", {}).get("device_genomes", {}).get("entries")
            if held != len(run.refs):
                raise SystemExit(
                    f"after /v1/warm the daemon reports {held} of {len(run.refs)} "
                    "references resident on the device; the configuration states "
                    "that all stay resident, host and device")
        for _ in range(run.traffic["warmup_requests_per_reference"]):
            for ref in run.refs:
                out = os.path.join(run.dir, "warm.vcf")
                code, payload, wall = post(run, run.traffic["endpoint"],
                                           filter_body(run, ref, out))
                run.say(f"warm-up request on ref {ref['seed']}: {code} {wall:.1f}s "
                        f"compile-cache events {run.compiles.take()}")
                if code != 200 or payload.get("status") != "ok":
                    raise SystemExit(f"warm-up request answered {code}: {payload}")
                os.remove(out)
    except BaseException:
        run.server.drain("benchmark")
        raise


def window(run) -> None:
    import jax

    traffic = run.traffic
    n_clients = traffic["clients"]
    n_trace = traffic["trace_requests"] if run.trace else 0
    per_client = -(-n_trace // n_clients)
    rows: list[dict] = []
    lock = threading.Lock()
    left = [n_clients * per_client]  # first requests not yet answered
    traced_done = threading.Event()
    run.files, run.traced = [], {}
    run.compiles.take()
    if n_trace:
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(run.trace_dir, profiler_options=opts)
        run.traced.update(t0=time.perf_counter(), wall0=time.time())
    t_win = time.perf_counter()

    def client(c: int) -> None:
        k = 0
        while time.perf_counter() - t_win < run.seconds or k < per_client:
            ref = run.refs[(c + k) % len(run.refs)]
            with lock:
                i = len(rows)
                row = {"i": i, "ref": ref["seed"], "client": c, "rc": 1,
                       "out": os.path.join(run.dir, f"out_{i}.vcf"),
                       "traced": False, "events": {}}
                rows.append(row)
            row["sent"] = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(
                        f"bench:c{c}r{k}:ref{ref['seed']}"):
                    code, payload, _ = post(run, traffic["endpoint"],
                                            filter_body(run, ref, row["out"]))
                row.update(code=code, status=payload.get("status"),
                           req=payload.get("req"),
                           rc=0 if code == 200 and payload.get("status") == "ok" else 1)
            except (OSError, ValueError) as e:
                # no answer, or none that parses: a failed request of the run
                row["status"] = f"{type(e).__name__}: {e}"
            row["answered"] = time.perf_counter()
            row["wall_s"] = row["answered"] - row["sent"]
            if k < per_client:
                with lock:
                    left[0] -= 1
                    if not left[0]:
                        run.traced.update(t1=row["answered"], wall1=time.time())
                        traced_done.set()
            k += 1
            if traffic["think_s"]:
                time.sleep(traffic["think_s"])

    clients = [threading.Thread(target=client, args=(c,), name=f"bench-client-{c}")
               for c in range(n_clients)]
    for t in clients:
        t.start()
    if n_trace:
        traced_done.wait()
        jax.profiler.stop_trace()
    for t in clients:
        t.join()
    run.window_s = max(r["answered"] for r in rows) - t_win
    rows[-1]["events"] = run.compiles.take()
    run.server.drain("benchmark")
    for r in rows:
        r["traced"] = bool(n_trace) and r["rc"] == 0 \
            and run.traced["t0"] <= r["sent"] and r["answered"] <= run.traced["t1"]
    run.files = rows
    if n_trace:
        deal_out_obs_log(run)


def deal_out_obs_log(run) -> None:
    """The daemon's obs log, dealt out to the traced rows (module docstring)."""
    traced = [r for r in run.files if r["traced"]]
    if not traced or not os.path.exists(run.obs_log):
        return
    by_req = {r["req"]: [] for r in traced}
    rest = by_req[traced[0]["req"]]
    lo, hi = run.traced["wall0"], run.traced["wall1"]
    with open(run.obs_log, encoding="utf-8") as fh:
        for line in fh:
            if not line.strip():
                continue
            event = json.loads(line)
            req = event.get("req")
            if req is not None:
                if req in by_req:
                    by_req[req].append(line)
            elif lo <= event.get("ts", 0.0) <= hi or event.get("kind") in (
                    "manifest", "metrics", "run_end"):
                rest.append(line)
    for r in traced:
        with open(r["out"] + ".obs.jsonl", "w", encoding="utf-8") as fh:
            fh.writelines(by_req[r["req"]])
    try:
        os.makedirs(os.path.dirname(KEPT_LOG), exist_ok=True)
        shutil.copyfile(run.obs_log, KEPT_LOG)
    except OSError:
        pass  # kept for reading by hand; no metric depends on it
