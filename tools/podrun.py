"""podrun — the local rank-partitioned launcher (docs/scaleout.md).

Spawns N worker processes of the flagship filter CLI, each pinned to
one rank of a :class:`~variantcalling_tpu.parallel.rank_plan.RankPlan`
via ``VCTPU_RANK``/``VCTPU_NUM_PROCESSES`` (no coordinator, no
jax.distributed — ranks share nothing but the input file and the final
commit), monitors them, and — when every rank staged its segment —
runs the rank-sequenced committer in-process (the same
``merge_ranks`` the ``vctpu merge-ranks`` CLI exposes).

    python -m tools.podrun --ranks 4 -- \
        --input_file calls.vcf.gz --model_file model.pkl --model_name m \
        --reference_file ref.fa --output_file out.vcf.gz --backend cpu

``--elastic`` switches to the ELASTIC pod (docs/scaleout.md "Elastic
membership"): workers are leased absolute byte spans (``VCTPU_SPAN``)
instead of rank fractions, and the
:class:`~variantcalling_tpu.parallel.elastic.Coordinator` state machine
re-offers a dead worker's span (re-cut at its journal watermark so the
journaled prefix is adopted, not recomputed), steals from stragglers,
grows the pool toward ``--max-ranks`` and sheds under host load. The
merged bytes are identical to the single-rank run whatever the final
span plan looks like.

Exit codes are DISTINCT per failure class, so harnesses (chaoshunt's
``rank_kill``/elastic fault classes) can tell what died:

- ``0``  — every worker completed and the merge committed;
- ``2``  — usage/configuration error (bad flags, no --output_file);
- ``3``  — classic mode only: one or more workers were SIGNAL-killed
  (the merge is SKIPPED: the destination stays untouched; a relaunch
  resumes the killed rank from its journal and skips finished ranks
  via their ``.done`` markers — the elastic coordinator re-assigns
  instead of exiting);
- ``4``  — workers completed but the merge failed;
- ``5``  — the pod timed out (remaining workers terminated);
- ``7``  — elastic mode: a span died more than its attempt budget
  (EXIT_SPAN_FAILED — loud, never a hang);
- else  — the first failing worker's own exit code (e.g. 1/2).
  (Workers themselves exit ``6`` when they lose a span lease race —
  benign, absorbed by the coordinator, never the pod's code.)

A ``<out>.podrun.json`` state file maps workers -> pids while the pod
runs (written atomically; removed on success) — operators and the chaos
harness use it to find a specific worker. Elastic state files carry
``"mode": "elastic"`` and per-worker ``span``/``gen`` instead of ranks.

``--fabric`` launches the SERVING fabric instead of a batch pod
(docs/serving_fabric.md): ``--ranks`` backend daemons (``vctpu serve
--fabric-backend``, each on an ephemeral port) plus one router
(``vctpu serve --fabric``) fronting them, then stays resident until
SIGTERM/SIGINT and drains the fleet router-first. Obs logs land in the
sibling shape ``vctpu obs`` merges into one timeline: the router at
``<base>.obs.jsonl``, backend H at ``<base>.obs.jsonl.backendH``. The
loadhunt ``backend_kill`` campaign uses the importable
:func:`start_fabric`/:func:`stop_fabric` pair directly.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EXIT_USAGE = 2
EXIT_KILLED = 3
EXIT_MERGE = 4
EXIT_TIMEOUT = 5


def state_path(out_path: str) -> str:
    return str(out_path) + ".podrun.json"


def _dump_state(out_path: str, doc: dict) -> None:
    tmp = state_path(out_path) + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, sort_keys=True)
        fh.write("\n")
    os.replace(tmp, state_path(out_path))


def _write_state(out_path: str, ranks: int, procs) -> None:
    _dump_state(out_path, {
        "ranks": ranks,
        "workers": [{"rank": r, "pid": p.pid}
                    for r, p in enumerate(procs)],
        "launcher_pid": os.getpid()})


def _flag_of(fwd: list[str], flag: str) -> str | None:
    for i, a in enumerate(fwd):
        if a == flag:
            return fwd[i + 1] if i + 1 < len(fwd) else None
        if a.startswith(flag + "="):
            return a.split("=", 1)[1]
    return None


def _output_file_of(fwd: list[str]) -> str | None:
    return _flag_of(fwd, "--output_file")


class FabricHandle:
    """A running local serving fabric (``start_fabric``): the router +
    backend processes, their addresses, and the artifact paths."""

    def __init__(self, base: str):
        self.base = base
        self.router = None          # subprocess.Popen
        self.router_address = None
        self.backends: list = []    # subprocess.Popen, 1-based ids
        self.backend_addresses: list[str] = []
        self.logs: list[str] = []


def _wait_ready(ready_file: str, proc, deadline: float, what: str) -> dict:
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"podrun fabric: {what} exited rc={proc.returncode} "
                "before becoming ready")
        try:
            with open(ready_file, encoding="utf-8") as fh:
                return json.load(fh)
        except (OSError, ValueError):
            time.sleep(0.05)
    raise RuntimeError(f"podrun fabric: {what} not ready in time")


def start_fabric(base: str, n_backends: int = 2, timeout: float = 90.0,
                 env: dict | None = None, backend_env: dict | None = None,
                 router_env: dict | None = None,
                 obs_logs: bool = True) -> FabricHandle:
    """Spawn the local serving fabric: ``n_backends`` ``vctpu serve
    --fabric-backend`` daemons on ephemeral ports, then one ``vctpu
    serve --fabric`` router registered over them. Artifacts hang off
    ``base``: ``.backendH.{ready,status,podlog}``, ``.router.*``, and
    the obs sibling shape (router ``<base>.obs.jsonl``, backend H
    ``<base>.obs.jsonl.backendH``) ``vctpu obs`` merges. Raises
    RuntimeError (fleet torn down) if any tier fails to come up."""
    env = dict(os.environ if env is None else env)
    env.setdefault("JAX_PLATFORMS", "cpu")
    h = FabricHandle(base)
    try:
        readies = []
        for i in range(1, n_backends + 1):
            ready = f"{base}.backend{i}.ready"
            for stale in (ready, f"{base}.backend{i}.status"):
                try:
                    os.remove(stale)
                except OSError:
                    pass
            cmd = [sys.executable, "-m", "variantcalling_tpu", "serve",
                   "--fabric-backend", "--port", "0", "--backend", "cpu",
                   "--ready-file", ready,
                   "--status-file", f"{base}.backend{i}.status"]
            if obs_logs:
                cmd += ["--obs-log", f"{base}.obs.jsonl.backend{i}"]
            log = f"{base}.backend{i}.podlog"
            h.logs.append(log)
            fh = open(log, "wb")
            h.backends.append(subprocess.Popen(  # noqa: S603  # vctpu-lint: disable=VCT005 — stop_fabric waits under its own bound
                cmd, env=dict(env, **(backend_env or {})), cwd=REPO,
                stdout=fh, stderr=subprocess.STDOUT))
            fh.close()
            readies.append(ready)
        deadline = time.monotonic() + timeout
        h.backend_addresses = [
            _wait_ready(r, p, deadline, f"backend {i + 1}")["address"]
            for i, (r, p) in enumerate(zip(readies, h.backends))]

        ready = f"{base}.router.ready"
        for stale in (ready, f"{base}.router.status"):
            try:
                os.remove(stale)
            except OSError:
                pass
        cmd = [sys.executable, "-m", "variantcalling_tpu", "serve",
               "--fabric", "--port", "0",
               "--backends", ",".join(h.backend_addresses),
               "--ready-file", ready,
               "--status-file", f"{base}.router.status"]
        if obs_logs:
            cmd += ["--obs-log", f"{base}.obs.jsonl"]
        log = f"{base}.router.podlog"
        h.logs.append(log)
        fh = open(log, "wb")
        h.router = subprocess.Popen(  # noqa: S603  # vctpu-lint: disable=VCT005 — stop_fabric waits under its own bound
            cmd, env=dict(env, **(router_env or {})), cwd=REPO,
            stdout=fh, stderr=subprocess.STDOUT)
        fh.close()
        h.router_address = _wait_ready(
            ready, h.router, time.monotonic() + timeout,
            "router")["address"]
    except Exception:
        stop_fabric(h)
        raise
    return h


def stop_fabric(h: FabricHandle, timeout: float = 45.0) -> dict:
    """Drain the fleet router-first (SIGTERM = graceful drain, exit 0)
    and collect each tier's shutdown report: ``{"router": {...},
    "backends": {id: {...}}}`` with rc + the ``--status-file`` doc
    (leaked-thread sentinel included) when one was written."""
    report: dict = {"router": None, "backends": {}}

    def stop_one(proc, status_file, what):
        if proc is None:
            return None
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=timeout)
        doc = {"rc": proc.returncode}
        try:
            with open(status_file, encoding="utf-8") as fh:
                doc.update(json.load(fh))
        except (OSError, ValueError):
            pass
        return doc

    report["router"] = stop_one(h.router, f"{h.base}.router.status",
                                "router")
    for i, p in enumerate(h.backends, start=1):
        report["backends"][i] = stop_one(p, f"{h.base}.backend{i}.status",
                                         f"backend {i}")
    return report


def _run_fabric(args) -> int:
    import signal

    base = args.base or "fabric"
    try:
        h = start_fabric(base, n_backends=args.ranks, timeout=args.timeout)
    except RuntimeError as e:
        print(str(e), file=sys.stderr)
        return EXIT_USAGE
    _dump_state(base, {
        "mode": "fabric", "router": {"pid": h.router.pid,
                                     "address": h.router_address},
        "workers": [{"backend": i, "pid": p.pid, "address": a}
                    for i, (p, a) in enumerate(
                        zip(h.backends, h.backend_addresses), start=1)],
        "launcher_pid": os.getpid()})
    print(f"podrun: fabric up — router {h.router_address} over "
          f"{args.ranks} backends {h.backend_addresses}", flush=True)

    stop = {"sig": None}

    def _sig(signum, frame):
        stop["sig"] = signum

    signal.signal(signal.SIGTERM, _sig)
    signal.signal(signal.SIGINT, _sig)
    try:
        while stop["sig"] is None:
            if h.router.poll() is not None:
                print("podrun: router exited "
                      f"rc={h.router.returncode}", file=sys.stderr)
                break
            time.sleep(0.2)
    finally:
        report = stop_fabric(h)
        try:
            os.remove(state_path(base))
        except OSError:
            pass
    leaked = [w for w, doc in [("router", report["router"])]
              + [(f"backend{i}", d) for i, d in report["backends"].items()]
              if doc and doc.get("leaked")]
    if leaked:
        print(f"podrun: fabric drain leaked threads in {leaked}",
              file=sys.stderr)
        return 1
    print("podrun: fabric drained", flush=True)
    return 0


def _parse_worker_env(specs: list[str]) -> dict[int, list[tuple[str, str]]]:
    """``IDX:KEY=VAL`` per-worker env overrides (a straggler harness
    slows exactly one initial worker this way; replacement workers
    spawned by the coordinator get NO overrides — slot is None)."""
    out: dict[int, list[tuple[str, str]]] = {}
    for spec in specs:
        try:
            idx, kv = spec.split(":", 1)
            key, val = kv.split("=", 1)
            out.setdefault(int(idx), []).append((key, val))
        except ValueError:
            raise SystemExit(
                f"podrun: bad --worker-env {spec!r} (want IDX:KEY=VAL)")
    return out


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    fwd: list[str] = []
    if "--" in argv:
        split = argv.index("--")
        argv, fwd = argv[:split], argv[split + 1:]
    ap = argparse.ArgumentParser(
        prog="python -m tools.podrun",
        description="spawn N rank-partitioned filter workers + the "
                    "rank-sequenced merge (docs/scaleout.md)")
    ap.add_argument("--ranks", type=int, required=True,
                    help="worker process count (N); elastic pods seed N "
                         "initial spans")
    ap.add_argument("--timeout", type=float, default=3600.0,
                    help="whole-pod wall bound in seconds "
                         "(default %(default)s)")
    ap.add_argument("--no-merge", action="store_true",
                    help="stage the segments only; commit later with "
                         "`vctpu merge-ranks <out>`")
    ap.add_argument("--keep-logs", action="store_true",
                    help="keep per-worker logs even on success")
    ap.add_argument("--elastic", action="store_true",
                    help="elastic membership: leased spans + the "
                         "coordinator state machine (re-assign on death, "
                         "straggler stealing, autoscaling) — "
                         "docs/scaleout.md \"Elastic membership\"")
    ap.add_argument("--min-ranks", type=int, default=1,
                    help="elastic: never shed below this many workers "
                         "(default %(default)s)")
    ap.add_argument("--max-ranks", type=int, default=None,
                    help="elastic: pool growth bound (default: --ranks)")
    ap.add_argument("--steal-factor", type=float, default=4.0,
                    help="elastic: steal when a worker's journal rate "
                         "falls below median/FACTOR (0 disables; "
                         "default %(default)s)")
    ap.add_argument("--grace", type=float, default=1.5,
                    help="elastic: seconds before a worker is eligible "
                         "for stealing (default %(default)s)")
    ap.add_argument("--max-load", type=float, default=None,
                    help="elastic: shed (no new joins, down to "
                         "--min-ranks) while loadavg exceeds this "
                         "(default: no shedding)")
    ap.add_argument("--worker-env", action="append", default=[],
                    metavar="IDX:KEY=VAL",
                    help="extra env for initial worker IDX (repeatable)")
    ap.add_argument("--chaos", choices=("steal_race", "join_during_merge"),
                    default=None,
                    help="elastic fault injection for the chaos harness")
    ap.add_argument("--fabric", action="store_true",
                    help="serving-fabric mode: spawn --ranks backend "
                         "daemons + 1 router and stay resident until "
                         "SIGTERM (docs/serving_fabric.md)")
    ap.add_argument("--base", default=None,
                    help="fabric: artifact base path (ready/status/obs/"
                         "log files hang off it; default ./fabric)")
    args = ap.parse_args(argv)
    if args.ranks <= 0:
        print("podrun: --ranks must be positive", file=sys.stderr)
        return EXIT_USAGE
    if args.fabric:
        if fwd:
            print("podrun: --fabric takes no forwarded CLI arguments "
                  "(clients bring the requests)", file=sys.stderr)
            return EXIT_USAGE
        return _run_fabric(args)
    if not fwd:
        print("podrun: pass the filter CLI arguments after `--`",
              file=sys.stderr)
        return EXIT_USAGE
    out_path = _output_file_of(fwd)
    if not out_path:
        print("podrun: the forwarded arguments must include "
              "--output_file (the merge target)", file=sys.stderr)
        return EXIT_USAGE
    try:
        worker_env = _parse_worker_env(args.worker_env)
    except SystemExit as e:
        print(e, file=sys.stderr)
        return EXIT_USAGE
    if args.elastic:
        return _run_elastic(args, fwd, out_path, worker_env)
    if args.chaos:
        print("podrun: --chaos requires --elastic", file=sys.stderr)
        return EXIT_USAGE

    procs: list[subprocess.Popen] = []
    logs: list[str] = []
    for r in range(args.ranks):
        env = dict(os.environ,
                   VCTPU_RANK=str(r), VCTPU_NUM_PROCESSES=str(args.ranks))
        for k, v in worker_env.get(r, []):
            env[k] = v
        log = f"{out_path}.rank{r}.podlog"
        logs.append(log)
        fh = open(log, "wb")
        procs.append(subprocess.Popen(  # noqa: S603
            [sys.executable, "-m", "variantcalling_tpu",
             "filter_variants_pipeline", *fwd],
            env=env, cwd=REPO, stdout=fh, stderr=subprocess.STDOUT))
        fh.close()  # the child holds the fd; the launcher only re-reads
    _write_state(out_path, args.ranks, procs)
    print(f"podrun: spawned {args.ranks} workers "
          f"(pids {[p.pid for p in procs]}) -> {out_path}", flush=True)

    deadline = time.monotonic() + args.timeout
    timed_out = False
    try:
        while any(p.poll() is None for p in procs):
            if time.monotonic() > deadline:
                timed_out = True
                for p in procs:
                    if p.poll() is None:
                        p.terminate()
                break
            time.sleep(0.05)
        for p in procs:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=30)
    except KeyboardInterrupt:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
        print("podrun: interrupted — workers terminated; segments + "
              "journals kept for resume", file=sys.stderr)
        return 130

    rcs = [p.returncode for p in procs]
    for r, rc in enumerate(rcs):
        if rc != 0:
            tail = b""
            try:
                with open(logs[r], "rb") as fh:
                    tail = fh.read()[-2000:]
            except OSError:
                pass
            print(f"podrun: rank {r} exited rc={rc}\n"
                  f"{tail.decode(errors='replace')}", file=sys.stderr)
    try:
        os.remove(state_path(out_path))
    except OSError:
        pass

    if timed_out:
        print(f"podrun: pod timed out after {args.timeout:.0f}s — "
              "segments + journals kept for resume", file=sys.stderr)
        return EXIT_TIMEOUT
    if any(rc is not None and rc < 0 for rc in rcs):
        # a signal-killed worker: its segment is incomplete, so the merge
        # MUST NOT run — the destination stays untouched-or-previous and
        # a relaunch resumes from the per-rank journals
        print(f"podrun: worker(s) signal-killed (rcs={rcs}) — merge "
              "skipped; relaunch to resume", file=sys.stderr)
        return EXIT_KILLED
    if any(rcs):
        return next(rc for rc in rcs if rc)

    if args.no_merge:
        print(f"podrun: {args.ranks} segments staged (--no-merge); commit "
              f"with `vctpu merge-ranks {out_path}`", flush=True)
    else:
        sys.path.insert(0, REPO)
        from variantcalling_tpu.parallel import rank_plan as rank_plan_mod

        try:
            stats = rank_plan_mod.merge_ranks(out_path, args.ranks)
        except rank_plan_mod.MergeError as e:
            print(f"podrun: merge failed: {e}", file=sys.stderr)
            return EXIT_MERGE
        print(f"podrun: wrote {out_path}: {stats['n']} variants, "
              f"{stats['n_pass']} PASS from {stats['ranks']} ranks",
              flush=True)
    if not args.keep_logs:
        for log in logs:
            try:
                os.remove(log)
            except OSError:
                pass
    return 0


def _run_elastic(args, fwd: list[str], out_path: str,
                 worker_env: dict[int, list[tuple[str, str]]]) -> int:
    """The elastic pod: scan the record region, seed the initial span
    plan, hand the coordinator a real-subprocess spawner, then commit
    the final (possibly re-cut) span plan."""
    sys.path.insert(0, REPO)
    from variantcalling_tpu import obs
    from variantcalling_tpu.io import vcf as vcf_mod
    from variantcalling_tpu.parallel import elastic
    from variantcalling_tpu.parallel import rank_plan as rank_plan_mod

    inp = _flag_of(fwd, "--input_file")
    if not inp:
        print("podrun: --elastic needs --input_file in the forwarded "
              "arguments (the span plan partitions it)", file=sys.stderr)
        return EXIT_USAGE
    try:
        header_end, total = vcf_mod.scan_record_region(inp)
    except Exception as e:  # noqa: BLE001  # vctpu-lint: disable=VCT002 — refuses loudly with exit 2, never continues
        print(f"podrun: cannot span-partition {inp}: {e}", file=sys.stderr)
        return EXIT_USAGE
    spans = elastic.initial_spans(header_end, total, args.ranks)

    run = obs.start_run("podrun", default_path=out_path + ".podrun.obs.jsonl")
    logs: list[str] = []

    def spawn(span, slot):
        env = dict(os.environ, VCTPU_SPAN=elastic.span_env(span))
        # a leased span IS the whole partition spelling — a leaked rank
        # env would make resolve() refuse the ambiguity (exit 2)
        env.pop("VCTPU_RANK", None)
        env.pop("VCTPU_NUM_PROCESSES", None)
        if obs.enabled():
            # one obs stream per worker attempt; the coordinator's own
            # stream holds the membership timeline
            env["VCTPU_OBS_PATH"] = (f"{out_path}.span{span.lo}-{span.hi}"
                                     f".g{span.gen}.obs.jsonl")
        if slot is not None:
            for k, v in worker_env.get(slot, []):
                env[k] = v
        log = f"{out_path}.span{span.lo}-{span.hi}.g{span.gen}.podlog"
        logs.append(log)
        fh = open(log, "ab")
        p = subprocess.Popen(  # noqa: S603  # vctpu-lint: disable=VCT005 — the Coordinator polls/kills under its own deadline
            [sys.executable, "-m", "variantcalling_tpu",
             "filter_variants_pipeline", *fwd],
            env=env, cwd=REPO, stdout=fh, stderr=subprocess.STDOUT)
        fh.close()
        return p

    def on_state(workers):
        _dump_state(out_path, {"mode": "elastic", "ranks": args.ranks,
                               "workers": workers,
                               "launcher_pid": os.getpid()})

    coord = elastic.Coordinator(
        out_path, spans, spawn,
        max_ranks=args.max_ranks if args.max_ranks else args.ranks,
        min_ranks=args.min_ranks, steal_factor=args.steal_factor,
        grace_s=args.grace, timeout_s=args.timeout,
        max_load=args.max_load, chaos=args.chaos, on_state=on_state)
    print(f"podrun: elastic pod, {len(spans)} initial spans "
          f"(max {coord.max_ranks} workers) -> {out_path}", flush=True)
    try:
        rc = coord.run()
    except KeyboardInterrupt:
        obs.end_run(run, status="interrupted")
        print("podrun: interrupted — workers terminated; segments + "
              "journals kept for resume", file=sys.stderr)
        return 130

    if args.chaos == "steal_race":
        print(f"podrun: chaos steal_race: claim_lost={coord.claim_lost}",
              flush=True)
    try:
        os.remove(state_path(out_path))
    except OSError:
        pass
    if rc != 0:
        _print_worker_tails(logs)
        obs.end_run(run, status=f"rc={rc}")
        print(f"podrun: elastic pod failed rc={rc} — segments + journals "
              "kept for resume", file=sys.stderr)
        return rc

    if args.chaos == "join_during_merge":
        if coord.chaos_join_during_merge():
            print("podrun: chaos join_during_merge: join_refused",
                  flush=True)
        else:
            obs.end_run(run, status="chaos_failed")
            print("podrun: chaos join_during_merge: duplicate claimant "
                  "was NOT refused", file=sys.stderr)
            return 1

    if args.no_merge:
        obs.end_run(run)
        print(f"podrun: {len(coord.spans)} span segments staged "
              "(--no-merge)", flush=True)
        return 0
    try:
        stats = elastic.merge_spans(out_path, coord.spans)
    except rank_plan_mod.MergeError as e:
        obs.end_run(run, status="merge_failed")
        print(f"podrun: merge failed: {e}", file=sys.stderr)
        return EXIT_MERGE
    obs.end_run(run)
    print(f"podrun: wrote {out_path}: {stats['n']} variants, "
          f"{stats['n_pass']} PASS from {stats['spans']} spans "
          f"({len(coord.transitions)} membership transitions)", flush=True)
    if not args.keep_logs:
        for log in logs:
            try:
                os.remove(log)
            except OSError:
                pass
    return 0


def _print_worker_tails(logs: list[str]) -> None:
    for log in logs:
        try:
            with open(log, "rb") as fh:
                tail = fh.read()[-1500:]
        except OSError:
            continue
        if tail:
            print(f"podrun: --- {os.path.basename(log)} ---\n"
                  f"{tail.decode(errors='replace')}", file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main())
