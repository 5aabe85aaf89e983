"""Tier-0 obs schema gate: generate a real obs run log and validate it
against the COMMITTED event schema (run by run_tests.sh before pytest).

The contract this guards: the schema artifact
(``variantcalling_tpu/obs/event_schema.json``) and the event writer
(``variantcalling_tpu/obs``) must never drift apart — an event the
writer emits that the committed schema rejects fails the whole test run
before pytest even starts, exactly like a lint finding. The generated
log exercises every producer wired into the stream (manifest, trace
spans incl. a worker thread, degradations, fault firings, metrics,
heartbeat, run end) and the Perfetto exporter's invariants (sorted ts,
ph/pid/tid on every trace event).

The audit is BIDIRECTIONAL: besides validating the generated stream,
:func:`static_kind_audit` walks the writer sources and fails on schema
kinds no code ever emits (dead schema surface the validator can never
exercise) and on emission sites whose kind is not a string literal
(invisible to both this audit and vctpu-lint's VCT007) outside the one
sanctioned ``obs.event`` forwarder.

Exit codes: 0 valid, 1 schema violations (printed), 2 internal error.
"""

from __future__ import annotations

import os
import sys
import tempfile
import threading
import time

#: emission sites allowed to pass a NON-LITERAL kind: the public
#: ``obs.event(kind, name, **fields)`` forwarder re-emits its caller's
#: kind verbatim — every other site must name its kind literally so the
#: static audit (and VCT007) can see it
_KIND_FORWARDERS = ("variantcalling_tpu/obs/__init__.py",)


def static_kind_audit(repo_root: str | None = None) -> list[str]:
    """The writer-side half of the schema gate, statically.

    Walks every ``.py`` under ``variantcalling_tpu/`` and ``tools/``
    (tests excluded — they emit deliberately-bogus kinds), collects the
    string-literal kinds passed to ``obs.event(...)`` / ``*._emit(...)``,
    and returns one error per (a) schema kind with no literal emission
    site anywhere — dead schema surface the generated-log validation can
    never exercise — and (b) emission site whose kind expression is not
    a string literal outside :data:`_KIND_FORWARDERS`. Complements
    VCT007, which checks the opposite direction (literal kind missing
    from the schema).
    """
    import ast
    import json

    root = repo_root or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    schema_path = os.path.join(
        root, "variantcalling_tpu", "obs", "event_schema.json")
    try:
        with open(schema_path, encoding="utf-8") as fh:
            kinds = set(json.load(fh)["kinds"])
    except (OSError, ValueError, KeyError) as e:
        return [f"static audit: cannot load event schema: {e}"]
    emitted: set[str] = set()
    errors: list[str] = []
    for top in ("variantcalling_tpu", "tools"):
        for dirpath, dirnames, files in os.walk(os.path.join(root, top)):
            dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
            for fname in sorted(files):
                if not fname.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fname)
                rel = os.path.relpath(path, root).replace(os.sep, "/")
                with open(path, encoding="utf-8") as fh:
                    src = fh.read()
                try:
                    tree = ast.parse(src, filename=rel)
                except SyntaxError:
                    continue  # the lint stage owns syntax findings
                for node in ast.walk(tree):
                    if not isinstance(node, ast.Call) or not node.args:
                        continue
                    func = node.func
                    is_emit = isinstance(func, ast.Attribute) and (
                        func.attr == "_emit"
                        or (func.attr == "event"
                            and isinstance(func.value, ast.Name)
                            and func.value.id == "obs"))
                    if not is_emit:
                        continue
                    arg = node.args[0]
                    if isinstance(arg, ast.Constant) \
                            and isinstance(arg.value, str):
                        emitted.add(arg.value)
                    elif rel not in _KIND_FORWARDERS:
                        errors.append(
                            f"{rel}:{node.lineno}: non-literal event kind "
                            "at an emission site — pass the kind as a "
                            "string literal so the schema<->writer audit "
                            "can see it (only the obs.event forwarder is "
                            "exempt)")
    for kind in sorted(kinds - emitted):
        errors.append(
            f"schema kind {kind!r} has no literal emission site under "
            "variantcalling_tpu/ or tools/ — dead schema surface: emit "
            "it or prune it from event_schema.json")
    return errors


def main() -> int:
    from variantcalling_tpu import obs
    from variantcalling_tpu.obs import export, schema
    from variantcalling_tpu.utils import degrade, faults, trace

    with tempfile.TemporaryDirectory(prefix="obs_schema_check_") as d:
        path = os.path.join(d, "run.jsonl")
        run = obs.start_run("obs_schema_check", force_path=path,
                            argv=["--tier0"], inputs={"self": __file__})
        if run is None:
            print("obs_schema_check: start_run returned None", file=sys.stderr)
            return 2
        # one of every producer the stream unifies
        with trace.stage("outer"):
            with trace.stage("inner"):
                pass
        def _worker_span():
            with trace.stage("worker-span"):
                pass

        worker = threading.Thread(target=_worker_span, daemon=True)
        worker.start()
        worker.join(timeout=10)
        degrade.record("obs.schema_check_probe", ValueError("expected"),
                       fallback="continue")
        faults.arm("io.chunk_read", times=1)
        try:
            faults.check("io.chunk_read")
        except OSError:
            pass
        finally:
            faults.reset()
        obs.counter("records").add(128)
        obs.gauge("queue.stage0.depth").set(2)
        obs.histogram("chunk.records").observe(128)
        obs.event("heartbeat", "stream", chunks=1, records=128, vps=1000,
                  pct=50.0, eta_s=1.0)
        obs.event("journal", "resume_decision", outcome="fresh")
        obs.counter("cache.hit").add(3)
        obs.counter("cache.miss").add(1)
        obs.counter("cache.bytes_saved").add(4096)
        obs.event("cache", "session", hits=3, misses=1, bytes_saved=4096,
                  published=1)
        # elastic pod membership transitions (parallel/elastic.py)
        obs.event("membership", "[0,1024)", action="join", gen=0, pid=1234)
        obs.event("membership", "[0,1024)", action="steal", gen=0,
                  done_bytes=512, rate=10.0, median=100.0)
        obs.event("membership", "[0,512)", action="recut", at=512,
                  adopted_chunks=2)
        # obs v2 profile producers (attribution events + bottleneck surface)
        obs.event("profile", "stage", stage="score_stage", work_s=0.5,
                  wait_in_s=0.1, wait_out_s=0.0, items=1, records=128)
        obs.event("profile", "pipeline", wall_s=0.6, records=128,
                  stages=["score_stage"], bytes_in=1024, bytes_out=2048)
        # the span primitive's full record: a stage inside a stage under
        # a chunk's trace scope with a StageProfiler bound — explicit
        # start, parent, trace_id, and the attribution row with its parent
        from variantcalling_tpu.obs import profile as profile_mod

        prof = profile_mod.StageProfiler()
        with obs.bind_profiler(prof), obs.trace_scope("t999"):
            with trace.stage("score_stage", records=128):
                with trace.stage("dispatch_wait"):
                    pass
            with trace.stage("render_stage", records=128):
                pass
        prof.emit(wall_s=0.01, records=128)
        # one backend compile seen by the jax.monitoring listener
        obs._on_jax_duration(obs.JAX_BACKEND_COMPILE_EVENT, 0.25,
                             fun_name="schema_check_probe")
        # causal-tracing producers (the live-telemetry plane): one chunk
        # DAG — ingest root, a fan-in score dispatch, the sequenced
        # commit — plus a recovery event carrying the trace linkage and
        # an in-run periodic metrics snapshot (kind=snapshot)
        errors_pre: list[str] = []
        tid = obs.new_trace()
        if tid is None:
            errors_pre.append("tracing inactive under force_path "
                              "(VCTPU_OBS_TRACE default must be on)")
        else:
            root = obs.trace_span(tid, "ingest", 0.01, records=128)
            obs.trace_span(tid, "score_stage", 0.5, parents=[root],
                           traces=[tid], chunks=1, rows=128)
            with obs.trace_scope(tid):
                obs.event("recovery", "chunk_retry", what="score_stage",
                          attempt=1, retries=1, chunk=0,
                          trace_id=obs.current_trace(), error="X: injected")
            obs.trace_span(tid, "writeback", 0.02, chunk=0, bytes_out=2048)
            obs.end_trace(tid)
        run._last_snapshot -= 1e9  # open the throttle: snapshot NOW
        if run._snapshot_s <= 0:
            run._snapshot_s = 10.0
        run._maybe_snapshot()
        # obs v3 continuous-profiler producer: a real (brief, high-Hz)
        # sampling window over this process, so the generated stream
        # carries genuine `sample` events + the cpuprof summary — the
        # schema and every flame/ledger reader validate against the
        # writer, not a synthetic imitation of it
        from variantcalling_tpu.obs import sampler as sampler_mod

        import zlib

        cpu_sampler = sampler_mod.CpuSampler(run, hz=200.0)
        cpu_sampler.start()
        # GIL-RELEASING busy work (zlib, like the real BGZF engine): a
        # pure-Python spin would hold the GIL and starve the sampler
        # thread of the very samples this stage asserts. Spin until an
        # on-CPU sample landed (bounded) — deterministic on any host.
        t_spin = time.perf_counter()
        payload = os.urandom(1 << 18)
        with sampler_mod.native_span("schema_check_probe"):
            while cpu_sampler.cpu_samples == 0 \
                    and time.perf_counter() - t_spin < 5.0:
                zlib.compress(payload, 6)
        cpu_sampler.stop()
        obs.end_run(run, "ok")

        with open(path, encoding="utf-8") as fh:
            lines = fh.read().splitlines()
        errors = static_kind_audit() + errors_pre \
            + schema.validate_lines(lines)
        # the stream must actually contain every producer's kind — a
        # silently-dropped event class would otherwise "validate"
        import json

        parsed = [json.loads(ln) for ln in lines]
        kinds = {e["kind"] for e in parsed}
        for required in ("manifest", "span", "degrade", "fault", "heartbeat",
                         "journal", "cache", "membership", "profile", "trace",
                         "snapshot", "sample", "recovery", "metrics",
                         "run_end"):
            if required not in kinds:
                errors.append(f"stream is missing a {required!r} event")
        # the primitive's span: start/parent/trace_id on the event, the
        # parent on the attribution row, the compile event's fields
        inner = [e for e in parsed if e["kind"] == "span"
                 and e["name"] == "dispatch_wait"]
        if not inner or inner[0].get("parent") != "score_stage" \
                or inner[0].get("trace_id") != "t999" \
                or "start" not in inner[0]:
            errors.append("the nested trace.stage span lacks start / "
                          f"parent / trace_id: {inner}")
        # ... with its layer (obs.layers.LAYER_OF) and, on a span named in
        # CPU_SPANS, its thread's on-CPU seconds
        render = [e for e in parsed if e["kind"] == "span"
                  and e["name"] == "render_stage"]
        if not inner or inner[0].get("layer") != "wait" or "cpu" in inner[0] \
                or not render or render[0].get("layer") != "render" \
                or not 0 <= render[0].get("cpu", -1) <= render[0]["dur"] + 0.01:
            errors.append("a trace.stage span lacks its layer, or a render "
                          "span an on-CPU time within its wall, or a wait "
                          f"span carries one: {inner} {render}")
        rows = {e.get("stage"): e for e in parsed if e["kind"] == "profile"
                and e["name"] == "stage"}
        if rows.get("dispatch_wait", {}).get("parent") != "score_stage":
            errors.append("the child span's profile/stage row names no "
                          "parent")
        if rows.get("dispatch_wait", {}).get("layer") != "wait" \
                or "cpu_s" not in rows.get("render_stage", {}):
            errors.append("the span's profile/stage row lacks layer / cpu_s")
        own = [e["name"] for e in parsed if e["kind"] == "span"
               and e.get("layer") == "tracing"]
        if own != ["obs_open", "obs_close"]:
            errors.append("the stream's own open and close are not the "
                          f"spans obs_open, obs_close: {own}")
        compiles = [e for e in parsed if e["kind"] == "profile"
                    and e["name"] == "backend_compile"]
        if not compiles or not {"span", "thread", "dur"} <= set(compiles[0]):
            errors.append("no profile/backend_compile event with span / "
                          "thread / dur")
        # causal-trace integrity: the recovery event's trace_id must
        # resolve to emitted trace spans, the fan-in span must list its
        # member trace and parent, and the rolling-window quantiles must
        # ride every histogram snapshot (live-plane contract)
        trace_evs = [e for e in parsed if e["kind"] == "trace"]
        span_ids = {e.get("span_id") for e in trace_evs}
        for e in parsed:
            if e["kind"] == "recovery" and "trace_id" in e:
                if not any(t.get("trace_id") == e["trace_id"]
                           for t in trace_evs):
                    errors.append(f"recovery event trace_id {e['trace_id']!r}"
                                  " resolves to no trace span")
        for e in trace_evs:
            for parent in e.get("parents", ()):
                if parent not in span_ids:
                    errors.append(f"trace span {e.get('span_id')!r} parent "
                                  f"{parent!r} is not an emitted span")
        fanin = [e for e in trace_evs if e.get("traces")]
        if not fanin:
            errors.append("no fan-in trace span (traces field) in the "
                          "generated stream")
        snap_evs = [e for e in parsed if e["kind"] == "snapshot"]
        for e in snap_evs:
            for hname, snap in (e.get("histograms") or {}).items():
                if "rolling" not in snap:
                    errors.append(f"snapshot histogram {hname!r} lacks the "
                                  "rolling-window block")
        # histogram snapshots must carry the SLO percentiles (obs v2)
        metrics_ev = [e for e in parsed if e["kind"] == "metrics"]
        hists = metrics_ev[-1]["histograms"] if metrics_ev else {}
        for hname, snap in hists.items():
            missing_pcts = {"p50", "p95", "p99"} - set(snap)
            if missing_pcts:
                errors.append(f"histogram {hname!r} snapshot missing "
                              f"{sorted(missing_pcts)}")
        threads = {e.get("thread") for e in parsed if e["kind"] == "span"}
        if len(threads) < 2:
            errors.append("spans from a worker thread did not land in the "
                          f"stream (threads seen: {sorted(threads)})")
        # continuous-profiler integrity (obs v3): the sampled window must
        # have produced on-CPU samples, the cpuprof summary must follow
        # the samples, and the flame/ledger readers must stand up on the
        # generated stream (speedscope frame indices in range, ledger
        # totals consistent with the sample fold)
        sample_evs = [e for e in parsed if e["kind"] == "sample"]
        if not any(e.get("cat") in ("gil", "native") for e in sample_evs):
            errors.append("sampling window produced no on-CPU sample "
                          "(cat gil/native) despite a busy spin")
        if not any(e["kind"] == "profile" and e["name"] == "cpuprof"
                   for e in parsed):
            errors.append("no profile/cpuprof summary event after sampling")
        from variantcalling_tpu.obs import sampler as sampler_reader

        scope = sampler_reader.to_speedscope(parsed)
        if scope is None:
            errors.append("to_speedscope returned None on a sampled stream")
        else:
            n_frames = len(scope["shared"]["frames"])
            for prof in scope["profiles"]:
                if len(prof["samples"]) != len(prof["weights"]):
                    errors.append("speedscope samples/weights length "
                                  "mismatch")
                for stack in prof["samples"]:
                    if any(i >= n_frames for i in stack):
                        errors.append("speedscope frame index out of range")
                        break
        ledger = sampler_reader.cpuledger(parsed)
        if ledger is None:
            errors.append("cpuledger returned None on a sampled stream")
        elif ledger["cpu_samples"] <= 0:
            errors.append("cpuledger counted no CPU samples")

        # exporter invariants (the acceptance-criteria Perfetto schema)
        events = export.read_events(path)
        trace_json = export.to_chrome_trace(events)
        ts = [e["ts"] for e in trace_json["traceEvents"]]
        if ts != sorted(ts):
            errors.append("exported trace ts not monotonically sorted")
        for e in trace_json["traceEvents"]:
            missing = {"ph", "pid", "tid", "ts"} - set(e)
            if missing:
                errors.append(f"trace event missing {sorted(missing)}: {e}")
                break
        export.summarize(events)  # must not raise on a fresh log
        b = export.bottleneck(events)  # nor the obs v2 roll-up
        if b.get("limiting_stage") != "score_stage":
            errors.append("bottleneck roll-up did not name the profiled "
                          f"stage (got {b.get('limiting_stage')!r})")
        # the critical-path engine must walk the generated chunk DAG and
        # name the seeded dominant edge (score_stage.work, dur 0.5)
        from variantcalling_tpu.obs import critical

        cp = critical.critical_path(events)
        if cp.get("chunks") != 1:
            errors.append(f"critical-path found {cp.get('chunks')} chunk "
                          "trace(s), expected 1")
        elif cp.get("dominant_p95_edge") != "score_stage.work":
            errors.append("critical-path dominant edge is "
                          f"{cp.get('dominant_p95_edge')!r}, expected "
                          "'score_stage.work'")

    if errors:
        for err in errors:
            print(f"obs_schema_check: {err}", file=sys.stderr)
        print(f"obs_schema_check: {len(errors)} violation(s) — the writer "
              "and variantcalling_tpu/obs/event_schema.json have drifted",
              file=sys.stderr)
        return 1
    print("obs_schema_check: generated log validates against the committed "
          f"schema (v{schema.SCHEMA_VERSION}, {len(lines)} events)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
