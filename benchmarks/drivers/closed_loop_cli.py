"""Traffic driver: one closed-loop client that calls the program's CLI entry
in-process, one whole file after another, the configuration's references
taking turns; the next file starts when the last is committed.

A driver is found by the ``driver`` key of a traffic file and offers
``warm_up(run)`` and ``window(run)``. ``window`` leaves in ``run.files`` one
row per file started (``i``, ``ref``, ``rc``, ``wall_s``, ``out``, ``traced``,
``events``), in ``run.window_s`` the measured length of the window and, in a
traced run, in ``run.traced`` the clock at both ends of the profiler's trace.
It reads from the traffic file: ``entry``, ``warmup_files_per_reference``,
``trace_files``.
"""

from __future__ import annotations

import os
import shutil
import time


def call_entry(run, ref: dict, out: str) -> tuple[int, float]:
    """``python -m variantcalling_tpu <entry> ...`` with every default as a
    user gets it, in this process (chip_smoke.Smoke.cli)."""
    from variantcalling_tpu.__main__ import main

    t0 = time.perf_counter()
    rc = main([run.traffic["entry"], "--input_file", ref["calls"],
               "--model_file", run.models, "--model_name", run.config["model_name"],
               "--reference_file", ref["path"], "--output_file", out])
    return rc, time.perf_counter() - t0


def warm_up(run) -> None:
    """Whole files per reference, the very callsets of the window: the genome
    goes to the device and every program shape compiles or loads here."""
    for _ in range(run.traffic["warmup_files_per_reference"]):
        for ref in run.refs:
            out = os.path.join(run.dir, "warm.vcf")
            rc, wall = call_entry(run, ref, out)
            run.say(f"warm-up file on ref {ref['seed']}: rc={rc} {wall:.1f}s "
                    f"compile-cache events {run.compiles.take()}")
            if rc != 0:
                raise SystemExit(f"warm-up file exited {rc}")
            for p in (out, out + ".obs.jsonl"):
                if os.path.exists(p):
                    os.remove(p)


def window(run) -> None:
    import jax

    run.files, run.traced = [], {}
    n_trace = run.traffic["trace_files"] if run.trace else 0
    run.compiles.take()
    t_win = time.perf_counter()
    i = 0
    while True:
        ref = run.refs[i % len(run.refs)]
        out = os.path.join(run.dir, f"out_{i}.vcf")
        if n_trace and i == 0:
            shutil.rmtree(run.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(run.trace_dir, profiler_options=opts)
            run.traced["t0"] = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench:file{i}:ref{ref['seed']}"):
            rc, wall = call_entry(run, ref, out)
        run.files.append({"i": i, "ref": ref["seed"], "rc": rc, "wall_s": wall,
                          "out": out, "traced": i < n_trace,
                          "events": run.compiles.take()})
        if n_trace and i == n_trace - 1:
            run.traced["t1"] = time.perf_counter()
            jax.profiler.stop_trace()
        i += 1
        if time.perf_counter() - t_win >= run.seconds and i >= n_trace:
            break
    run.window_s = time.perf_counter() - t_win
