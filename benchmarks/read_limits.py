#!/usr/bin/env python3
"""Reads the numbers that ``correct`` compares over many seeds in one process,
for setting a configuration's limits. Not part of a measured run.

    python3 benchmarks/read_limits.py --workload <cell> --seeds 1,2,3,4 \\
        [--weights-seeds 7,21] [--control bf16] [--seconds 8] [--env KEY=VALUE]

The set-up of ``run_cell.py`` is paid once; every further seed costs its
callsets, the driver's warm-up and a short window. ``--weights-seeds`` deals
the seeds out over several draws of the weights (each a new compile: its
warm-up time is the cost of drawing weights from ``--seed``). ``--control``
also reads the plain reference in that lower precision in the program's
place. ``--env`` sets a variable after the configuration's own ``env``, to
read the program under another setting. One line per seed goes to stderr, and
the table to ``chiprun_out/benchmarks/limits.<cell>.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import run_cell
from run_cell import OUT_DIR, Run, Spec, say


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--weights-seeds", default="")
    ap.add_argument("--control", default=None, choices=("bf16", "int8"))
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--env", action="append", default=[], metavar="KEY=VALUE")
    ap.add_argument("--tag", default="", help="suffix of the table's file name")
    ap.add_argument("--rehearse", type=int, default=0, metavar="DIVISOR")
    args = ap.parse_args(argv)
    spec = Spec(args.workload)
    run_cell.prepare_env(spec, trace=False)
    os.environ.update(kv.split("=", 1) for kv in args.env)

    import jax

    if args.rehearse:
        if jax.devices()[0].platform != "tpu":
            os.environ.setdefault("VCTPU_ENGINE", "jit")
    elif jax.devices()[0].platform != "tpu":
        say("no TPU: nothing was run")
        return run_cell.EXIT_NO_TPU
    seeds = [int(x) for x in args.seeds.split(",")]
    wseeds = [int(x) for x in args.weights_seeds.split(",") if x] \
        or [spec.config["weights_seed"]]
    plan = [(w, s) for k, w in enumerate(wseeds) for s in seeds[k::len(wseeds)]]
    run = Run(spec, plan[0][1], args.seconds, trace=False,
              divisor=max(1, args.rehearse), control=args.control)
    run.config = dict(run.config, weights_seed=plan[0][0])
    table = []
    for k, (w, s) in enumerate(plan):
        t0 = time.perf_counter()
        if k == 0:
            run.set_up()
            run.setup_s = time.perf_counter() - run_cell.T_START
        else:
            run.new_seed(s)
            if w != run.weights_seed:
                run.new_weights(w)
            run.driver.warm_up(run)
        warm_s = time.perf_counter() - t0
        run.driver.window(run)
        result = run.finish()
        run.report(result)
        row = {"weights_seed": w, "seed": s, "prepare_and_warm_up_s": warm_s,
               "correct": result["correct"], "files": len(run.files),
               "compiles_in_window": sum(
                   f["events"].get(run_cell.MISS_EVENT, 0) for f in run.details["files"]),
               "file_walls": [f["wall_s"] for f in run.details["files"]],
               **{k2: v["value"] for k2, v in result["compared"].items()}}
        if args.control:
            row["control_score_gap_max"] = min(
                r["control"]["score_gap_max"] for r in run.details["check"])
            row["control_filter_wrong"] = min(
                r["control"]["filter_wrong"] for r in run.details["check"])
        table.append(row)
        say("limits " + json.dumps(row))
    os.makedirs(OUT_DIR, exist_ok=True)
    name = f"limits.{spec.cell['name']}{args.tag}.json"
    with open(os.path.join(OUT_DIR, name), "w", encoding="utf-8") as fh:
        json.dump({"env": args.env, "control": args.control, "rows": table}, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
