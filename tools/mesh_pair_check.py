#!/usr/bin/env python3
"""The mesh layout's guarantee at a benchmark cell's size: the output's
bytes are the one-device run's but for the one ``##vctpu_mesh=dp=N`` line.

    python3 tools/mesh_pair_check.py --workload <cell> --seed <n>

One process on a host with several chips. It makes the cell's inputs as
``benchmarks/run_cell.py`` does (its references, callsets drawn from
``--seed``, the configuration's weights), then sends each reference's
callset through the cell's entry twice: with every default, as the cell runs
it (the mesh plan takes every local device), and with
``VCTPU_MESH_DEVICES=1``. For each reference it compares the sha256 of the
two outputs once the mesh line is dropped. The summary goes to stdout and to
``chiprun_out/mesh_pair/<cell>.seed<n>.json``; exit 0 when every pair is
equal, the meshed header says ``dp=<local devices>`` and the other has no
mesh line. ``--rehearse <divisor>`` divides the sizes, for a CPU with forced
host devices and ``VCTPU_MESH_DEVICES=4`` in the environment (exit 4).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
sys.path.insert(0, ROOT)

MESH_LINE = b"##vctpu_mesh="


def one_output(run, ref: dict, out: str) -> dict:
    from run_cell import body_count

    rc, wall = run.driver.call_entry(run, ref, out)
    with open(out, "rb") as fh:
        lines = fh.read().split(b"\n")
    os.remove(out)
    mesh = [ln for ln in lines if ln.startswith(MESH_LINE)]
    rest = b"\n".join(ln for ln in lines if not ln.startswith(MESH_LINE))
    return {"rc": rc, "wall_s": round(wall, 3), "records": body_count(rest),
            "mesh_lines": [ln.decode() for ln in mesh],
            "sha256_without_mesh_line": hashlib.sha256(rest).hexdigest()}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", type=int, default=0, metavar="DIVISOR")
    args = ap.parse_args(argv)

    import run_cell

    spec = run_cell.Spec(args.workload)
    run_cell.prepare_env(spec, trace=False)
    import jax

    n_dev = len(jax.local_devices())
    if args.rehearse and jax.default_backend() != "tpu":
        os.environ.setdefault("VCTPU_ENGINE", "jit")  # vctpu-lint: disable=VCT001 — a CPU rehearsal runs the chip's engine only when asked to (run_cell.py does the same)
    elif jax.default_backend() != "tpu" or n_dev < 2:
        run_cell.say(f"no TPU host with several chips: {n_dev} x "
                     f"{jax.default_backend()} - nothing was run")
        return run_cell.EXIT_NO_TPU
    run = run_cell.Run(spec, args.seed, 0.0, False, divisor=max(1, args.rehearse))
    os.makedirs(run_cell.WORK, exist_ok=True)
    shutil.rmtree(run.dir, ignore_errors=True)
    os.makedirs(run.dir)
    run.refs = run_cell.ensure_references(run.config, run.divisor)
    run.new_seed(args.seed)
    run.new_weights(run.config["weights_seed"])

    from variantcalling_tpu import knobs

    as_given = knobs.get_int("VCTPU_MESH_DEVICES")  # unset on a chip's host
    want = f"{MESH_LINE.decode()}dp={as_given or n_dev}"
    sides: dict = {"meshed": [], "one_device": []}
    for side, devices in (("meshed", as_given), ("one_device", 1)):
        if devices is None:
            os.environ.pop("VCTPU_MESH_DEVICES", None)  # vctpu-lint: disable=VCT001 — harness restores the registry-declared knob's default for the meshed side
        else:
            os.environ["VCTPU_MESH_DEVICES"] = str(devices)  # vctpu-lint: disable=VCT001 — harness pins the registry-declared knob for one side of the pair
        for ref in run.refs:
            row = one_output(run, ref, os.path.join(run.dir, f"{side}_{ref['seed']}.vcf"))
            run_cell.say(f"{side} ref {ref['seed']}: {json.dumps(row)}")
            sides[side].append(dict(row, ref=ref["seed"]))
    pairs = list(zip(sides["meshed"], sides["one_device"]))
    ok = all(a["rc"] == b["rc"] == 0 and a["records"] == b["records"] == run.n
             and a["mesh_lines"] == [want] and b["mesh_lines"] == []
             and a["sha256_without_mesh_line"] == b["sha256_without_mesh_line"]
             for a, b in pairs)
    summary = {"ok": ok, "cell": args.workload, "seed": args.seed,
               "variants_per_file": run.n, "expected_mesh_line": want,
               "device": {"platform": jax.devices()[0].platform,
                          "kind": jax.devices()[0].device_kind, "count": n_dev},
               **sides}
    out_dir = os.path.join(ROOT, "chiprun_out", "mesh_pair")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{args.workload}.seed{args.seed}.json"), "w",
              encoding="utf-8") as fh:
        json.dump(summary, fh, indent=1)
    print(json.dumps(summary), flush=True)
    if args.rehearse:
        run_cell.say("rehearsal, NOT a chip run")
        return run_cell.EXIT_REHEARSAL
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
