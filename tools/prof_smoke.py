"""Opt-in profiler smoke stage (``VCTPU_PROF_SMOKE=1`` in run_tests.sh):
profile a small real filter run with the obs v3 continuous sampler ON
and assert the whole lens stands up — non-empty flame export, a
cpuledger with CPU samples, and output bytes identical to an
unprofiled run (the obs output-neutrality contract, here asserted with
the sampler in the loop).

Bounded (~20s: fixture build + two small streaming runs). Exit codes:
0 green, 1 an assertion failed (printed), 2 environment problems
(streaming ineligible on this host).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile


def _fvp_args(vcf_in: str, out_path: str):
    return argparse.Namespace(
        input_file=vcf_in, output_file=out_path, runs_file=None,
        hpol_filter_length_dist=[10, 10], blacklist=None,
        blacklist_cg_insertions=False, annotate_intervals=[],
        flow_order="TGCA", is_mutect=False, limit_to_contig=None)


def main() -> int:
    import numpy as np

    from variantcalling_tpu.io.fasta import FastaReader
    from variantcalling_tpu.pipelines.filter_variants import run_streaming
    from variantcalling_tpu.synthetic import make_fixtures, synthetic_forest

    with tempfile.TemporaryDirectory(prefix="prof_smoke_") as d:
        make_fixtures(d, n=50_000, genome_len=400_000)
        model = synthetic_forest(np.random.default_rng(0), n_trees=40,
                                 depth=6)
        fasta = FastaReader(os.path.join(d, "ref.fa"))
        vcf_in = os.path.join(d, "calls.vcf")

        plain = os.path.join(d, "plain.vcf")
        prof = os.path.join(d, "prof.vcf")
        stats = run_streaming(_fvp_args(vcf_in, plain), model, fasta, {},
                              None)
        if stats is None:
            print("prof_smoke: streaming ineligible on this host "
                  "(VCTPU_THREADS=1 or no native engine) — nothing to "
                  "profile", file=sys.stderr)
            return 2
        saved = {k: os.environ.get(k)  # vctpu-lint: disable=VCT001 — harness save/restore of registry-declared knobs around the profiled leg
                 for k in ("VCTPU_OBS", "VCTPU_OBS_CPUPROF",
                           "VCTPU_OBS_CPUPROF_HZ", "VCTPU_OBS_PATH")}
        os.environ["VCTPU_OBS"] = "1"  # vctpu-lint: disable=VCT001 — harness arms the registry-declared obs knob for the on-leg
        os.environ["VCTPU_OBS_CPUPROF"] = "1"  # vctpu-lint: disable=VCT001 — harness arms the registry-declared profiler knob for the on-leg
        # the smoke run lasts well under a second: the conservative
        # default rate could miss it entirely — this is a FUNCTIONAL
        # smoke, not an overhead measurement, so sample fast
        os.environ["VCTPU_OBS_CPUPROF_HZ"] = "97"  # vctpu-lint: disable=VCT001 — harness pins a fast rate; this is a smoke, not an overhead measurement
        os.environ.pop("VCTPU_OBS_PATH", None)  # vctpu-lint: disable=VCT001 — harness clears a stale override so the log lands next to the output
        try:
            run_streaming(_fvp_args(vcf_in, prof), model, fasta, {}, None)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

        failures: list[str] = []
        with open(plain, "rb") as fh:
            plain_bytes = fh.read()
        with open(prof, "rb") as fh:
            prof_bytes = fh.read()
        if plain_bytes != prof_bytes:
            failures.append("profiled run changed output bytes — the "
                            "sampler must be output-neutral")

        log = prof + ".obs.jsonl"
        from variantcalling_tpu.obs import cli as obs_cli
        from variantcalling_tpu.obs import export, sampler as sampler_mod

        events = export.read_run(log)
        n_samples = sum(int(e.get("n", 0)) for e in events
                        if e.get("kind") == "sample")
        if n_samples == 0:
            failures.append("profiled run recorded no sample events")
        flame_out = log + ".speedscope.json"
        rc = obs_cli.run(["flame", log, "-o", flame_out])
        if rc != 0:
            failures.append(f"vctpu obs flame exited {rc}")
        elif os.path.getsize(flame_out) == 0:
            failures.append("flame export is empty")
        else:
            with open(flame_out, encoding="utf-8") as fh:
                scope = json.load(fh)
            if not any(p["weights"] for p in scope.get("profiles", [])):
                failures.append("flame export holds no weighted samples")
        ledger = sampler_mod.cpuledger(events)
        if ledger is None:
            failures.append("cpuledger returned None on the profiled log")
        elif "stages" not in ledger:
            failures.append("cpuledger carries no per-1M column (record "
                            "count missing from the log)")

        if failures:
            for f in failures:
                print(f"prof_smoke: {f}", file=sys.stderr)
            return 1
        print(f"prof_smoke: green — {n_samples} samples, bytes identical, "
              f"ledger total {ledger.get('total_cpu_s_per_1m')} cpu-s/1M "
              f"across {len(ledger.get('stages', {}))} stage(s)")
        return 0


if __name__ == "__main__":
    sys.exit(main())
