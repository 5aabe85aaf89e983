#!/bin/bash
# Run the test suite on a virtual 8-device CPU mesh.
#
# Every stage forces JAX_PLATFORMS=cpu: the suite spawns many jax
# processes and a chip belongs to one process at a time, and the 8-device
# mesh the parity matrices need only exists as forced host devices. The
# chip is exercised by `python chip_smoke.py` through the chip tool, and
# what it would be asked to compile by tests/unit/test_tpu_aot.py (AOT for
# a v5e topology, no chip needed). PYTHONPATH is cleared so the stages
# import this checkout and nothing else.
#
# VCTPU_FLAKEHUNT=1 additionally repeats the flakehunt-marked tests
# (the historically flaky multihost byte-parity path) 5x after the main
# run — the opt-in regression gate for the round-5 engine-parity flake
# (tools/flakehunt.sh is the general-purpose hunter).
set -o pipefail
cd "$(dirname "$0")"

# -- tier-0 lint stage (docs/static_analysis.md) ---------------------------
# vctpu-lint enforces the engine-determinism contract invariants (raw
# VCTPU_* environ reads, silent broad-except fallbacks, unordered
# tree-sum reductions, tracer host syncs, unbounded subprocesses,
# whole-program concurrency discipline); it runs BEFORE pytest and new
# findings fail the whole run. --json renders findings + per-checker
# wall time structured in the log. ruff (pyflakes + import order,
# [tool.ruff] in pyproject.toml) rides along when installed — the
# hermetic test container does not ship it.
echo "lint stage: python -m tools.vctpu_lint --json"
env PYTHONPATH= JAX_PLATFORMS=cpu python -m tools.vctpu_lint --json || {
  echo "vctpu-lint found new findings — failing before pytest" >&2
  exit 1
}
if command -v ruff >/dev/null 2>&1; then
  echo "lint stage: ruff check"
  ruff check variantcalling_tpu tools tests || exit 1
else
  echo "lint stage: ruff not installed — skipped"
fi

# -- tier-0 protocol model-check stage (docs/static_analysis.md) -----------
# Explicit-state BFS over the elastic lease protocol (tools/protocheck):
# one-owner-per-(span,generation), exact-once span coverage, no
# stale-generation commit, monotone seam merge — with the model's
# constants (lease scheme, O_EXCL flags, generation-bump rule, marker
# suffix) mechanically anchored against parallel/elastic.py and
# parallel/rank_plan.py. An invariant violation prints a minimal
# interleaving; anchor drift means code and model diverged. Bounded
# (~4k states, sub-second; 120s wall budget).
echo "protocheck stage: python -m tools.protocheck --json"
timeout -k 5 120 env PYTHONPATH= JAX_PLATFORMS=cpu python -m tools.protocheck --json || {
  echo "protocheck found an elastic-protocol violation or model/code anchor drift — failing before pytest" >&2
  exit 1
}

# -- opt-in chaos smoke stage (docs/robustness.md) -------------------------
# VCTPU_CHAOS=1: 10 fixed-seed chaos schedules over the streaming filter
# executor (tools/chaoshunt — fault classes x layouts x fresh/resumed,
# every invariant checked, violating schedules delta-shrunk to a repro
# JSON). Bounded (~2 min); the full ≥50-seed campaign is the local
# pre-merge sweep: python -m tools.chaoshunt --seeds 50.
if [ "${VCTPU_CHAOS:-0}" != "0" ]; then
  echo "chaos smoke stage: python -m tools.chaoshunt --seeds 10 --json"
  env PYTHONPATH= JAX_PLATFORMS=cpu python -m tools.chaoshunt --seeds 10 --json || {
    echo "chaoshunt found an invariant violation — failing before pytest (see the repro JSON above)" >&2
    exit 1
  }
fi

# -- opt-in load smoke stage (docs/serving.md) -----------------------------
# VCTPU_LOAD=1: 10 fixed-seed load×chaos schedules against a real
# `vctpu serve` daemon (tools/loadhunt — ≥8 concurrent clients × fault
# classes incl. poison chunk / native hang / dispatch OOM / mid-request
# disconnect, plus overload schedules that must shed explicitly; every
# SLO invariant checked, violations delta-shrunk to a repro JSON).
# Bounded (~1 min); larger sweeps: python -m tools.loadhunt --seeds 50.
if [ "${VCTPU_LOAD:-0}" != "0" ]; then
  echo "load smoke stage: python -m tools.loadhunt --seeds 10 --json"
  env PYTHONPATH= JAX_PLATFORMS=cpu python -m tools.loadhunt --seeds 10 --json || {
    echo "loadhunt found an SLO invariant violation — failing before pytest (see the repro JSON above)" >&2
    exit 1
  }
fi

# -- opt-in simulated multi-host stage (docs/scaleout.md) ------------------
# VCTPU_SCALEOUT=1: the 2-process local-launcher pipeline end-to-end on
# the cpu backend (tools/podrun spawns rank workers with VCTPU_RANK set,
# byte parity vs the single-rank run, SIGKILL-one-rank resume), the
# elastic-membership pod (span leases, mid-run SIGKILL answered by a
# re-cut in the SAME launch, chaos drills), plus the jax.distributed
# system tests — the PR 5 collectives capability probe turns their
# skips into real runs on jaxlib builds that support multi-process CPU
# collectives. Bounded (~3 min).
if [ "${VCTPU_SCALEOUT:-0}" != "0" ]; then
  echo "scaleout stage: pytest tests/system/test_scaleout.py tests/system/test_elastic.py tests/system/test_multihost.py"
  env PYTHONPATH= JAX_PLATFORMS=cpu \
    python -m pytest tests/system/test_scaleout.py tests/system/test_elastic.py tests/system/test_multihost.py -q -p no:cacheprovider || {
    echo "scaleout stage failed — the rank-partitioned path is broken" >&2
    exit 1
  }
fi

# -- opt-in serving-fabric smoke stage (docs/serving_fabric.md) ------------
# VCTPU_FABRIC=1: the end-to-end fabric tests against a real subprocess
# fleet (tools/podrun.start_fabric: 1 router + 2 resident backends,
# streamed bodies, sha256 parity vs the batch CLI, leak-free drain)
# plus a 2-seed backend_kill chaos campaign (SIGKILL a registered
# backend mid-request — re-span or shed, never hang). Bounded (~2 min);
# larger sweeps: python -m tools.loadhunt --campaign backend_kill --seeds 10.
if [ "${VCTPU_FABRIC:-0}" != "0" ]; then
  echo "fabric smoke stage: pytest tests/system/test_fabric_fleet.py + loadhunt --campaign backend_kill"
  env PYTHONPATH= JAX_PLATFORMS=cpu \
    python -m pytest tests/system/test_fabric_fleet.py -q -p no:cacheprovider || {
    echo "fabric fleet smoke failed — the router tier is broken" >&2
    exit 1
  }
  env PYTHONPATH= JAX_PLATFORMS=cpu \
    python -m tools.loadhunt --campaign backend_kill --seed-list 0,1 --records 1500 --json || {
    echo "backend_kill campaign found an invariant violation" >&2
    exit 1
  }
fi

# -- tier-0 jaxpr audit stage (docs/static_analysis.md) --------------------
# Trace every registered scoring program (forest strategies x
# shard_program at dp in {1,2} + the coverage reduce kernels) with
# ShapeDtypeStructs on the CPU backend and walk the closed jaxprs
# against the COMMITTED contract (tools/jaxpr_audit/contract.json): no
# host callbacks, no collectives/tree-axis reductions outside the
# sanctioned sequential_tree_sum loop, no f64, and the program-layout
# census within its committed budget. Post-trace contract breaks fail
# the run before pytest, like a lint finding (sub-30s, trace only — no
# compile).
echo "jaxpr audit stage: python -m tools.jaxpr_audit"
env PYTHONPATH= JAX_PLATFORMS=cpu python -m tools.jaxpr_audit || {
  echo "jaxpr audit found contract violations — failing before pytest" >&2
  exit 1
}

# -- tier-0 obs schema stage (docs/observability.md) -----------------------
# Generate a real obs run log and validate it against the COMMITTED event
# schema (variantcalling_tpu/obs/event_schema.json): writer/schema drift
# fails the run before pytest, like a lint finding. The generated log
# covers the live-telemetry kinds too (causal `trace` spans incl. a
# fan-in dispatch, periodic `snapshot` metrics with rolling-window
# quantiles, recovery trace linkage) and asserts the critical-path
# engine names the seeded dominant edge.
echo "obs schema stage: python -m tools.obs_schema_check"
env PYTHONPATH= JAX_PLATFORMS=cpu python -m tools.obs_schema_check || {
  echo "obs schema check failed — failing before pytest" >&2
  exit 1
}

# -- opt-in profiler smoke stage (docs/observability.md) -------------------
# VCTPU_PROF_SMOKE=1: profile a small real filter run with the obs v3
# continuous sampler ON (VCTPU_OBS_CPUPROF) and assert a non-empty flame
# export, a populated cpuledger, and byte-identical output vs an
# unprofiled run. Bounded (~20s).
if [ "${VCTPU_PROF_SMOKE:-0}" != "0" ]; then
  echo "prof smoke stage: python -m tools.prof_smoke"
  env PYTHONPATH= JAX_PLATFORMS=cpu python -m tools.prof_smoke || {
    echo "prof smoke failed — the continuous-profiler lens is broken" >&2
    exit 1
  }
fi

rc=0
env PYTHONPATH= JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  python -m pytest tests/ "$@" || rc=$?
if [ "${VCTPU_FLAKEHUNT:-0}" != "0" ]; then
  echo "VCTPU_FLAKEHUNT: repeating flakehunt-marked tests 5x"
  for i in 1 2 3 4 5; do
    echo "flakehunt repeat $i/5"
    env PYTHONPATH= JAX_PLATFORMS=cpu \
      XLA_FLAGS="--xla_force_host_platform_device_count=8" \
      python -m pytest tests/ -m flakehunt -q || rc=$?
  done
fi
exit $rc
