"""Tier-0 traced-jaxpr program audit — lint the ACTUALLY-COMPILED programs.

vctpu-lint's checkers (tools/vctpu_lint/) guard the determinism/byte-
parity contract at the SOURCE level, and its project model closes the
cross-file holes — but the last incident class was post-trace: XLA sees
the program after tracing, and a reduction that looks sanctioned in
source can reach the compiler reassociated (or a callback/f64 upcast can
ride in through a helper no checker scopes). This stage traces each
registered scoring program with ``jax.ShapeDtypeStruct``s — no data, no
compile, CPU backend — and walks the closed jaxprs against the COMMITTED
contract (``tools/jaxpr_audit/contract.json``, the ``event_schema.json``
pattern: the invariants are an artifact reviewed in diffs, not constants
buried in tool code):

- **Programs:** every forest strategy's margin program
  (``forest.make_margin_program``: gather walk, scan GEMM, wide
  contraction, pallas wide-block; traced over its weights operand and
  the feature matrix) x ``shard_score.shard_program`` at
  dp in {1, 2} (the mesh wrap `_predictor_for` installs), plus the
  coverage reduce kernels (``ops.coverage.binned_mean`` /
  ``depth_histogram`` on both methods).
- **No host callbacks** (``io_callback``/``pure_callback``/...):
  a callback inside a scoring program is a host sync XLA cannot see
  past, and its side effects break the pure-map byte-parity argument.
- **No collectives** (``psum``/``all_gather``/...): the mesh layout is a
  pure data-parallel MAP — per-variant margins must reduce inside ONE
  device's program; a cross-device margin reduction is the VCT009
  incident class arriving post-trace.
- **No unordered tree reduction:** a ``reduce_sum`` whose reduced axis
  has the forest's tree count is a margin sum XLA may reassociate (the
  round-5 1-ulp parity flake); the ONE sanctioned reduction is
  ``forest.sequential_tree_sum``'s loop-carried fori_loop, which lowers
  to ``while``/``scan`` — the audit also requires that loop to be
  PRESENT in every margin program.
- **Dtype policy:** no float64 anywhere in any scoring program (f64
  never survives the wire and silently doubles HBM), and margin outputs
  must be float32 (the accumulator dtype both engines agree on).
- **Program-layout census:** the distinct ``(dp, padded-batch)`` shapes
  the streaming dispatch can compile (mirroring ``_dispatch_fused``'s
  bucket-and-pad rule over ``featurize._bucket``'s ladder) gate against a
  committed budget —
  a change that breaks bucketing recompiles per chunk shape and fails
  here loudly, like a lint finding, instead of as a silent perf cliff.

Run as ``python -m tools.jaxpr_audit [--json]``; wired into
run_tests.sh as a tier-0 stage after lint, before pytest. Exit codes:
0 clean, 1 contract violations (printed), 2 usage/internal error.
See docs/static_analysis.md "Jaxpr audit contract".
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

CONTRACT_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "contract.json")


def load_contract(path: str = CONTRACT_PATH) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def ensure_cpu_devices(n: int) -> None:
    """Force a CPU backend with >= n virtual devices — BEFORE jax import.

    The audit is a tier-0 CPU stage (the point is to catch contract
    breaks before a chip ever sees the program); a caller that already
    forced a LARGER device count (tests/conftest.py forces 8) is
    respected, but a smaller one (a developer's exported
    ``--xla_force_host_platform_device_count=1`` from other local jax
    work) is raised to ``n`` — the dp=2 trace would otherwise fail the
    gate on a perfectly clean tree."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = \
            f"{flags} --xla_force_host_platform_device_count={n}".strip()
    elif int(m.group(1)) < n:
        os.environ["XLA_FLAGS"] = flags.replace(
            m.group(0), f"--xla_force_host_platform_device_count={n}")


# ---------------------------------------------------------------------------
# audit fixture forest
# ---------------------------------------------------------------------------


def audit_forest(contract: dict):
    """A deterministic synthetic FlatForest with a DISTINCTIVE tree count.

    ``tree_axis_size`` (committed in the contract) is chosen prime and
    unequal to every other dimension the scoring programs carry
    (features, window radius, batch), so "a reduced axis of this size"
    identifies the tree axis unambiguously in a traced jaxpr.
    """
    import numpy as np

    from variantcalling_tpu.models.forest import LEAF, FlatForest

    t = int(contract["tree_axis_size"])
    f = int(contract["n_features"])
    depth = 3
    m = 2 ** (depth + 1) - 1  # complete binary tree: 7 internal + 8 leaves
    rng = np.random.default_rng(0)
    internal = 2 ** depth - 1
    feature = np.full((t, m), LEAF, dtype=np.int32)
    feature[:, :internal] = rng.integers(0, f, size=(t, internal))
    threshold = rng.normal(size=(t, m)).astype(np.float32)
    left = np.arange(m, dtype=np.int32)[None, :].repeat(t, 0)
    right = left.copy()
    for node in range(internal):
        left[:, node] = 2 * node + 1
        right[:, node] = 2 * node + 2
    value = rng.normal(scale=0.1, size=(t, m)).astype(np.float32)
    return FlatForest(feature=feature, threshold=threshold, left=left,
                      right=right, value=value, max_depth=depth,
                      aggregation="logit_sum",
                      feature_names=[f"f{i}" for i in range(f)])


def weight_avals(weights):
    """The shapes of a program's weights operand (its device arrays stand
    for no data here: the audit traces, it never runs)."""
    import jax

    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), weights)


def build_programs(contract: dict) -> list[tuple[str, object, tuple, str]]:
    """-> [(label, fn, avals, kind)] for every program under contract.

    ``kind`` selects the check set: "margin" programs additionally
    require the sequential tree loop and the f32 margin output;
    "coverage" programs get the callback/collective/f64/tree-axis walk.
    """
    import jax
    import jax.numpy as jnp

    from variantcalling_tpu.models import forest as forest_mod
    from variantcalling_tpu.ops import coverage
    from variantcalling_tpu.parallel import shard_score

    forest = audit_forest(contract)
    f = int(contract["n_features"])
    rows = int(contract["batch_rows"])
    programs: list[tuple[str, object, tuple, str]] = []
    x_aval = jax.ShapeDtypeStruct((rows, f), jnp.float32)
    for strategy in contract["strategies"]:
        # interpret=True: this is a CPU trace-only stage, and building the
        # pallas entry warms the kernel up once — Mosaic compilation for
        # the chip is checked by tests/unit/test_tpu_aot.py instead
        program = forest_mod.make_margin_program(
            forest, f, strategy=strategy, interpret=True)
        for dp in contract["mesh_device_counts"]:
            fn = program.fn
            if dp > 1:
                plan = shard_score.MeshPlan(dp, str(dp), "jaxpr audit")
                mesh = shard_score.mesh_for(plan)
                fn = shard_score.shard_program(fn, mesh, n_data_args=1,
                                               replicated_leading=1)
            programs.append((f"margin/{strategy}/dp={dp}", fn,
                             (weight_avals(program.weights), x_aval), "margin"))
    programs.extend(build_fused_programs(contract))
    programs.extend(build_dan_programs(contract))
    depth_aval = jax.ShapeDtypeStruct((4096,), jnp.int32)
    programs.append(("coverage/binned_mean",
                     lambda d: coverage.binned_mean(d, 100),
                     (depth_aval,), "coverage"))
    for method in ("bincount", "matmul"):
        programs.append((
            f"coverage/depth_histogram[{method}]",
            # bind via default arg: the loop variable must not leak
            lambda d, m=method: coverage.depth_histogram(d, method=m),
            (depth_aval,), "coverage"))
    return programs


def build_fused_programs(contract: dict) -> list[tuple[str, object, tuple, str]]:
    """The streaming executor's REAL jit-engine scoring entry points
    (``pipelines/filter_variants._fused_program``): featurize + forest
    fused into one program, in both input layouts (host windows /
    HBM-resident genome with packed uint32 positions), single-device and
    shard_map-wrapped. These are the programs every overlapped megabatch
    dispatch actually runs — auditing only the bare margin predictors
    would let a callback/collective/f64 ride in through the featurize
    half unchecked (contract ``fused_dispatch``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from variantcalling_tpu.featurize import GENOME_ROW_WORDS, WINDOW_RADIUS
    from variantcalling_tpu.models.forest import FlatForest
    from variantcalling_tpu.parallel import shard_score
    from variantcalling_tpu.pipelines import filter_variants as fv

    spec = contract.get("fused_dispatch")
    if not spec:
        return []
    from variantcalling_tpu.featurize import BASE_FEATURES

    base = audit_forest(contract)
    # the fused program keys features by NAME: give the audit forest the
    # pipeline's real feature order (window-derived columns included)
    names = list(BASE_FEATURES)
    forest = FlatForest(
        feature=np.minimum(base.feature, len(names) - 1),
        threshold=base.threshold, left=base.left, right=base.right,
        value=base.value, max_depth=base.max_depth,
        aggregation=base.aggregation, feature_names=names)
    rows = int(contract["batch_rows"])
    win_aval = jax.ShapeDtypeStruct((rows, 2 * WINDOW_RADIUS + 1), jnp.uint8)
    genome_aval = jax.ShapeDtypeStruct((64, GENOME_ROW_WORDS), jnp.uint32)
    programs: list[tuple[str, object, tuple, str]] = []
    for variant in spec["variants"]:
        for dp in spec["mesh_device_counts"]:
            mesh = None
            if dp > 1:
                plan = shard_score.MeshPlan(dp, str(dp), "jaxpr audit")
                mesh = shard_score.mesh_for(plan)
            program = fv._fused_program(
                forest, names, "TGCA", genome_resident=(variant == "genome"),
                strategy="gather", mesh=mesh)
            # the dispatch's one buffer, in the program's wire layout
            wire_aval = jax.ShapeDtypeStruct((rows, program.layout.words), jnp.uint32)
            avals = (weight_avals(program.weights),
                     genome_aval if variant == "genome" else win_aval, wire_aval)
            programs.append((f"fused/{variant}/dp={dp}", program.fn, avals, "margin"))
    return programs


def build_dan_programs(contract: dict) -> list[tuple[str, object, tuple, str]]:
    """The DAN family's scoring programs (contract ``dan``): the fused
    batched forward pass (``models/dan.make_score_predictor``) traced
    bare over the (rows, F) feature matrix and through the real
    ``_fused_program`` entry, at every committed device count. Kind
    "dan" runs the callback/collective/f64/tree-axis walks and the
    f32-output check but NOT the sequential-loop requirement — a GEMM
    forward has no tree-sum ordering hazard (every reduction is a
    row-local contraction), which is exactly why the family composes
    with the dp mesh without the forest's loop discipline."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from variantcalling_tpu.featurize import BASE_FEATURES, WINDOW_RADIUS
    from variantcalling_tpu.models import dan as dan_mod
    from variantcalling_tpu.parallel import shard_score
    from variantcalling_tpu.pipelines import filter_variants as fv
    from variantcalling_tpu.synthetic import synthetic_dan

    spec = contract.get("dan")
    if not spec:
        return []
    names = list(BASE_FEATURES)
    model = synthetic_dan(np.random.default_rng(0), names,
                          embed_dim=int(spec["embed_dim"]),
                          hidden=int(spec["hidden"]),
                          n_layers=int(spec["n_layers"]))
    rows = int(contract["batch_rows"])
    x_aval = jax.ShapeDtypeStruct((rows, len(names)), jnp.float32)
    win_aval = jax.ShapeDtypeStruct((rows, 2 * WINDOW_RADIUS + 1), jnp.uint8)
    programs: list[tuple[str, object, tuple, str]] = []
    for dp in spec["mesh_device_counts"]:
        mesh = None
        if dp > 1:
            plan = shard_score.MeshPlan(dp, str(dp), "jaxpr audit")
            mesh = shard_score.mesh_for(plan)
        fn = dan_mod.make_score_predictor(model, names)
        if mesh is not None:
            fn = shard_score.shard_program(fn, mesh, n_data_args=1)
        programs.append((f"dan/score/dp={dp}", fn, (x_aval,), "dan"))
        fused = fv._fused_program(model, names, "TGCA", mesh=mesh)
        wire_aval = jax.ShapeDtypeStruct((rows, fused.layout.words), jnp.uint32)
        programs.append((f"dan/fused/windows/dp={dp}", fused.fn,
                         (weight_avals(fused.weights), win_aval, wire_aval), "dan"))
    return programs


# ---------------------------------------------------------------------------
# jaxpr walk + contract checks
# ---------------------------------------------------------------------------


def iter_eqns(jaxpr):
    """Yield every eqn in ``jaxpr`` and all nested sub-jaxprs (while/scan
    bodies, pjit/shard_map/pallas inner programs, cond branches)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def sub(params):
        for v in params.values():
            if isinstance(v, ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, Jaxpr):
                yield v
            elif isinstance(v, (tuple, list)):
                for x in v:
                    if isinstance(x, ClosedJaxpr):
                        yield x.jaxpr
                    elif isinstance(x, Jaxpr):
                        yield x

    stack = [jaxpr]
    seen: set[int] = set()
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        for eqn in j.eqns:
            yield eqn
            stack.extend(sub(eqn.params))


def audit_closed_jaxpr(closed, contract: dict, label: str,
                       kind: str = "margin") -> list[dict]:
    """Walk one traced program against the contract; -> violation dicts
    (empty == clean). Pure function of (jaxpr, contract) so tests can
    feed seeded-violation programs straight in."""
    violations: list[dict] = []

    def flag(rule: str, detail: str) -> None:
        violations.append({"program": label, "rule": rule, "detail": detail})

    forbidden = contract["forbidden_primitives"]
    callbacks = set(forbidden["host_callbacks"])
    collectives = set(forbidden["collectives"])
    tree_axis = int(contract["tree_axis_size"])
    forbid_dtypes = set(contract["dtype_policy"]["forbid"])
    margin_dtype = contract["dtype_policy"]["margin_dtype"]
    saw_loop = False

    def check_aval(aval, where: str) -> None:
        dtype = getattr(aval, "dtype", None)
        if dtype is not None and str(dtype) in forbid_dtypes:
            flag("dtype-policy",
                 f"{where} has forbidden dtype {dtype} — scoring programs "
                 f"are {margin_dtype}-accumulator only (f64 silently "
                 "doubles HBM and never survives the wire)")

    for eqn in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name in ("while", "scan"):
            saw_loop = True
        if name in callbacks:
            flag("host-callback",
                 f"host callback primitive {name!r} inside the traced "
                 "program — a host sync XLA cannot see past; scoring "
                 "programs must be pure device code")
        if name in collectives:
            flag("collective",
                 f"collective primitive {name!r} inside the traced "
                 "program — the scoring mesh is a pure data-parallel "
                 "map; margins reduce inside ONE device's program "
                 "(vctpu-lint VCT009's post-trace twin)")
        if name == "reduce_sum":
            axes = eqn.params.get("axes", ())
            in_shape = getattr(eqn.invars[0].aval, "shape", ())
            reduced = [in_shape[a] for a in axes if a < len(in_shape)]
            if tree_axis in reduced:
                flag("tree-axis-reduction",
                     f"reduce_sum over an axis of size {tree_axis} (the "
                     "tree axis) — XLA reassociates f32 reduce, margins "
                     "must accumulate through the sequential_tree_sum "
                     "fori_loop (round-5 1-ulp parity incident)")
        for v in list(eqn.invars) + list(eqn.outvars):
            check_aval(getattr(v, "aval", None), f"{name} operand")
    if kind == "margin" and contract.get("require_sequential_tree_loop") \
            and not saw_loop:
        flag("sequential-loop-missing",
             "no while/scan loop in the traced margin program — the "
             "sanctioned sequential_tree_sum accumulation (a loop-"
             "carried fori_loop XLA cannot reassociate) is absent")
    if kind in ("margin", "dan"):
        # score outputs are f32 for EVERY scoring family: the forest's
        # margin accumulator contract and the DAN's f32-end-to-end
        # determinism contract meet at the same output dtype
        for aval in closed.out_avals:
            if str(getattr(aval, "dtype", "")) != margin_dtype:
                flag("margin-dtype",
                     f"scoring program output dtype {aval.dtype} != "
                     f"{margin_dtype} — both engines agree on "
                     f"{margin_dtype} accumulators (engine contract)")
    return violations


# ---------------------------------------------------------------------------
# program-layout census
# ---------------------------------------------------------------------------


def layout_census(devices: int, bucket=None,
                  chunk: int | None = None) -> set[tuple[int, int]]:
    """Every distinct ``(dp, padded-batch-rows)`` layout the streaming
    dispatch can compile at ``devices``, mirroring ``_dispatch_fused``'s
    bucket-and-pad rule over all possible dispatch row counts.

    One compiled program per layout per (strategy, program identity): a
    run pins ONE strategy, so this set IS the run's compile count for
    the scoring hot loop. ``bucket``/``chunk`` are injectable for the
    seeded budget-overrun fixture; production values come from
    featurize/filter_variants.
    """
    if bucket is None:
        from variantcalling_tpu.featurize import _bucket as bucket
    if chunk is None:
        from variantcalling_tpu.pipelines.filter_variants import CHUNK as chunk
    chunk_size = max(chunk, devices) - (chunk % devices if devices > 1 else 0)
    layouts: set[tuple[int, int]] = set()
    for k in range(1, chunk_size + 1):
        target = min(chunk_size, -(-bucket(k) // devices) * devices)
        layouts.add((devices, target))
    return layouts


def check_layout_budget(contract: dict, bucket=None,
                        chunk: int | None = None) -> list[dict]:
    budget = int(contract["layout_budget"]["max_layouts_per_run"])
    violations: list[dict] = []
    for dp in contract["mesh_device_counts"]:
        layouts = layout_census(dp, bucket=bucket, chunk=chunk)
        if len(layouts) > budget:
            violations.append({
                "program": f"layout-census/dp={dp}",
                "rule": "layout-budget",
                "detail": f"{len(layouts)} distinct (dp, batch) program "
                          f"layouts at dp={dp} exceeds the committed "
                          f"budget of {budget} — the bucket ladder "
                          "(featurize._bucket) regressed; every extra layout is a "
                          "recompile in the scoring hot loop "
                          "(tools/jaxpr_audit/contract.json "
                          "layout_budget to extend, with justification)",
            })
    return violations


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def run_audit(contract: dict) -> tuple[list[dict], list[dict]]:
    """Trace + audit every program. -> (program reports, violations)."""
    import time

    import jax

    reports: list[dict] = []
    violations: list[dict] = []
    for label, fn, avals, kind in build_programs(contract):
        t0 = time.perf_counter()
        closed = jax.make_jaxpr(fn)(*avals)
        prims: dict[str, int] = {}
        for eqn in iter_eqns(closed.jaxpr):
            prims[eqn.primitive.name] = prims.get(eqn.primitive.name, 0) + 1
        vs = audit_closed_jaxpr(closed, contract, label, kind)
        violations.extend(vs)
        reports.append({"program": label, "kind": kind,
                        "eqns": sum(prims.values()),
                        "trace_s": round(time.perf_counter() - t0, 4),
                        "violations": len(vs)})
    violations.extend(check_layout_budget(contract))
    return reports, violations


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.jaxpr_audit",
        description="trace registered scoring programs and audit the "
                    "closed jaxprs against the committed contract")
    parser.add_argument("--contract", default=CONTRACT_PATH,
                        help="contract file (default: the committed one)")
    parser.add_argument("--json", action="store_true", dest="as_json",
                        help="machine-readable report")
    args = parser.parse_args(argv)

    try:
        contract = load_contract(args.contract)
    except (OSError, ValueError) as e:
        print(f"jaxpr_audit: cannot load contract {args.contract!r}: {e}",
              file=sys.stderr)
        return 2
    ensure_cpu_devices(max(contract["mesh_device_counts"]))
    try:
        reports, violations = run_audit(contract)
    except Exception as e:  # vctpu-lint: disable=VCT002 — tier-0 gate CLI boundary: maps ANY trace failure to a loud exit 2, never a silent pass
        print(f"jaxpr_audit: internal error: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 2

    if args.as_json:
        json.dump({"version": 1, "contract": args.contract,
                   "programs": reports, "violations": violations,
                   "exit": 1 if violations else 0},
                  sys.stdout, indent=2)
        sys.stdout.write("\n")
    else:
        for r in reports:
            print(f"  audited {r['program']}: {r['eqns']} eqns, "
                  f"{r['violations']} violation(s)")
        for v in violations:
            print(f"{v['program']}: {v['rule']}: {v['detail']}")
    if violations:
        print(f"{len(violations)} jaxpr contract violation(s) — see "
              "docs/static_analysis.md 'Jaxpr audit contract'",
              file=sys.stderr)
        return 1
    if not args.as_json:
        print(f"jaxpr_audit: {len(reports)} programs clean against "
              f"{os.path.basename(args.contract)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
