"""Tier-0 jaxpr program audit (ISSUE 9 tentpole c): every registered
scoring program traces clean against the committed contract, and each
seeded contract violation — f64 upcast, margin ``psum``, host
``io_callback``, tree-axis ``reduce_sum``, layout-budget overrun — is
demonstrably caught by its rule (the acceptance-criteria gate)."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tools import jaxpr_audit as ja

CONTRACT = ja.load_contract()


def audit(fn, avals, kind="margin", contract=CONTRACT, label="fixture"):
    closed = jax.make_jaxpr(fn)(*avals)
    return ja.audit_closed_jaxpr(closed, contract, label, kind)


def rules(violations):
    return sorted({v["rule"] for v in violations})


# ---------------------------------------------------------------------------
# the real programs pass (the clean half of the acceptance gate)
# ---------------------------------------------------------------------------


def test_all_registered_programs_clean():
    reports, violations = ja.run_audit(CONTRACT)
    assert violations == [], violations
    labels = {r["program"] for r in reports}
    # every strategy traces at every device count — pallas included: its
    # pallas_call declares the input shard's varying mesh axes on its
    # output, so it traces under shard_map's check_vma like the rest
    for strategy in CONTRACT["strategies"]:
        for dp in CONTRACT["mesh_device_counts"]:
            assert f"margin/{strategy}/dp={dp}" in labels
    assert "coverage/binned_mean" in labels
    assert "coverage/depth_histogram[matmul]" in labels


def test_margin_programs_contain_the_sequential_loop():
    # the sanctioned sequential_tree_sum accumulation must be PRESENT —
    # a strategy that quietly replaced the fori_loop with a reduce would
    # still trace "clean" of forbidden primitives
    for label, fn, avals, kind in ja.build_programs(CONTRACT):
        if kind != "margin":
            continue
        prims = {e.primitive.name
                 for e in ja.iter_eqns(jax.make_jaxpr(fn)(*avals).jaxpr)}
        assert prims & {"while", "scan"}, \
            f"{label}: no while/scan loop in {sorted(prims)}"


# ---------------------------------------------------------------------------
# seeded violations (the catching half of the acceptance gate)
# ---------------------------------------------------------------------------


def test_seeded_f64_upcast_caught():
    def upcast(x):
        return jnp.cumsum(x.astype(jnp.float64)).astype(jnp.float32)

    with jax.enable_x64(True):
        vs = audit(upcast, (jax.ShapeDtypeStruct((8,), jnp.float32),),
                   kind="coverage")
    assert "dtype-policy" in rules(vs)
    assert any("float64" in v["detail"] for v in vs)


def test_seeded_margin_psum_caught():
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))

    def body(margins):
        return jax.lax.psum(jnp.tanh(margins), "data")

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P("data"),), out_specs=P())
    vs = audit(fn, (jax.ShapeDtypeStruct((8,), jnp.float32),),
               kind="coverage")
    assert "collective" in rules(vs)


def test_seeded_io_callback_caught():
    from jax.experimental import io_callback

    def leaky(x):
        io_callback(lambda a: np.asarray(a),
                    jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return x

    vs = audit(leaky, (jax.ShapeDtypeStruct((8,), jnp.float32),),
               kind="coverage")
    assert "host-callback" in rules(vs)
    # pure_callback is just as much a host sync
    def pure_leak(x):
        return jax.pure_callback(
            lambda a: np.asarray(a),
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    vs = audit(pure_leak, (jax.ShapeDtypeStruct((8,), jnp.float32),),
               kind="coverage")
    assert "host-callback" in rules(vs)


def test_seeded_tree_axis_reduce_sum_caught():
    t = CONTRACT["tree_axis_size"]

    def unordered(per_tree):
        return jnp.sum(per_tree, axis=1)

    vs = audit(unordered, (jax.ShapeDtypeStruct((64, t), jnp.float32),))
    assert "tree-axis-reduction" in rules(vs)
    # a margin program with NO loop at all also fails the presence rule
    assert "sequential-loop-missing" in rules(vs)
    # ...but a sum over a non-tree-sized axis is not a tree reduction
    vs = audit(lambda x: jnp.sum(x, axis=1),
               (jax.ShapeDtypeStruct((64, t + 1), jnp.float32),),
               kind="coverage")
    assert "tree-axis-reduction" not in rules(vs)


def test_seeded_f64_margin_output_caught():
    def f64_margins(x):
        acc = jax.lax.fori_loop(
            0, x.shape[1],
            lambda t, a: a + x[:, t].astype(jnp.float64),
            jnp.zeros(x.shape[0], jnp.float64))
        return acc

    with jax.enable_x64(True):
        vs = audit(f64_margins,
                   (jax.ShapeDtypeStruct((8, 3), jnp.float32),))
    assert "margin-dtype" in rules(vs)


def test_seeded_layout_budget_overrun_caught():
    # a bucketing regression: linear 1000-row steps instead of the ladder
    # (powers of two up to 32,768 rows, four rungs an octave above: 18
    # layouts) explodes the distinct-layout census
    bad_bucket = lambda n: -(-n // 1000) * 1000
    vs = ja.check_layout_budget(CONTRACT, bucket=bad_bucket, chunk=1 << 15)
    assert rules(vs) == ["layout-budget"]
    # the production ladder fits the committed budget exactly
    assert ja.check_layout_budget(CONTRACT) == []


def test_layout_census_matches_committed_budget():
    budget = CONTRACT["layout_budget"]["max_layouts_per_run"]
    for dp in CONTRACT["mesh_device_counts"]:
        layouts = ja.layout_census(dp)
        assert len(layouts) <= budget
        # every layout is dp-divisible (shard_map's hard requirement)
        assert all(rows % dp == 0 for _, rows in layouts)


# ---------------------------------------------------------------------------
# CLI surface
# ---------------------------------------------------------------------------


def test_cli_clean_tree_exit_0_json(capsys):
    assert ja.main(["--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["violations"] == []
    assert doc["exit"] == 0
    assert len(doc["programs"]) >= 8


def test_cli_missing_contract_exit_2(capsys):
    assert ja.main(["--contract", "/nonexistent/contract.json"]) == 2
    assert "cannot load contract" in capsys.readouterr().err


def test_ensure_cpu_devices_raises_smaller_forced_count(monkeypatch):
    # a developer's exported --xla_force_host_platform_device_count=1
    # (common for other local jax work) must be RAISED to the contract's
    # max dp, or the dp=2 trace fails the tier-0 gate on a clean tree;
    # a larger pre-set count (conftest forces 8) is respected
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    ja.ensure_cpu_devices(2)
    assert "--xla_force_host_platform_device_count=2" \
        in os.environ["XLA_FLAGS"]
    monkeypatch.setenv(
        "XLA_FLAGS",
        "--foo --xla_force_host_platform_device_count=8 --bar")
    ja.ensure_cpu_devices(2)
    assert os.environ["XLA_FLAGS"] \
        == "--foo --xla_force_host_platform_device_count=8 --bar"
    monkeypatch.setenv("XLA_FLAGS", "--foo")
    ja.ensure_cpu_devices(2)
    assert "--xla_force_host_platform_device_count=2" \
        in os.environ["XLA_FLAGS"]


@pytest.mark.slow
def test_cli_subprocess_under_budget():
    # the run_tests.sh tier-0 stage: fresh process, CPU backend, <30s
    import os

    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    proc = subprocess.run(
        [sys.executable, "-m", "tools.jaxpr_audit"],
        capture_output=True, text=True, timeout=30,
        env={"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
             "JAX_PLATFORMS": "cpu"},
        cwd=repo)
    assert proc.returncode == 0, proc.stderr
    assert "programs clean" in proc.stdout
