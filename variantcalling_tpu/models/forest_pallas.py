"""Pallas TPU kernel for wide-block GEMM-forest inference.

The jnp formulation (``models/forest.predict_pertree_margin_wide``) scans
tree blocks with three matmuls per step; each step's decision and routing
intermediates round-trip through HBM unless XLA happens to fuse them.
This kernel keeps the whole per-block chain in VMEM.

Layout: variants ride the LANE axis. Every operand is the transpose of
the ``models/forest.to_wide`` encoding, so per (tree block, variant tile)
grid step

    xf    = a[b]^T  @ x^T        (G*I, TILE_N)   MXU, fp32 contraction
    d     = xf <= thr[b]         (G*I, TILE_N)   VPU
    match = m2[b]^T @ d          (G*L, TILE_N)   MXU, exact small ints in bf16
    hit   = match == plen - c    (G*L, TILE_N)   VPU
    out   = vsel[b] @ hit        (G,   TILE_N)   MXU, fp32 contraction

and the (G, TILE_N) per-tree margin block is the only thing written back
— a lane-dense store. ``vsel`` is the block-structured leaf-value
selector (row g holds tree g's leaf values in its own L columns, zeros
elsewhere): exactly one hit per (tree, variant) survives, every other
term is an exact +0.0, so the per-tree margin is the exact f32 leaf
value. The canonical-order tree reduction runs OUTSIDE the kernel through
the one shared ``forest.sequential_tree_sum``, so margins are
bit-identical to the gather walk, the jnp GEMM paths and the native C++
engine (checked on the chip by ``chip_smoke.py``).

Mosaic block shapes: the last two dimensions of every block are either
the full array extent or multiples of (8, 128); the tree-block index is a
leading squeezed dimension, and G*I, G*L, G and F are zero-padded to
tile multiples on the host (padded decision rows meet zero routing
columns, padded leaves carry plen - c = -1 and never match).

Missing-value routing (xgboost's ``default_left``): the wrapper feeds x
with NaN replaced by 0 plus its 0/1 NaN mask, and the step adds

    mf    = a[b]^T  @ mask^T     (G*I, TILE_N)   MXU, exact 0/1 in bf16
    d     = mf > 0.5 ? dleft[b] : xf <= thr[b]

(a column of ``a`` selects one feature, so ``mf`` is exactly that
feature's mask bit). The ``pallas_call`` is then named
``forest_wide_block_missing``; a forest without ``default_left`` traces
to the plain program above, name and operands unchanged.

Integration: the ``pallas`` entry of the models/forest strategy registry
(``VCTPU_FOREST_STRATEGY``). ``interpret=True`` runs the same kernel
through the Pallas interpreter; only tests ask for it.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

TILE_N = 512


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _wide_block_kernel(*refs, missing: bool):
    """One (variant tile, tree block) step; see the module docstring.
    ``refs`` are (xt, at, thr, m2t, q, vsel, out), with (mt, dleft)
    after xt and thr where the forest routes missing values."""
    if missing:
        xt_ref, mt_ref, at_ref, thr_ref, dleft_ref, *rest = refs
    else:
        xt_ref, at_ref, thr_ref, *rest = refs
    m2t_ref, q_ref, vsel_ref, out_ref = rest
    # feature pick must keep f32 values exact (thresholds compare tightly)
    xf = jnp.dot(at_ref[...], xt_ref[...],
                 precision=jax.lax.Precision.HIGHEST,
                 preferred_element_type=jnp.float32)
    if missing:
        # 0/1 selector against a 0/1 mask: exact at default precision
        mf = jnp.dot(at_ref[...].astype(jnp.bfloat16), mt_ref[...],
                     preferred_element_type=jnp.float32)
        d = jnp.where(mf > 0.5, dleft_ref[...],
                      (xf <= thr_ref[...]).astype(jnp.float32))
        d = d.astype(jnp.bfloat16)
    else:
        d = (xf <= thr_ref[...]).astype(jnp.bfloat16)
    # block-diagonal routing: 0/1 decisions against -1/0/+1 path entries,
    # exact in bf16 with f32 accumulation
    match = jnp.dot(m2t_ref[...], d, preferred_element_type=jnp.float32)
    hit = (match == q_ref[...]).astype(jnp.float32)
    out_ref[...] = jnp.dot(vsel_ref[...], hit,
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _kernel_tables(wf):
    """Host-side transpose + tile padding of a WideGemmForest into the
    kernel's operands (numpy, once per predictor build)."""
    b, f, gi = wf.a.shape
    gl = wf.m2.shape[2]
    g = wf.tree_block
    l = gl // g
    fp, gip, glp, gp = (_round_up(f, 8), _round_up(gi, 128),
                        _round_up(gl, 128), _round_up(g, 8))
    at = np.zeros((b, gip, fp), np.float32)
    at[:, :gi, :f] = wf.a.transpose(0, 2, 1)
    thr = np.zeros((b, gip, 1), np.float32)
    thr[:, :gi, 0] = wf.thr
    m2t = np.zeros((b, glp, gip), np.float32)
    m2t[:, :gl, :gi] = wf.m2.transpose(0, 2, 1)
    q = np.full((b, glp, 1), -1.0, np.float32)
    q[:, :gl, 0] = wf.plen - wf.c
    vsel = np.zeros((b, gp, glp), np.float32)
    for k in range(g):
        vsel[:, k, k * l:(k + 1) * l] = wf.value[:, k]
    if wf.dleft is None:
        return at, thr, m2t.astype(jnp.bfloat16), q, vsel
    dleft = np.zeros((b, gip, 1), np.float32)
    dleft[:, :gi, 0] = wf.dleft
    return at, thr, dleft, m2t.astype(jnp.bfloat16), q, vsel


def make_wide_pallas_margin_predictor(gf, tree_block: int | None = None,
                                      interpret: bool = False):
    """fn(x) -> canonical-order margin for a GemmForest, running the
    wide-block kernel (grid over (variant tile, tree block); all of a
    block's operands VMEM-resident). A forest with ``dleft`` routes NaN
    features by it (``forest_wide_block_missing``)."""
    from jax.experimental import pallas as pl

    from variantcalling_tpu.models import forest as forest_mod

    missing = gf.dleft is not None
    wf = forest_mod.to_wide(gf, tree_block)
    tables = _kernel_tables(wf)
    b, gip, fp = tables[0].shape
    gp, glp = tables[-1].shape[1:]
    f = wf.a.shape[1]
    g = wf.tree_block
    n_trees = wf.n_trees

    def table_spec(*shape):
        return pl.BlockSpec((None, *shape), lambda ni, bi: (bi, 0, 0))

    def predict(x):
        n = x.shape[0]
        if n == 0:  # a zero-size grid cannot dispatch
            return jnp.zeros((0,), jnp.float32)
        n_pad = _round_up(n, TILE_N)
        x = x.astype(jnp.float32)
        pad = ((0, fp - f), (0, n_pad - n))
        if missing:
            # NaN would poison the feature pick: pick from zeros, route by
            # the mask
            xs = (jnp.pad(jnp.nan_to_num(x, nan=0.0).T, pad),
                  jnp.pad(jnp.isnan(x).T.astype(jnp.bfloat16), pad))
        else:
            xs = (jnp.pad(x.T, pad),)
        # the mask matmul, and the mask tile and dleft column a step
        mask_flops = gip * fp if missing else 0
        mask_bytes = 2 * fp * TILE_N + 4 * gip if missing else 0
        per_tree = pl.pallas_call(
            functools.partial(_wide_block_kernel, missing=missing),
            grid=(n_pad // TILE_N, b),
            in_specs=[pl.BlockSpec((fp, TILE_N), lambda ni, bi: (0, ni))
                      for _ in xs]
            + [table_spec(*t.shape[1:]) for t in tables],
            out_specs=pl.BlockSpec((None, gp, TILE_N),
                                   lambda ni, bi: (bi, 0, ni)),
            # inside shard_map the output varies over the same mesh axes
            # as the input shard
            out_shape=jax.ShapeDtypeStruct((b, gp, n_pad), jnp.float32,
                                           vma=jax.typeof(xs[0]).vma),
            # XLA's cost analysis cannot see inside the custom call: count
            # the matmuls per grid step from the block shapes, and the x
            # tile(s) + one block's tables in, one margin block out
            cost_estimate=pl.CostEstimate(
                flops=2 * n_pad * b * (gip * fp + mask_flops + glp * gip
                                       + gp * glp),
                transcendentals=0,
                bytes_accessed=(n_pad // TILE_N) * b * (
                    4 * fp * TILE_N + 4 * gip * (fp + 1) + mask_bytes
                    + 2 * glp * gip + 4 * glp + 4 * gp * glp
                    + 4 * gp * TILE_N)),
            interpret=interpret,
            name="forest_wide_block_missing" if missing else "forest_wide_block",
        )(*xs, *(jnp.asarray(t) for t in tables))
        # (B, Gp, Np) -> (N, T): drop sublane padding, padded trees and
        # padded variants before the shared canonical-order reduction
        per_tree = per_tree[:, :g, :n].reshape(b * g, n)[:n_trees].T
        return forest_mod.sequential_tree_sum(per_tree)

    return predict
