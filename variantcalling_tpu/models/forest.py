"""Decision-forest inference as vmap'd node-gather traversal on TPU.

The reference's hot loop is sklearn RandomForest / xgboost ``predict_proba``
over ~5M variants on CPU (docs/howto-callset-filter.md:63,114; BASELINE
north_star). Here a trained forest is flattened into dense per-tree node
arrays and traversal is ``max_depth`` rounds of batched gathers — fully
vectorized over (variants × trees), jit/pjit-safe, and shardable along the
variants axis. Works for both class-probability forests (RF: mean of leaf
probabilities) and boosted margins (GBT: sum + sigmoid).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field, replace
from typing import Any, Callable, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

LEAF = -1


@dataclass
class FlatForest:
    """Dense forest: (n_trees, max_nodes) arrays; leaves self-loop with feature=LEAF."""

    feature: np.ndarray  # int32 (T, M); LEAF for leaf nodes
    threshold: np.ndarray  # float32 (T, M)
    left: np.ndarray  # int32 (T, M)
    right: np.ndarray  # int32 (T, M)
    value: np.ndarray  # float32 (T, M): leaf payload (class-1 prob or margin)
    max_depth: int
    aggregation: str = "mean"  # "mean" (RF proba) | "logit_sum" (GBT margin)
    base_score: float = 0.0  # added before sigmoid for logit_sum
    feature_names: list[str] = field(default_factory=list)
    pass_threshold: float = 0.5  # TREE_SCORE >= this -> PASS
    # xgboost-style missing-value routing: NaN features take the node's
    # default branch. None = no missing routing (NaN routes right, since
    # all NaN comparisons are false) — sklearn/boosting models never see
    # NaN (host columns are nan_to_num'd), so the hot path stays free of
    # the extra gather.
    default_left: np.ndarray | None = None  # bool (T, M) or None

    @property
    def n_trees(self) -> int:
        return self.feature.shape[0]


def sequential_tree_sum(per_tree: jnp.ndarray) -> jnp.ndarray:
    """(N, T) per-tree leaf margins -> (N,) canonical-order sum.

    THE one reduction every inference strategy (scan GEMM, wide GEMM,
    pallas) funnels through, and the order the gather walk's tree loop
    accumulates in itself (:func:`walk_margin`): a loop-carried fori_loop
    over trees t=0,1,...,T-1. XLA cannot reassociate a loop-carried f32 sum,
    and the native C++ walk accumulates in the same order, so any path
    that produces bit-exact per-tree leaf values produces bit-identical
    margins (the round-5 multihost byte-parity fix, see predict_margin).
    """
    t = per_tree.shape[1]

    def acc_body(ti, acc):
        return acc + per_tree[:, ti]

    # the zero carry is derived from the input so it carries the input's
    # varying mesh axes: inside shard_map a plain jnp.zeros is unvarying
    # and the loop's carry types would not match
    return jax.lax.fori_loop(0, t, acc_body, jnp.zeros_like(per_tree[:, 0]))


class NodeTable(NamedTuple):
    """The gather walk's node table: one flat column per field over all
    ``T*M`` node slots (tree ``t``'s node ``k`` at ``t*M + k``), children
    interleaved (``children[2*g]`` left, ``children[2*g + 1]`` right, as
    tree-local ids), ``default_left`` only for a forest that routes missing
    values. A pytree of arrays: the program takes it as an operand."""

    feature: np.ndarray  # int32 (T*M,); LEAF for leaves
    threshold: np.ndarray  # float32 (T*M,)
    children: np.ndarray  # int32 (2*T*M,)
    value: np.ndarray  # float32 (T*M,)
    default_left: np.ndarray | None = None  # bool (T*M,) or None


def node_table(forest: FlatForest) -> NodeTable:
    """The forest's :class:`NodeTable` on the host (built once a predictor).

    Flat columns, gathered with tree-local ids from one tree's slice at a
    time (:func:`walk_margin`); on a TPU a row gather of a packed
    ``(T*M, 5)`` table pads each 5-wide row to 128 lanes (an unpruned
    forest's 163,840-row level then needed 8.4 GB of scratch), and a
    level-major walk of all trees' columns at once reads them from HBM at
    about 13 ns a value (docs/perf_notes.md "The node table")."""
    # np.asarray first: boosting-trained forests hold concrete jax arrays
    left = np.asarray(forest.left, dtype=np.int32).reshape(-1)
    right = np.asarray(forest.right, dtype=np.int32).reshape(-1)
    return NodeTable(
        feature=np.asarray(forest.feature, dtype=np.int32).reshape(-1),
        threshold=np.asarray(forest.threshold, dtype=np.float32).reshape(-1),
        children=np.stack([left, right], axis=1).reshape(-1),
        value=np.asarray(forest.value, dtype=np.float32).reshape(-1),
        default_left=None if forest.default_left is None else
        np.asarray(forest.default_left, dtype=bool).reshape(-1))


def predict_margin(forest: FlatForest, x: jnp.ndarray) -> jnp.ndarray:
    """Raw per-variant leaf-value SUM in canonical tree order (jit-safe):
    :func:`walk_margin` over the forest's :func:`node_table`, built here at
    trace time (so a program made this way holds the table as constants;
    the pipeline's gather program passes it as an operand instead)."""
    return walk_margin(jax.tree.map(jnp.asarray, node_table(forest)), x,
                       forest.n_trees, forest.max_depth)


def walk_margin(table: NodeTable, x: jnp.ndarray, n_trees: int,
                max_depth: int) -> jnp.ndarray:
    """The gather walk over ``table`` (:class:`NodeTable` of ``n_trees``
    trees) as an operand.

    Tree by tree: tree ``t``'s columns are sliced out (an unpruned tree's
    are a few MB, which the TPU compiler keeps in VMEM), then ``max_depth``
    rounds in which every variant advances one level (leaves self-loop),
    so control flow is static — no per-variant Python, no host sync. A
    round gathers the node's column and threshold and the chosen child
    (and, where missing values are routed, the node's ``default_left``)
    with tree-local ids; the tested feature is picked from the transposed
    feature rows by a chain of selects on the column id, which on a TPU
    costs a fraction of a gather. Flat int32 indexing bounds 2*T*M to
    2^31. Without ``default_left`` a NaN goes right (``NaN <= t`` is
    false); with it, a NaN takes the node's default branch.

    The accumulation is SEQUENTIAL over trees (t=0,1,...,T-1, a
    loop-carried f32 sum from zero, as :func:`sequential_tree_sum`)
    rather than ``jnp.sum``: XLA's reduce reassociates f32 sums into
    SIMD-lane partials whose grouping varies with backend and device
    count, which made jit scores differ from the native C++ walk (and
    from themselves across mesh shapes) by 1 ulp — the round-5 multihost
    byte-parity flake. A loop-carried dependency cannot be reassociated,
    and the native walk accumulates in the same order
    (``native/src/vctpu_forest_tile.h`` forest_walk_tile), so the two
    engines' sums are bit-identical (tests/unit/test_engine_contract.py).
    """
    m = table.feature.shape[0] // n_trees
    n_features = x.shape[1]
    rows = jnp.asarray(x).T  # (F, N)

    def tree(t, acc):
        def column(col, width=1):
            return jax.lax.dynamic_slice(col, (t * m * width,), (m * width,))

        feature, threshold = column(table.feature), column(table.threshold)
        children = column(table.children, 2)
        default_left = None if table.default_left is None else column(table.default_left)

        def level(_, idx):
            f = feature[idx]
            xv = rows[0]  # a leaf's column is never read: it keeps its idx
            for j in range(1, n_features):
                xv = jnp.where(f == j, rows[j], xv)
            go_right = ~(xv <= threshold[idx])
            if default_left is not None:  # missing (NaN) takes the default branch
                go_right = jnp.where(jnp.isnan(xv), ~default_left[idx], go_right)
            nxt = children[2 * idx + go_right.astype(jnp.int32)]
            return jnp.where(f == LEAF, idx, nxt)

        # derived from x for the same reason as sequential_tree_sum's carry
        idx = jax.lax.fori_loop(0, max_depth, level,
                                jnp.zeros_like(rows[0], dtype=jnp.int32))
        return acc + column(table.value)[idx]

    return jax.lax.fori_loop(0, n_trees, tree, jnp.zeros_like(rows[0], dtype=jnp.float32))


def finalize_margin(margin: np.ndarray, forest: FlatForest) -> np.ndarray:
    """SHARED host finalization margin -> TREE_SCORE — the single place
    that turns a canonical-order leaf sum into the score, used by BOTH
    scoring engines so the final bits cannot depend on the engine.

    ``mean`` divides (IEEE division is correctly rounded, so either side
    could do it); ``logit_sum`` applies the sigmoid HERE because exp is
    implementation-defined — XLA's logistic and libm's expf disagree in
    the last ulp on ~4% of inputs, so neither engine may bake it in.
    """
    m = np.asarray(margin, dtype=np.float32)
    if forest.aggregation == "mean":
        return m / np.float32(forest.n_trees)
    if forest.aggregation == "logit_sum":
        z = m + np.float32(forest.base_score)
        return (np.float32(1.0) / (np.float32(1.0) + np.exp(-z))).astype(np.float32)
    raise ValueError(f"unknown aggregation {forest.aggregation!r}")


def predict_score(forest: FlatForest, x: jnp.ndarray) -> jnp.ndarray:
    """TREE_SCORE in [0,1] for a (N, F) feature matrix (jit-safe).

    Device-finalized convenience wrapper over :func:`predict_margin` —
    accelerator callers keep everything on device. The engine-parity
    paths (pipelines/filter_variants) instead fetch the margin and
    finalize on the host via :func:`finalize_margin`, because the device
    sigmoid's exp is not bit-portable.
    """
    return _device_finalize(predict_margin(forest, x), forest.aggregation,
                            forest.n_trees, forest.base_score)


@dataclass
class GemmForest:
    """MXU-friendly forest encoding (Hummingbird-style GEMM strategy).

    Tree traversal recast as matmuls so inference rides the systolic array
    instead of XLA's (slow on TPU) dynamic gathers:

      XF    = X @ A          (N,F)@(F,I) one-hot feature pick per internal node
      D     = XF <= thr      {0,1} decisions
      match = D @ M2 + c     (N,I)@(I,L); M2 = 2*B - P with B[i,l]=1 iff leaf
                             l sits in i's LEFT subtree, P[i,l]=1 iff i is on
                             l's path; c[l] = #right-turns on l's path
      leaf  = (match == path_len)   — exactly one leaf matches
      score = leaf @ value

    All matmul operands are small exact integers (|M2|<=1, path sums <=
    depth), so the routing matmuls are bit-exact even in bf16; the feature
    pick runs at HIGHEST precision to keep threshold compares faithful.
    """

    a: np.ndarray  # f32 (T, F, I) one-hot feature selectors
    thr: np.ndarray  # f32 (T, I)
    m2: np.ndarray  # f32 (T, I, L) = 2B - P
    c: np.ndarray  # f32 (T, L) right-turn counts
    plen: np.ndarray  # f32 (T, L); -1 for padded leaves
    value: np.ndarray  # f32 (T, L)
    aggregation: str
    base_score: float
    # missing routing: None when the source forest has no default_left bits
    # (no NaN machinery in the compiled program); else f32 (T, I) 0/1
    dleft: np.ndarray | None = None

    @property
    def n_leaves(self) -> int:
        return self.m2.shape[2]


def to_gemm(forest: FlatForest, n_features: int | None = None) -> GemmForest:
    """Rewrite a FlatForest into path-matrix (GEMM) form (host-side, once)."""
    t = forest.n_trees
    n_features = int(n_features if n_features is not None else max(int(forest.feature.max()) + 1, 1))
    per_tree = []
    max_i, max_l = 1, 1
    for ti in range(t):
        feat, left, right = forest.feature[ti], forest.left[ti], forest.right[ti]
        internals: list[int] = []
        leaves: list[int] = []
        paths: list[list[tuple[int, bool]]] = []
        stack: list[tuple[int, list[tuple[int, bool]]]] = [(0, [])]
        while stack:
            node, path = stack.pop()
            if feat[node] == LEAF:
                leaves.append(node)
                paths.append(path)
            else:
                k = len(internals)
                internals.append(node)
                stack.append((int(right[node]), path + [(k, False)]))
                stack.append((int(left[node]), path + [(k, True)]))
        per_tree.append((internals, leaves, paths))
        max_i = max(max_i, len(internals))
        max_l = max(max_l, len(leaves))
    a = np.zeros((t, n_features, max_i), dtype=np.float32)
    thr = np.zeros((t, max_i), dtype=np.float32)
    m2 = np.zeros((t, max_i, max_l), dtype=np.float32)
    c = np.zeros((t, max_l), dtype=np.float32)
    plen = np.full((t, max_l), -1.0, dtype=np.float32)  # -1: padded leaf never matches
    value = np.zeros((t, max_l), dtype=np.float32)
    dleft = None if forest.default_left is None else np.zeros((t, max_i), dtype=np.float32)
    for ti, (internals, leaves, paths) in enumerate(per_tree):
        for k, node in enumerate(internals):
            a[ti, forest.feature[ti, node], k] = 1.0
            thr[ti, k] = forest.threshold[ti, node]
            if dleft is not None:
                dleft[ti, k] = float(forest.default_left[ti, node])
        for j, (node, path) in enumerate(zip(leaves, paths)):
            value[ti, j] = forest.value[ti, node]
            plen[ti, j] = len(path)
            for k, went_left in path:
                m2[ti, k, j] = 1.0 if went_left else -1.0  # 2B-P: left=+1, right=-1
                if not went_left:
                    c[ti, j] += 1.0
    return GemmForest(a, thr, m2, c, plen, value, forest.aggregation, forest.base_score,
                      dleft=dleft)


# beyond this many leaves per tree the (N,I)@(I,L) routing matmul costs more
# than the gather walk saves; the AUTO strategy falls back to the gather
# traversal (an explicit VCTPU_FOREST_STRATEGY override is honored anyway)
GEMM_MAX_LEAVES = 512


def _device_finalize(margin: jnp.ndarray, aggregation: str, n_trees: int,
                     base_score: float) -> jnp.ndarray:
    """Margin -> score ON DEVICE (accelerator convenience; NOT bit-portable
    for logit_sum — engine-parity callers use the host finalize_margin)."""
    if aggregation == "mean":
        return margin / n_trees
    if aggregation == "logit_sum":
        return jax.nn.sigmoid(margin + base_score)
    raise ValueError(f"unknown aggregation {aggregation!r}")


def predict_margin_gemm(gf: GemmForest, x: jnp.ndarray) -> jnp.ndarray:
    """Raw canonical-order margin via the matmul formulation (jit-safe).

    Scans over trees so peak memory is O(N * (I+L)) rather than
    O(T * N * L): each step is two (N,·)@(·,·) matmuls that tile cleanly
    onto the systolic array. The scan carry accumulates per-tree leaf
    values in tree order — the same loop-carried (non-reassociable)
    sequence :func:`sequential_tree_sum` runs — so margins are
    bit-identical to the gather walk and the native C++ engine.
    """
    missing = gf.dleft is not None
    tables = (
        jnp.asarray(gf.a),
        jnp.asarray(gf.thr),
        jnp.asarray(gf.m2),
        jnp.asarray(gf.c),
        jnp.asarray(gf.plen),
        jnp.asarray(gf.value),
    ) + ((jnp.asarray(gf.dleft),) if missing else ())
    if missing:
        # NaN would poison every xf entry through the feature-pick matmul;
        # pick from a scrubbed copy and matmul the NaN mask through the
        # same selector to know, per node, whether its feature was missing
        x_miss = jnp.isnan(x).astype(jnp.float32)
        x = jnp.nan_to_num(x, nan=0.0)

    def per_tree(acc, tree):
        a, thr, m2, c, plen, value = tree[:6]
        # one-hot feature pick must preserve f32 values exactly: default
        # matmul precision rounds operands to bf16
        xf = jnp.dot(x, a, precision=jax.lax.Precision.HIGHEST)  # (N,I)
        d = (xf <= thr[None, :]).astype(jnp.float32)
        if missing:  # 0/1 mask matmul is exact even in bf16
            mf = jnp.dot(x_miss, a)  # (N,I) 1 where the node's feature is NaN
            d = jnp.where(mf > 0.5, tree[6][None, :], d)
        # routing matmul: operands are small exact integers — bf16-safe
        match = jnp.dot(d, m2) + c[None, :]  # (N,L)
        onehot = (match == plen[None, :]).astype(jnp.float32)
        s = jnp.dot(onehot, value, precision=jax.lax.Precision.HIGHEST)  # (N,)
        return acc + s, None

    total, _ = jax.lax.scan(per_tree, jnp.zeros_like(x[:, 0], dtype=jnp.float32), tables)
    return total


def predict_score_gemm(gf: GemmForest, x: jnp.ndarray) -> jnp.ndarray:
    """TREE_SCORE via the matmul formulation (device-finalized wrapper)."""
    return _device_finalize(predict_margin_gemm(gf, x), gf.aggregation,
                            gf.m2.shape[0], gf.base_score)


# --------------------------------------------------------------------------
# wide-contraction encoding: all trees per MXU pass
# --------------------------------------------------------------------------

#: default N-chunk of the wide driver (VCTPU_WIDE_CHUNK overrides): bounds
#: the decision tensor at O(chunk * T*I) and the routing intermediate at
#: O(chunk * G*L), so 5M-variant scoring never materializes (N, T*L)
WIDE_CHUNK = 1 << 14
WIDE_CHUNK_ENV = "VCTPU_WIDE_CHUNK"
#: tree-group blocking knob (G trees per routing block; VCTPU_WIDE_BLOCK)
WIDE_BLOCK_ENV = "VCTPU_WIDE_BLOCK"


def _int_env(name: str) -> int | None:
    """Positive-integer env knob, or None when unset. A malformed value is
    a configuration error (EngineError, CLI exit 2) like a bad
    VCTPU_ENGINE/VCTPU_FOREST_STRATEGY — never a mid-run ValueError
    traceback from inside a jit trace. Parsing lives in the typed knob
    registry (:mod:`variantcalling_tpu.knobs`)."""
    from variantcalling_tpu import knobs

    return knobs.get_int(name)


def default_tree_block(n_internal: int) -> int:
    """G such that the routing contraction dim G*I fills one 128-lane MXU
    tile: the block-diagonal operand wastes O(G^2) dense FLOPs, so G grows
    only until the contraction lanes are full (docs/perf_notes.md "Forest
    operand shapes": G=4 for I=31 -> K=124, 97% lane fill vs 24% for the
    per-tree scan)."""
    return max(1, 128 // max(n_internal, 1))


def resolved_tree_block(n_internal: int, n_trees: int,
                        tree_block: int | None = None) -> int:
    """The G :func:`to_wide` will actually pack with (arg beats the
    VCTPU_WIDE_BLOCK env beats the MXU-fill default; clamped to T)."""
    if tree_block is None:
        tree_block = _int_env(WIDE_BLOCK_ENV) or default_tree_block(n_internal)
    return max(1, min(int(tree_block), n_trees))


@dataclass
class WideGemmForest:
    """Block-packed wide-contraction forest (all trees per MXU pass).

    The per-tree scan (``predict_margin_gemm``) issues (N,F)@(F,I) and
    (N,I)@(I,L) matmuls whose contraction dims fill 9-24% of the 128-lane
    MXU. This encoding packs trees side by side so one pass computes every
    tree: the feature pick becomes (N,F)@(F,Tp*I) (K stays F but the
    output tile is Tp*I lanes wide), and routing becomes a BLOCK-DIAGONAL
    (N,G*I)@(G*I,G*L) contraction over groups of G trees. Trees are padded
    to Tp = ceil(T/G)*G with never-matching dummies (plen=-1, value=0);
    padding never enters the margin reduction (sliced off before
    :func:`sequential_tree_sum`), so the canonical tree order is exactly
    the real trees'.
    """

    a: np.ndarray  # f32 (B, F, G*I) per-block feature selectors
    thr: np.ndarray  # f32 (B, G*I)
    m2: np.ndarray  # f32 (B, G*I, G*L) block-diagonal routing
    c: np.ndarray  # f32 (B, G*L)
    plen: np.ndarray  # f32 (B, G*L); -1 for padded leaves AND padded trees
    value: np.ndarray  # f32 (B, G, L)
    dleft: np.ndarray | None  # f32 (B, G*I) or None
    n_trees: int  # real T — the slice fed to sequential_tree_sum
    tree_block: int  # G
    aggregation: str
    base_score: float

    @property
    def n_blocks(self) -> int:
        return self.m2.shape[0]


def to_wide(gf: GemmForest, tree_block: int | None = None) -> WideGemmForest:
    """Pack a GemmForest into block-diagonal wide operands (host, once)."""
    t, f, i = gf.a.shape
    l = gf.m2.shape[2]
    g = resolved_tree_block(i, t, tree_block)
    b = -(-t // g)
    tp = b * g

    def pad_trees(arr, fill=0.0):
        if tp == t:
            return arr
        width = [(0, tp - t)] + [(0, 0)] * (arr.ndim - 1)
        return np.pad(arr, width, constant_values=fill)

    a_p = pad_trees(gf.a)  # (Tp, F, I)
    thr_p = pad_trees(gf.thr)
    m2_p = pad_trees(gf.m2).reshape(b, g, i, l)
    c_p = pad_trees(gf.c)
    plen_p = pad_trees(gf.plen, fill=-1.0)  # padded trees: no leaf matches
    value_p = pad_trees(gf.value)
    a_w = np.ascontiguousarray(
        a_p.reshape(b, g, f, i).transpose(0, 2, 1, 3).reshape(b, f, g * i))
    m2_w = np.zeros((b, g * i, g * l), dtype=np.float32)
    for gi in range(g):
        m2_w[:, gi * i:(gi + 1) * i, gi * l:(gi + 1) * l] = m2_p[:, gi]
    dleft_w = None if gf.dleft is None else \
        pad_trees(gf.dleft).reshape(b, g * i)
    return WideGemmForest(
        a=a_w, thr=thr_p.reshape(b, g * i), m2=m2_w,
        c=c_p.reshape(b, g * l), plen=plen_p.reshape(b, g * l),
        value=value_p.reshape(b, g, l), dleft=dleft_w,
        n_trees=t, tree_block=g,
        aggregation=gf.aggregation, base_score=gf.base_score)


def wide_chunk() -> int:
    return _int_env(WIDE_CHUNK_ENV) or WIDE_CHUNK


def predict_pertree_margin_wide(wf: WideGemmForest, x: jnp.ndarray) -> jnp.ndarray:
    """(N, T) per-tree leaf margins via the wide-contraction formulation
    for ONE chunk (no internal N-chunking — see predict_margin_wide).

    Exactness: the feature pick runs at HIGHEST precision (threshold
    compares must see exact f32 values); the routing operands are exact
    small integers (bf16-safe); the leaf pick multiplies a 0/1 one-hot by
    the f32 leaf values and reduces over leaves — all-but-one terms are
    exact +0.0, so the per-tree margin is the exact leaf value regardless
    of reduction order. Bit-identical per-tree margins => bit-identical
    canonical-order sums.
    """
    missing = wf.dleft is not None
    n = x.shape[0]
    b = wf.n_blocks
    g = wf.tree_block
    gi = wf.thr.shape[1]
    a = jnp.asarray(wf.a).transpose(1, 0, 2).reshape(wf.a.shape[1], b * gi)
    thr = jnp.asarray(wf.thr).reshape(b * gi)
    if missing:
        x_miss = jnp.isnan(x).astype(jnp.float32)
        x = jnp.nan_to_num(x, nan=0.0)
    # ONE wide feature pick for every tree: (N,F)@(F,Tp*I)
    xf = jnp.dot(x, a, precision=jax.lax.Precision.HIGHEST)
    d = (xf <= thr[None, :]).astype(jnp.float32)
    if missing:
        mf = jnp.dot(x_miss, a)  # exact 0/1 matmul
        d = jnp.where(mf > 0.5, jnp.asarray(wf.dleft).reshape(b * gi)[None, :], d)
    d_blocks = d.reshape(n, b, gi).transpose(1, 0, 2)  # (B, N, G*I)

    def per_block(_, blk):
        db, m2b, cb, plenb, valb = blk
        # block-diagonal routing: (N,G*I)@(G*I,G*L), exact small ints
        match = jnp.dot(db, m2b) + cb[None, :]
        onehot = (match == plenb[None, :]).astype(jnp.float32)  # (N, G*L)
        # per-tree leaf pick: one exact f32 survives per (variant, tree)
        # (explicit leaf dim — reshape(-1) cannot infer it when n == 0)
        margins = jnp.einsum("ngl,gl->ng",
                             onehot.reshape(n, g, valb.shape[1]), valb,
                             precision=jax.lax.Precision.HIGHEST)
        return None, margins

    xs = (d_blocks, jnp.asarray(wf.m2), jnp.asarray(wf.c),
          jnp.asarray(wf.plen), jnp.asarray(wf.value))
    _, per_tree = jax.lax.scan(per_block, None, xs)  # (B, N, G)
    return per_tree.transpose(1, 0, 2).reshape(n, b * g)[:, :wf.n_trees]


def predict_margin_wide(wf: WideGemmForest, x: jnp.ndarray) -> jnp.ndarray:
    """Raw canonical-order margin via wide contractions (jit-safe).

    N-chunked driver: chunks of :func:`wide_chunk` variants run through
    ``lax.map`` so peak memory stays O(chunk * T*I) however large N is
    (the pipeline's outer 256k chunks would otherwise materialize a
    ~1.2 GB decision tensor at T=40). Rows are independent, so chunking
    cannot change any variant's bits.
    """
    n = x.shape[0]
    chunk = wide_chunk()

    def chunk_margin(xc):
        return sequential_tree_sum(predict_pertree_margin_wide(wf, xc))

    if n <= chunk:
        return chunk_margin(x)
    pad = (-n) % chunk
    xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
    out = jax.lax.map(chunk_margin, xp.reshape(-1, chunk, x.shape[1]))
    return out.reshape(-1)[:n]


def predict_score_wide(wf: WideGemmForest, x: jnp.ndarray) -> jnp.ndarray:
    """TREE_SCORE via wide contractions (device-finalized wrapper)."""
    return _device_finalize(predict_margin_wide(wf, x), wf.aggregation,
                            wf.n_trees, wf.base_score)


#: Strategy built by the most recent make_predictor/make_margin_predictor
#: call ("native-cpp" when the C++ engine scored).
last_strategy: str = "none"

#: explicit strategy override: {auto,gather,gemm,wide,pallas}
FOREST_STRATEGY_ENV = "VCTPU_FOREST_STRATEGY"
FOREST_STRATEGIES = ("auto", "gather", "gemm", "wide", "pallas")
#: the VCF header key the filter pipeline records the resolved strategy
#: under (next to ##vctpu_engine=; part of the chunk-journal resume identity)
STRATEGY_HEADER_KEY = "vctpu_forest_strategy"


def requested_strategy() -> str:
    """The env-requested strategy; raises EngineError on a bad value (the
    same fail-loudly style as a bad VCTPU_ENGINE — parse and validation
    live in the typed knob registry)."""
    from variantcalling_tpu import knobs

    return knobs.get_str(FOREST_STRATEGY_ENV)


def validate_strategy_env() -> None:
    """Up-front validation of EVERY strategy-related env knob (strategy
    name, wide chunk, wide block) — FilterContext calls this once per run
    so a malformed value exits 2 with a clear message before any scoring,
    on every engine, instead of surfacing mid-run from inside a jit
    trace."""
    requested_strategy()
    _int_env(WIDE_CHUNK_ENV)
    _int_env(WIDE_BLOCK_ENV)


def max_tree_leaves(forest: FlatForest) -> int:
    """Reachable leaves of the biggest tree, WITHOUT the O(T * nodes)
    Python traversal :func:`to_gemm` performs: every stored internal node
    is reachable and the trees are full binary (sklearn/xgboost/boosting
    ingest all guarantee both), so leaves = internal nodes + 1 — padding
    rows are feature=LEAF and do not count as internal. Matches
    ``to_gemm(forest).n_leaves`` (asserted in tests) at vectorized cost."""
    return int((forest.feature != LEAF).sum(axis=1).max()) + 1


def resolve_strategy(forest: FlatForest, n_features: int | None = None,
                     backend: str | None = None) -> str:
    """The concrete strategy a run will score with (never ``auto``) —
    resolved ONCE per run by the filter pipeline, recorded in the output
    header and the chunk-journal resume identity, and then PINNED: the
    predictor build honors it or fails loudly, so the recorded name can
    never silently diverge from the program that scored.

    Auto policy: CPU keeps the gather walk (the pipeline routes CPU
    single-device scoring through the native C++ engine before reaching
    here; this is the jit engine's CPU program). Accelerators take the
    wide-contraction GEMM; TPUs take the pallas wide-block kernel when
    enabled (VCTPU_PALLAS=0 opts out), missing-value routing included.
    Trees beyond GEMM_MAX_LEAVES fall back to the gather walk everywhere.
    """
    from variantcalling_tpu import knobs, obs

    req = requested_strategy()
    if req != "auto":
        resolved, why = req, "explicitly requested"
    else:
        backend = backend or jax.default_backend()
        if backend == "cpu":
            resolved, why = "gather", "auto: cpu backend keeps the gather walk"
        elif max_tree_leaves(forest) > GEMM_MAX_LEAVES:
            resolved, why = "gather", "auto: tree leaves exceed GEMM_MAX_LEAVES"
        elif backend == "tpu" and knobs.get_bool("VCTPU_PALLAS"):
            resolved, why = "pallas", "auto: tpu backend, pallas enabled"
        else:
            resolved, why = "wide", f"auto: {backend} backend wide-contraction"
    if obs.active():
        obs.event("resolve", "forest_strategy", value=resolved,
                  requested=req, reason=why)
    return resolved


class MarginProgram(NamedTuple):
    """A scoring program and what it is handed on every call.

    ``fn(weights, x)`` is jit-safe; ``weights`` is a pytree of device
    arrays passed as the program's first ARGUMENT, so a compiled program's
    key holds their shapes, not their bytes, and every batch size shares
    one device copy. The gather walk's node table travels so (an
    unpruned forest's table is hundreds of MB: as a constant it would be
    compiled into, and kept on the device by, every program of every batch
    size); the matmul strategies and the pallas kernel keep their
    kilobyte tables compiled in and take ``()``. ``walk_levels`` is the
    levels one call walks (the gather walk's ``max_depth``, else 0)."""

    fn: Callable
    weights: Any
    walk_levels: int = 0


def _build_margin_program(strategy: str, forest: FlatForest,
                          n_features: int | None, interpret: bool = False,
                          sharding=None) -> tuple[Callable, Any]:
    """``(fn, weights)`` for one concrete strategy: ``fn(weights, x)`` ->
    canonical-order margin (:class:`MarginProgram`). ``sharding`` places
    the weights (None: the default device).

    Raises on anything the strategy cannot serve (pallas lowering gaps,
    bad env values); :func:`make_margin_program` turns that into an
    EngineError.
    """
    if strategy == "gather":
        t, depth = forest.n_trees, forest.max_depth
        table = jax.device_put(node_table(forest), sharding)
        return (lambda w, x: walk_margin(w, x, t, depth)), table
    gf = to_gemm(forest, n_features)
    if strategy == "gemm":
        return (lambda _w, x: predict_margin_gemm(gf, x)), ()
    if strategy == "wide":
        wf = to_wide(gf)
        return (lambda _w, x: predict_margin_wide(wf, x)), ()
    if strategy == "pallas":
        from variantcalling_tpu.models.forest_pallas import \
            make_wide_pallas_margin_predictor

        fn = make_wide_pallas_margin_predictor(gf, interpret=interpret)
        # Mosaic lowering failures only surface at the first compile — do
        # it HERE so a gap is a construction-time EngineError, not an
        # exception from inside some chunk's dispatch
        n_feat = gf.a.shape[1]
        jax.block_until_ready(jax.jit(fn)(jnp.zeros((1, n_feat), jnp.float32)))
        return (lambda _w, x: fn(x)), ()
    raise ValueError(f"unknown forest strategy {strategy!r}")


def make_margin_program(forest: FlatForest, n_features: int | None = None,
                        strategy: str | None = None, interpret: bool = False,
                        sharding=None) -> MarginProgram:
    """The canonical-order margin program, by strategy (:class:`MarginProgram`).

    ``strategy=None`` reads ``VCTPU_FOREST_STRATEGY`` (default ``auto``,
    resolved once through :func:`resolve_strategy`). The resolved
    strategy builds or the call raises EngineError (exit-2 style at the
    CLI) — requested explicitly or chosen by ``auto``, a configuration is
    honored or the run dies, never silently scored by another program.
    ``interpret=True`` runs the pallas kernel through the Pallas
    interpreter (tests on a CPU backend ask for it; no product path does).
    ``sharding`` places the program's weights (the scoring mesh's
    replicated sharding under a mesh plan).

    Every strategy returns the SAME bits: bit-exact per-tree leaf margins
    reduced in canonical tree order (:func:`sequential_tree_sum` /
    the scan carry), finalized by the caller through the one shared
    :func:`finalize_margin`.
    """
    global last_strategy
    from variantcalling_tpu.engine import EngineError

    req = strategy if strategy is not None else requested_strategy()
    if req != "auto" and req not in FOREST_STRATEGIES:
        raise EngineError(
            f"forest strategy {req!r} is not one of "
            f"{'/'.join(FOREST_STRATEGIES[1:])}")
    resolved = resolve_strategy(forest, n_features) if req == "auto" else req
    try:
        fn, weights = _build_margin_program(resolved, forest, n_features, interpret,
                                            sharding)
    except Exception as e:  # noqa: BLE001 — any build failure is a config error
        how = "auto-resolved" if req == "auto" else \
            f"explicitly requested ({FOREST_STRATEGY_ENV} or a pinned run " \
            "configuration)"
        raise EngineError(
            f"forest strategy '{resolved}' was {how} but cannot serve this "
            f"forest/backend: {type(e).__name__}: {e}. There is no fallback "
            f"chain — pick a strategy that can with {FOREST_STRATEGY_ENV} "
            "(gather serves every forest on every backend). "
            "See docs/models.md.") from e
    last_strategy = resolved  # vctpu-lint: disable=VCT010 — run-scoped diagnostic; GIL-atomic store, the strategy is pinned per run so every writer agrees
    return MarginProgram(fn, weights, forest.max_depth if resolved == "gather" else 0)


def make_margin_predictor(forest: FlatForest, n_features: int | None = None,
                          strategy: str | None = None,
                          interpret: bool = False):
    """jittable fn(x) -> canonical-order margin: :func:`make_margin_program`
    with its weights bound (a program jitted from it holds them as
    constants; the filter pipeline passes them as arguments instead)."""
    program = make_margin_program(forest, n_features, strategy=strategy,
                                  interpret=interpret)
    return functools.partial(program.fn, program.weights)


def make_predictor(forest: FlatForest, n_features: int | None = None,
                   strategy: str | None = None):
    """Device-finalized fn(x) -> scores (accelerator convenience):
    the strategy-resolved margin program plus the on-device finalize.
    Engine-parity callers (the filter pipeline) use
    :func:`make_margin_predictor` + host :func:`finalize_margin` instead,
    because the device sigmoid's exp is not bit-portable. Records the
    choice in :data:`last_strategy`."""
    fn = make_margin_predictor(forest, n_features, strategy=strategy)
    agg, base = forest.aggregation, forest.base_score
    n_trees = forest.n_trees
    return lambda x: _device_finalize(fn(x), agg, n_trees, base)


def native_host_predictor(forest: FlatForest, strict: bool = False):
    """CPU fast path: the exact predict_score walk in C++ as a plain HOST
    function (numpy in, numpy out) — ~5x XLA:CPU's fused-gather lowering
    on one core. Callers split their program at the feature matrix and
    run this outside jit (a pure_callback inside the async chunk pipeline
    can deadlock XLA:CPU's single-threaded callback executor). Returns
    None when the native library is unavailable or the aggregation is
    unknown; use only on the CPU backend (accelerators keep GEMM/pallas).

    ``strict=True`` (the pinned-native engine paths): a mid-run native
    failure RAISES instead of silently computing the margin via XLA —
    an output stamped ``##vctpu_engine=native`` must never contain
    jit-scored rows (engine contract, docs/robustness.md)."""
    from variantcalling_tpu import native

    if not native.available() or forest.aggregation not in ("mean", "logit_sum"):
        return None
    feat = np.ascontiguousarray(forest.feature, dtype=np.int32)
    thr = np.ascontiguousarray(forest.threshold, dtype=np.float32)
    left = np.ascontiguousarray(forest.left, dtype=np.int32)
    right = np.ascontiguousarray(forest.right, dtype=np.int32)
    value = np.ascontiguousarray(forest.value, dtype=np.float32)
    dl = None if forest.default_left is None else \
        np.ascontiguousarray(forest.default_left, dtype=np.uint8)
    depth = forest.max_depth

    def fn(x: np.ndarray) -> np.ndarray:
        # raw canonical-order sums from the C++ walk; finalization happens
        # in the SHARED host code so the bits match the jit engine exactly
        margin = native.forest_predict(np.asarray(x), feat, thr, left, right,
                                       value, dl, depth, "sum", 0.0)
        if margin is None:
            if strict:
                from variantcalling_tpu.engine import EngineError

                raise EngineError(
                    "the native forest walk failed mid-run with the engine "
                    "pinned to native — refusing to silently score on the "
                    "jit walk. See docs/robustness.md.")
            # opportunistic callers: jnp walk fallback (bit-identical, the
            # canonical-order margin is engine-independent by construction)
            margin = np.asarray(predict_margin(forest, jnp.asarray(x)))
        return finalize_margin(margin, forest)

    return fn


def native_cols_predictor(forest: FlatForest):
    """CPU fast path over raw feature COLUMNS: the native engine tiles the
    column->matrix transpose L2-resident and walks each tile immediately,
    so the (n, f) float32 matrix never materializes (at 5M x 19 that is
    ~760 MB of skipped DRAM traffic vs build_matrix + the row walk).
    Bit-identical scores to :func:`native_host_predictor`. Returns None
    when unavailable; fn returns None when a column dtype is unsupported
    (caller falls back to the two-step path)."""
    from variantcalling_tpu import native

    if not native.available() or forest.aggregation not in ("mean", "logit_sum"):
        return None
    feat = np.ascontiguousarray(forest.feature, dtype=np.int32)
    thr = np.ascontiguousarray(forest.threshold, dtype=np.float32)
    left = np.ascontiguousarray(forest.left, dtype=np.int32)
    right = np.ascontiguousarray(forest.right, dtype=np.int32)
    value = np.ascontiguousarray(forest.value, dtype=np.float32)
    dl = None if forest.default_left is None else \
        np.ascontiguousarray(forest.default_left, dtype=np.uint8)
    depth = forest.max_depth

    def fn(cols: list[np.ndarray]) -> np.ndarray | None:
        margin = native.matrix_forest_predict(cols, feat, thr, left, right, value,
                                              dl, depth, "sum", 0.0)
        if margin is None:
            return None
        return finalize_margin(margin, forest)

    return fn


def from_sklearn(clf, feature_names: list[str] | None = None, pass_threshold: float = 0.5) -> FlatForest:
    """Flatten a fitted sklearn RandomForestClassifier/DecisionTree ensemble.

    Faithful to sklearn semantics: split is ``x[f] <= threshold`` goes left
    (sklearn uses <=); leaf value = class-1 fraction of training samples in
    the leaf; prediction = mean over trees (predict_proba).
    """
    raw = getattr(clf, "estimators_", None)
    if raw is None:
        estimators = [clf]
    elif isinstance(raw, np.ndarray):
        # GradientBoosting stores an (n_stages, n_classes) ndarray of
        # regressor trees -> boosted-margin aggregation, not mean-proba
        if raw.ndim == 2 and raw.shape[1] != 1:
            raise ValueError("only binary-class boosted ensembles are supported")
        return _from_sklearn_gbt(clf, raw.ravel().tolist(), feature_names, pass_threshold)
    else:
        estimators = list(raw)
    n_nodes = [e.tree_.node_count for e in estimators]
    m = max(n_nodes)
    t = len(estimators)
    feature = np.full((t, m), LEAF, dtype=np.int32)
    threshold = np.zeros((t, m), dtype=np.float32)
    left = np.zeros((t, m), dtype=np.int32)
    right = np.zeros((t, m), dtype=np.int32)
    value = np.zeros((t, m), dtype=np.float32)
    max_depth = 1
    for ti, est in enumerate(estimators):
        tr = est.tree_
        nc = tr.node_count
        f = tr.feature.astype(np.int32)
        is_leaf = tr.children_left == -1
        feature[ti, :nc] = np.where(is_leaf, LEAF, f)
        # sklearn compares float32-cast x against float64 thresholds; storing
        # the largest f32 <= threshold keeps `x <= thr` decisions bit-identical
        thr64 = tr.threshold
        thr32 = thr64.astype(np.float32)
        too_big = thr32.astype(np.float64) > thr64
        thr32[too_big] = np.nextafter(thr32[too_big], np.float32(-np.inf))
        threshold[ti, :nc] = thr32
        node_ids = np.arange(nc, dtype=np.int32)
        left[ti, :nc] = np.where(is_leaf, node_ids, tr.children_left)
        right[ti, :nc] = np.where(is_leaf, node_ids, tr.children_right)
        counts = tr.value[:, 0, :]  # (nc, n_classes) — class sample fractions
        if counts.shape[1] == 2:
            denom = counts.sum(axis=1)
            value[ti, :nc] = np.where(denom > 0, counts[:, 1] / np.maximum(denom, 1e-12), 0.0)
        else:
            # degenerate single-class fit: every leaf predicts that class
            classes = getattr(est, "classes_", getattr(clf, "classes_", np.array([1])))
            value[ti, :nc] = 1.0 if classes[0] == 1 else 0.0
        max_depth = max(max_depth, int(tr.max_depth))
    return FlatForest(
        feature=feature,
        threshold=threshold,
        left=left,
        right=right,
        value=value,
        max_depth=max_depth,
        aggregation="mean",
        feature_names=feature_names or [],
        pass_threshold=pass_threshold,
    )


def _from_sklearn_gbt(clf, trees: list, feature_names: list[str] | None, pass_threshold: float) -> FlatForest:
    """Flatten a fitted binary GradientBoostingClassifier.

    score = sigmoid(init_log_odds + lr * sum(tree margins)) — matches
    sklearn's staged decision function for the log-loss binary case.
    """
    lr = float(getattr(clf, "learning_rate", 1.0))
    base = 0.0
    init = getattr(clf, "init_", None)
    if init is not None and hasattr(init, "class_prior_"):
        p1 = float(np.clip(init.class_prior_[-1], 1e-12, 1 - 1e-12))
        base = float(np.log(p1 / (1 - p1)))
    m = max(t.tree_.node_count for t in trees)
    t_n = len(trees)
    feature = np.full((t_n, m), LEAF, dtype=np.int32)
    threshold = np.zeros((t_n, m), dtype=np.float32)
    left = np.zeros((t_n, m), dtype=np.int32)
    right = np.zeros((t_n, m), dtype=np.int32)
    value = np.zeros((t_n, m), dtype=np.float32)
    max_depth = 1
    for ti, est in enumerate(trees):
        tr = est.tree_
        nc = tr.node_count
        is_leaf = tr.children_left == -1
        feature[ti, :nc] = np.where(is_leaf, LEAF, tr.feature.astype(np.int32))
        thr64 = tr.threshold
        thr32 = thr64.astype(np.float32)
        too_big = thr32.astype(np.float64) > thr64
        thr32[too_big] = np.nextafter(thr32[too_big], np.float32(-np.inf))
        threshold[ti, :nc] = thr32
        node_ids = np.arange(nc, dtype=np.int32)
        left[ti, :nc] = np.where(is_leaf, node_ids, tr.children_left)
        right[ti, :nc] = np.where(is_leaf, node_ids, tr.children_right)
        value[ti, :nc] = lr * tr.value[:, 0, 0]
        max_depth = max(max_depth, int(tr.max_depth))
    return FlatForest(
        feature=feature,
        threshold=threshold,
        left=left,
        right=right,
        value=value,
        max_depth=max_depth,
        aggregation="logit_sum",
        base_score=base,
        feature_names=feature_names or [],
        pass_threshold=pass_threshold,
    )


def with_feature_order(forest: FlatForest, feature_names: list[str]) -> FlatForest:
    """Remap node feature indices to a new feature-column order."""
    if not forest.feature_names or forest.feature_names == feature_names:
        return forest
    mapping = np.asarray([feature_names.index(f) for f in forest.feature_names], dtype=np.int32)
    new_feat = np.where(forest.feature == LEAF, LEAF, mapping[np.maximum(forest.feature, 0)])
    return replace(forest, feature=new_feat.astype(np.int32), feature_names=list(feature_names))
