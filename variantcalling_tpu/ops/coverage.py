"""Device kernels for coverage analysis (BASELINE config 4).

The reference's coverage path is subprocess text plumbing: ``samtools
depth | awk`` per contig, pyBigWig value loops, ``awk`` re-binning
(coverage_analysis.py:653-683, 745-786, 798-856). Here a contig's depth is
one int32 vector and every product is a fused reduction:

- binning          = pad + reshape + mean          (one kernel per window)
- histogram        = bounded bincount              (one-hot psum per shard)
- percentiles      = cumsum over the histogram
- interval stats   = the same kernels over masked depth

All kernels are jit-safe with static shapes (depth vectors pad to the
window multiple) and shard along the position axis — multi-chip runs
psum partial histograms, per SURVEY §5.8.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MAX_DEPTH_BIN = 1000  # depths clip into [0, MAX_DEPTH_BIN] for histograms


def binned_mean(depth: jnp.ndarray, window: int) -> jnp.ndarray:
    """Mean depth per non-overlapping window; tail window averages its remainder."""
    n = depth.shape[0]
    n_win = -(-n // window)
    pad = n_win * window - n
    d = jnp.pad(depth.astype(jnp.float32), (0, pad))
    sums = d.reshape(n_win, window).sum(axis=1)
    counts = jnp.full(n_win, window, dtype=jnp.float32)
    if pad:
        counts = counts.at[-1].set(window - pad)
    return sums / counts


#: chunk length for the accelerator histogram path (one-hot rows per matmul)
_HIST_CHUNK = 1 << 13


def depth_histogram(depth: jnp.ndarray, mask: jnp.ndarray | None = None,
                    max_depth: int = MAX_DEPTH_BIN, method: str | None = None) -> jnp.ndarray:
    """(max_depth+1,) float histogram of clipped depth, optionally masked.

    ``method``: "bincount" (scatter-add — fine on CPU), "matmul" (chunked
    one-hot x ones contraction — scatter-add SERIALIZES on TPU, the same
    cliff the GBT trainer documents at models/boosting.py:99; the MXU path
    keeps histogramming at matmul rate), or None to pick by backend.
    """
    if method is None:
        method = "bincount" if jax.default_backend() == "cpu" else "matmul"
    clipped = jnp.clip(depth, 0, max_depth)
    n_bins = max_depth + 1
    if mask is not None:
        # masked-out positions route to a sacrificial bin then get dropped
        clipped = jnp.where(mask, clipped, max_depth + 1)
        n_bins = max_depth + 2
    if method == "bincount":
        hist = jnp.bincount(clipped, length=n_bins)
    elif method == "matmul":
        n = clipped.shape[0]
        pad = (-n) % _HIST_CHUNK
        # padding routes to an extra sacrificial column
        chunks = jnp.pad(clipped, (0, pad), constant_values=n_bins).reshape(-1, _HIST_CHUNK)
        ones = jnp.ones((_HIST_CHUNK,), jnp.bfloat16)

        def step(acc, chunk):
            oh = jax.nn.one_hot(chunk, n_bins + 1, dtype=jnp.bfloat16)  # (CH, B+1)
            part = jax.lax.dot_general(ones, oh, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
            # per-chunk sums are exact in f32 (<= CH); int32 accumulation
            # is exact to 2^31-1 per bin — one contig (<= 250M positions)
            # can never overflow it; whole-GENOME single calls should go
            # per-contig (as coverage_analysis does)
            return acc + part.astype(jnp.int32), None

        hist, _ = jax.lax.scan(step, jnp.zeros(n_bins + 1, jnp.int32), chunks)
        hist = hist[:n_bins]
    else:
        raise ValueError(f"unknown method {method!r}")
    return hist[: max_depth + 1].astype(jnp.float32)


#: windows per host tile: ~4M positions keeps a tile + its one-hot-free
#: products L2-resident, so the genome-scale reduce streams instead of
#: sweeping a multi-GB temporary three times (the 123 -> 48.6 Mbp/s cliff)
_HOST_TILE_POSITIONS = 4 << 20


def host_coverage_stats(depth: np.ndarray, window: int,
                        max_depth: int = MAX_DEPTH_BIN,
                        qs: np.ndarray | None = None,
                        from_diffs: bool = False) -> dict[str, np.ndarray]:
    """Single-pass HOST coverage reduce: per-window means + clipped depth
    histogram (+ percentiles), via the threaded native engine with a tiled
    numpy fallback.

    This is the CPU twin of the jitted kernels above — identical
    histograms/percentiles, and means bit-identical while every window SUM
    is exactly representable in f32 (< 2^24; always true at WGS depth
    scales — past that the exact int64 sum rounded once is MORE accurate
    than the jitted f32 accumulation, not equal to it). Built because the
    jitted CPU lowering ran at numpy parity (1.01x, round-5 VERDICT) and
    cliffed at genome scale: XLA:CPU materializes the
    f32 cast, the padded reshape and the clip as separate full-size
    passes. Here the depth vector is read ONCE in cache-sized tiles
    (difference-array inputs are integrated on the fly with
    ``from_diffs``, so the bam/cram depth path never materializes the
    depth vector at all).
    """
    from variantcalling_tpu import native

    depth = np.ascontiguousarray(depth, dtype=np.int32)
    got = native.coverage_stats(depth, window, max_bin=max_depth, from_diffs=from_diffs)
    if got is not None:
        means, hist = got
    else:
        n = len(depth)
        n_win = -(-n // window) if n else 0
        means = np.empty(n_win, dtype=np.float32)
        hist = np.zeros(max_depth + 1, dtype=np.int64)
        tile_w = max(1, _HOST_TILE_POSITIONS // window)
        run = np.int64(0)
        for wlo in range(0, n_win, tile_w):
            whi = min(wlo + tile_w, n_win)
            lo, hi = wlo * window, min(n, whi * window)
            seg = depth[lo:hi]
            if from_diffs:
                seg = np.cumsum(seg, dtype=np.int64) + run
                run = seg[-1] if len(seg) else run
            pad = (whi - wlo) * window - (hi - lo)
            # exact int64 window sums + ONE f32 rounding: matches the
            # native kernel at every depth magnitude (see host docstring)
            sums = np.pad(seg, (0, pad)).reshape(whi - wlo, window) \
                .sum(axis=1, dtype=np.int64)
            counts = np.full(whi - wlo, window, dtype=np.float32)
            if pad:
                counts[-1] = window - pad
            means[wlo:whi] = sums.astype(np.float32) / counts
            hist += np.bincount(np.clip(seg, 0, max_depth), minlength=max_depth + 1)
        hist = hist.astype(np.int64)
    out = {"means": means, "hist": hist.astype(np.float32)}
    if qs is not None:
        # numpy replica of percentiles_from_histogram (same clamping)
        q = np.maximum(np.asarray(qs, dtype=np.float32) * (1.0 - 1e-6), 1e-9)
        total = out["hist"].sum(dtype=np.float32)
        cdf = np.cumsum(out["hist"], dtype=np.float32) / max(total, 1.0)
        out["percentiles"] = np.argmax(cdf[None, :] >= q[:, None], axis=1).astype(np.int32)
    return out


def percentiles_from_histogram(hist: jnp.ndarray, qs: jnp.ndarray) -> jnp.ndarray:
    """Depth value at each quantile q in [0,1] (inverse CDF over the histogram)."""
    # clamp q: Q0 means "min observed depth" (not the first empty bin) and
    # float cdf may top out at 1-eps, so Q100 backs off by a ulp-scale margin
    qs = jnp.maximum(jnp.asarray(qs, dtype=jnp.float32) * (1.0 - 1e-6), 1e-9)
    total = jnp.sum(hist)
    cdf = jnp.cumsum(hist) / jnp.maximum(total, 1.0)
    # first depth whose cdf >= q
    return jnp.argmax(cdf[None, :] >= qs[:, None], axis=1).astype(jnp.int32)


def stats_from_histogram(hist: jnp.ndarray) -> dict[str, jnp.ndarray]:
    """mean/std/median + fraction-at-least thresholds, all from one histogram."""
    depths = jnp.arange(hist.shape[0], dtype=jnp.float32)
    total = jnp.maximum(jnp.sum(hist), 1.0)
    p = hist / total
    mean = jnp.sum(p * depths)
    var = jnp.sum(p * (depths - mean) ** 2)
    cdf = jnp.cumsum(p)
    median = jnp.argmax(cdf >= 0.5).astype(jnp.float32)
    out = {"mean": mean, "std": jnp.sqrt(var), "median": median}
    for thr in (1, 5, 10, 20, 50, 100):
        frac = jnp.sum(jnp.where(depths >= thr, p, 0.0))
        out[f"percent_larger_than_{thr:02d}x"] = 100.0 * frac
    # genome-stability style metrics: fraction within 25%-175% of median
    lo, hi = 0.25 * median, 1.75 * median
    out["percent_between_25_and_175_of_median"] = 100.0 * jnp.sum(
        jnp.where((depths >= lo) & (depths <= hi), p, 0.0)
    )
    return out


@jax.jit
def interval_histograms(depth: jnp.ndarray, interval_masks: jnp.ndarray) -> jnp.ndarray:
    """(K, MAX+1) histograms for K interval masks over one depth vector.

    One one-hot matmul on the MXU: (K, N) mask x (N, B) one-hot depth.
    Used for modest N per call (chunked by the caller).
    """
    onehot = jax.nn.one_hot(jnp.clip(depth, 0, MAX_DEPTH_BIN), MAX_DEPTH_BIN + 1, dtype=jnp.float32)
    return jnp.asarray(interval_masks, jnp.float32) @ onehot


def mask_from_intervals(length: int, starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Dense bool mask for [start, end) intervals over a contig (host-side)."""
    diff = np.zeros(length + 1, dtype=np.int32)
    np.add.at(diff, np.clip(starts, 0, length), 1)
    np.add.at(diff, np.clip(ends, 0, length), -1)
    return np.cumsum(diff[:-1]) > 0
