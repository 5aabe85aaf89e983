"""Halo exchange over position-sharded sequence data (shard_map + ppermute).

The framework's sequence axis is genomic position (SURVEY §5.7: contig/
window sharding is the long-context analog). Kernels whose stencil peeks
past a shard edge — motif windows (±5 bp), hpol proximity (±12 bp),
run-length scans — need their neighbors' edge bases. ``halo_exchange_1d``
is that primitive: inside a ``shard_map`` body, each shard ppermutes its
edges to its neighbors over ICI, so the composed program reads
``[left halo | local block | right halo]`` with no host gather and no
re-materialized global array.

``sharded_run_lengths`` composes it with the run-length scan
(:mod:`variantcalling_tpu.ops.runs`): runs crossing a shard edge keep
their exact length up to the halo cap.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from variantcalling_tpu.parallel.mesh import DATA_AXIS, pad_to_multiple


def halo_exchange_1d(block: jnp.ndarray, halo_left: int, halo_right: int,
                     axis_name: str = DATA_AXIS, fill=0,
                     n_shards: int | None = None) -> jnp.ndarray:
    """Pad a shard's local block with its neighbors' edges (traceable,
    call inside a shard_map body).

    Boundary shards (no neighbor on that side) read ``fill``. ppermute
    delivers zeros to devices with no source, so non-zero fills overwrite
    by shard index.

    ``n_shards`` is the STATIC mesh-axis size (the ppermute permutation
    is a Python list, not a traced value); None reads it from the
    enclosing ``shard_map`` with ``jax.lax.axis_size``.
    """
    if n_shards is None:
        n_shards = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    parts = [block]
    if halo_left:
        # my left halo = left neighbor's tail: shard i sends tail -> i+1
        tail = block[-halo_left:]
        recv = jax.lax.ppermute(tail, axis_name,
                                [(i, i + 1) for i in range(n_shards - 1)])
        if fill != 0:
            recv = jnp.where(idx > 0, recv, jnp.full_like(recv, fill))
        parts.insert(0, recv)
    if halo_right:
        head = block[:halo_right]
        recv = jax.lax.ppermute(head, axis_name,
                                [(i, i - 1) for i in range(1, n_shards)])
        if fill != 0:
            recv = jnp.where(idx < n_shards - 1, recv, jnp.full_like(recv, fill))
        parts.append(recv)
    return jnp.concatenate(parts)


def sharded_run_lengths(codes: np.ndarray, mesh: Mesh, halo: int = 256,
                        fill: int = 255,
                        min_halo: int | None = None) -> tuple[np.ndarray, np.ndarray]:
    """(run_starts bool, run_lengths int32) for a position-sharded genome.

    The sequence is padded to a dp multiple with an OUT-OF-BAND code
    (255 — not any base encoding, including N=4, so padding can never
    extend a run of real bases or Ns), sharded over the mesh dp axis, and
    each shard computes the run scan over
    ``[1-left-halo | local | halo-right]``:

    - the 1-base LEFT halo decides whether a local position starts a run;
    - the ``halo``-base RIGHT halo lets a run that crosses the right edge
      keep counting — exact for runs up to ``halo`` past the shard end
      (longer runs report the cap; biological hpols sit far below it).
    """
    from variantcalling_tpu.ops import runs as rops

    n = len(codes)
    n_dp = mesh.shape[DATA_AXIS]
    padded, _ = pad_to_multiple(np.asarray(codes, np.uint8), n_dp, fill=fill)
    # a halo is at most one whole neighbor block (ppermute moves block
    # edges, not transitive chains)
    halo = min(halo, len(padded) // n_dp)
    if min_halo is not None and halo < min_halo:
        raise ValueError(
            f"effective halo {halo} (shards of {len(padded) // n_dp}) is below the "
            f"caller's correctness floor {min_halo}; use fewer shards or the "
            "single-device scan for short sequences")

    def body(local):
        ext = halo_exchange_1d(local, 1, halo, fill=fill, n_shards=n_dp)
        starts = rops.run_starts(ext)[1:-halo] if halo else rops.run_starts(ext)[1:]
        lengths = rops.run_lengths(ext)[1:-halo] if halo else rops.run_lengths(ext)[1:]
        return starts, lengths

    fn = jax.shard_map(body, mesh=mesh, in_specs=P(DATA_AXIS),
                       out_specs=(P(DATA_AXIS), P(DATA_AXIS)))
    with mesh:
        starts, lengths = jax.jit(fn)(jnp.asarray(padded))
    starts = np.asarray(starts)[:n]
    lengths = np.asarray(lengths)[:n]
    return starts, lengths
