"""Device-data-parallel scoring dispatch — the filter hot path on a mesh.

After the parallel host-IO work (docs/streaming_executor.md "Parallel
host IO") the streaming filter executor is compute-bound on SCORING; this
module is ROADMAP item 2's answer: score on more than one device. The
run resolves ONE data-parallel mesh plan (``VCTPU_MESH_DEVICES``, next to
the engine and forest-strategy decisions), the fused featurize+score
program runs inside a ``shard_map`` over the mesh ``dp`` axis — each
device scores its shard of a device-count-multiple megabatch with the
run's pinned strategy — and per-chunk scores unpack back into canonical
chunk order before render/writeback.

Byte parity is the hard invariant (the PR 2 contract, extended to the
mesh layout): ``shard_map`` over the data axis is a pure MAP — every
variant's per-tree margins still reduce through the ONE shared
``forest.sequential_tree_sum`` inside its device's program and finalize
through ``forest.finalize_margin`` on the host, and devices exchange
NOTHING (no psum over margins — vctpu-lint VCT009 guards the merge
site), so output bytes are identical at every device count x engine x
strategy. The mesh layout is still recorded: ``##vctpu_mesh=dp=N``
header provenance when N > 1, the journal resume identity pins the
device count (a resume under a different count RESTARTS cleanly — the
header bytes differ, so splicing is impossible by construction), and
per-device obs attribution rides ``score.dN`` profile rows.

Testable on CPU: ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
provides N virtual devices (tests/conftest.py forces 8), so the parity
matrix runs in any container; real multi-host meshes light up through
the PR 5 collectives capability probe.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass

import numpy as np

import jax

from variantcalling_tpu import knobs, logger, obs
from variantcalling_tpu.parallel.pipeline import LadderEscalation

#: VCF header key recording the mesh layout of a >1-device run
MESH_HEADER_KEY = "vctpu_mesh"


class MeshDegradeRestart(LadderEscalation):
    """Device OOM survived the megabatch-shrink rung of the recovery
    ladder: the streaming run must RESTART on a dp=1 plan. A mid-run mesh
    change can never splice — the resume identity and the output header
    both pin the mesh layout (PR-2 contract) — so the supervisor
    (``pipelines/filter_variants.run_streaming``) discards the journal
    and re-runs the whole stream single-device (docs/robustness.md
    "Recovery ladder")."""

    def __init__(self, devices: int, cause: BaseException):
        super().__init__(
            f"device OOM survived megabatch shrink at dp={devices}; "
            f"degrading the run to dp=1 ({type(cause).__name__}: {cause})")
        self.devices = devices


def is_oom(exc: BaseException) -> bool:
    """Does this exception look like device-memory exhaustion? XLA
    surfaces OOM as an ``XlaRuntimeError`` whose text leads with the
    ``RESOURCE_EXHAUSTED`` status code (jaxlib does not export a stable
    exception subclass for it), so classification is textual — plus
    Python's own ``MemoryError`` for host-side allocation failures."""
    if isinstance(exc, MemoryError):
        return True
    text = str(exc)
    return "RESOURCE_EXHAUSTED" in text or "out of memory" in text.lower()

#: default per-device megabatch rows when VCTPU_MESH_MEGABATCH_ROWS unset
MEGABATCH_ROWS_PER_DEVICE = 1 << 14


@dataclass(frozen=True)
class MeshPlan:
    """The run-level scoring-mesh decision — resolved ONCE per run by
    ``FilterContext`` next to the engine and forest strategy, then pinned
    into every scoring dispatch, the output header and the journal
    resume identity."""

    devices: int  # resolved dp size; 1 == single-device (no mesh program)
    requested: str  # "auto" or the explicit VCTPU_MESH_DEVICES value
    reason: str  # human-readable resolution rationale

    def header_line(self) -> str:
        return f"##{MESH_HEADER_KEY}=dp={self.devices}"


#: per-process mesh cache: (device count) -> Mesh. Meshes are cheap but
#: NamedSharding/jit caches key on mesh identity — one object per size
#: keeps every consumer (genome upload, chunk device_put, shard_map
#: program) on literally the same mesh. The lock (vctpu-lint VCT010)
#: keeps pool workers racing a cache miss from minting TWO Mesh objects
#: for one size — distinct identities would silently double every jit
#: cache entry keyed on the mesh.
_MESH_CACHE: dict[int, object] = {}
_MESH_CACHE_LOCK = threading.Lock()


def resolve_plan(engine_name: str) -> MeshPlan:
    """Resolve the scoring-mesh plan for a run scored by ``engine_name``.

    Policy (mirrors ``forest.resolve_strategy``): an EXPLICIT
    ``VCTPU_MESH_DEVICES`` is honored or the run dies loudly
    (EngineError, exit 2) — never silently clamped. Auto keeps one
    device on the cpu backend (forced-host CPU meshes are a test
    construct, opted into explicitly) and takes every local device on
    accelerators. The native C++ engine scores on the host — it has no
    XLA program to shard, so any requested mesh resolves to 1 with the
    reason recorded (the parity matrix still runs native legs at forced
    device counts; they are byte-identical by construction).
    """
    from variantcalling_tpu.engine import EngineError

    req = knobs.get_int("VCTPU_MESH_DEVICES")
    requested = "auto" if req is None else str(req)
    if engine_name == "native":
        return MeshPlan(1, requested,
                        "native engine: host C++ walk, no XLA program")
    n_local = len(jax.local_devices())
    if req is not None:
        if req > n_local:
            raise EngineError(
                f"VCTPU_MESH_DEVICES={req} exceeds the {n_local} local "
                "device(s) — shrink the request or force host devices "
                "(XLA_FLAGS=--xla_force_host_platform_device_count=N). "
                "See docs/streaming_executor.md 'Mesh-sharded scoring'.")
        return MeshPlan(req, requested, "explicitly requested")
    backend = jax.default_backend()
    if backend == "cpu":
        return MeshPlan(1, requested,
                        "auto: cpu backend scores single-device "
                        "(set VCTPU_MESH_DEVICES to force a host mesh)")
    return MeshPlan(n_local, requested,
                    f"auto: {backend} backend, all {n_local} local devices")


def mesh_for(plan: MeshPlan):
    """The (dp, mp=1) Mesh of a >1-device plan (None for devices == 1).

    One Mesh object per device count per process — jit/NamedSharding
    caches key on mesh identity, so every consumer must share it."""
    if plan.devices <= 1:
        return None
    mesh = _MESH_CACHE.get(plan.devices)
    if mesh is None:
        from variantcalling_tpu.parallel.mesh import make_mesh

        with _MESH_CACHE_LOCK:
            mesh = _MESH_CACHE.get(plan.devices)
            if mesh is None:
                mesh = make_mesh(n_data=plan.devices, n_model=1,
                                 devices=jax.local_devices()[: plan.devices])
                _MESH_CACHE[plan.devices] = mesh
    return mesh


def shard_program(fn, mesh, n_data_args: int, replicated_leading: int = 0):
    """Wrap an UNJITTED scoring program body in a ``shard_map`` over the
    mesh data axis: the first ``replicated_leading`` arguments are
    replicated (the HBM-resident genome), the next ``n_data_args``
    shard their leading axis over ``dp`` (pytree-prefix specs, so a
    tuple-of-columns argument shards every leaf). The output is the
    per-variant margin/score vector, concatenated over ``dp``.

    This is a pure data-parallel MAP — the body contains no collectives;
    per-tree margins reduce inside each device's program through the one
    sanctioned ``forest.sequential_tree_sum`` (vctpu-lint VCT009 flags
    any cross-device margin reduction introduced here later)."""
    from jax.sharding import PartitionSpec as P

    from variantcalling_tpu.parallel.mesh import DATA_AXIS

    dp = P(DATA_AXIS)
    in_specs = tuple([P()] * replicated_leading + [dp] * n_data_args)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=dp)


def resolve_megabatch_rows(devices: int) -> int:
    """Target rows per streaming megabatch: enough to fill every device's
    shard (``MEGABATCH_ROWS_PER_DEVICE`` each) unless overridden."""
    rows = knobs.get_int("VCTPU_MESH_MEGABATCH_ROWS")
    if rows is not None:
        return rows
    return max(1, devices) * MEGABATCH_ROWS_PER_DEVICE


def pack_lengths(lengths: list[int]) -> list[tuple[int, int]]:
    """(start, stop) slices of each chunk inside the packed megabatch —
    canonical chunk order is the packing order, so unpacking is pure
    slicing (no reorder)."""
    spans = []
    lo = 0
    for n in lengths:
        spans.append((lo, lo + n))
        lo += n
    return spans


def unpack_scores(scores: np.ndarray, lengths: list[int]) -> list[np.ndarray]:
    """Split one packed megabatch score vector back into per-chunk score
    arrays, in canonical chunk order."""
    total = sum(lengths)
    if len(scores) != total:
        raise ValueError(
            f"packed scores have {len(scores)} rows, chunks sum to {total}")
    return [scores[lo:hi] for lo, hi in pack_lengths(lengths)]


def megabatch_stream(prepped, ctx, profiler=None):
    """Pack the streaming executor's chunk stream into device-count-sized
    megabatches, score each with ONE mesh dispatch, and yield per-chunk
    ``(table, score, filters)`` items in canonical chunk order.

    ``prepped`` yields ``(table, host_features)`` pairs in chunk order
    (host featurization fans out on the IO pool upstream). Consecutive
    chunks accumulate until the megabatch target
    (:func:`resolve_megabatch_rows`); the group scores through
    ``FilterContext.score_packed`` — one padded, dp-sharded device
    dispatch — and scores unpack back per chunk by slicing, so the
    stage downstream (render/writeback) sees exactly the serial chunk
    sequence. Per-device obs attribution: every dispatch adds a
    ``score.dN`` profile row per device (the devices run the same-shape
    shards in lockstep, so each device row carries the dispatch wall and
    its share of the records; ``vctpu obs bottleneck`` merges the family
    like the ``.wN`` worker families).

    ZERO-WAIT FEED (docs/streaming_executor.md "Overlapped megabatch
    dispatch"): the scoring dispatch runs on a dedicated one-worker
    dispatch pool with at most ONE group in flight — while group N
    scores, this generator keeps pulling ``prepped`` and PACKS group
    N+1, so the dispatch never sits idle waiting for the slowest member
    of the next group to featurize (``score_stage.wait`` was the
    dominant p95 critical-path edge before the overlap).
    Results still yield strictly in canonical chunk order: group N's
    scores are drained before group N+1's dispatch is submitted, and
    memory stays bounded at two groups (one in flight + one packing).
    ``VCTPU_MESH_OVERLAP=0`` restores the synchronous pack-then-score
    loop. The span ``megabatch_pack`` (this generator's thread) runs from
    a group's first chunk to its hand-over: the pulls of its further
    chunks from ``prepped`` and, in overlap mode, the drain of the group
    before it with the hand-down of that group's results — what the
    dispatch worker stands idle for, less the part of the drain it was
    still scoring in. Recovery semantics are unchanged — the whole ladder runs
    inside the dispatched body, and its escalations
    (:class:`MeshDegradeRestart`) surface when the group is drained.

    SUPERVISED dispatch (docs/robustness.md "Recovery ladder"): a failed
    megabatch never kills the run outright. Device OOM
    (``RESOURCE_EXHAUSTED``) first SHRINKS the packing target (halved for
    the rest of the stream) and re-dispatches the group chunk by chunk;
    a chunk that still OOMs alone escalates to
    :class:`MeshDegradeRestart` (the supervisor restarts the run at
    dp=1). Any other megabatch failure re-dispatches chunk by chunk so a
    poison chunk cannot take its group down with it; the poison chunk
    itself gets the bounded ``retry_chunk`` budget and then either
    fails the run loudly (default) or — ``VCTPU_QUARANTINE=1`` — yields
    a ``(table, None, None)`` quarantine marker for the render stage to
    divert. A ``(table, None)`` pair from upstream (featurize-stage
    quarantine) passes through as the same marker, after flushing the
    pending group so canonical chunk order is preserved.
    """
    from variantcalling_tpu.engine import EngineError
    from variantcalling_tpu.parallel.pipeline import (StageTimeoutError,
                                                      record_quarantine,
                                                      retry_chunk)
    from variantcalling_tpu.utils import faults
    from variantcalling_tpu.utils.trace import stage

    devices = ctx.mesh_plan.devices
    state = {"target": resolve_megabatch_rows(devices)}

    def dispatch(group):
        rows = sum(len(t) for t, _ in group)
        # injection point: the OOM/shrink/degrade ladder is proven
        # against this (tests/unit/test_streaming_faults.py)
        faults.check("xla.dispatch_oom")
        # one measurement: the dispatch's span and histogram (trace.stage,
        # parent of the feed/enqueue/wait spans inside), the fan-in causal
        # span and the per-device rows below
        with stage("score_stage", rows=rows, chunks=len(group)) as sp:
            scored = ctx.score_packed(group)
        dt = sp.seconds
        if obs.tracing():
            # megabatch FAN-IN: one dispatch span, MANY chunk parents —
            # the event lists every member trace id and parents to each
            # member's last span, and every member's cursor advances to
            # this span, so each chunk's DAG walks through the shared
            # dispatch (docs/observability.md "Causal chunk tracing")
            tids = [t for t in (getattr(tab, "_obs_trace", None)
                                for tab, _ in group) if t is not None]
            if tids:
                parents = [c for c in (obs.trace_cursor(t) for t in tids)
                           if c is not None]
                obs.trace_span(tids[0], "score_stage", dt, parents=parents,
                               traces=tids, chunks=len(group), rows=rows)
        if profiler is not None:
            share = rows // devices
            for d in range(devices):
                # lockstep data-parallel shards: each device works the
                # dispatch wall on its share of the rows; the family
                # merges to one `score xN` row at N-device capacity
                profiler.stage(f"score.d{d}", parent="score_stage").add_work(
                    dt, records=share + (rows - share * devices
                                         if d == devices - 1 else 0))
        return scored

    def quarantined(pair, exc):
        table = pair[0]
        record_quarantine("mesh chunk dispatch", len(table), exc)
        return table, None, None

    def chunk_supervised(pair):
        """One chunk through the per-chunk ladder: bounded re-dispatch,
        then OOM escalation or (opt-in) quarantine. The chunk's trace is
        bound to the thread for the duration so every ladder event
        (chunk_retry, quarantine) links to the chunk it recovers."""
        with obs.trace_scope(getattr(pair[0], "_obs_trace", None)):
            try:
                return retry_chunk(lambda: dispatch([pair]),
                                   "mesh chunk dispatch")
            except (EngineError, StageTimeoutError):
                raise
            # routed through degrade.record (quarantine) or re-raised
            except Exception as e:  # noqa: BLE001  # vctpu-lint: disable=VCT002 — quarantine records via degrade.record; every other path re-raises
                if is_oom(e):
                    raise MeshDegradeRestart(devices, e) from e
                if not knobs.get_bool("VCTPU_QUARANTINE"):
                    raise
                return [quarantined(pair, e)]

    def flush(group):
        try:
            scored = dispatch(group)
        except (EngineError, StageTimeoutError):
            raise
        # recovery ladder — every path below re-dispatches or re-raises
        except Exception as e:  # noqa: BLE001  # vctpu-lint: disable=VCT002 — ladder re-dispatches chunk by chunk; failures re-raise from chunk_supervised
            # causal linkage: every ladder event names the member chunks'
            # traces — the failed megabatch is a fan-in of all of them
            tids = [t for t in (getattr(tab, "_obs_trace", None)
                                for tab, _ in group) if t is not None]
            if is_oom(e):
                # rung: megabatch SHRINK — halve the packing target for
                # the rest of the stream, re-dispatch chunk by chunk
                state["target"] = max(1, state["target"] // 2)
                if obs.active():
                    obs.event("recovery", "megabatch_shrink",
                              rows=sum(len(t) for t, _ in group),
                              new_target=state["target"],
                              trace_ids=tids,
                              error=f"{type(e).__name__}: {e}")
                    obs.counter("recovery.megabatch_shrinks").add(1)
                logger.warning(
                    "mesh megabatch dispatch hit device OOM (%s); shrinking "
                    "the megabatch target to %d rows and re-dispatching "
                    "chunk by chunk", e, state["target"])
            else:
                # rung: megabatch SPLIT — one poison chunk must not take
                # its whole group down with it
                if obs.active():
                    obs.event("recovery", "megabatch_split",
                              chunks=len(group), trace_ids=tids,
                              error=f"{type(e).__name__}: {e}")
                    obs.counter("recovery.megabatch_splits").add(1)
            scored = []
            for pair in group:
                scored.extend(chunk_supervised(pair))
        return list(scored)

    from variantcalling_tpu.parallel.pipeline import IoPool

    pool = IoPool(1, name="vctpu-mesh-dispatch") \
        if knobs.get_bool("VCTPU_MESH_OVERLAP") else None
    pending = None  # the ONE in-flight dispatch future (overlap mode)

    def drain():
        """Results of the in-flight dispatch, in order; re-raises its
        failure (the ladder already ran inside the dispatched body)."""
        nonlocal pending
        if pending is None:
            return []
        out, pending = pending.result(), None
        return out

    group: list = []
    rows = 0
    pack = None  # the open ``megabatch_pack`` span of the group being packed

    def packed():
        """Close the group's pack span: the group is handed over now."""
        nonlocal pack
        pack.set(rows=rows, chunks=len(group))
        pack.__exit__(None, None, None)
        pack = None

    try:
        for table, hf in prepped:
            if hf is None:
                # featurize-stage quarantine marker from upstream: drain
                # the in-flight dispatch and flush the pending group first
                # (canonical chunk order), then pass the marker straight
                # through to the render/quarantine path
                yield from drain()
                if group:
                    packed()
                    yield from flush(group)
                    group, rows = [], 0
                yield (table, None, None)
                continue
            if not group:
                pack = stage("megabatch_pack")
                pack.__enter__()
            group.append((table, hf))
            rows += len(table)
            if rows >= state["target"]:
                if pool is None:
                    packed()
                    yield from flush(group)
                else:
                    # overlap: drain group N's results, hand group N+1 to
                    # the dispatch worker, keep packing group N+2 from
                    # ``prepped`` while it scores
                    yield from drain()
                    packed()
                    pending = pool.submit(flush, group)
                group, rows = [], 0
        yield from drain()
        if group:
            packed()
            yield from flush(group)
    finally:
        if pack is not None:  # the stream ended inside a group: no span
            pack.__exit__(GeneratorExit, None, None)
        if pool is not None:
            pool.shutdown()


def log_plan(plan: MeshPlan) -> None:
    """Record the per-run mesh resolution (obs resolve event + log line),
    the same shape the engine/strategy decisions emit."""
    if obs.active():
        obs.event("resolve", "mesh", value=str(plan.devices),
                  requested=plan.requested, reason=plan.reason)
        # once a run: a run that fell to dp=1 (or restarted there: 4 + 1)
        # cannot pass for a four-chip reading
        obs.counter("mesh.devices").add(plan.devices)
    if plan.devices > 1:
        logger.info("scoring mesh: dp=%d (%s)", plan.devices, plan.reason)
