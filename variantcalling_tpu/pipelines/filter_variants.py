"""filter_variants_pipeline — ML filtering of a called VCF on TPU.

Drop-in surface of the reference tool (docs/filter_variants_pipeline.md:
same flags), re-founded: VCF -> columnar table -> featurization + forest
inference as one jitted device program over the variants axis -> VCF
writeback with TREE_SCORE / PASS / LOW_SCORE / COHORT_FP / HPOL_RUN.

Hot-path structure (BASELINE north_star): per-variant work is a (N, F)
tensor; scoring shards over the mesh dp axis; chunked execution bounds
host memory with one compile per chunk shape.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import logging
import os
import pickle
import sys
import threading
import weakref
from typing import Any, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from variantcalling_tpu import engine as engine_mod
from variantcalling_tpu import knobs, logger, obs, wire
from variantcalling_tpu.engine import EngineError
from variantcalling_tpu.utils import degrade, keyed_cache
from variantcalling_tpu.utils.trace import note, stage, timed
from variantcalling_tpu.featurize import host_featurize, standard_genome_sharding
from variantcalling_tpu.io import bed as bedio
from variantcalling_tpu.io.fasta import FastaReader
from variantcalling_tpu.io.vcf import FactorizedColumn, VariantTable, read_vcf, write_vcf
from variantcalling_tpu.models import dan as dan_mod
from variantcalling_tpu.models import forest as forest_mod
from variantcalling_tpu.models import registry as registry_mod
from variantcalling_tpu.models import threshold as threshold_mod
from variantcalling_tpu.models.dan import DanModel
from variantcalling_tpu.models.forest import FlatForest
from variantcalling_tpu.models.registry import load_model
from variantcalling_tpu.models.threshold import ThresholdModel
from variantcalling_tpu.ops import intervals as iops

#: model types that ride the fused featurize+score device program
#: (everything else falls back to the host predict_proba path)
_FUSED_MODEL_TYPES = (FlatForest, ThresholdModel, DanModel)

LOW_SCORE = "LOW_SCORE"
COHORT_FP = "COHORT_FP"
HPOL_RUN = "HPOL_RUN"
PASS = "PASS"
CHUNK = 1 << 18

#: sidecar collecting the ORIGINAL records of quarantined chunks
#: (``VCTPU_QUARANTINE=1`` — docs/robustness.md "Recovery ladder")
QUARANTINE_SUFFIX = ".quarantine"


def quarantine_path(out_path: str) -> str:
    return str(out_path) + QUARANTINE_SUFFIX


def _traced_chunks(tables):
    """Causal-tracing ingest boundary (docs/observability.md "Causal
    chunk tracing"): every chunk table gets a run-scoped TRACE id here —
    the root ``ingest`` span of its DAG — carried on the table object
    (``_obs_trace``) so every downstream stage (featurize, score,
    megabatch dispatch, render, compress, sequenced commit) and every
    recovery-ladder action can link its span/event to the chunk. A
    no-op pass-through when tracing is off (``obs.new_trace`` returns
    None). The wrapper wraps ALL four streaming layouts' sources, so
    trace ids are allocated in canonical chunk order everywhere."""
    import time as _time

    it = iter(tables)
    while True:
        t0 = _time.perf_counter()  # vctpu-lint: disable=VCT006 — obs trace-span timing
        try:
            table = next(it)
        except StopIteration:
            return
        tid = obs.new_trace()
        if tid is not None:
            table._obs_trace = tid
            obs.trace_span(tid, "ingest",
                           _time.perf_counter() - t0,  # vctpu-lint: disable=VCT006 — obs trace-span timing
                           records=len(table))
        yield table


def _guard_chunk(table, what: str, body):
    """Rung 3 of the supervised recovery ladder for one chunk body.

    Runs ``body()``; on failure either re-raises (the DEFAULT — byte
    parity stays untouchable, a poison chunk fails the run loudly) or,
    when ``VCTPU_QUARANTINE=1`` and this is the FINAL re-dispatch attempt
    of the chunk's retry budget (:func:`pipeline.on_final_attempt`),
    diverts the chunk by returning ``None`` — the render stage then
    writes the ORIGINAL records to the ``<out>.quarantine`` sidecar and
    zero bytes to the main output. Diversion is loud by construction: it
    routes through ``degrade.record(warn=True)`` and a ``recovery`` obs
    event, so no record can leave the output silently.
    """
    from variantcalling_tpu.parallel import pipeline as pipeline_mod
    from variantcalling_tpu.utils import faults

    try:
        # injection point: deterministic per-chunk poison
        # (tests/unit/test_streaming_faults.py, tools/chaoshunt)
        faults.check("pipeline.chunk")
        return body()
    except (EngineError, pipeline_mod.StageTimeoutError,
            pipeline_mod.LadderEscalation):
        raise
    # quarantine records via degrade.record; every other path re-raises
    except Exception as e:  # noqa: BLE001  # vctpu-lint: disable=VCT002 — opt-in quarantine routes through degrade.record(warn=True) in record_quarantine; default re-raises
        if not knobs.get_bool("VCTPU_QUARANTINE") \
                or not pipeline_mod.on_final_attempt():
            raise
        pipeline_mod.record_quarantine(what, len(table), e)
        return None


def get_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="filter_variants_pipeline", description="Filter VCF")
    ap.add_argument("--input_file", required=True, help="Name of the input VCF file")
    ap.add_argument("--model_file", required=True, help="Pickle model file")
    ap.add_argument("--model_name", required=True, help="Model name inside the pickle")
    ap.add_argument(
        "--hpol_filter_length_dist",
        nargs=2,
        type=int,
        default=[10, 10],
        help="Length and distance to the hpol run to mark",
    )
    ap.add_argument("--runs_file", help="Homopolymer runs BED file")
    ap.add_argument("--blacklist", help="Blacklist file (bed/h5/pkl of loci)")
    ap.add_argument("--blacklist_cg_insertions", action="store_true", help="Filter CCG/GGC insertions")
    ap.add_argument("--reference_file", required=True, help="Indexed reference FASTA file")
    ap.add_argument("--output_file", required=True, help="Output VCF file")
    ap.add_argument("--is_mutect", action="store_true", help="Input is a Mutect callset")
    ap.add_argument("--flow_order", default="TGCA", help="Sequencing flow order (4 cycle)")
    ap.add_argument(
        "--annotate_intervals",
        action="append",
        default=[],
        help="interval files for annotation (multiple possible)",
    )
    ap.add_argument("--backend", default=None, choices=["tpu", "cpu"],
                    help="cpu pins JAX to the CPU platform; tpu requires a "
                         "TPU or exits 2; default: whatever JAX initializes")
    ap.add_argument("--limit_to_contig", default=None, help="Process a single contig")
    return ap


def _interval_name(path: str) -> str:
    base = os.path.basename(path)
    for suffix in (".bed.gz", ".bed", ".interval_list"):
        if base.endswith(suffix):
            return base[: -len(suffix)]
    return base


def read_blacklist(path: str) -> tuple[np.ndarray, np.ndarray]:
    """Blacklist loci -> (chrom object array, pos 1-based). Accepts bed/h5/pkl."""
    if path.endswith((".bed", ".bed.gz")):
        iv = bedio.read_bed(path)
        return iv.chrom, (iv.start + 1).astype(np.int64)
    if path.endswith((".h5", ".hdf", ".hdf5")):
        from variantcalling_tpu.utils.h5_utils import list_keys, read_hdf

        df = read_hdf(path, key=list_keys(path)[0])
        if isinstance(df.index, __import__("pandas").MultiIndex):
            df = df.reset_index()
        return df["chrom"].to_numpy(dtype=object), df["pos"].to_numpy(dtype=np.int64)
    with open(path, "rb") as fh:
        obj = pickle.load(fh)
    chroms, poss = zip(*obj) if obj else ((), ())
    out_c = np.empty(len(chroms), dtype=object)
    out_c[:] = chroms
    return out_c, np.asarray(poss, dtype=np.int64)


def _is_cg_insertion(table: VariantTable, windows: np.ndarray, center: int) -> np.ndarray:
    """CCG/GGC insertion artifacts (--blacklist_cg_insertions,
    docs/filter_variants_pipeline.md "Should CCG/GGC insertions be filtered out?").

    A single-base insertion of C between C and G (anchor C, next ref base G
    -> CCG) or of G between G and C (anchor G, next C -> GGC). Vectorized:
    the inserted base is the native scan's indel_nuc (single-base diff), the
    anchor and next reference base come from the gathered window tensor.
    """
    n = len(table)
    from variantcalling_tpu.featurize import classify_alleles

    alle = classify_alleles(table)
    aux = table.aux
    if aux is not None:
        prefix_ins = (aux.alle["aclass"] & 8).astype(bool)
        ref_len = aux.alle["ref_len"]
    else:
        ref_len = np.fromiter(map(len, table.ref), dtype=np.int64, count=n)
        alt0_len = np.fromiter(
            (len(a) if "," not in a else a.index(",") for a in table.alt), dtype=np.int64, count=n
        )
        cand = alle.is_ins & (alt0_len == ref_len + 1)
        prefix_ins = np.zeros(n, dtype=bool)
        for i in np.nonzero(cand)[0]:
            prefix_ins[i] = table.alt[i].split(",")[0].startswith(table.ref[i])
    # single-base left-anchored insertion; anchor base = ref[-1]. The window
    # is centered on POS (first ref base), so anchor sits at center+ref_len-1
    # and the next reference base right after it.
    cand = alle.is_ins & prefix_ins & (alle.indel_length == 1)
    anchor_idx = np.minimum(center + ref_len - 1, windows.shape[1] - 1)
    next_idx = np.minimum(anchor_idx + 1, windows.shape[1] - 1)
    rows = np.arange(n)
    anchor = windows[rows, anchor_idx].astype(np.int32)
    nxt = windows[rows, next_idx].astype(np.int32)
    ins = alle.indel_nuc  # C=1, G=2
    return cand & (((ins == 1) & (anchor == 1) & (nxt == 2)) | ((ins == 2) & (anchor == 2) & (nxt == 1)))


# Compiled predictors, one per model CONTENT per process. The key is
# (kind, registry.content_digest(model), feature order[, flow order,
# genome_resident], strategy token, mesh): every main() call unpickles its
# model anew, so a key on id(model) missed once per file and per pooled
# worker, and each miss made a jax.jit object whose first call per bucket
# traced, lowered and loaded its own copy of one executable. Two models of
# equal content now share one program, so there is no `is model` test on a
# hit: the digest covers everything a program closes over. A miss is single
# flight (utils/keyed_cache.py): one thread builds, the threads that want
# the same key meanwhile wait and take its result or its exception. Bounded
# FIFO so a long-lived process scoring many models does not accumulate
# compiled programs forever; `.clear()` empties it and its in-flight table.
_PREDICTOR_CACHE_MAX = 8
_PREDICTOR_CACHE = keyed_cache.KeyedCache(_PREDICTOR_CACHE_MAX)

#: ``jax.named_scope`` names of the fused program's three parts
SCOPE_WINDOW_GATHER = "vctpu_window_gather"
SCOPE_WINDOW_FEATURES = "vctpu_window_features"
SCOPE_MODEL = "vctpu_model"

#: how a lookup ended -> its counter
_LOOKUP_COUNTER = {keyed_cache.HIT: "predictor.reuses",
                   keyed_cache.BUILT: "predictor.builds",
                   keyed_cache.WAITED: "predictor.waits"}


def _cached_program(key: tuple, build):
    """The program under ``key``, built by ``build()`` on a miss (counters
    ``predictor.builds`` / ``predictor.reuses`` / ``predictor.waits``; the
    enclosing ``fused_program`` span learns ``built`` or ``waited``)."""
    value, how = _PREDICTOR_CACHE.get(key, build)
    obs.counter(_LOOKUP_COUNTER[how]).add(1)
    if how == keyed_cache.BUILT:
        note(built=True)
    elif how == keyed_cache.WAITED:
        note(waited=True)
    return value


def _strategy_token(strategy: str | None) -> tuple:
    """Predictor-cache key component: the pinned strategy (or the live env
    request) PLUS the wide-path knobs — tests flip these between calls,
    and a cached program compiled under the old values must not answer
    for the new."""
    return (strategy or knobs.raw(forest_mod.FOREST_STRATEGY_ENV) or "auto",
            knobs.raw(forest_mod.WIDE_CHUNK_ENV) or "",
            knobs.raw(forest_mod.WIDE_BLOCK_ENV) or "")


def _raw_predictor(model, feature_names: list[str], strategy: str | None = None,
                   sharding=None):
    """-> (:class:`forest_mod.MarginProgram`, host_finalize|None).

    The program's ``fn(weights, x)`` is jit-safe and takes the model's
    device operands as its first argument (``()`` where the tables are
    compiled in); ``host_finalize`` (if set) turns its fetched
    output into TREE_SCOREs on the host. FlatForests return canonical-order
    MARGINS from the strategy-resolved device program
    (:func:`forest_mod.make_margin_program` — gather walk, scan GEMM,
    wide-contraction GEMM or the pallas wide-block kernel, all bit-identical)
    and finalize through :func:`forest_mod.finalize_margin` — the same
    shared code the native engine uses, so every engine/strategy's score
    bits are identical by construction (sigmoid/exp is not bit-portable
    across XLA and libm). ``strategy`` pins the run-level resolution
    (FilterContext); None reads ``VCTPU_FOREST_STRATEGY``. ``sharding``
    places the weights (the mesh's replicated sharding, else None).
    """
    if isinstance(model, FlatForest):
        ordered = forest_mod.with_feature_order(model, feature_names)
        program = forest_mod.make_margin_program(
            ordered, len(feature_names), strategy=strategy, sharding=sharding)
        return program, (lambda m: forest_mod.finalize_margin(m, ordered))
    if isinstance(model, DanModel):
        # GEMM-native family: the fused forward pass IS the score (f32
        # end-to-end, docs/models.md) — no host finalize stage.
        fn = dan_mod.make_score_predictor(model, feature_names)
    else:
        def fn(xx):
            return threshold_mod.predict_score(model, xx, feature_names)
    return forest_mod.MarginProgram(lambda _w, xx: fn(xx), ()), None


def _predictor_for(model, feature_names: list[str], strategy: str | None = None,
                   mesh=None):
    key = ("x", registry_mod.content_digest(model), tuple(feature_names),
           _strategy_token(strategy), mesh)

    def build():
        program, finalize = _raw_predictor(model, feature_names, strategy=strategy,
                                           sharding=standard_genome_sharding(mesh))
        fn = program.fn
        if mesh is not None:
            # data-parallel mesh plan (>1 device): the SAME program body runs
            # per device over its dp shard of the feature matrix — a pure
            # map, margins never cross devices (docs/streaming_executor.md
            # "Mesh-sharded scoring"); the weights are replicated
            from variantcalling_tpu.parallel import shard_score

            fn = shard_score.shard_program(fn, mesh, n_data_args=1,
                                           replicated_leading=1)
        return functools.partial(jax.jit(fn), program.weights), finalize

    return _cached_program(key, build)


def _host_names(feature_names: list[str]) -> list[str]:
    """The columns the host sends to the fused program, in its argument
    order: every feature the device does not compute from the window."""
    from variantcalling_tpu.featurize import DEVICE_FEATURES

    return [f for f in feature_names if f not in DEVICE_FEATURES]


def _fused_program(model, feature_names: list[str], flow_order: str,
                   genome_resident: bool = False, strategy: str | None = None,
                   mesh=None):
    """One jitted device program: the wire -> TREE_SCORE; returns
    ``(jitted, layout, finalize)``.

    Fuses the window featurization kernels (gc/hmer/motif/cycle-skip) with
    model inference so only the per-variant score crosses back to the host
    — on TPU the feature tensors never leave HBM. Its arguments:

    - ``genome_resident=True``: ``(genome_rows, words)`` — the HBM-resident
      genome (``featurize.DeviceGenome.rows``, already on the device) and
      the dispatch's ONE buffer, ``uint32[rows, W/4]`` in the program's
      :class:`variantcalling_tpu.wire.WireLayout`: the packed position and
      every host column once, float32 or int32 where values need it, one
      byte for flags and base codes because of what they are. Windows are
      gathered on the device from the positions.
    - ``genome_resident=False``: ``(windows, words)`` — the ``(rows, 41)``
      uint8 host windows ride beside the same buffer (no position column).

    The layout is a function of the program's host columns
    (:func:`_host_names`) and of ``genome_resident``, both part of this
    cache key — never of a chunk's contents, so no data can cause a trace.
    The program unpacks the buffer (slices, shifts, same-width bitcasts) and
    assembles the f32 feature matrix on the device.
    """
    key = ("fused", registry_mod.content_digest(model), tuple(feature_names),
           flow_order, genome_resident, _strategy_token(strategy), mesh)
    return _cached_program(key, lambda: _build_fused_program(
        model, feature_names, flow_order, genome_resident, strategy, mesh))


class FusedProgram(NamedTuple):
    """What :func:`_fused_program` caches: the jitted entry
    ``fn(weights, genome_rows | windows, words)``, its wire ``layout``, the
    host ``finalize`` (None: the program's output is the score), the
    model's device operands ``weights`` that every dispatch hands it (one
    copy whatever the bucket size: the gather walk's node table, else
    ``()``), and ``walk_levels``, the levels one call walks (0: no walk)."""

    fn: Any
    layout: wire.WireLayout
    finalize: Any
    weights: Any
    walk_levels: int


def _build_fused_program(model, feature_names, flow_order, genome_resident,
                         strategy, mesh) -> FusedProgram:
    """A miss of :func:`_fused_program`."""
    from variantcalling_tpu.featurize import (CENTER, device_feature_dict,
                                              windows_from_packed)

    # This is the JIT engine's program: featurize + forest inference fused
    # into one device program (engine contract, docs/robustness.md — the
    # native engine short-circuits in fused_featurize_score and never
    # reaches here, so no native split hides inside the "jit" engine).
    # FlatForest programs return margins and `finalize` (shared with the
    # native engine) produces the final score bits on the host.
    program, finalize = _raw_predictor(model, feature_names, strategy=strategy,
                                       sharding=standard_genome_sharding(mesh))
    predictor = program.fn
    layout = wire.layout_for(tuple(_host_names(feature_names)), genome_resident)

    # The three parts carry names of their own into the compiled program
    # (jax.named_scope is metadata: no operation, byte or program moves),
    # so a device trace groups operations by part whatever their shapes.
    def body(weights, windows, col):
        with jax.named_scope(SCOPE_WINDOW_FEATURES):
            dev = device_feature_dict(windows, col["is_indel"].astype(bool),
                                      col["indel_nuc"].astype(jnp.int32),
                                      col["ref_code"].astype(jnp.int32),
                                      col["alt_code"].astype(jnp.int32),
                                      col["is_snp"].astype(bool),
                                      center=CENTER, flow_order=flow_order)
            cols = [
                (dev[f] if f in dev else col[f]).astype(jnp.float32)
                for f in feature_names
            ]
            x = jnp.stack(cols, axis=1)
        with jax.named_scope(SCOPE_MODEL):
            return predictor(weights, x)

    if genome_resident:
        def fn(weights, genome_rows, words):
            col = wire.unpack(layout, words)
            with jax.named_scope(SCOPE_WINDOW_GATHER):
                windows = windows_from_packed(genome_rows, col["pos"])
            return body(weights, windows, col)
    else:
        def fn(weights, windows, words):
            return body(weights, windows, wire.unpack(layout, words))

    if mesh is not None:
        # the mesh-sharded layout: the SAME fused body runs per device
        # over its dp shard (weights and genome replicated, the buffer's —
        # and the windows' — leading axis sharded) — a pure map with no
        # collectives, so per-row score bits cannot depend on the device
        # count
        from variantcalling_tpu.parallel import shard_score

        fn = shard_score.shard_program(
            fn, mesh, n_data_args=1 if genome_resident else 2,
            replicated_leading=2 if genome_resident else 1)

    return FusedProgram(jax.jit(fn), layout, finalize, program.weights,
                        program.walk_levels)


def _fused_native_chunk_score(ordered, hf, fo: np.ndarray, table,
                              fasta) -> np.ndarray | None:
    """The single-call native chunk body (ROADMAP item 4): contig runs +
    encoded contigs + host columns + forest go across the ctypes boundary
    ONCE per chunk (``native.fused_chunk_score``) and canonical-order
    margins come back — window gather, featurize, matrix fill and the
    forest walk all happen tile-at-a-time in C++, with no intermediate
    feature columns and no per-call Python between them. Margins are
    bit-identical to the unfused reference path below (shared C++ row
    featurize / tile fill / walk; locked by the parity matrix in
    tests/unit/test_fused_native.py). Returns finalized scores, or None
    when this chunk cannot take the fused path (unsorted chunk, no
    native library) — the caller falls through to the reference path.
    """
    from variantcalling_tpu import native
    from variantcalling_tpu.featurize import (CENTER, DEVICE_FEATURES,
                                              _contig_runs)

    n = len(table)
    codes, uniques, bounds = _contig_runs(table, n)
    if bounds is None:  # unsorted chunk: reference path masks per contig
        return None
    empty = np.empty(0, dtype=np.uint8)
    seqs = [fasta.fetch_encoded(c) if c in fasta.references else empty
            for c in uniques]
    dev_cols = np.asarray(
        [hf.names.index(k) if k in hf.names else -1 for k in DEVICE_FEATURES],
        dtype=np.int32)
    cols = [None if f in DEVICE_FEATURES else np.asarray(hf.cols[f])
            for f in hf.names]
    alle = hf.alle
    margin = native.fused_chunk_score(
        seqs, bounds, table.pos - 1, CENTER,
        alle.is_indel, alle.indel_nuc, alle.ref_code, alle.alt_code,
        alle.is_snp, fo, cols, dev_cols,
        ordered.feature, ordered.threshold, ordered.left, ordered.right,
        ordered.value, ordered.default_left, ordered.max_depth, "sum", 0.0)
    if margin is None:
        return None
    return forest_mod.finalize_margin(margin, ordered)


def _native_cpu_featurize_score(model, hf, flow_order: str, table, fasta) -> np.ndarray | None:
    """All-native CPU hot path: numpy window gather + C++ featurize + C++
    forest walk; returns scores or None when the native engine cannot
    serve this batch.

    Engine contract (docs/robustness.md): the CALLER decides what None
    means. When the run's resolved engine is ``native``, None raises
    :class:`EngineError` — the pre-contract behavior of silently falling
    back to the jitted path made output bytes depend on machine load
    (round-5 VERDICT Weak #1) and is forbidden."""
    from variantcalling_tpu import native
    from variantcalling_tpu.featurize import CENTER, DEVICE_FEATURES, gather_windows
    from variantcalling_tpu.ops.features import A, C, G, T

    ordered = forest_mod.with_feature_order(model, hf.names)
    if not native.available() or ordered.aggregation not in ("mean", "logit_sum"):
        return None
    if hf.windows is None and (table is None or fasta is None):
        return None
    alle = hf.alle
    fo = np.asarray([{"A": A, "C": C, "G": G, "T": T}[c] for c in flow_order],
                    dtype=np.int32)
    if hf.windows is None and knobs.get_bool("VCTPU_NATIVE_FUSED"):
        # the fused per-chunk entry: ONE native call for the whole
        # parse-output -> featurize -> score body. The unfused path
        # below stays as the byte-parity reference (VCTPU_NATIVE_FUSED=0)
        score = _fused_native_chunk_score(ordered, hf, fo, table, fasta)
        if score is not None:
            forest_mod.last_strategy = "native-cpp"  # vctpu-lint: disable=VCT010 — run-scoped diagnostic; GIL-atomic store, every concurrent chunk writes the same value
            return score
    dev = None
    if hf.windows is None:
        # fused gather+featurize: windows stream out of the encoded contig
        # without ever materializing the (N, 41) tensor
        from variantcalling_tpu.featurize import featurize_gather_fused

        dev = featurize_gather_fused(table, fasta, alle, fo)
    if dev is None:
        windows = hf.windows if hf.windows is not None else gather_windows(table, fasta)
        dev = native.featurize_windows(windows, CENTER, alle.is_indel, alle.indel_nuc,
                                       alle.ref_code, alle.alt_code, alle.is_snp, fo)
    if dev is None:
        return None
    raw = [np.asarray(dev[f] if f in dev else hf.cols[f]) for f in hf.names]
    # fused column->tile->walk first: no (n, f) matrix ever materializes
    cf = forest_mod.native_cols_predictor(ordered)
    score = cf(raw) if cf is not None else None
    if score is None:
        nf = forest_mod.native_host_predictor(ordered, strict=True)
        if nf is None:
            return None
        x = native.build_matrix(raw)
        if x is None:  # unsupported column dtype: numpy assembly
            x = np.stack([c.astype(np.float32, copy=False) for c in raw], axis=1)
        score = nf(x)
    # no XLA program exists on this path — record that
    forest_mod.last_strategy = "native-cpp"  # vctpu-lint: disable=VCT010 — run-scoped diagnostic; GIL-atomic store, every concurrent chunk writes the same value
    return score


class _FusedInputs:
    """One chunk's prepared inputs for the fused featurize+score program —
    the unit :func:`_dispatch_fused` packs into device megabatches
    (parallel/shard_score.py). ``program`` is the cached
    :class:`FusedProgram`; chunks sharing it fill consecutive rows of
    one staging buffer, chunks that resolved a different layout dispatch
    alone. ``table`` feeds the wire's native fill (``hf.alle`` is None:
    ``hf.cols`` holds only the Python-made columns); a complete ``hf``
    feeds the numpy fill. ``strategy`` names the program a dispatch runs:
    the resolved forest strategy, ``jit`` for the other families."""

    __slots__ = ("n", "program", "genome", "gpos_fill", "windows", "table",
                 "hf", "strategy", "_columns")

    def __init__(self, n, program, genome, gpos_fill, windows, table, hf,
                 strategy: str = "jit"):
        self.n = n
        self.program = program
        self.genome = genome
        self.gpos_fill = gpos_fill
        self.windows = windows
        self.table = table
        self.hf = hf
        self.strategy = strategy
        self._columns = None

    def fill(self, buf, row0: int, lo: int, hi: int) -> bool:
        """Rows ``[lo, hi)`` of this chunk into ``buf`` from row ``row0``;
        True when the native fill wrote them. Where missing values are kept
        as NaN (a ``default_left`` model), counts ``feed.float_cells`` (the
        float32 cells of the rows written) and ``feed.nan_cells`` (those
        written as NaN)."""
        keep_nan = self.hf.keep_nan
        if buf.windows is not None:
            buf.windows[row0:row0 + (hi - lo)] = self.windows[lo:hi]
        native = self.hf.alle is None
        if native:
            nans = wire.fill_native(buf, row0, self.table, lo, hi, self.hf.cols,
                                    self.genome, keep_nan)
        else:
            if self._columns is None:  # once a chunk, however many buckets it spans
                from variantcalling_tpu.featurize import globalize_positions

                gpos = globalize_positions(self.table, self.genome) \
                    if self.genome is not None else None
                self._columns = wire.numpy_columns(buf.layout, self.hf, gpos)
            nans = wire.fill_numpy(buf, row0, self._columns, lo, hi, keep_nan)
        if keep_nan:
            obs.counter("feed.float_cells").add((hi - lo) * len(buf.layout.floats))
            obs.counter("feed.nan_cells").add(nans)
        return native


def _prepare_fused_inputs(model, hf, flow_order: str,
                          table: VariantTable | None = None,
                          fasta: FastaReader | None = None,
                          strategy: str | None = None,
                          plan=None) -> _FusedInputs:
    """Host half of the fused scoring path for ONE chunk: window/genome
    layout decision and program build (strategy + mesh pinned). The rows
    themselves are written by :func:`_dispatch_fused`, straight into the
    dispatch's staging buffer.

    With ``table``+``fasta`` and no precomputed host windows, the
    device-resident-genome path runs: the encoded genome lives in HBM
    (featurize.device_genome, replicated over the run's scoring mesh)
    and windows are gathered inside the fused program from 4-byte PACKED
    uint32 global positions. Genomes whose positions cannot pack into 4
    bytes (> ~4 Gbp incl. N gaps) fall back to the host window gather —
    checked from contig lengths before any encode/upload is paid.
    """
    from variantcalling_tpu.parallel import shard_score

    plan = plan or shard_score.resolve_plan("jit")
    mesh = shard_score.mesh_for(plan)
    with stage("prepare_inputs"):
        windows = hf.windows
        genome = None
        gpos_fill = 0
        genome_resident = windows is None and table is not None and fasta is not None
        if genome_resident:
            from variantcalling_tpu.featurize import (device_genome, gather_windows,
                                                      genome_packable,
                                                      packed_position_fill)

            if not genome_packable(fasta):
                # positions won't fit 4-byte packing (> ~4 Gbp incl. gaps):
                # host window gather, without paying the genome upload
                genome_resident = False
                windows = gather_windows(table, fasta)
            else:
                # replicate the genome across the run mesh so chunk dispatches
                # never reshard the multi-GB array (a 1-device plan falls
                # through to the process-default policy); the helper keeps
                # the cache key identical across every consumer
                from variantcalling_tpu.featurize import standard_genome_sharding

                genome = device_genome(
                    fasta, sharding=standard_genome_sharding(mesh))
                gpos_fill = packed_position_fill(genome)
        if genome_resident:
            obs.request_note(genome_resident=True)  # serve counts such requests

    with stage("fused_program", built=False, waited=False):
        program = _fused_program(model, hf.names, flow_order,
                                 genome_resident=genome_resident,
                                 strategy=strategy, mesh=mesh)
    n = len(table) if table is not None else len(windows)
    # the program's name for score.dispatches.<strategy>: a run pins its
    # forest strategy; an unpinned build resolved it as it was made
    label = (strategy or forest_mod.last_strategy) \
        if isinstance(model, FlatForest) else "jit"
    return _FusedInputs(n, program, genome, gpos_fill, windows, table, hf, label)


#: argument signatures each live jit object has been called at. A jit
#: object's first call at a signature traces, lowers and loads (or compiles)
#: an executable, and jax does not share that work between threads: pooled
#: workers that reach a new bucket size together would each do all of it.
_CALLED_AT: "weakref.WeakKeyDictionary[object, set]" = weakref.WeakKeyDictionary()
_CALLED_AT_LOCK = threading.Lock()
_FIRST_CALLS = keyed_cache.SingleFlight()


def _enqueue(fn, sig: tuple, call_args: tuple):
    """``fn(*call_args)``, the first call at ``sig`` made by one thread
    alone: threads that arrive with the same (program, ``sig``) meanwhile
    wait for it to return (or take its exception), then make their own,
    which finds the executable in place. Once a signature has been called
    the gate is one set lookup."""
    if sig in _CALLED_AT.get(fn, ()):
        return fn(*call_args)

    def first_call():
        res = fn(*call_args)
        with _CALLED_AT_LOCK:
            _CALLED_AT.setdefault(fn, set()).add(sig)
        return res

    res, waited = _FIRST_CALLS.do((id(fn), sig), first_call)
    if not waited:
        return res
    # the flight's result was the first caller's: a waiter makes its own call
    obs.counter("predictor.waits").add(1)
    note(waited=True)
    return fn(*call_args)


def _dispatch_fused(inputs: list[_FusedInputs], plan) -> np.ndarray:
    """Score one or more prepared chunks as padded device megabatches;
    returns the PACKED ``(sum(n),)`` score vector in chunk order (callers
    split per chunk with ``shard_score.unpack_scores``).

    Every input must share the same compiled program (the caller groups
    by ``program`` identity). The megabatch is cut into buckets of the
    ladder (``featurize._bucket``: padding under a fifth of a dispatch
    above 32,768 rows) rounded up to a dp multiple — ``shard_map`` requires
    dp-divisible shapes and distinct batch sizes must reuse compiled
    programs instead of retracing — and padding rows are dropped on
    unpack. Scoring is row-local, so the packed scores are bit-identical
    to per-chunk dispatch at any device count (the mesh parity matrix in
    tests/unit/test_shard_score.py locks this).

    A dispatch is ONE host-to-device copy (two with host windows): the
    chunks' rows are written in one pass each into a bucket-sized staging
    buffer of the program's wire layout (:mod:`variantcalling_tpu.wire`),
    which goes back to the pool with the array whose readiness frees it.
    Counters: ``feed.dispatches``, ``feed.rows`` (real rows sent),
    ``feed.padded_rows`` (rows of the buckets sent), ``feed.h2d_arrays``
    (arrays handed to the device, the genome excluded),
    ``feed.native_fills`` / ``feed.numpy_fills`` (dispatches whose rows
    every chunk's native fill wrote / the rest), ``score.dispatches.<s>``
    (dispatches of the program of strategy ``s``: ``wide``, ``pallas``, ...,
    or ``jit`` for the other families) and, where NaN is kept, the fill's
    ``feed.float_cells`` / ``feed.nan_cells``; under a mesh plan also
    ``mesh.dispatches``, ``mesh.chunks`` (chunks packed into them),
    ``mesh.rows`` and ``mesh.padded_rows`` (as the feed's two); under the
    gather walk ``forest.walk_levels`` (the levels each dispatch walks).
    The model's weights ride every dispatch as the program's first
    argument, the one device copy the program was built with.
    """
    from variantcalling_tpu.featurize import _bucket
    from variantcalling_tpu.parallel import shard_score
    from variantcalling_tpu.parallel.mesh import data_sharding

    first = inputs[0]
    fn, layout, finalize, weights, walk_levels = first.program
    mesh = shard_score.mesh_for(plan)
    n_dev = plan.devices
    sharding = data_sharding(mesh, 2) if mesh is not None else None
    chunk_size = max(CHUNK, n_dev) - (CHUNK % n_dev if n_dev > 1 else 0)
    genome = first.genome
    # where the backend reads host memory in place, the buffer is busy
    # until the program has run; elsewhere until its copy has landed
    until_result = wire.put_reads_host_memory()
    # what, besides the bucket size, makes jax trace this program anew
    shapes = genome.rows.shape if layout.resident else first.windows.shape[1:]

    spans = shard_score.pack_lengths([i.n for i in inputs])
    n = spans[-1][1]
    if mesh is not None:
        obs.counter("mesh.chunks").add(len(inputs))
    out = np.empty(n, dtype=np.float32)
    pending: list[tuple[int, int, object]] = []

    # forest programs return canonical-order margins on every backend and
    # the SHARED host finalization (forest.finalize_margin) produces the
    # score bits both engines agree on; DAN/threshold programs return
    # final scores and have no host finalize (finalize is None)
    def finish(res, k):
        with stage("dispatch_wait"):  # blocked on the device, then D2H
            arr = np.asarray(res)[:k]
        if finalize is None:
            return arr
        with stage("score_finalize"):
            return finalize(arr)

    for lo in range(0, n, chunk_size):
        hi = min(lo + chunk_size, n)
        # a rung of the bucket ladder (rounded up to a dp multiple) so distinct
        # batch sizes reuse the same compiled program instead of retracing
        target = min(chunk_size, -(-_bucket(hi - lo) // n_dev) * n_dev)

        # async dispatch overlaps chunk i+1's upload with chunk i's compute;
        # the bounded in-flight window keeps device residency at O(chunk)
        # (plus the resident genome) instead of the whole dataset
        with stage("dispatch_feed", rows=target):
            buf = wire.POOL.take(target, layout)
            native = True
            for inp, (start, stop) in zip(inputs, spans):
                a, b = max(lo, start), min(hi, stop)
                if a < b:
                    native &= inp.fill(buf, a - lo, a - start, b - start)
            # padding positions sit past the genome end -> all-N windows
            buf.pad_from(hi - lo, first.gpos_fill)
            sent = tuple(jax.device_put(a, sharding) for a in buf.arrays())
        obs.counter("feed.dispatches").add(1)
        obs.counter("feed.rows").add(hi - lo)
        obs.counter("feed.padded_rows").add(target)
        obs.counter("feed.h2d_arrays").add(len(sent))
        obs.counter("feed.native_fills" if native else "feed.numpy_fills").add(1)
        obs.counter(f"score.dispatches.{first.strategy}").add(1)
        if walk_levels:
            obs.counter("forest.walk_levels").add(walk_levels)
        if mesh is not None:
            obs.counter("mesh.dispatches").add(1)
            obs.counter("mesh.rows").add(hi - lo)
            obs.counter("mesh.padded_rows").add(target)
        call_args = (weights, genome.rows, *sent) if layout.resident \
            else (weights, *sent)
        # the enqueue; on a first call also trace + lower + cache load or compile
        with stage("dispatch_enqueue", rows=target, waited=False):
            res = _enqueue(fn, (target, shapes), call_args)
        wire.POOL.give(buf, (res,) if until_result else sent)
        pending.append((lo, hi, res))
        while len(pending) > 2:
            plo, phi, res = pending.pop(0)
            out[plo:phi] = finish(res, phi - plo)
    for lo, hi, res in pending:
        out[lo:hi] = finish(res, hi - lo)
    return out


def fused_featurize_score(model, hf, flow_order: str, table: VariantTable | None = None,
                          fasta: FastaReader | None = None,
                          engine: engine_mod.EngineDecision | None = None,
                          strategy: str | None = None,
                          plan=None) -> np.ndarray:
    """Chunked fused featurize+score over a HostFeatures batch; returns scores.

    The scoring engine is the RUN-LEVEL decision from
    :mod:`variantcalling_tpu.engine` (``VCTPU_ENGINE``): ``native`` runs
    the whole hot path in the C++ engine and RAISES if it cannot
    (never a silent jit fallback — output bytes must not depend on which
    engine happened to load); ``jit`` never touches the native scorer.
    ``plan`` pins the run-level scoring-mesh decision
    (``FilterContext.mesh_plan``); None resolves per call — with >1
    devices the fused program runs inside a ``shard_map`` over the mesh
    dp axis (parallel/shard_score.py), byte-identical to single-device.
    """
    eng = engine or engine_mod.resolve()
    # native engine: window gather -> featurize -> forest walk in C++ —
    # one pass per 41-byte window row, ~10x XLA:CPU's multi-kernel
    # lowering, byte-parity with the jit engine locked by
    # tests/unit/test_engine_contract.py. Meshes and accelerators resolve
    # to jit and keep the fused on-device program below.
    if isinstance(model, FlatForest) and eng.name == "native":
        score = _native_cpu_featurize_score(model, hf, flow_order, table, fasta)
        if score is None:
            raise EngineError(
                "the resolved scoring engine 'native' could not serve this "
                "batch (native library unloadable mid-run, unsupported "
                "aggregation, or windows unavailable). Refusing to silently "
                "fall back to the jit engine — rerun with VCTPU_ENGINE=jit "
                "to opt into the jitted scorer. See docs/robustness.md.")
        return score

    from variantcalling_tpu.parallel import shard_score

    plan = plan or shard_score.resolve_plan(eng.name)
    fi = _prepare_fused_inputs(model, hf, flow_order, table=table, fasta=fasta,
                               strategy=strategy, plan=plan)
    return _dispatch_fused([fi], plan)


def score_variants(model, x: np.ndarray, feature_names: list[str],
                   engine: engine_mod.EngineDecision | None = None,
                   strategy: str | None = None, plan=None) -> np.ndarray:
    """Jitted chunked scoring, sharded over the mesh dp axis; returns TREE_SCORE per row.

    Multi-device (a >1-device mesh plan): the feature chunk is device_put
    with a dp sharding and the scoring program runs in a ``shard_map``
    over the variants axis (model arrays replicated); a single-device
    plan degrades to plain jit. The scoring engine follows the run-level
    contract (``VCTPU_ENGINE``): ``native`` runs the C++ walk or raises —
    never a silent jit fallback.
    """
    if not isinstance(model, _FUSED_MODEL_TYPES):
        # raw sklearn estimator that escaped conversion
        return np.asarray(model.predict_proba(x)[:, 1])
    eng = engine or engine_mod.resolve()
    if isinstance(model, FlatForest) and eng.name == "native":
        nf = forest_mod.native_host_predictor(
            forest_mod.with_feature_order(model, feature_names), strict=True)
        if nf is None:
            raise EngineError(
                "the resolved scoring engine 'native' could not serve this "
                "run (native library unloadable mid-run or unsupported "
                "aggregation). Refusing to silently fall back to the jit "
                "engine; rerun with VCTPU_ENGINE=jit. See docs/robustness.md.")
        return nf(np.ascontiguousarray(x, dtype=np.float32))  # C++ walk

    from variantcalling_tpu.parallel import shard_score
    from variantcalling_tpu.parallel.mesh import data_sharding

    plan = plan or shard_score.resolve_plan(eng.name)
    mesh = shard_score.mesh_for(plan)
    with stage("fused_program", built=False, waited=False):
        fn, finalize = _predictor_for(model, feature_names, strategy=strategy,
                                      mesh=mesh)
    n_dev = plan.devices
    sharding = data_sharding(mesh, 2) if mesh is not None else None
    chunk_size = max(CHUNK, n_dev) - (CHUNK % n_dev if n_dev > 1 else 0)

    n = x.shape[0]
    out = np.empty(n, dtype=np.float32)
    for lo in range(0, n, chunk_size):
        hi = min(lo + chunk_size, n)
        chunk = x[lo:hi]
        if hi - lo < chunk_size and (n > chunk_size or n_dev > 1):
            # pad the tail chunk: steady-state shape (one compile) + dp divisibility
            target = chunk_size if n > chunk_size else ((hi - lo + n_dev - 1) // n_dev) * n_dev
            chunk = np.pad(chunk, ((0, target - (hi - lo)), (0, 0)))
        dev_chunk = jax.device_put(chunk, sharding) if sharding is not None else jnp.asarray(chunk)
        res = np.asarray(fn(dev_chunk))[: hi - lo]
        out[lo:hi] = finalize(res) if finalize is not None else res
    return out


class FilterContext:
    """Chunk-invariant scoring state for the filter pipeline.

    Built once per run (model wiring, blacklist, hpol runs file, interval
    sets), then :meth:`score_table` is applied to the whole table (serial
    path) or to each streamed chunk (streaming executor). Every product is
    row-local by construction — a variant's TREE_SCORE and FILTER depend
    only on that variant's record plus this shared state — which is what
    makes chunked scoring bit-identical to whole-table scoring.
    """

    def __init__(
        self,
        model,
        fasta: FastaReader,
        runs_file: str | None = None,
        hpol_length: int = 10,
        hpol_dist: int = 10,
        blacklist: tuple[np.ndarray, np.ndarray] | None = None,
        blacklist_cg_insertions: bool = False,
        annotate_intervals: dict[str, bedio.IntervalSet] | None = None,
        flow_order: str = "TGCA",
        is_mutect: bool = False,
        engine: engine_mod.EngineDecision | None = None,
        mesh_plan=None,
        rank_plan=None,
    ):
        # the run-level scoring engine (VCTPU_ENGINE): resolved once and
        # held here so every chunk of a run scores on the SAME engine.
        # Only FlatForests have a native scorer — an EXPLICIT native
        # request with another model type fails loudly, while an
        # auto-resolved native downgrades to jit HERE (once, before any
        # scoring) so the recorded engine matches what actually scores.
        eng = engine or engine_mod.resolve()
        if eng.name == "native" and not isinstance(model, FlatForest):
            if eng.requested == "native":
                raise EngineError(
                    "the native scoring engine was explicitly required but "
                    f"only FlatForest models have a native scorer (got "
                    f"{type(model).__name__}) — rerun with VCTPU_ENGINE=jit "
                    "or auto. See docs/robustness.md.")
            from dataclasses import replace

            eng = replace(eng, name="jit",
                          reason=f"{type(model).__name__} has no native scorer")
        self.engine = eng
        # per-RUN resolution event: engine.resolve() caches per process,
        # so emitting here (where the run pins its engine) is the only way
        # every run's stream records the decision that scored it
        if obs.active():
            obs.event("resolve", "engine", value=eng.name,
                      requested=eng.requested, reason=eng.reason)
        # the run-level FOREST STRATEGY (VCTPU_FOREST_STRATEGY): resolved
        # once here, recorded next to ##vctpu_engine= in the output header
        # and in the chunk-journal resume identity, then PINNED into every
        # scoring call — the predictor build honors it or raises
        # (EngineError, exit 2), so the recorded name can never silently
        # diverge from the program that scored. The native engine's C++
        # walk has no XLA strategy; it records "native-cpp" — but a
        # MALFORMED env value (strategy name, wide chunk/block knobs) is a
        # configuration error on every engine (same rule as a bad
        # VCTPU_ENGINE), so validate them all up front.
        forest_mod.validate_strategy_env()
        if eng.name == "native":
            self.forest_strategy = "native-cpp"
            if obs.active():
                obs.event("resolve", "forest_strategy", value="native-cpp",
                          requested="-", reason="native engine: C++ walk, no "
                          "XLA strategy")
        elif isinstance(model, FlatForest):
            self.forest_strategy = forest_mod.resolve_strategy(model)
        else:
            self.forest_strategy = "jit"  # threshold/dan/sklearn program
        # the run-level MODEL FAMILY (VCTPU_MODEL_FAMILY): resolved once
        # here under the exact contract the engine/strategy obey — auto
        # resolves to the loaded model's family; an EXPLICIT request for
        # a family the model file didn't serve fails loudly (EngineError,
        # exit 2) instead of silently scoring with the other family. The
        # resolved family is recorded as ##vctpu_model_family= when it is
        # not the forest default, pinned into the journal resume identity
        # and the chunk-cache fingerprint (io/identity.py) together with
        # a DAN weights digest, and emitted as a resolve obs event.
        fam_req = knobs.get("VCTPU_MODEL_FAMILY")
        fam = registry_mod.family_of(model)
        if fam_req != "auto" and fam_req != fam:
            raise EngineError(
                f"VCTPU_MODEL_FAMILY={fam_req} was explicitly requested but "
                f"the loaded model is family {fam!r} "
                f"({type(model).__name__}) — point --model_file/--model_name "
                f"at a {fam_req} model or rerun with VCTPU_MODEL_FAMILY="
                "auto. See docs/models.md.")
        self.model_family = fam
        self.model_digest = (dan_mod.weights_digest(model)
                             if isinstance(model, DanModel) else None)
        if obs.active():
            obs.event("resolve", "model_family", value=fam,
                      requested=fam_req,
                      reason=f"model type {type(model).__name__}")
        # the run-level SCORING MESH (VCTPU_MESH_DEVICES): resolved once
        # here next to the engine and strategy, recorded as
        # ##vctpu_mesh= in the output header when >1 device and pinned
        # into the chunk-journal resume identity — then every scoring
        # dispatch of the run shards over exactly this mesh
        # (parallel/shard_score.py). Output bytes are identical at every
        # device count by construction (pure data-parallel map; parity
        # matrix in tests/unit/test_shard_score.py), so the header line
        # is the only byte that names the layout.
        # ``mesh_plan`` pins an externally-decided plan — the recovery
        # ladder's dp=1 restart after device OOM (run_streaming) is the
        # one caller; everything else resolves here as before
        from variantcalling_tpu.parallel import shard_score

        self.mesh_plan = mesh_plan if mesh_plan is not None \
            else shard_score.resolve_plan(eng.name)
        shard_score.log_plan(self.mesh_plan)
        # the run-level RANK plan (VCTPU_RANK/VCTPU_NUM_PROCESSES or an
        # initialized jax.distributed runtime): resolved once here next
        # to the mesh plan, recorded as ##vctpu_ranks= when >1 rank and
        # pinned into the chunk-journal resume identity — the scale-out
        # layout every rank of a pod run agrees on (docs/scaleout.md).
        # ``rank_plan`` pins an externally-resolved plan (the scale-out
        # driver passes the one it partitioned by).
        from variantcalling_tpu.parallel import rank_plan as rank_plan_mod

        self.rank_plan = rank_plan if rank_plan is not None \
            else rank_plan_mod.resolve()
        rank_plan_mod.log_plan(self.rank_plan)
        # set by note_chunk: a chunk of this run was large enough to send
        # the genome to the device
        self.genome_wanted = False
        self.model = model
        self.fasta = fasta
        self.hpol_length = hpol_length
        self.hpol_dist = hpol_dist
        self.blacklist = blacklist
        self.blacklist_cg_insertions = blacklist_cg_insertions
        self.annotate_intervals = annotate_intervals
        self.flow_order = flow_order
        self.is_mutect = is_mutect
        # xgboost models define missing-value semantics on NaN (default_left
        # routing): zero-filling absent fields would walk the wrong branch
        self.keep_nan = getattr(model, "default_left", None) is not None
        self.extra_info = ["TLOD"] if is_mutect else []
        # hpol runs load once (length-filtered); globalization waits for the
        # first table so contig lengths come from its header exactly as the
        # single-shot path did
        self._runs: bedio.IntervalSet | None = None
        if runs_file:
            runs = bedio.read_bed(runs_file)
            keep = (runs.end - runs.start) >= hpol_length
            self._runs = bedio.IntervalSet(runs.chrom[keep], runs.start[keep], runs.end[keep])
        self._runs_global: tuple | None = None

    def _hpol_near(self, table: VariantTable) -> np.ndarray | None:
        if self._runs is None or not len(self._runs):
            return None
        if self._runs_global is None:
            contig_lengths = table.header.contig_lengths or {
                c: self.fasta.get_reference_length(c) for c in self.fasta.references
            }
            coords = iops.GenomeCoords(contig_lengths)
            self._runs_global = (coords, *coords.globalize_intervals(self._runs))
        coords, gs, ge = self._runs_global
        gpos = coords.globalize(np.asarray(table.chrom), table.pos - 1)
        return iops.distance_to_nearest(gpos, gs, ge) <= self.hpol_dist

    @property
    def mesh(self):
        """The run's scoring Mesh (None for a single-device plan)."""
        from variantcalling_tpu.parallel import shard_score

        return shard_score.mesh_for(self.mesh_plan)

    def _pinned_strategy(self) -> str | None:
        # pin the run-level strategy into the predictor build (registry
        # names only — "native-cpp"/"jit" rides the engine decision)
        return self.forest_strategy \
            if self.forest_strategy in forest_mod.FOREST_STRATEGIES else None

    def note_chunk(self, table: VariantTable) -> None:
        """The mesh layout's feed calls this for every chunk, in canonical
        order on its own thread, before the chunk goes to the pool. The
        pool featurizes a file's chunks side by side and ahead of the first
        dispatch, which is what uploads the genome: a short tail chunk
        asked alone (:func:`featurize._genome_resident_worthwhile`) finds
        no genome there yet in a process's first file of a reference and
        every later time does, so the first file took another program for
        its tail than the files after it, and the second file compiled.
        Once a chunk of the run is large enough for the upload, the chunks
        after it gather from the device too: they are dispatched after it."""
        from variantcalling_tpu.featurize import GENOME_RESIDENT_MIN_VARIANTS

        if len(table) >= GENOME_RESIDENT_MIN_VARIANTS:
            self.genome_wanted = True

    def note_raw_chunk(self, buf) -> None:
        """:meth:`note_chunk` for the layouts whose feed holds raw text
        (the pooled one and the cached serial one): the same rule in the
        same canonical order, asked of the chunk's lines, since nothing is
        parsed yet. Without it the layout of a short tail chunk is a race
        in a process's first file of a reference: a ``.vcf.gz`` input's
        first chunk is two reads long, so its thirteen chunks start side by
        side on thirteen workers, the tail finds no genome on the device
        and takes the host-window program, and the next file of that
        reference compiles the resident one at the tail's rung inside a
        measured window (PERF.md section 6, PR 36). Lines are counted only
        until the answer is yes and only while the genome is not on the
        device: a file of a resident process counts nothing."""
        from variantcalling_tpu.featurize import (GENOME_RESIDENT_MIN_VARIANTS,
                                                  _genome_resident_worthwhile,
                                                  standard_genome_sharding)

        if self.genome_wanted or _genome_resident_worthwhile(
                (), self.fasta, sharding=standard_genome_sharding(self.mesh)):
            return
        if int(np.count_nonzero(buf == 0x0A)) >= GENOME_RESIDENT_MIN_VARIANTS:
            self.genome_wanted = True

    @timed(name="host_featurize")
    def host_features(self, table: VariantTable):
        """Host featurization for one table/chunk — the CPU half of
        scoring, shared by :meth:`score_table` and the mesh megabatch
        path (it fans out on the IO pool in the streaming executor)."""
        model, fasta = self.model, self.fasta
        # host windows are needed only by the cg-insertion check and the
        # raw-sklearn fallback; the fused path gathers windows from the
        # device-resident genome instead — unless the job is too small to
        # justify the whole-genome HBM upload (_genome_resident_worthwhile)
        from variantcalling_tpu.featurize import (_genome_resident_worthwhile,
                                                  standard_genome_sharding)

        mesh = self.mesh
        genome_sharding = standard_genome_sharding(mesh)
        needs_host_windows = (
            self.blacklist_cg_insertions
            or not isinstance(model, _FUSED_MODEL_TYPES)
            or not (self.genome_wanted or _genome_resident_worthwhile(
                table, fasta, sharding=genome_sharding))
        )
        # a chunk bound for the fused jit program whose table came through
        # the native parser needs no base column made here: the wire's
        # native fill writes them from the scan's arrays, straight into the
        # dispatch's staging buffer (variantcalling_tpu/wire.py)
        wire_native = (self.engine.name == "jit"
                       and isinstance(model, _FUSED_MODEL_TYPES)
                       and wire.native_fillable(table))
        hf = host_featurize(table, fasta, annotate_intervals=self.annotate_intervals,
                            extra_info_fields=self.extra_info,
                            compute_windows=needs_host_windows, keep_nan=self.keep_nan,
                            base_columns=not wire_native)
        if self.is_mutect and "TLOD" in hf.cols:
            hf.cols["tlod"] = hf.cols.pop("TLOD")
            hf.names[hf.names.index("TLOD")] = "tlod"
        return hf

    def _score_hf(self, table: VariantTable, hf) -> np.ndarray:
        model, fasta = self.model, self.fasta
        strat = self._pinned_strategy()
        if isinstance(model, _FUSED_MODEL_TYPES):
            # fused featurize+score: window features and the model program
            # (forest walk or DAN forward) run as one device program, only
            # TREE_SCORE returns to the host
            return fused_featurize_score(model, hf, self.flow_order, table=table,
                                         fasta=fasta, engine=self.engine,
                                         strategy=strat, plan=self.mesh_plan)
        # raw sklearn estimator: materialize the matrix from the same hf
        from variantcalling_tpu.featurize import materialize_features

        fs = materialize_features(hf, flow_order=self.flow_order)
        return score_variants(model, fs.matrix(), fs.feature_names,
                              engine=self.engine, strategy=strat,
                              plan=self.mesh_plan)

    def score_table(self, table: VariantTable) -> tuple[np.ndarray, np.ndarray]:
        """Score one table (whole callset or one streamed chunk); returns
        (tree_score float array, FILTER FactorizedColumn)."""
        hf = self.host_features(table)
        score = self._score_hf(table, hf)
        return score, self.assemble_filters(table, score, hf)

    def score_packed(self, pairs) -> list[tuple]:
        """Score a GROUP of consecutive chunks as one packed megabatch —
        the mesh-sharded streaming path (shard_score.megabatch_stream).

        ``pairs`` is ``[(table, host_features), ...]`` in canonical chunk
        order. Chunks whose prepared inputs share one compiled program
        concatenate into a single padded, dp-sharded dispatch; scores
        unpack back per chunk by slicing (scoring is row-local, so the
        packed bits equal per-chunk dispatch bits — the streaming==serial
        invariant, now also across packing). Chunks that resolved a
        different program layout (e.g. a host-window tail next to
        genome-resident neighbors) score alone, preserving order.
        Returns ``[(table, score, filters), ...]``.
        """
        model = self.model
        if self.mesh_plan.devices <= 1 or self.engine.name == "native" \
                or not isinstance(model, _FUSED_MODEL_TYPES):
            out = []
            for table, hf in pairs:
                score = self._score_hf(table, hf)
                out.append((table, score, self.assemble_filters(table, score, hf)))
            return out
        from variantcalling_tpu.parallel import shard_score

        strat = self._pinned_strategy()
        prepped = [
            (table, hf,
             _prepare_fused_inputs(model, hf, self.flow_order, table=table,
                                   fasta=self.fasta, strategy=strat,
                                   plan=self.mesh_plan))
            for table, hf in pairs]
        out = []
        run: list = []  # consecutive chunks sharing one compiled program

        def flush_run():
            if not run:
                return
            scores = _dispatch_fused([fi for _, _, fi in run], self.mesh_plan)
            for (table, hf, fi), score in zip(
                    run, shard_score.unpack_scores(
                        scores, [fi.n for _, _, fi in run])):
                out.append((table, score,
                            self.assemble_filters(table, score, hf)))
            run.clear()

        for item in prepped:
            if run and item[2].program is not run[-1][2].program:
                flush_run()
            run.append(item)
        flush_run()
        return out

    @timed(name="score_finalize")
    def assemble_filters(self, table: VariantTable, score: np.ndarray,
                         hf) -> FactorizedColumn:
        """FILTER assembly from a table's scores — row-local, shared by
        the per-chunk and packed-megabatch paths."""
        model = self.model
        pass_thr = getattr(model, "pass_threshold", 0.5)
        n = len(table)
        low = score < pass_thr

        cohort_fp = np.zeros(n, dtype=bool)
        blacklist = self.blacklist
        if blacklist is not None and len(blacklist[0]):
            # vectorized (chrom, pos) join: map chroms to small ints, pack into
            # one int64 key, sorted-membership — no per-record Python on the 5M path
            chroms = {c: i for i, c in enumerate(dict.fromkeys(np.concatenate([blacklist[0], table.chrom]).tolist()))}
            cidx_bl = np.fromiter((chroms[c] for c in blacklist[0]), dtype=np.int64, count=len(blacklist[0]))
            cidx_tb = np.fromiter((chroms[c] for c in table.chrom), dtype=np.int64, count=n)
            key_bl = np.sort((cidx_bl << 40) | blacklist[1].astype(np.int64))
            key_tb = (cidx_tb << 40) | table.pos.astype(np.int64)
            loc = np.searchsorted(key_bl, key_tb)
            loc = np.minimum(loc, len(key_bl) - 1)
            cohort_fp = key_bl[loc] == key_tb
        if self.blacklist_cg_insertions and hf.windows is not None:
            from variantcalling_tpu.featurize import CENTER

            cohort_fp |= _is_cg_insertion(table, hf.windows, CENTER)

        near = self._hpol_near(table)
        hpol_near = near if near is not None else np.zeros(n, dtype=bool)

        # FILTER assembly as integer codes over the 6 possible values (no
        # per-record Python and no factorize on the 5M writeback path):
        # COHORT_FP beats LOW_SCORE; HPOL_RUN appends with ';'
        base_idx = np.where(cohort_fp, 1, np.where(low, 2, 0)).astype(np.int32)
        return FactorizedColumn(
            base_idx + 3 * hpol_near,
            [PASS, COHORT_FP, LOW_SCORE, HPOL_RUN,
             f"{COHORT_FP};{HPOL_RUN}", f"{LOW_SCORE};{HPOL_RUN}"],
        )


def warm_program(model, fasta: FastaReader | None, flow_order: str = "TGCA",
                 engine: engine_mod.EngineDecision | None = None) -> bool:
    """Build (or find) the fused program that a plain request for ``model``
    on ``fasta`` asks for — default flags, no annotation intervals — with
    engine, strategy and mesh resolved as a run resolves them and the
    window layout chosen by the rule a chunk follows
    (``_genome_resident_worthwhile``: resident iff the genome is on the
    device already). ``vctpu serve``'s ``/v1/warm``. False where such a
    request runs no jitted program (native engine, a model outside the
    fused families)."""
    from variantcalling_tpu.featurize import (BASE_FEATURES,
                                              _genome_resident_worthwhile,
                                              genome_packable,
                                              standard_genome_sharding)

    ctx = FilterContext(model, fasta, flow_order=flow_order, engine=engine)
    if ctx.engine.name != "jit" or not isinstance(model, _FUSED_MODEL_TYPES):
        return False
    mesh = ctx.mesh
    resident = fasta is not None and genome_packable(fasta) \
        and _genome_resident_worthwhile(
            (), fasta, sharding=standard_genome_sharding(mesh))
    _fused_program(model, list(BASE_FEATURES), flow_order,
                   genome_resident=resident, strategy=ctx._pinned_strategy(),
                   mesh=mesh)
    return True


def filter_variants(
    table: VariantTable,
    model,
    fasta: FastaReader,
    runs_file: str | None = None,
    hpol_length: int = 10,
    hpol_dist: int = 10,
    blacklist: tuple[np.ndarray, np.ndarray] | None = None,
    blacklist_cg_insertions: bool = False,
    annotate_intervals: dict[str, bedio.IntervalSet] | None = None,
    flow_order: str = "TGCA",
    is_mutect: bool = False,
    engine: engine_mod.EngineDecision | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Core: returns (tree_score float array, new FILTER object array)."""
    ctx = FilterContext(
        model, fasta, runs_file=runs_file, hpol_length=hpol_length,
        hpol_dist=hpol_dist, blacklist=blacklist,
        blacklist_cg_insertions=blacklist_cg_insertions,
        annotate_intervals=annotate_intervals, flow_order=flow_order,
        is_mutect=is_mutect, engine=engine,
    )
    return ctx.score_table(table)


def _replace_or_append_meta(header, prefix: str, line: str) -> None:
    """A stale line inherited from a previously-filtered input must not
    mislabel THIS run: replace in place (position preserved), append when
    absent."""
    replaced = False
    for i, old in enumerate(header.lines):
        if old.startswith(prefix):
            header.lines[i] = line
            replaced = True
    if not replaced:
        header.add_meta_line(line)


def _ensure_output_header(header, engine: engine_mod.EngineDecision | None = None,
                          strategy: str | None = None,
                          mesh_plan=None, rank_plan=None,
                          model_family: str | None = None) -> None:
    """The filter pipeline's header additions — ONE place so the serial and
    streaming writers emit identical header bytes. Records the scoring
    engine (``##vctpu_engine=...``), the resolved forest strategy
    (``##vctpu_forest_strategy=...``), the model family when it is not
    the forest default (``##vctpu_model_family=dan``) and — for
    >1-device runs — the scoring-mesh layout (``##vctpu_mesh=dp=N``) so
    every output file names the full scoring configuration that produced
    it (engine contract, docs/robustness.md)."""
    header.ensure_filter(LOW_SCORE, "Model score below threshold")
    header.ensure_filter(COHORT_FP, "Blacklisted cohort false-positive locus")
    header.ensure_filter(HPOL_RUN, "Variant close to long homopolymer run")
    header.ensure_info("TREE_SCORE", "1", "Float", "Filtering model confidence score")
    eng = engine or engine_mod.resolve()
    _replace_or_append_meta(header, f"##{engine_mod.HEADER_KEY}=",
                            eng.header_line())
    if strategy is not None:
        key = forest_mod.STRATEGY_HEADER_KEY
        _replace_or_append_meta(header, f"##{key}=", f"##{key}={strategy}")
    # model-family provenance: non-forest families record the family that
    # scored; forest runs emit NO line (and strip a stale one inherited
    # from a re-filtered input) so pre-existing forest outputs stay
    # byte-identical to every prior release
    fam_prefix = f"##{dan_mod.FAMILY_HEADER_KEY}="
    if model_family is not None and model_family != "forest":
        _replace_or_append_meta(header, fam_prefix,
                                f"{fam_prefix}{model_family}")
    else:
        header.lines[:] = [ln for ln in header.lines
                           if not ln.startswith(fam_prefix)]
    # mesh provenance: >1-device runs record the dp layout; single-device
    # runs emit NO line (and strip a stale one inherited from a
    # re-filtered input) — record bytes are identical at every device
    # count, so the header line is the only byte naming the layout
    from variantcalling_tpu.parallel.shard_score import MESH_HEADER_KEY

    mesh_prefix = f"##{MESH_HEADER_KEY}="
    if mesh_plan is not None and mesh_plan.devices > 1:
        _replace_or_append_meta(header, mesh_prefix, mesh_plan.header_line())
    else:
        header.lines[:] = [ln for ln in header.lines
                           if not ln.startswith(mesh_prefix)]
    # rank provenance (docs/scaleout.md): >1-rank runs record the pod
    # layout; single-rank runs emit NO line (and strip a stale one from
    # a re-filtered input) — record bytes are rank-count-invariant, so
    # this line is the only byte naming the scale-out layout. The line
    # names only n (never the rank id): every rank's segment must emit
    # IDENTICAL header bytes for the seam commit's cross-rank check.
    from variantcalling_tpu.parallel.rank_plan import RANKS_HEADER_KEY

    ranks_prefix = f"##{RANKS_HEADER_KEY}="
    if rank_plan is not None and rank_plan.ranks > 1:
        _replace_or_append_meta(header, ranks_prefix,
                                rank_plan.header_line())
    else:
        header.lines[:] = [ln for ln in header.lines
                           if not ln.startswith(ranks_prefix)]
    # explicitly-set scoring knobs (wide chunk/block, pallas opt-out):
    # full provenance next to the engine/strategy lines. Execution-only
    # knobs are excluded so streaming/serial/resumed runs stay
    # byte-identical (knobs.header_line contract). With nothing set (the
    # common case) no line is emitted — and a stale line inherited from a
    # re-filtered input is REMOVED, so it cannot mislabel this run.
    knob_line = knobs.header_line()
    knob_prefix = f"##{knobs.HEADER_KEY}="
    if knob_line != knob_prefix:
        _replace_or_append_meta(header, knob_prefix, knob_line)
    else:
        header.lines[:] = [ln for ln in header.lines
                           if not ln.startswith(knob_prefix)]


def streaming_eligible(args_limit_to_contig=None,
                       allow_multiprocess: bool = False) -> bool:
    """The streaming executor runs when host threads are available
    (``VCTPU_THREADS`` != 1, ``VCTPU_STREAM`` != 0), the native engine is
    built, and the job is single-process / whole-file. Anything else —
    including ``VCTPU_THREADS=1`` — cleanly selects the serial path.
    ``allow_multiprocess`` is the rank-partitioned scale-out driver's
    opt-in (parallel/rank_plan.py): each rank IS one of N processes by
    design, streaming over its own span."""
    from variantcalling_tpu import native
    from variantcalling_tpu.parallel.pipeline import resolve_threads

    if not knobs.get_bool("VCTPU_STREAM") or resolve_threads() <= 1:
        return False
    if not native.available() or args_limit_to_contig:
        return False
    if not allow_multiprocess:
        try:
            if jax.process_count() > 1:
                return False
        except Exception as e:  # noqa: BLE001 — uninitialized backend == single process
            degrade.record("pipeline.process_count_probe", e,
                           fallback="assume single process")
    return True


def _sink_write(sink, data) -> None:
    """Write ``data`` to the output sink with bounded retry on transient
    IO errors (ENOSPC, EIO — docs/robustness.md failure matrix).

    Retry is only attempted on REWINDABLE sinks (plain files): the
    pre-write position is restored with seek+truncate before each retry,
    so a partially-flushed attempt cannot duplicate bytes. Non-rewindable
    sinks (the BGZF writer buffers and may have flushed some compressed
    blocks when the error surfaced) do NOT retry — a duplicate-free
    partial file cannot be guaranteed there, so the failure propagates and
    the atomic commit discards the torn ``.partial`` instead of ever
    committing duplicated records.
    """
    from variantcalling_tpu.parallel.pipeline import retry_transient
    from variantcalling_tpu.utils import faults

    pos = None
    try:
        pos = sink.tell()
    except (AttributeError, OSError):
        pos = None

    def attempt() -> None:
        if pos is not None and sink.tell() != pos:
            sink.seek(pos)
            sink.truncate()
        # injection point "io.writeback": fires before bytes move, so the
        # injected failure is always cleanly retryable
        faults.check("io.writeback")
        sink.write(data)

    retry_transient(attempt, "output writeback",
                    attempts=None if pos is not None else 1)


def run_streaming(args, model, fasta: FastaReader, annotate, blacklist,
                  engine: engine_mod.EngineDecision | None = None,
                  rank_plan=None) -> dict | None:
    """Chunked three-stage streaming execution: BGZF/VCF chunk ingest ->
    fused featurize+score -> ordered VCF writeback, overlapped on the
    bounded-queue stage executor (parallel/pipeline.py).

    The FASTA 2-bit encode rides a prefetch thread (threaded native encode
    + persistent ``.venc`` cache), so the genome encode hides behind
    scoring instead of serializing in front of the run — the round-5
    "warmup cliff". Output is byte-identical to the serial path: chunks
    are sequence-numbered, written strictly in order, and every stage runs
    the same code the whole-table path runs.

    Failure semantics (docs/robustness.md):

    - output is committed ATOMICALLY: bytes accumulate in
      ``<out>.partial`` and are renamed onto the destination only after
      the last chunk — a crash/SIGKILL never leaves a partial file at the
      destination path;
    - plain ``.vcf`` outputs keep a chunk JOURNAL (``<out>.journal``,
      io/journal.py) so an interrupted run RESUMES: journaled chunks are
      skipped (their bytes are already in the partial file) and the
      continuation is byte-identical to an uninterrupted run
      (``VCTPU_RESUME=0`` opts out; ``.gz`` outputs restart — BGZF block
      state does not survive a kill);
    - transient ingest/writeback IO errors are retried with backoff
      (``VCTPU_IO_RETRIES``/``VCTPU_IO_BACKOFF_S``), a hung stage trips
      the executor watchdog (``VCTPU_STAGE_TIMEOUT_S``), and every
      failure path joins the prefetch thread and drains/joins the stage
      workers before re-raising.

    Returns a stats dict, or None when ineligible (caller runs serial).
    """
    multiproc = rank_plan is not None and (rank_plan.ranks > 1
                                           or rank_plan.span is not None)
    if not streaming_eligible(args.limit_to_contig,
                              allow_multiprocess=multiproc):
        return None

    # telemetry: callers that came through run() already opened the obs
    # run (start_run returns None and events just join it); direct
    # callers (tools, tests) get their own stream here
    inputs = {"input": args.input_file}
    if getattr(args, "model_file", None):
        inputs["model"] = args.model_file
    obs_run = obs.start_run("filter_variants_pipeline",
                            default_path=str(args.output_file) + ".obs.jsonl",
                            inputs=inputs)
    try:
        from variantcalling_tpu.parallel import shard_score

        try:
            stats = _run_streaming_impl(args, model, fasta, annotate,
                                        blacklist, engine=engine,
                                        rank_plan=rank_plan)
        except shard_score.MeshDegradeRestart as e:
            # recovery ladder, top rung: device OOM survived the
            # megabatch shrink — restart the WHOLE stream on a dp=1
            # plan. The journal restarts with it: the resume identity
            # and the output header both pin the mesh layout, so the
            # dp>1 partial can never splice into a dp=1 continuation.
            from variantcalling_tpu.io import journal as journal_mod

            degrade.record("shard_score.device_oom", e, warn=True,
                           fallback="restarting the streaming run on a "
                                    "dp=1 mesh plan")
            if obs.active():
                obs.event("recovery", "dp_degrade",
                          devices_from=e.devices, devices_to=1)
                obs.counter("recovery.dp_degrades").add(1)
            logger.warning("%s — restarting the stream single-device", e)
            journal_mod.discard(str(args.output_file))
            plan1 = shard_score.MeshPlan(
                1, "degraded",
                f"recovery ladder: device OOM at dp={e.devices}, "
                "degraded to dp=1")
            stats = _run_streaming_impl(args, model, fasta, annotate,
                                        blacklist, engine=engine,
                                        mesh_plan=plan1,
                                        rank_plan=rank_plan)
    except BaseException as e:
        obs.end_run(obs_run, f"error: {type(e).__name__}")
        raise
    obs.end_run(obs_run, "ok")
    return stats


def _run_streaming_impl(args, model, fasta: FastaReader, annotate, blacklist,
                        engine: engine_mod.EngineDecision | None = None,
                        mesh_plan=None, rank_plan=None) -> dict:
    # obs v2 attribution: created BEFORE the reader so the parallel-IO
    # worker pools (shard inflate / chunk parse) attribute their work
    # from the very first shard; the executor feeds per-stage work/
    # queue-wait/backpressure into the same profile, every trace.stage
    # span finds it in the context this run's threads share (so two runs
    # in flight under one obs run keep their own), and the stream adds
    # the IO byte totals. One emit at commit time -> `vctpu obs bottleneck` names the
    # limiting stage (ROADMAP item 1).
    from variantcalling_tpu.obs import profile as profile_mod

    prof = profile_mod.StageProfiler() if profile_mod.enabled() else None
    # `stream_open`: a stream's head, from here to the pipeline's first pull
    # (reader and header scan, FilterContext, output header, identity, resume
    # decision, partial and journal open). _stream_chunks closes it there; a
    # failure before that unwinds it here
    with obs.bind_profiler(prof), contextlib.ExitStack() as opening:
        opening.enter_context(stage("stream_open"))
        return _stream_chunks(args, model, fasta, annotate, blacklist, prof,
                              opening, engine=engine, mesh_plan=mesh_plan,
                              rank_plan=rank_plan)


def _stream_chunks(args, model, fasta: FastaReader, annotate, blacklist, prof,
                   opening, engine: engine_mod.EngineDecision | None = None,
                   mesh_plan=None, rank_plan=None) -> dict:
    import contextvars
    import threading
    import time as _time
    import zlib

    from variantcalling_tpu.utils import cancellation
    from variantcalling_tpu.utils import faults
    from variantcalling_tpu.io import chunk_cache as chunk_cache_mod
    from variantcalling_tpu.io import identity as identity_mod
    from variantcalling_tpu.io import journal as journal_mod
    from variantcalling_tpu.io.tabix import (StreamedIndex, build_tabix_index,
                                             chunk_index_facts)
    from variantcalling_tpu.io.vcf import (VcfChunkReader, assemble_table_bytes,
                                           render_table_bytes_python)
    from variantcalling_tpu.parallel.pipeline import (StagePipeline,
                                                      resolve_stage_timeout,
                                                      resolve_threads,
                                                      retry_chunk,
                                                      retry_transient)

    from variantcalling_tpu.obs import sampler as sampler_mod

    # declared up front, so that a run that only reused reads "0 builds"
    obs.counter("predictor.builds").add(0)
    obs.counter("predictor.reuses").add(0)
    obs.counter("predictor.waits").add(0)
    for name in ("dispatches", "rows", "padded_rows", "h2d_arrays",
                 "native_fills", "numpy_fills", "float_cells", "nan_cells"):
        obs.counter(f"feed.{name}").add(0)
    # a cell that silently changed programs reads 0, not nothing
    for name in forest_mod.FOREST_STRATEGIES[1:] + ("jit",):
        obs.counter(f"score.dispatches.{name}").add(0)
    obs.counter("forest.walk_levels").add(0)
    for name in ("dispatches", "chunks", "rows", "padded_rows"):
        obs.counter(f"mesh.{name}").add(0)
    for name in ("in_bytes", "in_blocks", "inflate_shards", "text_bytes_in",
                 "text_bytes_out", "out_bytes", "out_blocks", "deflate_members",
                 "libdeflate_members"):
        obs.counter(f"bgzf.{name}").add(0)
    for name in ("records", "index_streamed", "index_second_pass", "index_skipped"):
        obs.counter(f"tabix.{name}").add(0)
    # continuous-profiler attribution (obs v3): this thread runs the
    # sequenced single-writer commit loop for the duration of the run
    sampler_mod.register_current("committer")
    # rank-partitioned ingest (docs/scaleout.md): a multi-rank plan
    # restricts the reader to THIS rank's contiguous line-aligned span —
    # chunk boundaries, the journal and the output segment are all
    # rank-local, and the rank-sequenced committer splices the segments.
    # Resolved HERE when not passed (direct callers under a launcher
    # env), so the reader's span and the header's ##vctpu_ranks= line
    # can never disagree.
    if rank_plan is None:
        from variantcalling_tpu.parallel import rank_plan as rank_plan_mod

        rank_plan = rank_plan_mod.resolve()
    span = (rank_plan.rank, rank_plan.ranks) \
        if rank_plan is not None and rank_plan.ranks > 1 else None
    # elastic span workers (docs/scaleout.md "Elastic membership") carry
    # absolute byte targets instead of a rank fraction — same cut rule,
    # so re-cut spans tile the record body exactly like rank spans
    targets = rank_plan.span if rank_plan is not None else None
    reader = VcfChunkReader(args.input_file, profiler=prof, rank_span=span,
                            span_targets=targets)
    header = reader.header
    ctx = FilterContext(
        model, fasta, runs_file=args.runs_file,
        hpol_length=args.hpol_filter_length_dist[0],
        hpol_dist=args.hpol_filter_length_dist[1],
        blacklist=blacklist,
        blacklist_cg_insertions=args.blacklist_cg_insertions,
        annotate_intervals=annotate, flow_order=args.flow_order,
        is_mutect=args.is_mutect, engine=engine, mesh_plan=mesh_plan,
        rank_plan=rank_plan,
    )
    _ensure_output_header(header, engine=ctx.engine, strategy=ctx.forest_strategy,
                          mesh_plan=ctx.mesh_plan, rank_plan=ctx.rank_plan,
                          model_family=ctx.model_family)

    # kill the warmup cliff: encode (and persist) the genome on a prefetch
    # thread; scoring's per-contig fetch_encoded waits only for the contig
    # it needs, so encode overlaps scoring instead of preceding it. The
    # cancel event stops the prefetch between contigs once the run is done
    # (a tiny job on a huge genome must not block on untouched contigs),
    # and the join guarantees process exit never kills a .venc write
    # mid-file.
    prefetch_cancel = threading.Event()
    # the prefetch runs in the CALLER's context (fresh copy — a Context
    # object is single-threaded) so request-scoped knobs (genome-cache
    # settings) follow it, like every pooled worker (pipeline.IoPool)
    _prefetch_ctx = contextvars.copy_context()
    prefetch = threading.Thread(
        target=lambda: _prefetch_ctx.run(fasta.encode_all,
                                         cancel=prefetch_cancel),
        name="genome-prefetch", daemon=True)
    prefetch.start()

    def score_stage(table):
        # the chunk body rides the recovery ladder: the executor (serial
        # layout) or raw_chunk_worker (pooled layout) provides the bounded
        # re-dispatch; the guard provides the opt-in quarantine rung —
        # a diverted chunk flows on as a (table, None, None) marker.
        # The chunk's trace binds to the thread for the duration so
        # ladder events link to it, and the body emits its trace span.
        # ONE measurement (trace.stage) is the stage's span, histogram,
        # attribution row (``score_stage.w<idx>`` on a pooled worker) and
        # causal span, on whichever thread runs it.
        tid = getattr(table, "_obs_trace", None)
        with obs.trace_scope(tid), \
                stage("score_stage", records=len(table), causal=True):
            out = _guard_chunk(table, "score_stage",
                               lambda: ctx.score_table(table))
        if out is None:
            return table, None, None
        score, filters = out
        return table, score, filters

    # the executor records nothing of its own for a stage that does
    score_stage.self_timed = True

    def raw_chunk_worker(item):
        """The ZERO-WAIT pooled chunk body: parse -> fused featurize+
        score -> render as ONE task over a RAW chunk buffer
        (``VcfChunkReader.iter_raw``). A chunk is parsed immediately
        before it scores on the same worker, so no parsed table ever
        waits in a queue between a parse task and a score task — the
        ``score_stage.wait`` edge that dominated the p95 critical path of
        the layout before it is gone structurally, not hidden. Parse rides
        inside the chunk's retry budget (it is a pure function of the held
        buffer, so re-dispatch cannot change bytes; its own transient-IO
        retry stays inside ``parse_chunk``). Trace ids were allocated at
        the raw feed in canonical chunk order; the ingest span is
        emitted here with the parse duration — ONCE per chunk, whatever
        the retry budget spends (a re-dispatched body re-parses but must
        not grow a second root span), so the chunk DAG keeps the exact
        shape every obs consumer expects.

        Chunk-cache fast path (VCTPU_CACHE=1, docs/caching.md): the
        worker keys the RAW span (CRC32 + length under the scoring
        fingerprint) BEFORE parsing — a hit replays the stored rendered
        body straight to the sequenced commit, skipping parse→featurize→
        score→render entirely (its chunk DAG is one ``cache_hit`` span
        plus the committer's writeback). A miss computes as always and
        STAGES the result by sequence number; the committer publishes it
        only after the chunk commits."""
        seq, buf_np, lazy_buf, tid = item
        ckey = None
        if cache_session is not None:
            ckey = cache_session.key_of(buf_np)
            hit = cache_session.get(ckey)
            if hit is not None:
                cbody, k, p = hit
                if tid is not None:
                    obs.trace_span(tid, "cache_hit", 0.0, records=k)
                return cbody, k, p, None, tid, None
        ingest_span_emitted = [False]

        def body():
            faults.check("pipeline.stage")
            faults.check("pipeline.stage_hang")
            t0 = _time.perf_counter()  # vctpu-lint: disable=VCT006 — obs trace-span timing
            table = reader.parse_chunk(buf_np, lazy_buf)
            if tid is not None:
                table._obs_trace = tid
                if not ingest_span_emitted[0]:
                    ingest_span_emitted[0] = True
                    obs.trace_span(tid, "ingest",
                                   _time.perf_counter() - t0,  # vctpu-lint: disable=VCT006 — obs trace-span timing
                                   records=len(table))
            return render_stage(score_stage(table))

        with obs.trace_scope(tid):
            out = retry_chunk(body, "chunk_worker")
        if ckey is not None and out[3] is None:
            # clean chunks only: a quarantined chunk's zero-byte body is
            # a degradation artifact, not a pure function of the input
            cache_session.stage(seq, ckey, out[0], out[1], out[2])
        return out

    def _traced_raw(raws):
        """Allocate trace ids at the raw feed, in canonical chunk order
        (the ``_traced_chunks`` contract, kept for the raw layout — the
        pooled workers parse concurrently, so allocation cannot wait
        until parse time). The sequence number rides along: it is the
        chunk-cache staging key, matched against the committer's chunk
        counter at publish time (both count post-skip delivery order)."""
        for seq, (buf_np, lazy_buf) in enumerate(raws):
            ctx.note_raw_chunk(buf_np)
            yield seq, buf_np, lazy_buf, obs.new_trace()

    def render_stage(item):
        table, score, filters = item
        # the trace id rides the rendered tuple from here on — the table
        # is dropped after render, but compress + the sequenced commit
        # still emit spans of this chunk's DAG
        tid = getattr(table, "_obs_trace", None)
        with stage("render_stage", trace=tid, records=len(table), causal=True):
            if score is None:
                # quarantined chunk (recovery ladder): ZERO bytes reach the
                # main output; the ORIGINAL records (no TREE_SCORE, original
                # FILTER) go to the <out>.quarantine sidecar for triage
                qbody = assemble_table_bytes(table)
                if qbody is None:
                    qbody = render_table_bytes_python(table)
                return b"", len(table), 0, bytes(qbody), tid, None
            extra = {"TREE_SCORE": np.round(score, 4)}
            body = assemble_table_bytes(table, new_filters=filters,
                                        extra_info=extra)
            if body is None:  # native hiccup mid-run: Python renderer, same bytes
                body = render_table_bytes_python(table, new_filters=filters,
                                                 extra_info=extra)
        # a .gz output's last slot: what this chunk says of the .tbi, worked
        # out here, beside the render, where the table still is (None for
        # plain text, which scans nothing)
        facts = None
        if index is not None and index.complete:
            with stage("tabix_index", records=len(table)):
                facts = chunk_index_facts(body, table)
        return (body, len(table), int(np.sum(filters.codes == 0)), None, tid,
                facts)

    render_stage.self_timed = True

    out_path = str(args.output_file)
    gz = out_path.endswith(".gz")
    header_bytes = (b"".join((line + "\n").encode() for line in header.lines)
                    + (header.column_header() + "\n").encode())

    # parallel writeback (gz outputs): rendered chunk bodies compress to
    # BGZF blocks in their own pipeline stage — block framing tracked by
    # a deterministic carry identical to the serial BgzfWriter's, deflate
    # fanned out (native block-sharded compressor, or the IO pool) — and
    # the consumer below is the sequenced single-writer merge: it drains
    # compressed chunks strictly in sequence order through the same
    # .partial + os.replace atomic path plain outputs use.
    compressor = index = None
    if gz:
        from variantcalling_tpu.io.bgzf import BgzfChunkCompressor
        from variantcalling_tpu.parallel.pipeline import resolve_io_threads

        compress_pool = (reader.shared_pool() if resolve_io_threads() > 1
                         else None)
        compressor = BgzfChunkCompressor(pool=compress_pool)
        # the .tbi is gathered from what the run holds (io/tabix.py): a
        # chunk's facts on the worker that renders it, their place in the
        # file here, where chunks pass in order and the members appear
        index = StreamedIndex()

        def compress_stage(item):
            body, k, p, q, tid, facts = item
            if not len(body):  # quarantined chunk: nothing to compress
                return b"", k, p, q, tid, None
            data = memoryview(body) if isinstance(body, np.ndarray) else body
            text_start = compressor.bytes_in
            with stage("compress_stage", trace=tid, causal=True,
                       bytes_in=len(data)) as sp:
                out = compressor.add(data)
                sp.set(bytes_out=len(out))
            with stage("tabix_index"):
                index.add_chunk(facts, text_start)
                index.add_blocks(out)
            return out, k, p, q, tid, None

        compress_stage.self_timed = True

        # the ONE stage that is NOT a pure chunk body: the compressor's
        # block carry absorbs every byte it sees, so a re-dispatch (chunk
        # retry or watchdog duplicate) would silently drop or duplicate
        # compressed records — the executor must run it exactly once per
        # item and fail loudly instead (the pre-ladder gz semantics)
        compress_stage.retry_safe = False

    # the WHOLE scoring configuration, spelled ONCE (io/identity.py):
    # already-committed chunks carry the old run's scores, so resuming —
    # or replaying a cached chunk body — under a different model/flags/
    # engine would silently mix configurations. Built unconditionally:
    # the chunk cache needs the identity even for .gz / resume-opted-out
    # runs. Per-field rationale (strategy/mesh/ranks) lives with the
    # spelling in identity_mod.scoring_config.
    scoring_cfg = identity_mod.scoring_config(
        args, engine=ctx.engine.name, forest_strategy=ctx.forest_strategy,
        mesh_devices=ctx.mesh_plan.devices,
        rank=ctx.rank_plan.rank, ranks=ctx.rank_plan.ranks,
        span=ctx.rank_plan.span,
        model_family=ctx.model_family, model_digest=ctx.model_digest)

    # resume only for plain-text outputs: a killed BGZF writer's in-flight
    # block state is unrecoverable, so .gz runs restart (still atomic)
    resume_enabled = not gz and knobs.get_bool("VCTPU_RESUME")
    resume = None
    journal: journal_mod.ChunkJournal | None = None
    meta = None
    if resume_enabled:
        meta = identity_mod.resume_meta(args, chunk_bytes=reader.chunk_bytes,
                                        header_bytes=header_bytes,
                                        config=scoring_cfg)
        # claim=True: the re-tokened partial is OURS from the instant it
        # exists — this writer releases the token on every exit path
        resume = journal_mod.try_resume(out_path, meta, claim=True)

    n_total = n_pass = n_chunks = 0
    q_path = quarantine_path(out_path)
    if resume is None:
        # fresh run: a stale quarantine sidecar from an older run must
        # not mix its records with this run's diversions (a RESUMED run
        # keeps it — journaled quarantined chunks are skipped, so their
        # sidecar records are not regenerated)
        try:
            os.remove(q_path)
        except OSError:
            pass
    # the partial path carries a UNIQUE per-run suffix (pid + random,
    # recorded in the journal header so resume finds it): two concurrent
    # runs targeting the same output accumulate independent partials and
    # the atomic os.replace commit makes the destination last-complete-
    # writer-wins — the old fixed <out>.partial let them silently
    # clobber each other's bytes. A resumed run reopens the token its
    # journal recorded; abandoned partials are swept by
    # journal_mod.discard's cleanup. The token is CLAIMED before the
    # file exists (a concurrent run's discard/sweep must always see it
    # as in use — io/journal.token_in_use, the serve same-process
    # concurrency case) and every raise between the claim and the main
    # try/finally below releases it: a long-lived daemon must not
    # accrete phantom claims from failed sink opens. The main body's
    # teardown/commit paths own the release from there on. The
    # remaining fallible setup (executor-knob parses, input stat) runs
    # BEFORE the claim for the same reason.
    resolve_threads()
    resolve_stage_timeout()
    # a rank-span reader processes only its share: heartbeat progress
    # divides by the SPAN's bytes, not the whole file's
    input_bytes = reader.span_bytes if reader.span_bytes is not None \
        else os.path.getsize(args.input_file)
    part_token = None
    try:
        if gz:
            journal_mod.discard(out_path)  # stale leftovers of older runs
            part_token = journal_mod.new_partial_token()
            journal_mod.claim_token(part_token)
            part_path = journal_mod.partial_path(out_path, part_token)
            # the compress stage produces finished BGZF blocks; the
            # committer writes them raw (and rewindably, so transient
            # write errors are retryable — the old in-consumer
            # BgzfWriter could not rewind)
            sink = journal_mod.open_partial(out_path, part_token, "wb")
            if obs.active():
                obs.event("journal", "resume_decision", outcome="disabled",
                          reason="gz output: BGZF block state does not "
                                 "survive a kill")
        elif resume is not None:
            n_chunks = resume.chunks
            n_total = resume.n_records
            n_pass = resume.n_pass
            part_token = resume.partial_token  # re-tokened + claimed by try_resume
            part_path = journal_mod.partial_path(out_path, part_token)
            reader.skip(resume.chunks)
            # truncated to the watermark already
            sink = journal_mod.open_partial(out_path, part_token, "ab")
            journal = journal_mod.ChunkJournal(out_path)
            journal.reopen()
            logger.info("streaming resume: %d chunks (%d records) already "
                        "committed", resume.chunks, resume.n_records)
            if obs.active():
                obs.event("journal", "resume_decision", outcome="resumed",
                          chunks=resume.chunks, records=resume.n_records,
                          watermark=resume.watermark)
        else:
            journal_mod.discard(out_path)
            part_token = journal_mod.new_partial_token()
            journal_mod.claim_token(part_token)
            part_path = journal_mod.partial_path(out_path, part_token)
            sink = journal_mod.open_partial(out_path, part_token, "wb")
            if resume_enabled:
                journal = journal_mod.ChunkJournal(out_path)
                journal.begin(dict(meta, partial=part_token))
            if obs.active():
                obs.event("journal", "resume_decision",
                          outcome="fresh" if resume_enabled else "opted_out",
                          journaling=resume_enabled)
    except BaseException:
        if part_token is not None:
            journal_mod.release_token(part_token)
        raise

    if prof is not None:
        prof.stage("writeback")  # the row exists even for an empty stream
    # the parallel layout (VCTPU_IO_THREADS > 1): scoring AND record
    # render ride the SAME ordered-window fan-out as chunk parse — all
    # per-chunk work shares the IO pool, reassembled into canonical
    # sequence order before the stream enters the stage pipeline, so the
    # committer sees exactly the serial chunk sequence. Only the
    # order-dependent tail stays sequenced: the BGZF carry (compress
    # stage) and the single-writer commit. The serial-IO layout
    # (VCTPU_IO_THREADS=1) keeps the dedicated score/render stage
    # threads, as before.
    #
    # MESH layout (ctx.mesh_plan.devices > 1, docs/streaming_executor.md
    # "Mesh-sharded scoring"): host featurization still fans out per
    # chunk on the IO pool, but the DEVICE dispatch packs consecutive
    # chunks into device-count-sized megabatches scored by ONE shard_map
    # program over the mesh dp axis (shard_score.megabatch_stream), with
    # per-chunk scores unpacked back into canonical chunk order before
    # the pooled render fan-out. The chunk sequence, journal identity
    # and output bytes are identical to the single-device layouts.
    source_pooled = reader.io_threads > 1
    mesh_scoring = ctx.mesh_plan.devices > 1
    # chunk-result cache (VCTPU_CACHE=1, docs/caching.md): opened AFTER
    # the resume decision so a resumed run's cache spans key identically
    # (reader.skip preserves the deterministic chunk cut; seq numbers
    # below count post-skip delivery order on both sides). The mesh
    # megabatch layout bypasses the cache — its device-count-sized
    # batches span chunks, so there is no per-chunk raw-span fast path
    # to skip (documented limitation; record bytes would still match).
    cache_session = None
    if not mesh_scoring:
        cache_session = chunk_cache_mod.open_session(
            scoring_cfg, rank=ctx.rank_plan.rank, ranks=ctx.rank_plan.ranks)
    if mesh_scoring:
        from variantcalling_tpu.parallel import shard_score
        from variantcalling_tpu.parallel.pipeline import imap_ordered

        def prep_worker(table):
            def body():
                faults.check("pipeline.stage")
                faults.check("pipeline.stage_hang")
                # hf None == featurize-stage quarantine marker; the
                # megabatch stream passes it through to the render path
                def featurize():
                    with stage("featurize_stage", records=len(table)):
                        return ctx.host_features(table)

                hf = _guard_chunk(table, "featurize_stage", featurize)
                return table, hf

            tid = getattr(table, "_obs_trace", None)
            with obs.trace_scope(tid):
                t0 = _time.perf_counter()  # vctpu-lint: disable=VCT006 — obs trace-span timing
                out = retry_chunk(body, "featurize prep")
                if tid is not None:
                    obs.trace_span(tid, "featurize_stage",
                                   _time.perf_counter() - t0,  # vctpu-lint: disable=VCT006 — obs trace-span timing
                                   records=len(table))
            return out

        def noted(tables):
            for table in tables:
                ctx.note_chunk(table)
                yield table

        if source_pooled:
            window = reader.io_threads + 2
            prepped = imap_ordered(reader.shared_pool(), prep_worker,
                                   noted(_traced_chunks(reader)), window=window)
            scored = shard_score.megabatch_stream(prepped, ctx, profiler=prof)
            source = imap_ordered(reader.shared_pool(), render_stage,
                                  scored, window=window)
            stages = []
        else:
            def timed_tables():
                # serial-IO mesh layout: the reader's inflate/parse work
                # is attributed HERE, per table — the executor's feed
                # sees the whole featurize+score megabatch wall in its
                # next(), and that wall already belongs to the
                # featurize_stage/score.dN rows recorded inside this
                # source chain; booking it as ingest work again would
                # double-count it (the pipeline books its feed-blocked
                # time as queue-wait instead: source_pooled below)
                it = iter(reader)
                while True:
                    try:
                        # items=0: the executor feed counts the pulled
                        # items on this row (the pooled-source rule) —
                        # work seconds only here
                        with stage("ingest", items=0):
                            table = next(it)
                    except StopIteration:
                        return
                    yield table

            source = shard_score.megabatch_stream(
                map(prep_worker, noted(_traced_chunks(timed_tables()))), ctx,
                profiler=prof)
            stages = [render_stage]
    elif source_pooled:
        from variantcalling_tpu.parallel.pipeline import imap_ordered

        # the zero-wait feed: the in-flight window holds RAW BYTE
        # buffers, and each pooled task runs the chunk's WHOLE body
        # (parse -> fused featurize+score -> render) — nothing parsed
        # ever queues between stages (ROADMAP item 4)
        source = imap_ordered(reader.shared_pool(), raw_chunk_worker,
                              _traced_raw(reader.iter_raw()),
                              window=reader.io_threads + 2)
        stages = []
    elif cache_session is not None:
        # serial-IO cached layout: the same raw-buffer chunk body, run
        # inline on the feed — lookups must key on the RAW span (parsed
        # tables have no stable byte identity), so the cache rides the
        # raw feed here too; stages collapse into the worker exactly as
        # in the pooled layout, keeping one code path for hit/miss/stage
        source = map(raw_chunk_worker, _traced_raw(reader.iter_raw()))
        stages = []
    else:
        source = _traced_chunks(reader)
        stages = [score_stage, render_stage]
    if compressor is not None:
        stages.append(compress_stage)
    pipe = StagePipeline(stages, queue_depth=2,
                         profiler=prof, source_name="ingest",
                         # mesh serial-IO counts too: the source chain
                         # attributes its own ingest/featurize/score work
                         # (timed_tables + trace.stage + score.dN), so
                         # feed-blocked time is queue-wait, never work —
                         # and the serial cached layout likewise runs the
                         # self-attributing chunk body inline on the feed
                         consumer_name="writeback",
                         source_pooled=(source_pooled or mesh_scoring
                                        or cache_session is not None),
                         # SUPERVISED mode (docs/robustness.md "Recovery
                         # ladder"): stage-item re-dispatch, watchdog v2
                         # (stack dump + one wedged-chunk retry before
                         # abort), duplicate-delivery drop
                         recover=True)
    gen = pipe.run(source)
    ok = False
    # heartbeat bookkeeping (obs only). Progress (pct) counts ALL
    # committed chunks incl. resumed ones; rate (vps) and ETA use only
    # THIS session's work over this session's elapsed time, so a resumed
    # run neither inflates its rate nor stalls its ETA. Chunk boundaries
    # are a pure function of (input bytes, chunk_bytes) — but only for
    # PLAIN-TEXT inputs: a .gz reader consumes chunk_bytes of
    # decompressed text while getsize() is compressed, so gz runs emit
    # heartbeats without pct/eta rather than a clamped-to-100 lie.
    # (input_bytes was stat'ed above, before the token claim.)
    bytes_comparable = not args.input_file.endswith(".gz")
    resumed_chunks = n_chunks
    resumed_records = n_total
    n_quar_chunks = n_quar_records = 0
    qsink = None
    t_start = _time.perf_counter()  # vctpu-lint: disable=VCT006 — obs heartbeat timing
    try:
        with sink:
            if resume is None:
                if compressor is not None:
                    # the header rides the SAME block stream the chunk
                    # bodies do (it usually just seeds the carry — the
                    # serial BgzfWriter buffered it identically). Safe
                    # ordering: the compress stage has not started — the
                    # pipeline workers spin up on the first next() below.
                    head = compressor.add(header_bytes)
                    index.add_blocks(head)
                    _sink_write(sink, head)
                else:
                    _sink_write(sink, header_bytes)
            opening.close()  # `stream_open` ends: the first pull is next
            for body, k, p, qbody, trace_id, _facts in gen:
                # cooperative per-request cancellation (vctpu serve
                # deadlines/drain, docs/serving.md): chunk-granular by
                # design — raising here unwinds through the normal
                # failure teardown (workers joined, journal+partial
                # kept for resume), never a torn commit. One contextvar
                # read per chunk outside a serve request.
                cancellation.check("streaming filter run")
                if qbody:
                    # quarantined chunk: its ORIGINAL records append to
                    # the sidecar (plain text, never compressed) and the
                    # main output gets zero bytes for this chunk — the
                    # journal entry below records body_len=0, so resume
                    # stays consistent. The sidecar itself is BEST-EFFORT
                    # triage, appended BEFORE the journal claims the
                    # chunk: a kill inside that window re-processes the
                    # chunk on resume, which can DUPLICATE records in the
                    # sidecar — never lose them (the reverse order would
                    # lose them from both outputs). docs/robustness.md.
                    if qsink is None:
                        qsink = open(q_path, "ab")
                    _sink_write(qsink, qbody)
                    qsink.flush()
                    n_quar_chunks += 1
                    n_quar_records += k
                data = memoryview(body) if isinstance(body, np.ndarray) else body
                # the sequenced commit: the TERMINAL span of the chunk's
                # DAG (named like the profiler's consumer stage so
                # critical-path reconciles against it)
                with stage("writeback", trace=trace_id, causal=True,
                           chunk=n_chunks, bytes_out=len(data)):
                    _sink_write(sink, data)
                obs.end_trace(trace_id)
                n_total += k
                n_pass += p
                n_chunks += 1
                if obs.active():
                    obs.counter("records").add(k)
                    obs.counter("records_pass").add(p)
                    obs.histogram("chunk.records").observe(k)
                    elapsed = _time.perf_counter() - t_start  # vctpu-lint: disable=VCT006 — obs heartbeat timing
                    hb = {"chunks": n_chunks, "records": n_total,
                          "records_pass": n_pass,
                          "vps": round((n_total - resumed_records) / elapsed)
                          if elapsed > 0 else 0}
                    if bytes_comparable:
                        done = min(n_chunks * reader.chunk_bytes, input_bytes)
                        session_done = min(
                            (n_chunks - resumed_chunks) * reader.chunk_bytes,
                            input_bytes)
                        hb["pct"] = round(100.0 * done / input_bytes, 2)
                        hb["eta_s"] = round(
                            elapsed * (input_bytes - done) / session_done, 2) \
                            if 0 < session_done and done < input_bytes else 0.0
                    obs.event("heartbeat", "stream", **hb)
                if journal is not None:
                    # the chunk's checksum, the flush and the journal line:
                    # the committer's own work between two writebacks
                    with stage("journal_append", chunk=n_chunks - 1):
                        # the journal must never claim bytes still sitting
                        # in the Python write buffer — a SIGKILL would then
                        # leave the partial file behind the watermark and
                        # resume would (safely but wastefully) start fresh
                        sink.flush()
                        if journal_mod.fsync_enabled():
                            # durability knob (VCTPU_JOURNAL_FSYNC): the
                            # chunk's bytes reach the platter before the
                            # journal claims them (journal.append fsyncs
                            # its own line next) — a power cut can then
                            # cost at most the in-flight chunk
                            os.fsync(sink.fileno())
                        journal.append(n_chunks - 1, k, p, len(data),
                                       zlib.crc32(data),
                                       in_end=reader.chunk_end(n_chunks - 1))
                if cache_session is not None:
                    # committed-prefix publication: entries become
                    # visible (disk store / serve warm index) only once
                    # their chunk's bytes are in the partial file — and
                    # past the journal line when journaling — so a
                    # cancelled request or failed run never publishes
                    # an entry no output carried (docs/caching.md)
                    cache_session.publish_up_to(
                        n_chunks - resumed_chunks - 1)
            if compressor is not None:
                # the final partial block + EOF sentinel — the committer
                # (this thread) is the only writer, in sequence order
                tail = compressor.finish()
                index.add_blocks(tail)
                _sink_write(sink, tail)
        ok = True
    finally:
        # guaranteed teardown on EVERY exit path: stage workers drained and
        # joined (generator close runs StagePipeline's finally), the IO
        # worker pool shut down, prefetch cancelled and joined (a dying
        # process must not kill a .venc persist mid-file), journal handle
        # closed.
        with stage("stream_close"):
            try:
                gen.close()
            finally:
                reader.close()
                prefetch_cancel.set()
                prefetch.join()
            if qsink is not None:
                qsink.close()
            if journal is not None:
                journal.close()
            if cache_session is not None and not ok:
                # failure/cancellation: drop everything unpublished — the
                # stores hold only committed chunks' entries
                cache_session.discard()
        if not ok:
            # failure exit: the partial (if kept) now awaits a RESUME —
            # release the claim so the resumer (or a superseding fresh
            # run's discard) may take the file over
            journal_mod.release_token(part_token)
            if journal is None:
                # non-resumable run: never leave droppings next to the
                # destination (the destination itself was never touched)
                journal_mod.remove_partial(out_path, part_token)
            else:
                logger.info("streaming run failed after %d chunks; partial "
                            "output + journal kept for resume at %s",
                            n_chunks, part_path)
                if obs.active():
                    obs.event("journal", "kept_for_resume", chunks=n_chunks)

    def _commit():
        # injection point "io.commit": fires BEFORE the rename, so an
        # injected ENOSPC is cleanly retryable and a persistent one
        # leaves journal + partial behind for resume
        faults.check("io.commit")
        journal_mod.commit_partial(out_path, part_token)  # vctpu-lint: disable=VCT008 — THE one sanctioned atomic commit

    # the journal outlives the commit attempt (recovery ladder): an
    # ENOSPC on the rename itself must leave journal + partial behind so
    # the NEXT run resumes (skipping every chunk) instead of recomputing
    # — journal.finish() therefore runs only after the rename landed
    with stage("commit", records=n_total, chunks=n_chunks):
        try:
            retry_transient(_commit, "output commit")
        except BaseException:
            journal_mod.release_token(part_token)
            if journal is None:
                # non-resumable run: never leave droppings at the destination
                journal_mod.remove_partial(out_path, part_token)
            else:
                logger.info("output commit failed after %d chunks; partial "
                            "output + journal kept for resume at %s",
                            n_chunks, part_path)
                if obs.active():
                    obs.event("journal", "kept_for_resume", chunks=n_chunks)
            raise
        journal_mod.release_token(part_token)  # committed: the partial is gone
        if journal is not None:
            journal.finish()
        if cache_session is not None:
            cache_session.finish()
    if obs.active():
        obs.event("journal", "committed", chunks=n_chunks, records=n_total)
    if n_quar_chunks:
        logger.warning("quarantine: %d chunk(s), %d record(s) diverted to %s "
                       "— the main output is INCOMPLETE by that many records",
                       n_quar_chunks, n_quar_records, q_path)
    if gz:
        # the index is part of what the user waits for: inside the run's
        # wall, under a span. It is written from what the pipeline gathered;
        # a file the facts do not cover (a replayed cached body, records out
        # of order) is indexed by a second pass over the committed file
        try:
            with stage("tabix_index", records=n_total,
                       bytes=os.path.getsize(out_path)):
                if index.write(out_path + ".tbi"):
                    obs.counter("tabix.index_streamed").add(1)
                    obs.counter("tabix.records").add(index.records)
                else:
                    obs.counter("tabix.index_second_pass").add(1)
                    build_tabix_index(out_path)
        except (ValueError, OSError) as e:
            # unsorted/odd inputs: the VCF itself is still valid
            logger.warning("no tabix index beside %s: %s", out_path, e)
            obs.counter("tabix.index_skipped").add(1)
    if prof is not None:
        # ingest byte attribution: the reader consumes chunk_bytes of
        # (decompressed) text per chunk; cap at the file size only when
        # the two are comparable (plain-text inputs, heartbeat contract)
        approx = n_chunks * reader.chunk_bytes
        prof.stage("ingest").bytes_in = \
            min(approx, input_bytes) if bytes_comparable else approx
        # writing the rows out is tracing's own work, under a span of its
        # own (which closes after the rows are written: it leaves none)
        with stage("profile_emit"):
            prof.emit(wall_s=_time.perf_counter() - t_start,  # vctpu-lint: disable=VCT006 — obs profile wall clock
                      records=n_total - resumed_records)
    return {"n": n_total, "n_pass": n_pass, "chunks": n_chunks,
            "engine": ctx.engine.name,
            "resumed_chunks": resume.chunks if resume is not None else 0,
            "quarantined_chunks": n_quar_chunks,
            "quarantined_records": n_quar_records,
            "cache": cache_session.stats() if cache_session is not None
            else None,
            "mode": "streaming" if pipe.parallel else "serial-chunked"}


def run(argv: list[str]) -> int:
    args = get_parser().parse_args(argv)

    # whole-registry knob validation FIRST (docs/static_analysis.md): any
    # malformed VCTPU_* value exits 2 here with a clear message, uniformly
    # across engines and forest strategies, before any ingest or scoring
    # work starts — and before obs opens a run stream (a run that cannot
    # start leaves no half-written telemetry). An explicit --backend the
    # process cannot honor is the same class of error.
    try:
        engine_mod.pin_backend(args.backend)
        knobs.validate_all()
    except EngineError as e:
        logger.error("%s", e)
        return 2

    # the run manifest opens the telemetry stream (VCTPU_OBS=1): resolved
    # knobs, topology, input identity, argv — then every span/degradation/
    # resolution/heartbeat of this run lands in the same ordered JSONL
    # (docs/observability.md). Output bytes are identical either way.
    obs_run = obs.start_run(
        "filter_variants_pipeline",
        default_path=str(args.output_file) + ".obs.jsonl", argv=argv,
        inputs={"input": args.input_file, "model": args.model_file,
                "reference": args.reference_file})
    status = "error"
    try:
        rc = _run_impl(args)
        status = "ok" if rc == 0 else f"exit {rc}"
        return rc
    except BaseException as e:
        status = f"error: {type(e).__name__}"
        raise
    finally:
        obs.end_run(obs_run, status)


def _run_impl(args) -> int:
    # the head of a cold run under one span (a daemon's requests enter at
    # run_loaded with all of this resident, and emit none)
    with stage("run_open"):
        # resolve the scoring engine ONCE, up front (engine contract,
        # docs/robustness.md): an explicitly required native engine that
        # cannot build/load fails the run HERE with a clear message — never
        # a silent jit fallback half-way through scoring. Multi-host runs
        # also agree on one engine across ranks so the allgathered score
        # slices cannot mix engines within one output file.
        try:
            eng = engine_mod.resolve_for_run()
        except EngineError as e:
            logger.error("%s", e)
            return 2

        with stage("model_load"):
            model = load_model(args.model_file, args.model_name)
        fasta = FastaReader(args.reference_file)
        annotate = {_interval_name(p): bedio.read_intervals(p) for p in args.annotate_intervals}
        blacklist = read_blacklist(args.blacklist) if args.blacklist else None
    return run_loaded(args, model, fasta, annotate, blacklist, engine=eng)


def run_loaded(args, model, fasta: FastaReader, annotate, blacklist,
               engine: engine_mod.EngineDecision | None = None) -> int:
    """The filter pipeline over ALREADY-LOADED resources — the split
    that lets ``vctpu serve`` (docs/serving.md) run requests against its
    resident model/genome caches without re-paying the load, while the
    cold CLI (:func:`_run_impl`) rides the same code so serve output is
    byte-identical to the batch path by construction."""
    from variantcalling_tpu.utils import cancellation
    from variantcalling_tpu.utils.trace import report

    eng = engine if engine is not None else engine_mod.resolve_for_run()
    # rank-partitioned scale-out FIRST (docs/scaleout.md): a multi-rank
    # plan (VCTPU_RANK under the local launcher, or an initialized
    # jax.distributed runtime) runs this process as ONE rank of a pod —
    # full sharded ingest -> fused score -> render over its contiguous
    # span, staged into a rank segment for the rank-sequenced committer.
    from variantcalling_tpu.parallel import rank_plan as rank_plan_mod

    try:
        plan = rank_plan_mod.resolve()
        partitioned = plan.ranks > 1 or plan.span is not None
        if partitioned and rank_plan_mod.scaleout_eligible(args):
            if plan.span is not None:
                logger.info("elastic scale-out: span [%d,%d) gen %d",
                            plan.span[0], plan.span[1], plan.gen)
            else:
                logger.info("rank-partitioned scale-out: rank %d of %d "
                            "(%s)", plan.rank, plan.ranks, plan.source)
            with stage("scaleout"):
                try:
                    return rank_plan_mod.run_scaleout(
                        args, model, fasta, annotate, blacklist,
                        engine=eng, plan=plan)
                except Exception as e:
                    from variantcalling_tpu.parallel import elastic

                    if isinstance(e, elastic.LeaseLost):
                        # benign: another worker holds this (span, gen)
                        # lease — exit distinctly so the coordinator can
                        # tell a lost race from a real failure
                        logger.info("%s — yielding (exit %d)", e,
                                    elastic.EXIT_LEASE_LOST)
                        return elastic.EXIT_LEASE_LOST
                    raise
        if partitioned and plan.source in ("env", "span"):
            # an env/span-launched worker has NO collectives to merge
            # scores through — silently writing the FULL output would
            # make N workers race on one destination; fail loudly
            raise EngineError(
                f"VCTPU_{'SPAN' if plan.span is not None else 'RANK'} is "
                "set but this job cannot run the rank-partitioned "
                "streaming executor (it needs the native engine, "
                "VCTPU_STREAM=1, VCTPU_THREADS>1 and no "
                "--limit_to_contig) — unset it or fix the "
                "configuration; docs/scaleout.md")
    except EngineError as e:
        logger.error("%s", e)
        return 2
    # streaming executor next: overlapped ingest/score/writeback with
    # byte-identical output; falls through to the serial path when
    # ineligible (VCTPU_THREADS=1, multi-process, region-limited, no
    # native engine)
    if streaming_eligible(args.limit_to_contig):
        logger.info("streaming %s", args.input_file)
        try:
            with stage("stream"):
                stats = run_streaming(args, model, fasta, annotate, blacklist,
                                      engine=eng)
        except EngineError as e:
            logger.error("%s", e)
            return 2
        if stats is not None:
            if logger.isEnabledFor(logging.DEBUG):
                # formatted only when asked for: a resident process's span
                # table holds thousands of spans of hundreds of threads
                logger.debug("%s", report())
            logger.info("wrote %s: %d variants, %d PASS (engine %s)",
                        args.output_file, stats["n"], stats["n_pass"],
                        stats["engine"])
            return 0

    logger.info("reading %s", args.input_file)
    with stage("ingest"):
        table = read_vcf(args.input_file)
    # serial path: cancellation polls at stage boundaries (the
    # streaming path polls per chunk)
    cancellation.check("filter run")
    if args.limit_to_contig:
        keep = np.asarray(table.chrom) == args.limit_to_contig
        table = _subset(table, keep)

    # multi-host launch (VCTPU_COORDINATOR set -> __main__ initialized
    # jax.distributed): ranks score CONTIGUOUS slices of the callset on
    # their local-device meshes, then allgather scores+filters so every
    # rank holds the full result and writes an identical file. Work is
    # sharded by variant range, collectives ride the global mesh.
    try:
        n_proc = jax.process_count()
    except Exception as e:  # noqa: BLE001 — uninitialized backend == single process
        degrade.record("pipeline.process_count_probe", e, fallback="n_proc=1")
        n_proc = 1
    work = table
    if n_proc > 1:
        bounds = np.linspace(0, len(table), n_proc + 1).astype(np.int64)
        pid = jax.process_index()
        mask = np.zeros(len(table), dtype=bool)
        mask[bounds[pid]:bounds[pid + 1]] = True
        work = _subset(table, mask)
        logger.info("rank %d/%d scoring variants [%d, %d)", pid, n_proc,
                    int(bounds[pid]), int(bounds[pid + 1]))

    try:
        ctx = FilterContext(
            model, fasta, runs_file=args.runs_file,
            hpol_length=args.hpol_filter_length_dist[0],
            hpol_dist=args.hpol_filter_length_dist[1],
            blacklist=blacklist,
            blacklist_cg_insertions=args.blacklist_cg_insertions,
            annotate_intervals=annotate, flow_order=args.flow_order,
            is_mutect=args.is_mutect, engine=eng,
        )
        with stage("featurize+score"):
            score, filters = ctx.score_table(work)
    except EngineError as e:
        logger.error("%s", e)
        return 2

    if n_proc > 1:
        from variantcalling_tpu.parallel import distributed as dist

        # keep the score's own dtype: a float32 cast here could round a
        # float64 score differently than the single-process run writes it
        score = dist.allgather_concat(np.asarray(score))
        # the FILTER uniques table is a fixed literal identical on every
        # rank, so only the int32 codes cross the wire — writeback stays
        # integer-only (no 5M-string gather, no re-factorize)
        filters = FactorizedColumn(dist.allgather_concat(filters.codes),
                                   filters.uniques)
        assert len(score) == len(table), (len(score), len(table))
        if jax.process_index() != 0 and not knobs.get_bool("VCTPU_ALL_RANKS_WRITE"):
            # every rank holds the full result, but only rank 0 touches the
            # output path: concurrent identical-byte writes to a shared
            # filesystem race benignly at best (truncate-then-write), and a
            # straggler could transiently truncate a finished file.
            # VCTPU_ALL_RANKS_WRITE=1 restores every-rank writes for
            # deployments whose output path is per-host local disk.
            logger.info("rank %d/%d: writeback delegated to rank 0",
                        jax.process_index(), n_proc)
            return 0

    cancellation.check("filter run")
    _ensure_output_header(table.header, engine=ctx.engine,
                          strategy=ctx.forest_strategy,
                          mesh_plan=ctx.mesh_plan,
                          rank_plan=ctx.rank_plan,
                          model_family=ctx.model_family)
    with stage("writeback"):
        # verbatim_core: this pipeline never edits CHROM..QUAL, so record
        # assembly can splice FILTER/TREE_SCORE between original byte spans
        write_vcf(args.output_file, table, new_filters=filters,
                  extra_info={"TREE_SCORE": np.round(score, 4)}, verbatim_core=True)
    if logger.isEnabledFor(logging.DEBUG):
        logger.debug("%s", report())
    logger.info(
        "wrote %s: %d variants, %d PASS", args.output_file, len(table), int(np.sum(filters == PASS))
    )
    return 0


def _subset(table: VariantTable, keep: np.ndarray) -> VariantTable:
    return table.subset(keep)


if __name__ == "__main__":
    sys.exit(run(sys.argv[1:]))
