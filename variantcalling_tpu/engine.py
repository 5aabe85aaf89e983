"""Deterministic scoring-engine contract (``VCTPU_ENGINE``).

The filter pipeline can score a FlatForest through two engines that are
required to produce byte-identical formatted output (locked by
``tests/unit/test_engine_contract.py``):

- ``native`` — the C++ host engine (window gather + featurize + forest
  walk in ``native/src``), the CPU fallback hot path;
- ``jit``    — the jitted XLA program (fused featurize + gather-walk /
  GEMM / pallas forest), the accelerator path.

Round-5 VERDICT found the worst failure mode a filtering framework can
have: the engine was chosen PER CALL (``_native_cpu_featurize_score``
returned ``None`` on any hiccup — e.g. g++ build contention under suite
load — and the caller silently fell back to jit), so which engine scored
a run depended on machine load. This module makes the choice a RUN-LEVEL
contract instead:

- the engine is resolved **once per process** (:func:`resolve`), from
  ``VCTPU_ENGINE`` ∈ {``auto``, ``native``, ``jit``} (default ``auto``);
- ``VCTPU_REQUIRE_NATIVE=1`` (or ``VCTPU_ENGINE=native``) **fails loudly**
  (:class:`EngineError`, CLI exit code 2) when the native engine cannot
  build/load — no silent degradation;
- once resolved, **mid-run switching is impossible**: a native hiccup
  after resolution raises instead of degrading to jit
  (``pipelines/filter_variants.py``), and the jit engine never touches the
  native scorer;
- the decision is recorded in the log and in the output VCF header
  (``##vctpu_engine=<name>``) so every output file names the engine that
  produced it.

Scope: the contract covers the **scoring** hot path (featurize + forest
inference). IO-layer native acceleration (BGZF, VCF scan/assemble) keeps
its per-call fallbacks — those paths are byte-identical to their Python
twins by construction and test, so they cannot change output bytes.

Legacy knob: ``VCTPU_NATIVE_FOREST=0`` still forces jit (it predates this
module; ``VCTPU_ENGINE=jit`` is the documented spelling).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, replace

from variantcalling_tpu import knobs, logger
from variantcalling_tpu.utils import degrade

ENGINE_ENV = "VCTPU_ENGINE"
REQUIRE_ENV = "VCTPU_REQUIRE_NATIVE"
HEADER_KEY = "vctpu_engine"


class EngineError(RuntimeError):
    """A requested/resolved engine cannot serve this run. Never caught by
    a fallback — the run fails with a clear message (exit code 2)."""


@dataclass(frozen=True)
class EngineDecision:
    """The resolved, immutable engine choice for this process."""

    name: str  # "native" | "jit"
    requested: str  # "auto" | "native" | "jit" (what the env asked for)
    reason: str  # human-readable resolution rationale

    def header_line(self) -> str:
        return f"##{HEADER_KEY}={self.name}"


_LOCK = threading.Lock()
_RESOLVED: EngineDecision | None = None


def pin_backend(requested: str | None) -> None:
    """Honor a CLI ``--backend``. ``None`` (no flag) takes whatever
    platform JAX initializes — the output header names the engine that
    scored. ``cpu`` pins JAX to the CPU platform (effective before the
    backend initializes). ``tpu`` is a requirement, not a hint: the run
    must find ``jax.default_backend() == "tpu"`` or it dies with
    EngineError (exit 2) instead of carrying on through the host engine.
    """
    import jax

    if requested == "cpu":
        jax.config.update("jax_platforms", "cpu")
    elif requested == "tpu":
        try:
            found = jax.default_backend()
        except RuntimeError as e:  # backend initialization failed
            raise EngineError(
                f"--backend tpu was requested but JAX could not initialize "
                f"a backend: {e}") from e
        if found != "tpu":
            raise EngineError(
                f"--backend tpu was requested but JAX initialized the "
                f"{found!r} platform — no TPU is visible to this process "
                "(is another process holding the chip, or JAX_PLATFORMS "
                "set?). Drop the flag to run on what JAX finds.")


def _requested() -> str:
    req = knobs.get_str(ENGINE_ENV)
    if knobs.get_bool(REQUIRE_ENV):
        if req == "jit":
            raise EngineError(
                f"{REQUIRE_ENV}=1 conflicts with {ENGINE_ENV}=jit — drop one")
        req = "native"
    return req


def _native_usable() -> bool:
    from variantcalling_tpu import native

    return native.available()


def _auto_wants_native() -> bool:
    """The auto policy (unchanged from the pre-contract
    ``use_native_cpu_forest``): single local CPU device — the sharded mesh
    path and accelerators stay on XLA."""
    if not knobs.get_bool("VCTPU_NATIVE_FOREST"):
        return False
    # a backend that fails to initialize raises from here: on a machine
    # that should have an accelerator that is the finding, not a reason
    # to pick an engine
    import jax

    return jax.default_backend() == "cpu" and len(jax.local_devices()) == 1


def resolve() -> EngineDecision:
    """Resolve (once per process) and return the engine decision.

    Subsequent calls return the cached decision — the probe that decides
    (native library build/load, backend) runs exactly once, so a later
    build failure or env mutation cannot flip the engine mid-run.
    """
    global _RESOLVED
    with _LOCK:
        if _RESOLVED is not None:
            return _RESOLVED
        req = _requested()
        if req == "native":
            if not _native_usable():
                raise EngineError(
                    "the native scoring engine was required "
                    f"({ENGINE_ENV}=native or {REQUIRE_ENV}=1) but the native "
                    "library failed to build/load on this host (g++ missing, "
                    "build failure, or VCTPU_NO_NATIVE set). Refusing to fall "
                    "back to the jit engine; unset the requirement or fix the "
                    "toolchain. See docs/robustness.md."
                )
            decision = EngineDecision("native", req, "explicitly requested")
        elif req == "jit":
            decision = EngineDecision("jit", req, "explicitly requested")
        elif _auto_wants_native() and _native_usable():
            decision = EngineDecision(
                "native", req, "auto: single local CPU device, native library loaded")
        else:
            decision = EngineDecision("jit", req, "auto: accelerator/mesh backend, "
                                      "VCTPU_NATIVE_FOREST=0, or no native library")
        logger.info("scoring engine resolved: %s (%s)", decision.name, decision.reason)
        # NOTE: no obs event here — resolution is cached per process, so a
        # cache-miss emission would vanish from every later run's stream.
        # The per-run "resolve"/"engine" event is emitted by FilterContext,
        # which pins the decision into each run.
        _RESOLVED = decision
        return decision


def resolve_request() -> EngineDecision:
    """Per-REQUEST engine decision for the ``vctpu serve`` daemon
    (docs/serving.md): an EXPLICIT scoped/env request (``VCTPU_ENGINE``
    under ``knobs.scope``, or ``VCTPU_REQUIRE_NATIVE``) resolves fresh —
    the process cache must not pin request A's engine onto request B —
    while ``auto`` returns the cached process decision (the probe that
    decides auto ran once and its inputs are process facts, not request
    settings). Explicit native still fails loudly when unusable; the
    failure is then a per-request configuration error."""
    req = _requested()
    if req == "auto":
        return resolve()
    if req == "native":
        if not _native_usable():
            raise EngineError(
                "this request requires the native scoring engine "
                f"({ENGINE_ENV}=native or {REQUIRE_ENV}=1) but the native "
                "library is not loaded on this host. See "
                "docs/robustness.md.")
        return EngineDecision("native", req, "explicitly requested (scoped)")
    return EngineDecision("jit", req, "explicitly requested (scoped)")


def resolve_for_run() -> EngineDecision:
    """:func:`resolve` plus multi-host agreement: every rank must score
    with the SAME engine, or the allgathered score slices could mix
    engines within one output file.

    Collective-safe under per-rank failure: a rank whose local resolution
    raised still ENTERS the agreement allgather (with an error token), so
    healthy ranks never deadlock waiting for it — every rank then fails
    the job loudly. Disagreement among healthy ranks downgrades
    auto-resolved ranks to jit; a rank that EXPLICITLY requested native
    raises instead (the fail-loudly contract beats the agreement).
    Call on every rank or none.
    """
    local_error: EngineError | None = None
    decision: EngineDecision | None = None
    try:
        decision = resolve()
    except EngineError as e:
        local_error = e
    try:
        import jax

        n_proc = jax.process_count()
    except Exception as e:  # noqa: BLE001 — uninitialized backend == single process
        degrade.record("engine.process_count_probe", e, fallback="n_proc=1")
        n_proc = 1
    if n_proc <= 1:
        if local_error is not None:
            raise local_error
        return decision
    from variantcalling_tpu.parallel import distributed as dist

    # token carries (resolved name, what was requested) so EVERY rank can
    # compute the SAME verdict from the same gathered list — one rank
    # raising while another proceeds would just move the deadlock to the
    # next collective
    token = "error/-" if local_error is not None \
        else f"{decision.name}/{decision.requested}"
    tokens = [t.split("/", 1) for t in dist.allgather_strings([token])]
    if local_error is not None:
        raise local_error
    names = {t[0] for t in tokens}
    if "error" in names:
        raise EngineError(
            "scoring-engine resolution failed on another rank (see its log "
            "for the cause); failing this rank too so the job exits "
            "consistently instead of deadlocking in a later collective")
    if len(names) > 1:
        if any(req == "native" for _, req in tokens):
            raise EngineError(
                "ranks resolved different scoring engines "
                f"({','.join(sorted(names))}) and at least one rank "
                f"explicitly requires native ({ENGINE_ENV}=native or "
                f"{REQUIRE_ENV}=1) — refusing to downgrade it silently. "
                "Pin the same engine on every rank.")
        downgraded = replace(
            decision, name="jit",
            reason=f"ranks disagreed ({','.join(sorted(names))}): "
                   "pinning every rank to jit")
        logger.warning("scoring engine: %s", downgraded.reason)
        from variantcalling_tpu import obs

        if obs.active():
            obs.event("resolve", "engine", value=downgraded.name,
                      requested=downgraded.requested, reason=downgraded.reason)
        global _RESOLVED
        with _LOCK:
            _RESOLVED = downgraded  # the whole process follows the agreement
        return downgraded
    return decision


def reset_for_tests() -> None:
    """Drop the cached decision so a test can re-resolve under a patched
    env. Production code must never call this — the cache IS the no-switch
    guarantee."""
    global _RESOLVED
    with _LOCK:
        _RESOLVED = None
