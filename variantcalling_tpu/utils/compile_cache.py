"""Persistent XLA compilation cache for every CLI entry point.

The flagship pipelines are CLI tools invoked once per file (reference
docs/howto-callset-filter.md's per-callset invocations), so without a
persistent cache each process re-pays the full jit compile of the fused
featurize+score program — one program per batch bucket it meets —
before touching a single variant. JAX's compilation cache persists
compiled executables on disk keyed by (HLO, jaxlib, flags, device kind);
warm invocations deserialize instead of compiling.

Cache location: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself
reads it and this module names no directory at all — whoever runs the
program (a test harness, a chip runner, a deployment) places the cache.
Where it is not set, the cache is ONE fixed directory inside the checkout
(:data:`DEFAULT_DIR`, git-ignored): the path is part of the cache key's
environment, so a directory that moves between runs (a home directory on
a machine that is thrown away, a temp name, a pid) never hits. JAX's own
``JAX_ENABLE_COMPILATION_CACHE=false`` turns caching off.

Note: XLA:CPU logs a benign machine-feature mismatch (E-level,
``+prefer-no-scatter``/``+prefer-no-gather``) when loading AOT results;
these are XLA-internal pseudo-features, not real ISA bits. We leave
stderr untouched — suppressing C++ E-logs would also hide real faults.
"""

from __future__ import annotations

import os
import sys

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_MIN_COMPILE_ENV = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
#: the fused pipeline programs compile in seconds; cache anything that
#: takes meaningful time so warm runs skip it (JAX's default is 1.0)
_MIN_COMPILE_SECS = 0.5

#: the in-checkout cache directory used when the environment names none
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")

_ENABLED = False


def cache_dir() -> str:
    """The directory compiled programs persist in for this process."""
    return os.environ.get(CACHE_DIR_ENV) or DEFAULT_DIR


def enable_persistent_cache() -> bool:
    """Make sure JAX's compilation cache persists to :func:`cache_dir`;
    returns True when enabled (idempotent).

    When jax is not imported yet (the CLI dispatch fast path — many tools
    are pandas-only and must not pay a jax import at startup), the cache
    is configured through JAX's environment knobs, which jax reads at
    import time; only an already-imported jax needs config.update."""
    global _ENABLED
    if _ENABLED:
        return True
    placed_by_env = bool(os.environ.get(CACHE_DIR_ENV))
    try:
        if not placed_by_env:
            os.makedirs(DEFAULT_DIR, exist_ok=True)
        if "jax" not in sys.modules:
            os.environ.setdefault(CACHE_DIR_ENV, DEFAULT_DIR)
            os.environ.setdefault(_MIN_COMPILE_ENV, str(_MIN_COMPILE_SECS))
        else:
            import jax

            if not placed_by_env:
                jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
            if _MIN_COMPILE_ENV not in os.environ:
                jax.config.update("jax_persistent_cache_min_compile_time_secs",
                                  _MIN_COMPILE_SECS)
    except Exception as e:  # noqa: BLE001 — caching is best-effort, never fatal
        from variantcalling_tpu.utils import degrade

        degrade.record("compile_cache.enable", e,
                       fallback="persistent XLA cache disabled", warn=True)
        return False
    _ENABLED = True
    return True
