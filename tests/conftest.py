"""Test harness: force an 8-device virtual CPU mesh before JAX import.

The reference tests only single-box process parallelism (SURVEY.md §4);
this framework's multi-chip paths are validated on a forced-host CPU mesh
(`--xla_force_host_platform_device_count=8`). The suite proves bytes,
counts and control flow; the TPU is exercised by ``chip_smoke.py`` through
the chip tool, and what the chip would be asked to compile is checked here
by AOT-compiling for a v5e topology (tests/unit/test_tpu_aot.py).
"""

import os
import sys

# Force CPU for tests even on a machine with an accelerator: the suite
# spawns many jax processes, a chip belongs to one process at a time, and
# the 8-device mesh the parity matrices need only exists as host devices.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# If something imported jax before this file ran (a sitecustomize, a pytest
# plugin), it captured JAX_PLATFORMS before the env mutation above. The
# config can still be redirected until the first backend init.
if "jax" in sys.modules:
    import jax

    jax.config.update("jax_platforms", "cpu")

import pathlib

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def fresh_predictor_cache():
    """An empty compiled-predictor cache. It is keyed on the model's
    CONTENT, so a test that expects a build must not depend on which test
    scored an equal model before it in the same worker."""
    from variantcalling_tpu.pipelines import filter_variants as fv

    fv._PREDICTOR_CACHE.clear()
    return fv._PREDICTOR_CACHE


def assert_no_stream_leaks(dirs=(), grace_s: float = 3.0) -> None:
    """The chaos invariant, enforced on the regular suite (ISSUE 10): no
    ``vctpu-*``/``pipe-*``/``genome-prefetch`` thread survives a test
    (pool/worker joins are time-bounded, so a short grace window is
    legitimate) and no stray ``.partial``/``.journal``/``.quarantine``
    sidecar is left in the watched fixture directories. The streaming
    test modules install this as an autouse fixture."""
    import glob
    import threading
    import time

    def leaked():
        # "vctpu-" covers the IO pools, mesh dispatch AND the obs v3
        # continuous profiler ("vctpu-sampler"); "obs-sampler" is the
        # obs v2 resource-watermark thread
        return sorted(
            t.name for t in threading.enumerate()
            if t.name.startswith(("vctpu-", "pipe-", "genome-prefetch",
                                  "obs-sampler")))

    deadline = time.time() + grace_s
    names = leaked()
    while names and time.time() < deadline:
        time.sleep(0.05)
        names = leaked()
    assert not names, f"leaked executor threads: {names}"
    strays = []
    for d in dirs:
        # "*.partial*" also catches the unique-suffix partials
        # (<out>.partial.<pid>-<hex>, ISSUE 14 atomic-commit fix)
        for pattern in ("*.partial*", "*.journal", "*.quarantine"):
            strays += glob.glob(os.path.join(str(d), pattern))
    assert not strays, f"stray streaming sidecar files: {strays}"


def get_resource_dir(test_file: str) -> pathlib.Path:
    """Map tests/<tier>/<name>.py → tests/resources/<tier>/<name>/ (reference convention, conftest.py:1-9)."""
    p = pathlib.Path(test_file).resolve()
    tests_root = p
    while tests_root.name != "tests":
        tests_root = tests_root.parent
    rel = p.relative_to(tests_root).with_suffix("")
    return tests_root / "resources" / rel


@pytest.fixture
def resource_dir(request):
    d = get_resource_dir(str(request.fspath))
    d.mkdir(parents=True, exist_ok=True)
    return d
