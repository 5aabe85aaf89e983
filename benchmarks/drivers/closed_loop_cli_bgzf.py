"""Traffic driver: ``closed_loop_cli``'s closed loop of one client, on the
container users hand the filter: every callset goes in as BGZF ``.vcf.gz``
and every output is a ``.vcf.gz`` with its ``.tbi``.

``warm_up`` compresses each reference's callset with the plain writer of
``container_reference.py`` (inside ``setup_s``; ``compressed`` below), then
runs the plain-text driver's warm-up. ``window`` is the plain-text driver's window, each file
through ``call_entry`` below. After ``run.window_s`` is taken, every output is
held to the configuration's ``container`` block (``container_reference``:
framing, size at the stated level, index), inflated through Python's ``gzip``
to the plain file the row's ``out`` names, where the harness counts and
compares records as it does for the plain-text cells, and the ``.vcf.gz`` and
``.tbi`` are removed. A row whose output breaks a guarantee gets a non-zero
``rc``. What was read of each output is the row's ``container``.
"""

from __future__ import annotations

import hashlib
import importlib.util
import os
import time
import zlib

import container_reference
import fixtures

HERE = os.path.dirname(os.path.abspath(__file__))
RC_CONTAINER = 65  # a file that exited 0 and whose container broke a guarantee


def _plain_driver():
    """A copy of the plain-text driver of this module's own: its loop and its
    warm-up are used as they are, its ``call_entry`` is the one below."""
    spec = importlib.util.spec_from_file_location(
        "bench_drivers_closed_loop_cli_for_bgzf", os.path.join(HERE, "closed_loop_cli.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CLI = _plain_driver()
_plain_call_entry = CLI.call_entry


def compressed(run, ref: dict) -> str:
    """The reference's callset as BGZF beside it, written by the plain writer
    when it is missing or older than the callset (``read_limits.py`` draws a
    new callset per seed in one process)."""
    plain, gz = ref["calls"], ref["calls"] + ".gz"
    if not os.path.exists(gz) or os.path.getmtime(gz) < os.path.getmtime(plain):
        box = run.config["container"]
        n = container_reference.compress_file(plain, gz, box["level"], box["block_payload"])
        run.say(f"compressed {os.path.basename(plain)}: {os.path.getsize(plain)} -> {n} bytes")
    return gz


def call_entry(run, ref: dict, out: str) -> tuple[int, float]:
    """The same entry at the same defaults: ``calls_<ref>.vcf.gz`` in,
    ``<out>.gz`` (and ``<out>.gz.tbi``) out."""
    return _plain_call_entry(run, dict(ref, calls=compressed(run, ref)), out + ".gz")


CLI.call_entry = call_entry


def _remove(*paths: str) -> None:
    for p in paths:
        if os.path.exists(p):
            os.remove(p)


def warm_up(run) -> None:
    t0 = time.perf_counter()
    for ref in run.refs:
        compressed(run, ref)
    run.say(f"compressed {len(run.refs)} callsets in {time.perf_counter() - t0:.1f}s")
    CLI.warm_up(run)
    warm = os.path.join(run.dir, "warm.vcf.gz")
    _remove(warm, warm + ".tbi", warm + ".obs.jsonl")


def window(run) -> None:
    CLI.window(run)  # run.window_s is taken: what follows is outside the window
    t0 = time.perf_counter()
    verdicts: dict = {}
    for f in run.files:
        gz = f["out"] + ".gz"
        if os.path.exists(gz + ".obs.jsonl"):
            os.replace(gz + ".obs.jsonl", f["out"] + ".obs.jsonl")
        if f["rc"] == 0:
            f["container"] = hold(run, f, gz, verdicts)
            if "broken" in f["container"]:
                run.say(f"file {f['i']}: {f['container']['broken']}")
                f["rc"] = RC_CONTAINER
        _remove(gz, gz + ".tbi")
    run.say(f"held {len(run.files)} outputs to the container's guarantees in "
            f"{time.perf_counter() - t0:.1f}s ({len(verdicts)} distinct)")


def hold(run, f: dict, gz: str, verdicts: dict) -> dict:
    """3(b) on every output; (c) and (d) once per distinct pair of ``.vcf.gz``
    and ``.tbi`` bytes (a file of the same input is the same bytes, so the
    verdict is the same). Leaves the inflated text at the row's ``out``."""
    box = run.config["container"]
    ref = next(r for r in run.refs if r["seed"] == f["ref"])
    got: dict = {}
    try:
        got.update(container_reference.validate_container(gz))
        got["text_bytes_gzip"] = container_reference.inflate_file(gz, f["out"])
        if not os.path.exists(gz + ".tbi"):
            raise ValueError(f"no {os.path.basename(gz)}.tbi beside the output")
        key = tuple(_sha256(p) for p in (gz, gz + ".tbi"))
        if key not in verdicts:
            clen = ref["genome_len"] // ref["n_contigs"]
            verdicts[key] = {
                **container_reference.check_size(
                    os.path.getsize(gz), f["out"], box["size_tolerance"], box["level"]),
                **container_reference.check_index(
                    gz, f["out"], fixtures.contig_names(ref["n_contigs"]),
                    [clen] * ref["n_contigs"], box["index_regions_checked"],
                    f"{run.seed}:{ref['seed']}")}
        got.update(verdicts[key])
    except (ValueError, OSError, EOFError, zlib.error) as e:
        got["broken"] = f"{type(e).__name__}: {e}"
    return got


def _sha256(path: str) -> str:
    with open(path, "rb") as fh:
        return hashlib.file_digest(fh, "sha256").hexdigest()
