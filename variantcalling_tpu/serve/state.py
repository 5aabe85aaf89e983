"""Resident warmed state for the ``vctpu serve`` daemon.

The expensive per-run loads — model unpickle + predictor build, FASTA
index + encoded-genome handles — are held here keyed by file identity
``(abspath, size, mtime_ns)``, so a warm request pays none of them and a
CHANGED file on disk is picked up automatically (the stale entry ages
out of the bounded FIFO). The process-level caches underneath (the
``.venc`` genome sidecar + device-genome cache in ``featurize``, the
compiled-predictor cache in ``pipelines/filter_variants``, the one Mesh
per size in ``shard_score``, the persistent XLA compile cache) were
already designed for a long-lived process; this module is the thin
request-facing layer that keeps the HOST objects resident too.

Thread safety: single-flight builds (``utils/keyed_cache.py``, shared
with ``device_genome`` and the predictor cache) — two concurrent requests
for the same model block on one load; requests for different models load
in parallel; the table locks are only held for dict bookkeeping.
"""

from __future__ import annotations

import os
import threading

from variantcalling_tpu import logger
from variantcalling_tpu.utils import keyed_cache

#: bounded FIFO sizes: models are small (pickles), genomes hold memmaps
_MAX_MODELS = 8
_MAX_FASTAS = 2


def file_identity(path: str) -> tuple[str, int, int]:
    st = os.stat(path)
    return (os.path.abspath(path), int(st.st_size), int(st.st_mtime_ns))


class _KeyedCache:
    """Bounded FIFO with single-flight builds (same-key requests build
    once; distinct keys build concurrently), counting hits and misses."""

    def __init__(self, name: str, max_entries: int):
        self.name = name
        self._cache = keyed_cache.KeyedCache(max_entries, on_evict=self._evicted)
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def _evicted(self, key: tuple) -> None:
        logger.info("serve: %s cache evicted %s", self.name, key[0])

    def get(self, key: tuple, build):
        value, how = self._cache.get(key, build)
        with self._lock:
            if how == keyed_cache.BUILT:
                self.misses += 1
            else:  # found, or another request's build awaited
                self.hits += 1
        return value

    def stats(self) -> dict:
        with self._lock:
            return {"entries": len(self._cache), "hits": self.hits,
                    "misses": self.misses}


class ResidentState:
    """The daemon's warmed state: resident models + FastaReaders."""

    def __init__(self):
        self._models = _KeyedCache("model", _MAX_MODELS)
        self._fastas = _KeyedCache("genome", _MAX_FASTAS)

    def get_model(self, model_file: str, model_name: str):
        from variantcalling_tpu.models.registry import load_model

        key = (*file_identity(model_file), model_name)
        return self._models.get(
            key, lambda: load_model(model_file, model_name))

    def get_fasta(self, reference_file: str):
        from variantcalling_tpu.io.fasta import FastaReader

        key = file_identity(reference_file)
        return self._fastas.get(key, lambda: FastaReader(reference_file))

    def stats(self) -> dict:
        from variantcalling_tpu.featurize import device_genome_stats

        return {"models": self._models.stats(),
                "genomes": self._fastas.stats(),
                "device_genomes": device_genome_stats()}
