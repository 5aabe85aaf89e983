"""Build-once tables: a single-flight gate and the bounded cache on top of it.

The package keeps a handful of expensive per-process objects keyed by
what they were made from: compiled predictors
(``pipelines/filter_variants._PREDICTOR_CACHE``), device-resident genomes
(``featurize.device_genome``), the serve daemon's resident models and
FASTA readers (``serve/state``). Chunks fan out on the IO pool, so a
miss is seen by many threads at once; every one of those tables wants the
same miss protocol, and this module is its one spelling:

- of the threads that ask for one key while nobody has it, ONE runs the
  build; the others wait for it and take its result;
- a build that raises hands the SAME exception to every waiter and
  leaves nothing behind, so the next caller builds again;
- distinct keys never wait on each other.
"""

from __future__ import annotations

import threading

#: how :meth:`KeyedCache.get` came by its value
HIT, BUILT, WAITED = "hit", "built", "waited"

_MISSING = object()


class _Flight:
    __slots__ = ("done", "value", "error", "waiters")

    def __init__(self):
        self.done = threading.Event()
        self.waiters = 0
        self.value = None
        self.error: BaseException | None = None


class SingleFlight:
    """The in-flight table: ``do(key, fn)`` runs ``fn`` on the first
    thread to ask for ``key``; threads that ask while it runs wait and
    get its value, or its exception."""

    def __init__(self):
        self._lock = threading.Lock()
        self._flights: dict = {}

    def do(self, key, fn):
        """-> ``(value, waited)``; ``waited`` is True on the threads that
        took another thread's result."""
        with self._lock:
            flight = self._flights.get(key)
            leader = flight is None
            if leader:
                flight = self._flights[key] = _Flight()
            else:
                flight.waiters += 1
        if not leader:
            flight.done.wait()
            if flight.error is not None:
                raise flight.error
            return flight.value, True
        try:
            flight.value = fn()
        except BaseException as e:
            flight.error = e
            raise
        finally:
            with self._lock:
                # a clear() may have let another leader in under this key
                if self._flights.get(key) is flight:
                    del self._flights[key]
            flight.done.set()
        return flight.value, False

    def waiting(self) -> int:
        """Threads now waiting on another thread's flight."""
        with self._lock:
            return sum(f.waiters for f in self._flights.values())

    def clear(self) -> None:
        """Forget the flights under way: their waiters are still released
        by their leaders, but a new caller starts a flight of its own."""
        with self._lock:
            self._flights.clear()


class KeyedCache:
    """Bounded FIFO of built values with single-flight misses.

    A hit is one lock-free dict probe. ``on_evict(key)`` is told of every
    entry the bound pushes out.
    """

    def __init__(self, max_entries: int, on_evict=None):
        self.max_entries = max_entries
        self._on_evict = on_evict
        self._lock = threading.Lock()
        self._entries: dict = {}
        self._flights = SingleFlight()

    def get(self, key, build):
        """-> ``(value, how)`` with ``how`` one of :data:`HIT`,
        :data:`BUILT` (this thread ran ``build``) or :data:`WAITED`
        (another thread's build, awaited)."""
        hit = self._entries.get(key, _MISSING)
        if hit is not _MISSING:
            return hit, HIT

        def fill():
            # re-check: the flight this thread just missed has landed
            hit = self._entries.get(key, _MISSING)
            if hit is not _MISSING:
                return hit, HIT
            value = build()
            with self._lock:
                while len(self._entries) >= self.max_entries:
                    evicted = next(iter(self._entries))
                    del self._entries[evicted]
                    if self._on_evict is not None:
                        self._on_evict(evicted)
                self._entries[key] = value
            return value, BUILT

        (value, how), waited = self._flights.do(key, fill)
        return value, WAITED if waited else how

    def clear(self) -> None:
        """Empty the cache and its in-flight table."""
        with self._lock:
            self._entries.clear()
        self._flights.clear()

    def waiting(self) -> int:
        """Threads now waiting on another thread's build."""
        return self._flights.waiting()

    def __contains__(self, key) -> bool:
        return key in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    def items(self) -> list:
        with self._lock:
            return list(self._entries.items())
