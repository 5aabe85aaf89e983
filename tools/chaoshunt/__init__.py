"""chaoshunt — seeded chaos campaigns over the parallel streaming executor.

The fault-tolerance layer (watchdog v2, chunk re-dispatch, atomic commit,
journal resume, quarantine — docs/robustness.md "Recovery ladder") makes
promises about every fault *interleaving*, but hand-written tests only
exercise single faults at chosen points. This harness searches the space
the tests cannot enumerate: it draws randomized fault SCHEDULES over the
``faults.POINTS`` catalog (transient / persistent / hang / device-OOM /
commit-ENOSPC, plus SIGKILL-at-random-progress legs), runs the streaming
filter in a subprocess under each schedule — fresh, and resumed when the
faulted leg left a journal — across the executor layouts (serial,
``VCTPU_IO_THREADS=4``, ``VCTPU_MESH_DEVICES=2``), and checks the
INVARIANTS after every leg:

- success  ⇒ output bytes identical to a clean reference (modulo the
  provenance header lines that legitimately name the layout);
- failure  ⇒ a distinct exit code, the destination untouched (or still
  the previous complete file), no leaked ``vctpu-*``/``pipe-*`` threads,
  and sidecars either absent or a valid resumable journal+partial pair;
- SIGKILL  ⇒ destination absent, complete, or the intact previous file —
  never torn bytes (a kill can land right after the atomic commit);
- resume   ⇒ the rerun completes byte-identically and removes the pair.

A failing schedule is DELTA-SHRUNK to a minimal repro (drop faults,
reduce times, drop the kill, simplify the layout — while the violation
persists) and written as a JSON file the suite can replay
(``python -m tools.chaoshunt --replay repro.json``).

CLI contract (shared with ``vctpu-lint``): exit 0 when
every invariant held, 1 on a violation, 2 on usage errors. ``--json``
emits the machine-readable campaign report. ``run_tests.sh`` runs a
bounded 10-seed smoke behind ``VCTPU_CHAOS=1``.
"""

from tools.chaoshunt.harness import (  # noqa: F401
    FaultSpec,
    Schedule,
    draw_schedule,
    run_campaign,
    run_schedule,
    shrink_schedule,
)
