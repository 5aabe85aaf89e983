"""protocheck — explicit-state model checking for the elastic lease protocol.

The elastic pod (``variantcalling_tpu/parallel/elastic.py``) promises a
distributed-protocol correctness argument that no test can exhaust by
sampling: however workers join, crash, steal and re-cut, the committed
spans tile the input exactly once, every (span, generation) has at most
one owner, no superseded generation's bytes ever commit, and the seam
merge proceeds monotonically. This package checks those invariants the
way the jaxpr audit checks lowering: mechanically, bounded, in tier-0.

Three parts:

* :mod:`tools.protocheck.model` — a small transition system over
  abstract pod states ({worker join, O_EXCL lease acquire, crash,
  steal/re-cut at the journal watermark, seam commit, generation bump})
  with the four invariants, explored breadth-first so any violation
  comes with a MINIMAL interleaving. Seeded mutations (``--mutate``)
  break one protocol rule at a time and must each be caught — the
  checker's own regression suite.
* :mod:`tools.protocheck.anchor` — mechanical anchoring of the model's
  constants (lease filename scheme, O_EXCL flags, generation-bump rule,
  watermark re-cut shape, merge contiguity, marker suffix) against the
  REAL ``elastic.py``/``rank_plan.py`` ASTs via the vctpu-lint project
  index: change the code without the model and the stage fails.
* :mod:`tools.protocheck.__main__` — the tier-0 CLI (lint exit-code
  contract: 0 clean, 1 violation/drift, 2 usage), ``--json`` for the
  machine-readable record, ``--trace`` to print violating interleavings.

Run as ``python -m tools.protocheck``; docs/static_analysis.md
("Protocol model checking") documents the model <-> code anchoring and
how to extend transitions or invariants.
"""

from tools.protocheck.model import Model, explore  # noqa: F401
