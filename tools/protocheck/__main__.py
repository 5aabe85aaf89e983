"""CLI: ``python -m tools.protocheck`` — the tier-0 protocol stage.

Exit codes follow the lint contract: 0 clean (all invariants hold on
the anchored model, exploration complete), 1 an invariant violation or
model/code anchor drift, 2 usage error.

``--json`` emits the machine-readable record::

    {"states": N, "complete": true, "wall_s": ..., "anchors": [...],
     "violations": [{"invariant": ..., "trace": [...]}, ...],
     "mutation": null, "deadlocks": 0}

``--mutate NAME`` seeds one protocol bug (drop_o_excl /
commit_stale_gen / double_cover) — used by the regression tests, where
a CLEAN result is the failure. ``--trace`` prints each violation's
minimal interleaving.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from tools.protocheck import anchor as anchor_mod
from tools.protocheck.model import MUTATIONS, Model, explore


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.protocheck",
        description="explicit-state model checker for the elastic lease "
                    "protocol (anchored to parallel/elastic.py)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable result record")
    ap.add_argument("--trace", action="store_true",
                    help="print the minimal violating interleaving(s)")
    ap.add_argument("--mutate", choices=MUTATIONS, default=None,
                    help="seed one protocol bug (the mutation tests)")
    ap.add_argument("--max-states", type=int, default=200_000,
                    help="state-space bound (default %(default)s)")
    ap.add_argument("--total", type=int, default=4,
                    help="abstract input length (default %(default)s)")
    ap.add_argument("--workers", type=int, default=2,
                    help="worker-pool width (default %(default)s)")
    ap.add_argument("--no-anchors", action="store_true",
                    help="skip the model<->code anchor check (snippet/"
                    "mutation runs)")
    try:
        args = ap.parse_args(argv)
    except SystemExit as e:
        return 2 if e.code not in (0, None) else 0
    if args.total <= 0 or args.workers <= 0 or args.max_states <= 0:
        print("protocheck: --total/--workers/--max-states must be "
              "positive", file=sys.stderr)
        return 2

    t0 = time.perf_counter()
    drift: list[str] = []
    if not args.no_anchors:
        drift = anchor_mod.verify()
    model = Model(total=args.total, workers=args.workers,
                  mutate=args.mutate)
    res = explore(model, max_states=args.max_states)
    wall = time.perf_counter() - t0

    doc = {
        "states": res.states,
        "complete": res.complete,
        "deadlocks": res.deadlocks,
        "mutation": args.mutate,
        "wall_s": round(wall, 3),
        "anchors": drift,
        "violations": [{"invariant": msg, "trace": trace}
                       for msg, trace in res.violations],
    }
    bad = bool(drift or res.violations or not res.complete)
    if args.as_json:
        json.dump(doc, sys.stdout, indent=2)
        print()
    else:
        for msg in drift:
            print(msg)
        for msg, trace in res.violations:
            print(f"violation: {msg}")
            if args.trace:
                print("  minimal interleaving:")
                for step in trace:
                    print(f"    {step}")
        if not res.complete:
            print(f"protocheck: state bound {args.max_states} hit before "
                  "exhausting the space — raise --max-states",
                  file=sys.stderr)
        if not bad:
            print(f"protocheck: {res.states} states explored, all "
                  f"invariants hold, model anchored to code "
                  f"({wall:.2f}s)")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
