"""One counter of the program's registry over another, as a plain quotient
(``counter_ratio`` gives the same as a percentage), from the end-of-run
snapshots the traced rows hold. Nothing to read where no snapshot carries
``part``, or where ``whole`` sums to 0."""


def read(ctx, part, whole):
    finals = [e.get("counters", {}) for e in ctx["obs_events"]
              if e.get("kind") == "metrics" and e.get("name") == "final"]
    if not any(part in c for c in finals):
        return None
    over = sum(c.get(whole, 0) for c in finals)
    return sum(c.get(part, 0) for c in finals) / over if over else None
