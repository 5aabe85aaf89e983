"""One counter of the program's registry as a share of another, from the
end-of-run snapshots the traced rows hold (obs ``metrics``/``final``; a
daemon writes one for its whole life). Nothing to read where no snapshot
carries ``part``."""


def read(ctx, part, whole):
    finals = [e.get("counters", {}) for e in ctx["obs_events"]
              if e.get("kind") == "metrics" and e.get("name") == "final"]
    if not any(part in c for c in finals):
        return None
    over = sum(c.get(whole, 0) for c in finals)
    return 100.0 * sum(c.get(part, 0) for c in finals) / over if over else None
