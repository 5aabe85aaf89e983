"""Summed duration of the program's spans named ``part`` as a share of the
summed duration of its spans named ``whole`` (obs ``span`` events of the
traced requests). Nothing to read where the program wrote no ``whole``."""


def total(ctx, name):
    return sum(e.get("dur", 0.0) for e in ctx["obs_events"]
               if e.get("kind") == "span" and e.get("name") == name)


def read(ctx, part, whole):
    over = total(ctx, whole)
    return 100.0 * total(ctx, part) / over if over > 0 else None
