"""Summed duration of the program's spans named in ``parts`` as a share of
the summed duration of its spans named in ``whole`` (obs ``span`` events of
the traced files or requests). Nothing to read where the program wrote none
of ``parts`` or none of ``whole``."""


def total(ctx, names):
    durs = [e.get("dur", 0.0) for e in ctx["obs_events"]
            if e.get("kind") == "span" and e.get("name") in names]
    return sum(durs) if durs else None


def read(ctx, parts, whole):
    part, over = total(ctx, parts), total(ctx, whole)
    return 100.0 * part / over if part is not None and over else None
