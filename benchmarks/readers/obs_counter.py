"""A counter of the program's own metrics registry: the traced files'
end-of-run snapshots (obs ``metrics``/``final``), summed, over the number of
traced files (``per_file``) or as the sum. A program whose snapshots do not
carry the counter gives nothing to read."""


def read(ctx, counter, per_file=True):
    finals = [e for e in ctx["obs_events"]
              if e.get("kind") == "metrics" and e.get("name") == "final"]
    values = [e["counters"][counter] for e in finals
              if counter in e.get("counters", {})]
    if not values:
        return None
    return float(sum(values)) / len(finals) if per_file else float(sum(values))
