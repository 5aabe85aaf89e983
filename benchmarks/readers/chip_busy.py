"""Each chip's busy share of the traced interval (the union of its ``XLA
Ops`` intervals over the harness's clock between the trace's two ends), then
one number over the chips: ``least`` is the least busy chip's share,
``spread`` the busiest chip's share less the least busy one's, in points.
Nothing to read with fewer than two chips in the trace."""

import trace_reduce


def shares(ctx):
    if ctx["traced_s"] <= 0:
        return []
    return [100.0 * trace_reduce.busy_seconds(events) / ctx["traced_s"]
            for events in ctx["device_events"]]


def read(ctx, what):
    per_chip = shares(ctx)
    if len(per_chip) < 2:
        return None
    return min(per_chip) if what == "least" else max(per_chip) - min(per_chip)
