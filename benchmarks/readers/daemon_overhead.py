"""What a resident daemon adds to the pipeline it runs, as a share of the
time its requests held an execution slot: 1 - summed pipeline wall (obs
``profile/pipeline`` rows) over summed (``serve_request`` - ``serve_admit``)
spans, over the traced requests. Nothing to read where the program wrote no
``serve_request`` span."""

import lookup


def read(ctx):
    total = lookup.load("readers", "span_share").total
    held = total(ctx, "serve_request") - total(ctx, "serve_admit")
    wall = sum(e.get("wall_s", 0.0) for e in ctx["obs_events"]
               if e.get("kind") == "profile" and e.get("name") == "pipeline")
    return 100.0 * (1.0 - wall / held) if held > 0 and wall > 0 else None
