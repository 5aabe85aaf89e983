"""Work of one family of executor stages (obs ``profile/stage`` rows, summed
over its workers) as a share of the pipeline's wall (``profile/pipeline``),
over the traced files."""


def read(ctx, families):
    work = sum(e.get("work_s", 0.0) for e in ctx["obs_events"]
               if e.get("kind") == "profile" and e.get("name") == "stage"
               and str(e.get("stage", "")).split(".")[0] in families)
    wall = sum(e.get("wall_s", 0.0) for e in ctx["obs_events"]
               if e.get("kind") == "profile" and e.get("name") == "pipeline")
    return 100.0 * work / wall if wall > 0 and work > 0 else None
