"""The on-CPU share of one family of executor stages: summed ``cpu_s`` over
summed ``work_s`` of its obs ``profile/stage`` rows, over the traced files.
``cpu_s`` is the rows' threads' own CPU clocks inside the family's spans
(native code that released the interpreter counts; a thread blocked on the
interpreter, a lock, a queue, the device or the disk does not), so 100 less
this share is the part of the family's wall its threads waited. Only rows
that carry ``cpu_s`` count, on both sides. Nothing to read where the program
wrote no such row."""


def read(ctx, families):
    rows = [e for e in ctx["obs_events"]
            if e.get("kind") == "profile" and e.get("name") == "stage"
            and "cpu_s" in e
            and str(e.get("stage", "")).split(".")[0] in families]
    work = sum(e.get("work_s", 0.0) for e in rows)
    return 100.0 * sum(e["cpu_s"] for e in rows) / work if work > 0 else None
