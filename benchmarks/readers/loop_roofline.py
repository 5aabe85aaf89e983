"""The share of its roofline of a program part that runs as one device loop:
the least time the chip could take for the traced files' rows (the larger of
operations over peak FLOP/s and bytes over peak bytes/s, counted by the
configuration's family), over the summed device time of the events whose HLO
text holds the array the family names as the loop's own (``loop_operand``:
a shape only that loop carries, such as the whole node table the walk's tree
loop slices). A loop's event spans every operation inside it, whose own
events name only their slices of the array and are not counted again.
Nothing to read where no such event ran."""


def read(ctx):
    cfg, peaks, work = ctx["config"], ctx["peaks"], ctx["family"]
    mark = work.loop_operand(cfg)
    hits = [d for dev in ctx["device_events"] for n, _, d in dev if mark in n]
    if not hits or not ctx["traced_rows"]:
        return None
    seconds = sum(hits) / 1e9 / len(ctx["device_events"])
    flops = work.flops_per_variant(cfg) * ctx["traced_rows"]
    nbytes = work.bytes_per_variant(cfg) * ctx["traced_rows"] + work.table_bytes(cfg) * len(hits)
    least = max(flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
