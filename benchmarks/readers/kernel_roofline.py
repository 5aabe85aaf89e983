"""A kernel's share of its roofline: the least time the chip could take for
the traced files' rows (the larger of operations over peak FLOP/s and bytes
over peak bytes/s, counted by the configuration's family), over the device
time of the events whose name holds ``pattern``. Nothing to read where no
such event ran."""


def read(ctx, pattern):
    hits = [d for dev in ctx["device_events"] for n, _, d in dev if pattern in n]
    if not hits or not ctx["traced_rows"]:
        return None
    seconds = sum(hits) / 1e9 / len(ctx["device_events"])
    cfg, peaks, work = ctx["config"], ctx["peaks"], ctx["family"]
    flops = work.flops_per_variant(cfg) * ctx["traced_rows"]
    nbytes = work.bytes_per_variant(cfg) * ctx["traced_rows"] \
        + work.table_bytes(cfg) * len(hits)
    least = max(flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
