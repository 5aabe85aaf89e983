"""The share of its roofline of a program part that the compiler splits into
several operations: the least time the chip could take for the traced files'
rows (the larger of operations over peak FLOP/s and bytes over peak bytes/s,
counted by the configuration's family) over the summed device time of the
events whose result type holds any of ``patterns``. A device event is named
by its HLO text (``%fusion.78 = pred[16384,128]{...} fusion(...)``): its
result type is what follows `` = `` up to the first space, so a loop whose
carried tuple holds such a shape (``%while.30 = (s32[], f32[...]) while``) is
not counted beside the operations inside it. The model's tables count once
per event that holds ``patterns[0]``, the operation that opens each call of
the part. Nothing to read where no such event ran."""


def result_type(name: str) -> str:
    _, eq, rest = name.partition(" = ")
    return rest.split(" ", 1)[0] if eq else name


def read(ctx, patterns):
    devices = ctx["device_events"]
    typed = [[(result_type(n), d) for n, _, d in dev] for dev in devices]
    hits = [d for dev in typed for t, d in dev if any(p in t for p in patterns)]
    if not hits or not ctx["traced_rows"]:
        return None
    seconds = sum(hits) / 1e9 / len(devices)
    calls = sum(patterns[0] in t for dev in typed for t, _ in dev)
    cfg, peaks, work = ctx["config"], ctx["peaks"], ctx["family"]
    flops = work.flops_per_variant(cfg) * ctx["traced_rows"]
    nbytes = work.bytes_per_variant(cfg) * ctx["traced_rows"] + work.table_bytes(cfg) * calls
    least = max(flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
