"""The whole device step's share of the chip's peak: operations the
configuration's model needs for the variants of the traced files (counted by
its family), over the traced interval on the harness's clock times the
peak."""


def read(ctx):
    if not ctx["traced_rows"] or ctx["traced_s"] <= 0:
        return None
    need = ctx["family"].flops_per_variant(ctx["config"]) * ctx["traced_rows"]
    return 100.0 * need / (ctx["traced_s"] * ctx["peaks"]["flops_bf16"])
