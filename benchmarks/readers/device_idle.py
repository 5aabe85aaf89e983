"""Share of the traced interval in which no operation ran on the device."""


def read(ctx):
    if ctx["traced_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["traced_s"])
