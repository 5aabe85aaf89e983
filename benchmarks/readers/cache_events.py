"""JAX compilation-cache events inside the window: ``which`` is ``miss`` (a
program XLA compiled now; 0 is the expected reading) or ``hit`` (one loaded
from the persistent cache), ``per_file`` divides by the window's files."""


def read(ctx, which, per_file=False):
    n = ctx["compile_events"].get(ctx[which + "_event"], 0)
    return n / ctx["n_files"] if per_file and ctx["n_files"] else float(n)
