"""Backend compiles and persistent-cache loads inside the window, from the
program's compile counter (KERNEL_COUNTERS); a warmed-up run reads 0."""
from benchlib import spans

UNIT = "count"


def read(ctx):
    log = spans.compile_log()
    if log is None:
        return None
    return sum(1 for t, _, _ in log if ctx.t_w0 <= t <= ctx.t_w1)
