"""Seconds of backend compiles and persistent-cache loads before the
window, from the program's compile counter (KERNEL_COUNTERS, counting
from the import of the serving module)."""
from benchlib import spans

UNIT = "s"


def read(ctx):
    log = spans.compile_log()
    if log is None:
        return None
    return sum(sec for t, _, sec in log if t < ctx.t_w0)
