"""Share of the traced window in which no operation ran on the device."""
UNIT = "%"


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
