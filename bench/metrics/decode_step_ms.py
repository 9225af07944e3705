"""Median wall time of the window's jitted step calls of chunk width 1
(pure decode steps), until their results are ready."""
import statistics

UNIT = "ms"


def read(ctx):
    walls = [c.wall for c in ctx.calls(1)]
    return 1e3 * statistics.median(walls) if walls else None
