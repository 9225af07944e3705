"""Median wall time of the window's jitted step calls wider than one
token (steps that carry a prompt chunk), until their results are ready."""
import statistics

UNIT = "ms"


def read(ctx):
    walls = [c.wall for c in ctx.calls() if c.c > 1]
    return 1e3 * statistics.median(walls) if walls else None
