"""95th percentile of every gap between consecutive tokens of one request
whose both tokens fall inside the window (linear interpolation)."""
import numpy as np

UNIT = "ms"


def read(ctx):
    if not ctx.itl:
        return None
    return 1e3 * float(np.percentile(np.asarray(ctx.itl, np.float64), 95))
