"""Mean over the window's steps of Server.step's wall time less the wall
time of the jitted step call inside it (planning, input upload, logits
transfer, sampling, bookkeeping)."""
UNIT = "ms"


def read(ctx):
    steps = [s for s in ctx.window_steps() if s.calls]
    if not steps:
        return None
    host = [(s.t1 - s.t0) - sum(c.wall for c in s.calls) for s in steps]
    return 1e3 * sum(host) / len(host)
