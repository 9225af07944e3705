"""Process start to the first request of the window: weights, server
build (offline weight quantization, pool), compiles, the first wave's
prefill."""
UNIT = "s"


def read(ctx):
    return ctx.setup_s
