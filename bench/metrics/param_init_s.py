"""Host clock around the benchmark's one jitted call that makes the
weights from the seed on the device."""
UNIT = "s"


def read(ctx):
    return ctx.param_init_s
