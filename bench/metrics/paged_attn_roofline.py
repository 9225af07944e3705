"""Least time the traced steps' paged attention needs at the chip's peaks
(live positions only), as a share of the flash kernel's device time."""
UNIT = "%"


def read(ctx):
    return ctx.kernel_roofline("paged_attn")
