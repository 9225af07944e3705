"""Least time the traced steps' CIM matmuls need at the chip's peaks, as a
share of the fused CIM kernel's device time in the trace."""
UNIT = "%"


def read(ctx):
    return ctx.kernel_roofline("cim_mvm")
