"""Share of the fp32 fused top-k kernel's device time that its operations
and bytes need at least, %."""
from bench.layers import roofline_pct


def read(ctx):
    return roofline_pct(ctx, "fused_topk")
