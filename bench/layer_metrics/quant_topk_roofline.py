"""Share of the int8 asymmetric-distance top-k kernel's device time that
its operations and bytes need at least, %."""
from bench.layers import roofline_pct


def read(ctx):
    return roofline_pct(ctx, "quant_topk")
