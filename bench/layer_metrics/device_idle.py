"""Share of the traced window in which no operation ran on the device, %."""
from bench.layers import device_idle_pct


def read(ctx):
    return device_idle_pct(ctx)
