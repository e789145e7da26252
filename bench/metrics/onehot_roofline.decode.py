"""The onehot kernel's share of its roofline in the window: each call's
ideal time from its shapes (bench/cost.py) over the device time of its
trace events (name map: bench/kernels.json)."""
from bench.readers import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "onehot")
