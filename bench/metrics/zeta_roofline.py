"""Kernels: the least time the zeta kernels' HBM bytes need at the
device's peak bandwidth, over their summed device time in the trace, in
percent (see bench/roofline/zeta.py for the bytes)."""
from bench.roofline import zeta as kernel
from bench.roofline import share


def read(ctx):
    return share(ctx, kernel)
