"""Engine: median over the window's answered requests of their
dispatch's host preparation (``timing_s["prepare"]``, the program's
``plan.prepare`` phase: the per-query connectivity masks, padding, the
cardinalities' f64 bits, host-to-device copies), in ms.  None where no
response carries the breakdown."""
from bench import readers


def read(ctx):
    return readers.timing_median_ms(ctx, "prepare")
