"""Admission: median over the window's answered requests of the seconds
their canonical labelling took (``timing_s["canonicalize"]``, the
program's ``plan.canonicalize`` phase), in ms.  None where no response
carries the breakdown."""
from bench import readers


def read(ctx):
    return readers.timing_median_ms(ctx, "canonicalize")
