"""Engine: median over the window's answered requests of their
dispatch's result fetch from the solve mesh (``timing_s["fetch"]``, the
program's ``plan.fetch`` phase: device-to-host copies, f64 bits back,
join-tree assembly), in ms.  None where no response carries the
breakdown."""
from bench import readers


def read(ctx):
    return readers.timing_median_ms(ctx, "fetch")
