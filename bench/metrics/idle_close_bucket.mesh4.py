"""Admission: percent of the traced window in which no operation ran on
a chip of the solve mesh while the program's ``plan.close_bucket`` span
(batch formation on the event loop, where the layer cache's seeds are
looked up) was open, averaged over the four chips.  None where the trace
is not the window's or holds no ``plan.close_bucket`` span."""
from bench import readers


def read(ctx):
    return readers.idle_under_percent(ctx, "plan.close_bucket")
