"""Admission: percent of the traced window in which no operation ran on
a chip of the solve mesh while the program's ``plan.admit`` span
(``ServingRuntime.submit``, on the event loop) was open, averaged over
the four chips.  None where the trace is not the window's or holds no
``plan.admit`` span."""
from bench import readers


def read(ctx):
    return readers.idle_under_percent(ctx, "plan.admit")
