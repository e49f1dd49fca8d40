"""Admission: percent of the traced window in which no operation ran on
the device while the program's ``plan.admit`` span (``ServingRuntime.
submit``, on the event loop) was open: the idle time admission holds the
chip back.

The harness hands readers the device ops only, so this reads the host
planes of the newest trace under ``.bench_trace`` and takes that trace
only if its ``bench.traced`` span starts where the window does.  Idle is
what ``trace_reduce`` takes it to be: the window less the union of the
device ops, per device plane, averaged over the planes.  None where that
trace is not the window's or holds no ``plan.admit`` span.
"""
from bench import readers


def read(ctx):
    return readers.idle_under_percent(ctx, "plan.admit")
