"""Exact plans completed per second on the four-chip solve mesh: those
not failed, over the window from its start to the completion of the last
request issued in it.  The same quantity as ``plans_per_s``, under a
bound of its own set from this cell's spread."""


def read(ctx):
    return ctx["window"].plans_per_s()
