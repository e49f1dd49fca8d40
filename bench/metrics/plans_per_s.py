"""Exact plans completed per second: those not failed, over the window
from its start to the completion of the last request issued in it."""


def read(ctx):
    return ctx["window"].plans_per_s()
