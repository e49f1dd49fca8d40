"""Device: percent of the traced window in which no operation ran on a
chip of the solve mesh (1 - union of device-op intervals / window, on
each TPU plane), averaged over the four chips."""
from bench import readers


def read(ctx):
    return readers.idle_percent(ctx)
