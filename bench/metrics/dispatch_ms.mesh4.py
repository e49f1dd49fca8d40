"""Engine: mean ``DispatchRecord.execute_s`` (host wall time around one
fused program on the 4-chip solve mesh, blocked until ready) over the
window's dispatches, in ms."""
from bench import readers


def read(ctx):
    return readers.execute_mean_ms(ctx)
