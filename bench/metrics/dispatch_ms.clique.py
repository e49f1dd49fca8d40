"""Engine: mean ``DispatchRecord.execute_s`` (host wall time around one
fused program, blocked until ready) over the window's dispatches, in ms."""


def read(ctx):
    count, total = ctx["layers"]["execute"]
    return total / count * 1e3 if count else None
