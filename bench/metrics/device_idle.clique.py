"""Device: percent of the traced window in which no operation ran on the
device (1 - union of device-op intervals / window)."""


def read(ctx):
    red = ctx["trace"]
    if red is None or not red.window_s:
        return None
    return red.idle_share * 100.0
