"""Engine: median over the window's answered requests of their
dispatch's result fetch (``timing_s["fetch"]``, the program's
``plan.fetch`` phase: device-to-host copies, f64 bits back, join-tree
assembly), in ms.  None where no response carries the breakdown."""
import statistics

KEY = "fetch"


def read(ctx):
    vals = [t[KEY] for r in ctx["window"].recs
            if (t := getattr(r.resp, "timing_s", None)) and KEY in t]
    return statistics.median(vals) * 1e3 if vals else None
