"""Engine: median over the window's answered requests of their
dispatch's host preparation (``timing_s["prepare"]``, the program's
``plan.prepare`` phase: padding, candidate tables, f64 bits,
host-to-device copies), in ms.  None where no response carries the
breakdown."""
import statistics

KEY = "prepare"


def read(ctx):
    vals = [t[KEY] for r in ctx["window"].recs
            if (t := getattr(r.resp, "timing_s", None)) and KEY in t]
    return statistics.median(vals) * 1e3 if vals else None
