"""Admission: median over the window's answered requests of the seconds
their canonical labelling took (``timing_s["canonicalize"]``, the
program's ``plan.canonicalize`` phase), in ms.  The median shrugs off the
few requests the profiler's stop stalls.  None where no response carries
the breakdown."""
import statistics

KEY = "canonicalize"


def read(ctx):
    vals = [t[KEY] for r in ctx["window"].recs
            if (t := getattr(r.resp, "timing_s", None)) and KEY in t]
    return statistics.median(vals) * 1e3 if vals else None
