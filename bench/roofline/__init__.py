"""Roofline shares of the program's kernels.

Each kernel module here gives ``matches(call)`` and ``bytes_needed(call)``
for one Pallas call, from its operand and result shapes, which ``hlo.py``
reads from the instruction text a TPU trace names each operation by.
The share is bounded by HBM bandwidth alone: the kernels do int32 VPU
arithmetic, for which no peak is published."""
from __future__ import annotations


def share(ctx, kernel) -> "float | None":
    """Percent of the HBM roofline the kernel's device events reach in
    the traced window: sum of bytes needed / peak bandwidth, over the
    summed device time of the same events.  None where the trace holds
    no event of the kernel that is charged bytes."""
    from bench.roofline.hlo import parse_call
    red = ctx.get("trace")
    if red is None:
        return None
    secs, need = 0.0, 0
    for e in red.ops:
        if e.start_ns < red.lo_ns or e.end_ns > red.hi_ns:
            continue                       # only calls wholly inside
        call = parse_call(e.name)
        if call is None or not kernel.matches(call):
            continue
        secs += e.dur_ns * 1e-9
        need += kernel.bytes_needed(call)
    if secs <= 0 or need <= 0:        # no whole call of the kernel
        return None
    return need / ctx["peaks"]["hbm_bytes_per_s"] / secs * 100.0
