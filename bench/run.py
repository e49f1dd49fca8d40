#!/usr/bin/env python3
"""Measure the plan server on the chip, one cell of BENCHMARK.json:

    python3 bench/run.py --workload clique.n15 --seed 7 --seconds 10 --trace 0

Builds the ``PlanServer`` its configuration describes, prewarms exactly
the buckets the cell's traffic hits, sends warm-up traffic, then drives
``PlanServer.plan_async`` for ``--seconds`` (open loop at a fixed rate,
or closed loop with a fixed number of clients) and holds every answer to
the plain reference of the traffic's cost, ``bench/costs/<cost>.py``.
``--trace 1`` is a separate run that also profiles a few seconds of the
window and reports the per-layer metrics instead of the end-to-end ones.

The last line of standard output is the result object; counts, the
generator's lateness and the compared numbers with their limits come
before it (the compared numbers also last on standard error).  Exits
non-zero and prints no result when JAX sees no TPU or fewer chips than
the cell asks for.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.load_cell(args.workload)
    harness.pin_compile_cache()
    dev = harness.require_tpu(cell.entry["chips"])
    from repro.kernels.ops import INTERPRET_ENV, interpret_requested
    if interpret_requested():
        print(f"bench: {INTERPRET_ENV}=1 would run the kernels in "
              "interpret mode; unset it", file=sys.stderr)
        return 2
    try:
        peaks = harness.peaks_for(dev.device_kind)
    except KeyError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    from repro.core import engine as engine_mod
    cache = engine_mod.use_compile_cache(ROOT)   # before the first compile
    print(f"device: {dev.platform} {dev.device_kind} x"
          f"{harness.device_count()}; compile cache {cache} "
          f"({harness.dir_bytes(cache)} bytes)", flush=True)
    seed = args.seed % (1 << 63)
    result = harness.measure(cell, seed, args.seconds, bool(args.trace),
                             T0, peaks,
                             log=lambda s: print(s, flush=True))
    print(f"compile cache after: {harness.dir_bytes(cache)} bytes")
    print(f"memory_peak_bytes: {result['device']['memory_peak_bytes']}")
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
