#!/usr/bin/env python3
"""Find an open-loop cell's knee on the chip: one set-up, then a window
at each offered rate, in one process.

    python3 bench/sweep.py --workload <open-loop cell> --rates 10,20,30 --seconds 8

For each rate it prints the requests completed per second of the window,
the median and 95th-percentile latency from the due time, and the median
latency of the window's last third over its first third (above 1 the
backlog grows).  The knee is the highest rate whose completions keep up
with arrivals and whose backlog does not grow; the cell's traffic file
then takes about four fifths of it, as a number.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import dataclasses

    import numpy as np

    from bench import harness
    from bench.traffic import gen
    cell = harness.load_cell(args.workload)
    harness.pin_compile_cache()
    dev = harness.require_tpu(cell.entry["chips"])
    from repro.core import engine as engine_mod
    engine_mod.use_compile_cache(ROOT)
    srv = harness.build_server(cell.config)
    warm, sent = harness.set_up(srv, cell.mix, args.seed)
    print(f"{dev.device_kind}: set-up {time.perf_counter() - T0:.1f} s, "
          f"{warm['compiled']} compiled, {sent} warm-up requests", flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dataclasses.replace(cell.mix, rate=rate)
        seed = args.seed + 1 + i
        if mix.pool_size:
            harness.serve_pool(srv, mix, seed)
        win = harness.run_window(srv, mix, gen.Stream(mix, seed),
                                 args.seconds)
        lat = np.array([r.latency for r in win.recs])
        third = max(len(lat) // 3, 1)
        trend = float(np.median(lat[-third:]) / np.median(lat[:third]))
        late = win.lateness_ms()
        print(json.dumps({
            "rate": rate, "requests": len(win.recs),
            "completed_per_s": len(win.recs) / win.elapsed,
            "elapsed_s": win.elapsed, "failed": win.n_failed(),
            "p50_ms": win.latency_ms(50), "p95_ms": win.latency_ms(95),
            "backlog_trend": trend, "lateness_p95_ms": late["p95"]}),
            flush=True)
    srv.async_runtime().close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
