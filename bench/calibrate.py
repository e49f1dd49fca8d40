#!/usr/bin/env python3
"""Read the numbers ``correct`` compares, for sound runs of the program
and for the control, over many seeds in one process on the chip:

    python3 bench/calibrate.py --workload clique.n15 --seeds 1,2,3 --seconds 3

For each seed: a short window at the cell's own load, the program's
answers held to the reference (the lower readings), and the same sample
of requests answered by the control, the reference computed in float32,
the precision below the configuration's float64 (the upper readings).
The limits in ``harness.LIMITS`` are set between the two; PERF.md keeps
the readings.  The benchmark's own runs never run the control.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    from bench import harness
    from bench.traffic import gen
    cell = harness.load_cell(args.workload)
    harness.pin_compile_cache()
    dev = harness.require_tpu(cell.entry["chips"])
    from repro.core import engine as engine_mod
    engine_mod.use_compile_cache(ROOT)
    mix = cell.mix
    seeds = [int(s) for s in args.seeds.split(",")]
    srv = harness.build_server(cell.config)
    harness.set_up(srv, mix, seeds[0])
    print(f"{dev.device_kind}: set-up {time.perf_counter() - T0:.1f} s",
          flush=True)
    for seed in seeds:
        if mix.pool_size:
            harness.serve_pool(srv, mix, seed)
        win = harness.run_window(srv, mix, gen.Stream(mix, seed),
                                 args.seconds)
        sound = harness.window_checks(win, mix, seed)
        sample = harness.sample(win.recs, mix.check_sample, seed)
        ctrl = harness.compare(sample, mix.cost, len(sample), seed,
                               answer=harness.control_answer(mix.cost))
        print(json.dumps({
            "seed": seed, "requests": len(win.recs),
            "failed": win.n_failed(),
            "program": {k: c["value"] for k, c in sound.items()},
            "program_correct": harness.correct(sound),
            "control": {k: c["value"] for k, c in ctrl.items()},
            "control_correct": harness.correct(ctrl)}), flush=True)
    srv.async_runtime().close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
