"""The four-chip cell's window with its mesh path intact or broken, in a
process of its own (JAX fixes the device count when it starts).  On four
virtual CPU devices, at n = 6-7 with the mesh engaged from n = 6
(``shard_min_n``; the cell engages it at n = 14):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 bench/tests/mesh_rehearsal.py [--fault exchange]

and on four chips at the cell's own size (``--cell-size``), one window
per seed after one set-up:

    python3 bench/tests/mesh_rehearsal.py --cell-size --fault exchange \\
        --seeds 1,2,3 --seconds 8

The cell's own configuration and traffic file go through the harness's
set-up, window and checks.  ``--fault exchange`` leaves the exchange
between the chips out: each layer's merge (``lattice._merge_blocks``)
returns the calling device's own block, unmerged.  Prints one JSON line
per seed: ``correct``, the compared numbers, and the window's dispatches
as [shards, devices] pairs."""
import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

CELL = "acyclic_out.mesh4"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--fault", choices=("none", "exchange"),
                    default="none")
    ap.add_argument("--cell-size", action="store_true")
    ap.add_argument("--seeds", default=str(2 ** 33 + 91))
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    from bench import harness
    from bench.traffic import gen
    from repro.core import engine, lattice
    if args.fault == "exchange":
        lattice._merge_blocks = lambda part, axis: part
    c = harness.load_cell(CELL)
    config, mix = c.config, c.mix
    if not args.cell_size:
        config = {**config, "batch_policy": {**config["batch_policy"],
                                             "shard_min_n": 6}}
        mix = dataclasses.replace(mix, n_values=(6, 7), check_sample=8)
    seeds = [int(s) for s in args.seeds.split(",")]
    srv = harness.build_server(config)
    harness.set_up(srv, mix, seeds[0])
    try:
        for seed in seeds:
            mark = engine.dispatch_mark()
            win = harness.run_window(srv, mix, gen.Stream(mix, seed),
                                     args.seconds)
            checks = harness.window_checks(win, mix, seed)
            print(json.dumps({
                "seed": seed, "correct": harness.correct(checks),
                "attempted": len(win.recs), "failed": win.n_failed(),
                "checks": {k: v["value"] for k, v in checks.items()},
                "dispatches": [[r.shards, harness.mesh_size(r)]
                               for r in engine.dispatches_since(mark)]}),
                flush=True)
    finally:
        srv.async_runtime().close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
