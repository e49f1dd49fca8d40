"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]

Prints ``name,us_per_call,derived`` CSV lines per benchmark plus the
detailed per-figure tables.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from benchmarks import figures
from repro.core import engine

RESULTS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "results")


def _csv(name: str, seconds: float, derived: str = ""):
    print(f"{name},{seconds * 1e6:.1f},{derived}", flush=True)


def bench_fig6(quick: bool):
    rows = figures.fig6_clique_cmax(
        n_max=14 if quick else 17, n_dpconv_max=15 if quick else 19,
        seeds=(0,) if quick else (0, 1))
    for r in rows:
        _csv(f"fig6/dpconv_max/n={r['n']}", r["dpconv_max_s"],
             f"speedup_vs_dpsub={r['speedup'] and round(r['speedup'], 2)}")
        if r["dpsub_max_s"]:
            _csv(f"fig6/dpsub_max/n={r['n']}", r["dpsub_max_s"], "")
    return rows


def bench_fig5(quick: bool):
    rows = figures.fig5_ccap_overhead_sparse(
        ns=(8, 10) if quick else (8, 10, 12, 14, 16),
        seeds=(0,) if quick else (0, 1, 2))
    for r in rows:
        _csv(f"fig5/ccap_sparse/n={r['n']}", r["ccap_s"],
             f"overhead_vs_cout={r['overhead']:.2%}")
    return rows


def bench_fig8(quick: bool):
    rows = figures.fig8_ccap_clique(
        ns=(10, 12) if quick else (10, 12, 14, 16),
        seeds=(0,) if quick else (0, 1))
    for r in rows:
        _csv(f"fig8/ccap_dpconv/n={r['n']}", r["ccap_dpconv_s"],
             f"slowdown_vs_vanilla={r['dpconv_slowdown']:.2%};"
             f"naive={r['naive_slowdown']:.2%}")
    return rows


def bench_fig4(quick: bool):
    rows = figures.fig4_approx(ns=(8,) if quick else (8, 10))
    for r in rows:
        _csv(f"fig4/approx/n={r['n']}/eps={r['eps']}", r["approx_s"],
             f"ratio={r['ratio']:.4f}")
    return rows


def bench_ccap_quality(quick: bool):
    res = figures.ccap_quality(ns=(8,) if quick else (8, 9, 10),
                               n_queries=16 if quick else 90)
    _csv("sec8/ccap_quality", 0.0,
         f"improvable={res['frac_peak_improvable']:.1%};"
         f"peak_ratio={res['mean_peak_ratio']:.2f};"
         f"cmax_loss={res['cmax_cout_loss']:.3f};"
         f"ccap_loss={res['ccap_cout_loss']:.3f}")
    return res


def bench_kernels(quick: bool):
    rows = figures.kernel_bench(ns=(14, 16) if quick else (16, 18, 20))
    for r in rows:
        _csv(f"kernels/zeta_butterfly/n={r['n']}", r["butterfly_s"])
        _csv(f"kernels/zeta_kron_matmul/n={r['n']}", r["kron_matmul_s"],
             f"vs_butterfly={r['butterfly_s'] / r['kron_matmul_s']:.2f}x")
    return rows


def bench_greedy_gap(quick: bool):
    rows = figures.greedy_gap(ns=(8,) if quick else (8, 10, 12),
                              n_queries=6 if quick else 15)
    for r in rows:
        _csv(f"sec10/greedy_gap/n={r['n']}", 0.0,
             f"goo_gmean={r['goo_ratio_gmean']:.3f};"
             f"goo_worst={r['goo_ratio_max']:.2f}x;"
             f"leftdeep_gmean={r['leftdeep_ratio_gmean']:.3f}")
    return rows


def bench_planner(quick: bool):
    rows = figures.planner_bench(n_ops=(6,) if quick else (6, 8, 10),
                                 trials=8 if quick else 20)
    for r in rows:
        _csv(f"planner/einsum/n={r['n_operands']}", 0.0,
             f"greedy_total_ratio_gmean="
             f"{r['greedy_total_ratio_gmean']:.3f};"
             f"worst={r['greedy_total_ratio_max']:.2f}x")
    return rows


BENCHES = {
    "fig6": bench_fig6,
    "fig5": bench_fig5,
    "fig8": bench_fig8,
    "fig4": bench_fig4,
    "ccap_quality": bench_ccap_quality,
    "greedy_gap": bench_greedy_gap,
    "kernels": bench_kernels,
    "planner": bench_planner,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args(argv)
    engine.use_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    os.makedirs(RESULTS, exist_ok=True)
    print("name,us_per_call,derived")
    all_out = {}
    for name, fn in BENCHES.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        all_out[name] = fn(args.quick)
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    out = os.path.join(RESULTS, "bench_summary.json")
    with open(out, "w") as f:
        json.dump(all_out, f, indent=1, default=str)
    print(f"# summary written to {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
