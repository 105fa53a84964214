"""Benchmark driver: one function per paper table/figure.

Prints ``name,metric,value`` CSV lines, writes per-figure CSVs under
results/paper/, and validates the paper's headline claims:
  * iCh is top-3 at 28 threads on every application (paper §6.1);
  * iCh's average gap to the best method is small (paper: ~5.4%);
  * iCh beats plain stealing on BFS and K-Means (paper: +9.6%..54%).

Usage: PYTHONPATH=src python -m benchmarks.run [--fast] [--only NAME]
       PYTHONPATH=src python -m benchmarks.run --bench serve [--fast]

`--bench paper` (default) reproduces the paper figures; `--bench serve`
runs the serving tail-latency sweep (bench_serve: offered load x dispatch
policy, simulated clock) and refreshes BENCH_serve.json.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro import compile_cache

from . import bench_paper as B
from . import common as C


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller n (quick smoke; claims still checked)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--bench", default="paper",
                    choices=["paper", "serve"],
                    help="paper = figure reproduction; serve = serving "
                         "tail-latency sweep (BENCH_serve.json)")
    args = ap.parse_args()
    if args.bench == "serve":
        from . import bench_serve as BV
        BV.main(seeds=(BV.SEEDS[0],) if args.fast else BV.SEEDS)
        return
    n = 20_000 if args.fast else 50_000
    n_spmv = 40_000 if args.fast else 100_000

    t_start = time.time()
    tables = {}
    all_rows = []

    benches = {
        "synth": lambda: B.bench_synth(n),
        "bfs": lambda: B.bench_bfs(n),
        "kmeans": lambda: B.bench_kmeans(n),
        "lavamd": lambda: B.bench_lavamd(),
        "spmv": lambda: B.bench_spmv(n_spmv),
        "sensitivity": lambda: B.bench_sensitivity(),
        "moe_balance": lambda: B.bench_moe_balance(),
    }
    for name, fn in benches.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        rows, summary = fn()
        dt = time.time() - t0
        all_rows += rows
        C.write_csv(f"results/paper/{name}.csv", "app,method,p,value", rows)
        print(f"# {name}: {dt:.1f}s")
        if name in ("synth", "bfs", "kmeans", "lavamd"):
            tables.update(summary)
        elif name == "spmv":
            tables["spmv_geo"] = summary["spmv_geo"]
        for r in rows:
            print(r)

    # ---- paper-claim validation (the reproduction scorecard) ----
    speedup_apps = {k: v for k, v in tables.items() if k != "spmv_geo"}
    print("\n# === paper-claim validation (28 threads) ===")
    ranks, gaps = {}, {}
    for app, table in speedup_apps.items():
        r = C.rank_of_ich(table)
        g = C.gap_to_best(table)
        ranks[app], gaps[app] = r, g
        best_m = max(table, key=lambda m: table[m][28])
        print(f"claim,{app},ich_rank,{r},gap_to_best,{100*g:.1f}%,best={best_m}")
    if "spmv_geo" in tables:
        geo = tables["spmv_geo"]
        order = sorted(geo, key=geo.get, reverse=True)
        r = order.index("ich") + 1
        g = (geo[order[0]] - geo["ich"]) / geo[order[0]]
        ranks["spmv"], gaps["spmv"] = r, g
        print(f"claim,spmv(geomean),ich_rank,{r},gap_to_best,{100*g:.1f}%,best={order[0]}")
    if ranks:
        print(f"claim,ALL,ich_always_top3,{max(ranks.values()) <= 3}")
        print(f"claim,ALL,avg_gap_to_best,{100*float(np.mean(list(gaps.values()))):.1f}%"
              f" (paper: ~5.4%)")
        for app in ("bfs/Uniform", "bfs/Scale-Free", "kmeans"):
            if app in speedup_apps:
                t = speedup_apps[app]
                print(f"claim,{app},ich_vs_stealing,"
                      f"{100*(t['ich'][28]/t['stealing'][28]-1):+.1f}% (paper: +9.6%/+54%/+12.3%)")
    print(f"# total {time.time()-t_start:.1f}s")


if __name__ == "__main__":
    compile_cache.configure()
    main()
