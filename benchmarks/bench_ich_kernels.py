"""Schedule-construction benchmark for the iCh kernel family (BFS, K-Means,
SpMV) + the schedule/simulator cross-check, on the unified `repro.sched` API.

For each paper application we build the schedule through the
`LoopScheduler` facade from its per-item cost description and report slot
efficiency (useful work units / padded R*W slots) and the predicted
per-tile load imbalance. We then CROSS-CHECK the construction against the
discrete-event simulator via `Schedule.replay()`: the schedule's tiles,
re-dispatched as explicit central-queue chunks over the flattened
work-unit cost array, must be handed out chunk-for-chunk with exactly the
work `Schedule.tile_cost()` predicts. This ties the kernel layer to the
simulator layer — the same `Schedule` object drives both. Run standalone:

  PYTHONPATH=src python -m benchmarks.bench_ich_kernels
"""
import numpy as np

from repro import compile_cache
from repro.core import workloads as WL
from repro.sched import ExplicitCosts, LoopScheduler
from repro.sched.api import Schedule

SCHED = LoopScheduler(p=8)


def crosscheck(s: Schedule) -> float:
    """Replay the schedule in the simulator; return max |tile - chunk| work
    mismatch (must be ~0)."""
    res = s.replay(record_chunks=True)
    sim_work = np.array([w for (_, _, _, w) in res.chunk_log])
    assert len(sim_work) == s.n_tiles
    return float(np.abs(sim_work - s.tile_cost()).max())


def report(app: str, s: Schedule):
    work = s.tile_work()
    slots = s.n_tiles * s.rows_per_tile * s.width
    eff = work.sum() / slots
    imb = work.max() / max(work.mean(), 1e-12)
    err = crosscheck(s)
    ok = "OK" if err < 1e-6 else f"FAIL({err:.2e})"
    print(f"{app},{s.width},{s.n_tiles},{eff:.3f},{imb:.3f},{ok}")
    return err


def main(n: int = 20_000) -> float:
    print("app,W,tiles,slot_eff,tile_imbalance,sim_crosscheck")
    worst = 0.0

    # BFS: per-vertex cost = degree (uniform + scale-free graphs, §5.1)
    rng = np.random.default_rng(0)
    for kind, deg in (("bfs/uniform", rng.integers(1, 21, n)),
                      ("bfs/scale_free",
                       np.minimum(rng.zipf(2.3, n), n // 10))):
        s = SCHED.schedule(deg.astype(np.int64))
        worst = max(worst, report(kind, s))

    # K-Means: heavy-tailed per-point predicted cost, reshuffled per round
    # (float costs quantize to >= 1 work unit on the provider's path)
    rounds, _ = WL.kmeans_rounds(n=n, rounds=3)
    for r, costs in enumerate(rounds):
        s = SCHED.schedule(ExplicitCosts(np.asarray(costs, np.float64)))
        worst = max(worst, report(f"kmeans/round{r}", s))

    # SpMV: Table-1 stat-matched row-nnz arrays (subset for speed)
    for spec in WL.TABLE1[:5]:
        sizes = WL.matrix_row_nnz(spec, n).astype(np.int64)
        s = SCHED.schedule(sizes)
        worst = max(worst, report(f"spmv/{spec.name}", s))

    print(f"MAX_CROSSCHECK_ERR,{worst:.3e}")
    stats = SCHED.cache_stats
    print(f"SCHEDULE_CACHE,misses,{stats.misses},hits,{stats.hits}")
    return worst


if __name__ == "__main__":
    compile_cache.configure()
    main()
