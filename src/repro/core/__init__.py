"""Core of the reproduction: the iCh adaptive self-scheduling loop scheduler
(Booth & Lane, 2020) plus the baseline scheduler family, a discrete-event
simulator for scheduler-quality evaluation, a real threaded executor, and the
paper's workload generators.
"""
from .policies import (
    Policy,
    assigned,
    binlpt,
    dynamic,
    guided,
    ich,
    ich_chunk,
    ich_initial_d,
    paper_policy_grid,
    pretiled,
    static,
    stealing,
    taskloop,
)
from .tiling import (
    TileSchedule,
    WorkerShards,
    build_schedule,
    coverage_counts,
    ich_tile_width,
    make_shards,
    pack_csr,
    partition_tiles,
    shard_schedule,
    split_items,
)
from .simulator import (
    SimParams,
    SimResult,
    best_time_over_grid,
    eps_sensitivity,
    replay_refined,
    simulate,
    speedup,
    worst_stealing,
)
from .welford import (Welford, WelfordVec, adapt_d, classify, ich_band,
                      steal_merge, LOW, NORMAL, HIGH)
from .executor import parallel_for, ExecStats

__all__ = [
    "Policy", "assigned", "binlpt", "dynamic", "guided", "ich", "ich_chunk",
    "ich_initial_d", "paper_policy_grid", "pretiled", "static", "stealing",
    "taskloop",
    "TileSchedule", "WorkerShards", "build_schedule", "coverage_counts",
    "ich_tile_width", "make_shards", "pack_csr", "partition_tiles",
    "shard_schedule", "split_items",
    "SimParams", "SimResult", "best_time_over_grid", "eps_sensitivity",
    "replay_refined", "simulate", "speedup", "worst_stealing",
    "Welford", "WelfordVec", "adapt_d", "classify", "ich_band",
    "steal_merge",
    "LOW", "NORMAL", "HIGH", "parallel_for", "ExecStats",
]
