"""`repro.sched` — the single public entry point for loop scheduling.

The paper's point is that ONE adaptive scheduler serves every irregular
workload without per-application tuning; this package is that claim as an
API (DESIGN.md §3). One facade spans all four backends:

    from repro import sched

    scheduler = sched.LoopScheduler(p=28)
    s = scheduler.schedule(costs)          # -> Schedule (cached, LRU)
    s.simulate()                           # (a) discrete-event simulator
    s.parallel_for(body)                   # (b) real threaded executor
    s.lower()                              # (c) TileSchedule for Pallas
    spmv = scheduler.build("spmv", indptr, indices, data)   # (d) kernels
    y = spmv(x)

New applications plug in through the registry instead of a new ops class:

    sched.register("myapp", costs=..., build=...)
    op = scheduler.build("myapp", *inputs)

Exports are lazy (PEP 562) for two reasons: `repro.core` imports
`repro.sched.defaults` for the unified iCh epsilon, so this init must not
eagerly import core back; and the numpy-only surface (facade, simulator,
executor) must stay importable without paying for jax.
"""
from .defaults import (ICH_EPS, MAX_WIDTH, MIN_WIDTH, ROWS_PER_TILE,
                       SUPERSTEP)

_LAZY = {
    # facade + schedule object (sched/api.py)
    "LoopScheduler": "api",
    "Schedule": "api",
    "default_scheduler": "api",
    # measured-cost feedback (sched/adaptive.py)
    "CostRefiner": "adaptive",
    # cost providers (sched/costs.py)
    "CostProvider": "costs",
    "DegreeCosts": "costs",
    "ExpertLoadCosts": "costs",
    "ExplicitCosts": "costs",
    "NnzCosts": "costs",
    "RefinedCosts": "costs",
    "RemainingTokensCosts": "costs",
    "as_cost_provider": "costs",
    # MoE dispatch planning (sched/moe.py, DESIGN.md §2.8)
    "DispatchPlan": "moe",
    "cap_scale_from_costs": "moe",
    "expert_capacity": "moe",
    "plan_dispatch": "moe",
    "read_routing": "moe",
    "refine_cap_scale": "moe",
    "route": "moe",
    # schedule cache (sched/cache.py)
    "CacheStats": "cache",
    "ScheduleCache": "cache",
    # workload/kernel registry (sched/registry.py)
    "WorkloadSpec": "registry",
    "get": "registry",
    "register": "registry",
    "registered": "registry",
    # shard dispatch (sched/data_sched.py)
    "ShardDispatcher": "data_sched",
    # policy family + simulator knobs, re-exported so facade users need only
    # this package (the objects live in repro.core and stay usable from there)
    "Policy": "_core",
    "assigned": "_core",
    "binlpt": "_core",
    "dynamic": "_core",
    "guided": "_core",
    "ich": "_core",
    "paper_policy_grid": "_core",
    "pretiled": "_core",
    "static": "_core",
    "stealing": "_core",
    "taskloop": "_core",
    "SimParams": "_core",
    "SimResult": "_core",
    "TileSchedule": "_core",
    "WorkerShards": "_core",
}

__all__ = ["ICH_EPS", "MAX_WIDTH", "MIN_WIDTH", "ROWS_PER_TILE", "SUPERSTEP",
           *sorted(_LAZY)]


def __getattr__(name):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    if mod == "_core":
        from repro.core import policies, simulator, tiling
        for m in (policies, simulator, tiling):
            if hasattr(m, name):
                return getattr(m, name)
        raise AttributeError(name)  # pragma: no cover - _LAZY names exist
    import importlib
    return getattr(importlib.import_module(f".{mod}", __name__), name)


def __dir__():
    return sorted(__all__)
