"""The `LoopScheduler` facade and the `Schedule` it hands out.

One object per constructed schedule, three consumers (DESIGN.md §3):

* ``Schedule.simulate()`` / ``Schedule.replay()`` — the discrete-event
  simulator (`core/simulator.py`): `simulate` runs the schedule's policy
  over the per-item cost array; `replay` re-dispatches the constructed
  tiles chunk-for-chunk (`policies.pretiled` over flattened work units),
  which is the simulator-side ground truth for what the Pallas kernels
  will execute.
* ``Schedule.parallel_for()`` / ``Schedule.parallel_for_units()`` — the
  real threaded executor (`core/executor.py`): per-item under the policy,
  or per-work-unit under the exact tile chunking.
* ``Schedule.lower()`` — the `TileSchedule` the Pallas kernels consume
  (`core/tiling.py`; scalar-prefetched `item_id`, packed payload layout).

`LoopScheduler` is the construction front-end: cost provider in, cached
`Schedule` out, plus `build(name, *inputs)` to instantiate a registered
workload's kernel op, and direct pass-throughs to the simulator/executor
for policy studies that need no tiles.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Callable, Optional

import numpy as np

from repro import obs
from repro.core import executor as E
from repro.core import policies as P
from repro.core import simulator as S
from repro.core import tiling as T
from repro.robust import faults as F
from repro.robust import recovery as R

from .adaptive import CostRefiner
from .cache import CacheStats, ScheduleCache
from .costs import CostProvider, RefinedCosts, as_cost_provider
from .defaults import (ICH_EPS, MAX_WIDTH, MIN_WIDTH, ROWS_PER_TILE,
                       SUPERSTEP)


@dataclasses.dataclass(frozen=True, eq=False)
class Schedule:
    """An immutable constructed schedule: per-item costs + policy + tiles.

    Identity semantics (eq=False): schedules compare by object identity,
    matching the cache's `is` contract — generated field equality would
    try to bool() ndarray comparisons and raise.

    `tiles` is the (T, R) iCh tile layout; `sizes`/`costs` are the per-item
    work units / float costs it was built from; `policy`/`p` are the
    runtime-side defaults its simulator/executor methods use. `p` and
    `superstep` are also the kernel-lowering defaults: `shard()` partitions
    the tiles across `p` accelerator workers in supersteps of `superstep`
    tiles (DESIGN.md §2.6).
    """

    sizes: np.ndarray        # (n,) int64 work units per item
    costs: np.ndarray        # (n,) float64 per-item costs
    policy: P.Policy
    p: int
    tiles: T.TileSchedule
    # simulator time model inherited from the constructing LoopScheduler
    sim_params: S.SimParams = dataclasses.field(default_factory=S.SimParams)
    superstep: int = SUPERSTEP
    # memoized worker shard layouts keyed (p, superstep); benign build race
    _shards: dict = dataclasses.field(default_factory=dict, repr=False)
    # ---- measured-cost feedback state (DESIGN.md §2.7) ----
    # refinement generation: 0 = built from a-priori estimates, g+1 = built
    # by the g-th schedule's refine(); part of the schedule-cache key, so a
    # refined schedule can never be served a stale lowering
    generation: int = 0
    # True when sizes describe a payload layout (CSR nnz / degrees) that
    # refine() must keep; False when they are quantized cost estimates
    structural_sizes: bool = True
    # construction parameters refine() rebuilds with (None width = the
    # width rule's, None rule = re-band)
    width_arg: Optional[int] = None
    width_rule: Optional[Callable[..., int]] = None
    band_eps: float = ICH_EPS
    # lazily-created CostRefiner lives here (frozen dataclass; same benign
    # setdefault race as _shards)
    _feedback: dict = dataclasses.field(default_factory=dict, repr=False)
    # the constructing facade — refine() re-enters its cache; None for
    # hand-assembled Schedules (refine then rebuilds directly)
    _scheduler: Optional["LoopScheduler"] = dataclasses.field(
        default=None, repr=False)

    # ------------------------------------------------------------- lowering
    def lower(self) -> T.TileSchedule:
        """The static tile schedule a Pallas kernel consumes."""
        return self.tiles

    def shard(self, *, p: Optional[int] = None,
              superstep: Optional[int] = None) -> T.WorkerShards:
        """The worker-sharded lowering of the tiles (DESIGN.md §2.6): a
        cost-balanced, item-closed LPT partition of the tiles across `p`
        accelerator workers, padded to supersteps of `superstep` tiles —
        the layout the 2D `ich_*_sharded` kernels consume. Memoized per
        (p, superstep) on this Schedule."""
        key = (int(p if p is not None else self.p),
               int(superstep if superstep is not None else self.superstep))
        hit = self._shards.get(key)
        if hit is None:
            # benign build race: the first insert wins and both callers
            # get the winning layout
            hit = self._shards.setdefault(key, T.shard_schedule(
                self.tiles, self.tile_cost(), key[0], superstep=key[1]))
        return hit

    @property
    def n_items(self) -> int:
        return int(self.sizes.size)

    @property
    def n_tiles(self) -> int:
        return self.tiles.n_tiles

    @property
    def rows_per_tile(self) -> int:
        return self.tiles.rows_per_tile

    @property
    def width(self) -> int:
        return self.tiles.width

    @property
    def item_id(self) -> np.ndarray:
        """(T, R) scalar-prefetch schedule (-1 = padding slot)."""
        return self.tiles.item_id

    # ------------------------------------------- work-unit space utilities
    def unit_ranges(self) -> np.ndarray:
        """(T, 2) [begin, end) tile chunks in flattened work-unit space."""
        return self.tiles.slot_ranges()

    def unit_costs(self) -> np.ndarray:
        """Per-work-unit cost array that `unit_ranges` indexes into."""
        return self.tiles.unit_costs(self.costs, self.sizes)

    def unit_to_item(self) -> np.ndarray:
        """Flattened-unit -> item map (item i owns sizes[i] units)."""
        return np.repeat(np.arange(self.n_items, dtype=np.int64), self.sizes)

    def tile_work(self) -> np.ndarray:
        """Work units packed into each tile, shape (T,)."""
        return self.tiles.tile_work()

    def tile_cost(self) -> np.ndarray:
        """Predicted per-tile cost; what `replay` must reproduce."""
        return self.tiles.tile_cost(self.costs, self.sizes)

    def slot_cost(self) -> np.ndarray:
        """Per-slot (T, R) cost decomposition; rows sum to `tile_cost`.
        This is the stream the sharded kernels account their per-worker
        cost output against (`sched/kernels.py`)."""
        return self.tiles.slot_cost(self.costs, self.sizes)

    def imbalance(self, *, p: Optional[int] = None,
                  superstep: Optional[int] = None) -> float:
        """max/mean per-worker cost of the sharded lowering (1.0 =
        perfectly balanced). The load-balance figure the refine loop
        drives down: observe() + refine() re-partitions from measured
        costs, so a schedule built from stale estimates converges toward
        imbalance 1.0 over rounds (tests/test_adaptive_properties.py,
        tests/test_moe_sched.py)."""
        shards = self.shard(p=p, superstep=superstep)
        wc = shards.worker_cost(self.tile_cost())
        mean = float(wc.mean())
        return float(wc.max()) / mean if mean > 0 else 1.0

    # ------------------------------------------------------- (a) simulator
    def simulate(self, *, p: Optional[int] = None,
                 policy: Optional[P.Policy] = None,
                 params: Optional[S.SimParams] = None,
                 **kw) -> S.SimResult:
        """Discrete-event run of `policy` (default: the schedule's) over the
        per-item cost array."""
        return S.simulate(self.costs, p or self.p, policy or self.policy,
                          params if params is not None else self.sim_params,
                          **kw)

    def replay(self, *, p: Optional[int] = None,
               params: Optional[S.SimParams] = None,
               record_chunks: bool = True) -> S.SimResult:
        """Replay the constructed tiles through the simulator: each tile is
        dispatched as one explicit central-queue chunk over the flattened
        work units. `chunk_log` ranges equal `unit_ranges()` row-for-row
        and per-chunk work equals `tile_cost()` (the kernel/simulator
        cross-check in benchmarks/bench_ich_kernels.py)."""
        return S.simulate(self.unit_costs(), p or self.p,
                          P.pretiled(self.unit_ranges()),
                          params if params is not None else self.sim_params,
                          record_chunks=record_chunks)

    def replay_sharded(self, *, p: Optional[int] = None,
                       superstep: Optional[int] = None,
                       params: Optional[S.SimParams] = None,
                       record_chunks: bool = True) -> S.SimResult:
        """Replay the WORKER-SHARDED lowering through the simulator: each
        tile is dispatched on exactly the worker `shard()` assigned it
        (`policies.assigned`, static assignment — no queue, no stealing).
        Per-worker dispatched work must equal `shard().worker_cost(
        tile_cost())` worker-for-worker, and under zero overhead/jitter the
        makespan is the partition's max per-worker cost — the simulator
        cross-check for the sharded kernel execution layer
        (tests/test_sharding.py)."""
        shards = self.shard(p=p, superstep=superstep)
        return S.simulate(self.unit_costs(), shards.p,
                          P.assigned(self.unit_ranges(), shards.worker),
                          params if params is not None else self.sim_params,
                          record_chunks=record_chunks)

    # ------------------------------- measured-cost feedback (DESIGN.md §2.7)
    @property
    def refiner(self) -> CostRefiner:
        """This schedule's cost refiner (created on first use). Carries the
        per-item Welford statistics across observe() rounds and — through
        refine() — across schedule generations."""
        r = self._feedback.get("refiner")
        if r is None:
            r = self._feedback.setdefault(
                "refiner", CostRefiner.for_costs(self.sizes, self.costs))
        return r

    def observe(self, measured, *, level: str = "auto",
                space: str = "auto", normalize: Optional[bool] = None,
                shards: Optional[T.WorkerShards] = None) -> "Schedule":
        """Fold one execution round's measured costs into the refiner.

        Accepts what each execution layer emits:

        * a `SimResult` with `chunk_log` (from `replay`/`replay_sharded`/
          `simulate(record_chunks=True)`) — per-chunk dispatched work, in
          item space (simulate) or flattened work-unit space (replays);
          inferred from the simulated n, with the same `space=` escape
          hatch as ExecStats when the two coincide;
        * an `ExecStats` with `chunk_log` (from `parallel_for(record_chunks
          =True)` / `parallel_for_units`) — per-chunk wall seconds,
          normalized onto the estimate scale by default (wall clocks and
          abstract cost units share no unit). Chunk ranges live in ITEM
          space (`parallel_for`) or flattened WORK-UNIT space
          (`parallel_for_units`); this is inferred from where the ranges
          end, and when n_items == n_units with non-uniform sizes makes
          the two indistinguishable, `space="items"`/`"units"` must say
          which executor produced the stats;
        * a (p, S_B) array — the sharded kernels' per-worker, per-superstep
          cost output (`sched/kernels.py` ops' `.observe()`). Attributed
          through the schedule's DEFAULT shard lowering unless `shards`
          names the lowering the measurement came from — shapes alone
          cannot identify a lowering (distinct supersteps can share a
          (p, S_B) grid), so a non-default lowering must be passed
          explicitly;
        * a 1-D array — per-item (`level="item"`) or per-tile
          (`level="tile"`) measurements; "auto" infers from the length and
          raises when n_items == n_tiles makes it ambiguous.

        Returns self, so a round reads
        ``schedule.observe(measured).refine()``.
        """
        r = self.refiner
        if isinstance(measured, S.SimResult):
            if not measured.chunk_log:
                raise ValueError(
                    "SimResult carries no chunk_log; run the simulator "
                    "with record_chunks=True to observe it")
            ranges = [(b, e) for (b, e, _, _) in measured.chunk_log]
            work = np.array([wk for (_, _, _, wk) in measured.chunk_log])
            n_units = int(self.sizes.sum())
            if space not in ("auto", "items", "units"):
                raise ValueError(f"space must be 'auto', 'items' or "
                                 f"'units', got {space!r}")
            # simulate() runs over per-item costs, replay()/replay_sharded()
            # over flattened work units; same ambiguity rule as ExecStats
            # below when the two coincide with non-uniform sizes
            if space != "auto":
                unit_space = space == "units"
                expect = n_units if unit_space else self.n_items
                if measured.n != expect:
                    raise ValueError(
                        f"SimResult ran over n={measured.n} iterations but "
                        f"the {space} space has {expect} entries")
            elif measured.n == self.n_items == n_units \
                    and not (self.sizes == 1).all():
                raise ValueError(
                    "n_items == work units with non-uniform sizes: pass "
                    "space='items' (a simulate() run) or space='units' "
                    "(a replay)")
            elif measured.n == self.n_items:
                unit_space = False
            elif measured.n == n_units:
                unit_space = True
            else:
                raise ValueError(
                    f"SimResult over n={measured.n} iterations matches "
                    f"neither items ({self.n_items}) nor work units "
                    f"({n_units}) of this schedule")
            if unit_space:
                r.observe_unit_ranges(ranges, work)
            else:
                r.observe_item_ranges(ranges, work)
            return self
        if isinstance(measured, E.ExecStats):
            if not measured.chunk_log:
                raise ValueError(
                    "ExecStats carries no chunk_log; run parallel_for with "
                    "record_chunks=True to observe it")
            ranges = np.array([(b, e) for (b, e, _, _) in measured.chunk_log],
                              np.int64)
            secs = np.array([dt for (_, _, _, dt) in measured.chunk_log])
            n_units = int(self.sizes.sum())
            end = int(ranges[:, 1].max(initial=0))
            if space not in ("auto", "items", "units"):
                raise ValueError(f"space must be 'auto', 'items' or "
                                 f"'units', got {space!r}")
            # parallel_for chunks cover [0, n_items), parallel_for_units
            # [0, n_units); when the two coincide AND sizes are non-
            # uniform, the spaces distribute differently and the caller
            # must say which executor produced the stats
            if space != "auto":
                unit_space = space == "units"
                expect = n_units if unit_space else self.n_items
                if end != expect:
                    raise ValueError(
                        f"ExecStats chunks end at {end} but the "
                        f"{space} space has {expect} entries")
            elif end == self.n_items == n_units \
                    and not (self.sizes == 1).all():
                raise ValueError(
                    "n_items == work units with non-uniform sizes: pass "
                    "space='items' (parallel_for stats) or space='units' "
                    "(parallel_for_units stats)")
            elif end == self.n_items:
                unit_space = False
            elif end == n_units:
                unit_space = True
            else:
                raise ValueError(
                    f"ExecStats chunks end at {end}, matching neither "
                    f"items ({self.n_items}) nor work units ({n_units})")
            if normalize is None:
                normalize = True  # wall seconds -> estimate scale
            if normalize and secs.sum() > 0:
                if unit_space:
                    unit_est = self.unit_costs()
                    covered = sum(float(unit_est[b:e].sum())
                                  for b, e in ranges)
                else:
                    covered = sum(float(r.est[b:e].sum()) for b, e in ranges)
                if covered > 0:
                    secs = secs * (covered / secs.sum())
            if unit_space:
                r.observe_unit_ranges(ranges, secs)
            else:
                r.observe_item_ranges(ranges, secs)
            return self
        arr = np.asarray(measured, np.float64)
        if arr.ndim == 2:
            sh = shards if shards is not None else self.shard()
            if sh.block_perm.shape != arr.shape:
                raise ValueError(
                    f"worker-step observation {arr.shape} does not match "
                    f"the {'given' if shards is not None else 'default'} "
                    f"shard lowering's (p, S_B) grid "
                    f"{sh.block_perm.shape}; pass shards=<the lowering the "
                    "measurement came from> (shapes alone cannot identify "
                    "a lowering)")
            r.observe_worker_steps(self.tiles, sh, arr)
            return self
        if arr.ndim != 1:
            raise ValueError(f"cannot interpret a {arr.ndim}-D observation")
        if level == "auto":
            if arr.size == self.n_items == self.n_tiles:
                raise ValueError(
                    "n_items == n_tiles: pass level='item' or level='tile'")
            level = ("item" if arr.size == self.n_items else
                     "tile" if arr.size == self.n_tiles else None)
            if level is None:
                raise ValueError(
                    f"observation of length {arr.size} matches neither "
                    f"items ({self.n_items}) nor tiles ({self.n_tiles})")
        if level == "item":
            r.observe_items(arr)
        elif level == "tile":
            r.observe_tiles(self.tiles, arr)
        else:
            raise ValueError(f"unknown observation level {level!r}")
        return self

    def refine(self, *, blend: Optional[float] = None) -> "Schedule":
        """Re-construct from the refiner's current refined costs: re-tile
        (unless sizes are structural), re-partition, and re-shard, under a
        fresh cache GENERATION so no stale lowering (tiles, shard layouts,
        packed payloads) is ever reused. The refiner — with all its
        accumulated per-item statistics — transfers to the new schedule, so
        rounds keep compounding: ``s = s.observe(m).refine()``.
        """
        r = self.refiner
        if blend is not None:
            r.blend = float(blend)
        refined = r.refresh_estimates()
        provider = RefinedCosts(self.sizes, refined,
                                generation=self.generation + 1,
                                structural=self.structural_sizes)
        if self._scheduler is not None:
            new = self._scheduler.schedule(
                provider, policy=self.policy, p=self.p,
                rows_per_tile=self.rows_per_tile, width=self.width_arg,
                eps=self.band_eps, superstep=self.superstep,
                _generation=self.generation + 1,
                _width_rule=self.width_rule)
        else:  # hand-assembled schedule: rebuild directly, no cache
            width = _tile_width(self.width_arg, self.width_rule,
                                provider.sizes(), self.band_eps, MIN_WIDTH,
                                MAX_WIDTH, self.rows_per_tile)
            tiles = T.build_schedule(provider.sizes(),
                                     rows_per_tile=self.rows_per_tile,
                                     width=width, eps=self.band_eps)
            new = dataclasses.replace(
                self, sizes=provider.sizes(), costs=provider.costs(),
                tiles=tiles, generation=self.generation + 1,
                _shards={}, _feedback={})
        new._feedback["refiner"] = r.successor(new.sizes)
        return new

    def replay_refined(self, true_costs, *, sharded: bool = False,
                       p: Optional[int] = None,
                       superstep: Optional[int] = None,
                       params: Optional[S.SimParams] = None,
                       record_chunks: bool = False) -> S.SimResult:
        """Deterministically answer "what does THIS schedule cost on that
        workload": replay the constructed chunks with per-item costs
        `true_costs` (measured or ground truth) instead of the estimates
        the schedule was built from — `simulator.replay_refined` over the
        tile ranges, through the central pretiled queue, or as the static
        sharded assignment when `sharded=True`. The observe/refine loop
        must drive this makespan down (tests/test_adaptive_properties.py)."""
        true_costs = np.asarray(true_costs, np.float64)
        if true_costs.shape != (self.n_items,):
            raise ValueError(f"true costs must have shape "
                             f"({self.n_items},), got {true_costs.shape}")
        unit = self.tiles.unit_costs(true_costs, self.sizes)
        prm = params if params is not None else self.sim_params
        if sharded:
            shards = self.shard(p=p, superstep=superstep)
            return S.replay_refined(unit, self.unit_ranges(), shards.p,
                                    workers=shards.worker, params=prm,
                                    record_chunks=record_chunks)
        return S.replay_refined(unit, self.unit_ranges(), p or self.p,
                                params=prm, record_chunks=record_chunks)

    # --------------------------- fault replay & chaos runs (DESIGN.md §2.9)
    def replay_faulty(self, plan: F.FaultPlan, *,
                      p: Optional[int] = None,
                      policy: Optional[P.Policy] = None,
                      params: Optional[S.SimParams] = None,
                      record_chunks: bool = False,
                      record_assignment: bool = False) -> F.FaultReport:
        """Simulate this schedule's policy over its cost array twice —
        fault-free and under the seeded `FaultPlan` — and report both runs
        plus the makespan inflation the chaos scenario costs it. Dead
        workers' queued work is reclaimed by survivors through the steal
        machinery, so the faulty run still dispatches every item exactly
        once (or raises `repro.robust.FaultError` when no live worker
        remains). Deterministic: the same plan replays bit-identically."""
        return F.simulate_faulty(
            self.costs, p or self.p, policy or self.policy, plan,
            params=params if params is not None else self.sim_params,
            record_chunks=record_chunks,
            record_assignment=record_assignment)

    def reshard_survivors(self, *, dead,
                          checkpoint: Optional[R.CheckpointLog] = None,
                          p: Optional[int] = None,
                          superstep: Optional[int] = None) -> R.RecoveryPlan:
        """Recovery re-lowering for an interrupted sharded run (DESIGN.md
        §2.11): given the workers lost and a `CheckpointLog` of blocks
        completed at superstep barriers, re-partition every incomplete
        item-closed chain onto the p-k survivors with the same
        `partition_tiles` LPT the original lowering used. The returned
        `RecoveryPlan` carries the survivor layout (`.shards`), the
        completed-prefix layout (`.done_shards`), and `.combine()` — both
        layouts drive the standard sharded kernels over the original flat
        payload, and the combined output is bit-identical to the
        fault-free run. Without a checkpoint the plan is a worst-case
        full re-execution on the survivors."""
        shards = self.shard(p=p, superstep=superstep)
        return R.plan_recovery(self.tiles, self.tile_cost(), shards,
                               dead=dead, checkpoint=checkpoint)

    # -------------------------------------------------------- (b) executor
    def parallel_for(self, body: Callable[[int], None], *,
                     p: Optional[int] = None,
                     policy: Optional[P.Policy] = None,
                     seed: int = 0, record_chunks: bool = False,
                     deterministic: bool = False,
                     faults: Optional[F.FaultPlan] = None,
                     retries: int = 0, retry_backoff_s: float = 0.0,
                     watchdog_s: Optional[float] = None,
                     sleep_fn: Optional[Callable[[float], None]] = None
                     ) -> E.ExecStats:
        """Run `body(i)` for every item on real threads under `policy`
        (default: the schedule's). `record_chunks=True` fills the per-chunk
        wall-time log `observe()` consumes (DESIGN.md §2.7). `faults`,
        `retries`/`retry_backoff_s`, `watchdog_s`, and `sleep_fn` pass
        through to the supervised executor (DESIGN.md §2.9): injected
        chaos, per-item retry budget, heartbeat-based dead-worker
        detection, and the virtual-sleep hook for zero-wall-clock
        retry/stall suites."""
        return E.parallel_for(self.n_items, body, p or self.p,
                              policy or self.policy, seed=seed,
                              record_chunks=record_chunks,
                              deterministic=deterministic, faults=faults,
                              retries=retries,
                              retry_backoff_s=retry_backoff_s,
                              watchdog_s=watchdog_s, sleep_fn=sleep_fn)

    def parallel_for_units(self, body: Callable[[int], None], *,
                           p: Optional[int] = None,
                           seed: int = 0, record_chunks: bool = False,
                           deterministic: bool = False,
                           faults: Optional[F.FaultPlan] = None,
                           retries: int = 0, retry_backoff_s: float = 0.0,
                           sleep_fn: Optional[Callable[[float], None]] = None
                           ) -> E.ExecStats:
        """Run `body(u)` for every flattened work unit on real threads,
        dispatched in exactly the constructed tile chunks (one central-queue
        chunk per tile — the threaded twin of `replay`). With
        `record_chunks=True` the returned stats carry one wall-time record
        per tile, ready for `observe()`. `faults`/`retries` pass through to
        the supervised executor (central path: no watchdog — there are no
        per-worker deques to reclaim; survivors drain the shared queue)."""
        n_units = int(self.sizes.sum())
        return E.parallel_for(n_units, body, p or self.p,
                              P.pretiled(self.unit_ranges()), seed=seed,
                              record_chunks=record_chunks,
                              deterministic=deterministic, faults=faults,
                              retries=retries,
                              retry_backoff_s=retry_backoff_s,
                              sleep_fn=sleep_fn)


def _tile_width(width, rule, sizes, eps, min_w, max_w,
                rows_per_tile) -> Optional[int]:
    """An explicit width, else the rule's, else None (the band)."""
    if width is not None or rule is None:
        return width
    return rule(sizes, eps, min_w, max_w, rows_per_tile)


class LoopScheduler:
    """Facade over policies, simulator, executor, and Pallas lowering.

    Construction parameters set here are the instance defaults; every
    method takes per-call overrides. Schedules are cached (LRU) on
    ``(cost fingerprint, full policy, p, construction params)`` — the FULL
    frozen `Policy`, not its label, which is lossy — see `sched/cache.py`.

    Memory: each cached `Schedule` pins O(n) per-item arrays plus its
    tiles (~tens of MB at a million items), so `cache_size` bounds
    retained memory at roughly `cache_size * max_schedule_bytes`. Size it
    to the working set of DISTINCT cost distributions you re-present
    (matrices, graphs, batch shapes); for one-shot schedules (a fresh
    cost array every request, never re-seen) pass `cache_size=0` to
    disable caching entirely.
    """

    def __init__(self, *, p: int = 8, policy: Optional[P.Policy] = None,
                 rows_per_tile: int = ROWS_PER_TILE,
                 min_w: int = MIN_WIDTH, max_w: int = MAX_WIDTH,
                 superstep: int = SUPERSTEP,
                 cache_size: int = 32,
                 sim_params: Optional[S.SimParams] = None):
        self.p = int(p)
        self.policy = policy if policy is not None else P.ich(ICH_EPS)
        self.rows_per_tile = int(rows_per_tile)
        self.min_w = int(min_w)
        self.max_w = int(max_w)
        self.superstep = int(superstep)
        self.sim_params = sim_params if sim_params is not None else S.SimParams()
        self.cache = ScheduleCache(cache_size) if cache_size > 0 else None

    # ------------------------------------------------- schedule construction
    def schedule(self, costs, *, policy: Optional[P.Policy] = None,
                 p: Optional[int] = None,
                 rows_per_tile: Optional[int] = None,
                 width: Optional[int] = None,
                 eps: Optional[float] = None,
                 superstep: Optional[int] = None,
                 _generation: int = 0,
                 _width_rule: Optional[Callable[..., int]] = None
                 ) -> Schedule:
        """Construct (or fetch from cache) the schedule for `costs`.

        `costs` is a `CostProvider` or a bare per-item array
        (`as_cost_provider`). The tile width comes from the paper's band at
        `eps` (default: the policy's epsilon for adaptive policies, else
        the unified `ICH_EPS`) unless `width` pins it explicitly.

        The cache key includes the worker-partition parameters `p` and
        `superstep`: the returned `Schedule` lowers to a p-worker shard
        layout (and carries policy/p as its simulator/executor defaults),
        so entries differing only in those must be distinct objects — a
        p=2 schedule's memoized shards and packed kernels must never be
        served to a p=4 caller (tests/test_sched_api.py proves distinct
        p values don't collide). It also includes the refinement
        GENERATION (`_generation`, set by `Schedule.refine`): a refined
        schedule's lowerings are always freshly keyed, never a stale
        entry's (sched/cache.py). `_width_rule` is a registered workload's
        tile-width rule (`build`; `refine` keeps it).
        """
        with obs.span("sched.schedule"):
            return self._schedule(as_cost_provider(costs), policy, p,
                                  rows_per_tile, width, eps, superstep,
                                  _generation, _width_rule)

    def _schedule(self, provider: CostProvider, policy, p, rows_per_tile,
                  width, eps, superstep, _generation,
                  width_rule) -> Schedule:
        pol = policy if policy is not None else self.policy
        pp = int(p if p is not None else self.p)
        rpt = int(rows_per_tile if rows_per_tile is not None
                  else self.rows_per_tile)
        band_eps = float(eps if eps is not None
                         else (pol.eps if pol.adaptive else ICH_EPS))
        sstep = int(superstep if superstep is not None else self.superstep)
        gen = int(_generation)
        # absent a declaration, sizes count as structural: keeping them
        # across refinement is always payload-safe (see sched/costs.py)
        structural = bool(getattr(provider, "sizes_are_structural", True))
        # an explicit width wins over the rule; the key holds the rule, not
        # the width it resolves to, so a hit never scans the sizes
        rule = width_rule if width is None else None
        # the policy keys as the full (frozen, hashable) dataclass, not just
        # label(): labels are lossy — taskloop's drops num_tasks, pretiled's
        # drops the actual ranges — and would alias distinct policies onto
        # one cache entry
        key = (provider.fingerprint(), pol, pp, rpt, width, rule,
               band_eps, self.min_w, self.max_w, sstep, gen)

        def build() -> Schedule:
            with obs.span("sched.construct"):
                sizes = provider.sizes()
                w = _tile_width(width, rule, sizes, band_eps, self.min_w,
                                self.max_w, rpt)
                tiles = T.build_schedule(
                    sizes, rows_per_tile=rpt, width=w,
                    eps=band_eps, min_w=self.min_w, max_w=self.max_w)
                return Schedule(
                    sizes=sizes, costs=provider.costs(), policy=pol, p=pp,
                    tiles=tiles, sim_params=self.sim_params,
                    superstep=sstep, generation=gen,
                    structural_sizes=structural, width_arg=width,
                    width_rule=rule, band_eps=band_eps, _scheduler=self)

        if self.cache is None:
            return build()
        return self.cache.get_or_build(key, build)

    # ----------------------------------------------------- workload registry
    def build(self, workload: str, *inputs,
              policy: Optional[P.Policy] = None, p: Optional[int] = None,
              rows_per_tile: Optional[int] = None,
              width: Optional[int] = None, eps: Optional[float] = None,
              superstep: Optional[int] = None):
        """Instantiate a registered workload's kernel op from raw inputs.

        Looks up `workload` in the registry (`sched.register` /
        `sched.get`), derives its cost provider from `inputs`, routes the
        schedule through the cache, and hands both to the entry's builder.
        The tile width follows the entry's width rule (the band where it
        declares none) unless `width` pins it.
        """
        from . import registry
        with obs.span("sched.build", workload=workload):
            entry = registry.get(workload)
            # the provider hashes its inputs as it is made: part of the
            # schedule's cost, so inside its span
            with obs.span("sched.schedule"):
                s = self._schedule(entry.costs(*inputs), policy, p,
                                   rows_per_tile, width, eps, superstep, 0,
                                   entry.width)
            return entry.build(s, *inputs)

    # --------------------------------------------- direct backend shortcuts
    def simulate(self, costs, *, policy: Optional[P.Policy] = None,
                 p: Optional[int] = None,
                 params: Optional[S.SimParams] = None,
                 **kw) -> S.SimResult:
        """Simulator pass-through for policy studies that need no tiles
        (the paper-figure benchmarks); `costs` is per-ITEM here."""
        return S.simulate(np.asarray(costs, np.float64),
                          p or self.p, policy or self.policy,
                          params if params is not None else self.sim_params,
                          **kw)

    def parallel_for(self, n: int, body: Callable[[int], None], *,
                     policy: Optional[P.Policy] = None,
                     p: Optional[int] = None, seed: int = 0) -> E.ExecStats:
        """Threaded-executor pass-through: `body(i)` for i in [0, n)."""
        return E.parallel_for(n, body, p or self.p, policy or self.policy,
                              seed=seed)

    @property
    def cache_stats(self) -> CacheStats:
        return self.cache.stats if self.cache is not None else CacheStats()


_DEFAULT: Optional[LoopScheduler] = None
_DEFAULT_LOCK = threading.Lock()


def default_scheduler() -> LoopScheduler:
    """Process-wide facade instance (one shared schedule cache) — what
    `ShardDispatcher` uses when it is given no scheduler."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = LoopScheduler()
        return _DEFAULT
