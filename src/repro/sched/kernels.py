"""Registry-backed kernel ops for the three paper applications.

Each op binds a constructed `Schedule` to a workload's payloads once
(pack), then applies the Pallas kernel many times. SpMV and BFS gather
their payload (vals * x[cols], mask * frontier[cols]) over every packed
slot, padding included, so they register `core.tiling.gather_width`: the
cheapest width under the band. MoE dispatch runs each slot row as a dense
product and registers `core.tiling.token_block_width`: whole 128-token
blocks. K-Means and serve-prefill keep the band's width. These are the
implementations behind `scheduler.build("spmv" | "bfs" | "kmeans", ...)`.

Ops execute on the worker-sharded 2D kernels (DESIGN.md §2.6): the
schedule's tiles are cost-partitioned across `schedule.p` accelerator
workers at superstep-block granularity (`Schedule.shard()`), payloads
stay in the FLAT (T_pad, R, W) pack (padded to whole supersteps), and
each grid step fetches one worker's next block of `schedule.superstep`
tiles via the prefetched block-index stream — lowering to the shard
layout moves no payload bytes. Each kernel has this one grid, checked
against its `ref.py` oracle; its bits do not depend on p or the superstep
(`core/segmented.py`, tests/test_sharding.py).

Measured-cost feedback (DESIGN.md §2.7): every op passes the schedule's
per-slot cost stream into the sharded kernel, which emits a per-worker,
per-superstep cost output alongside its payload result. The op stashes the
latest stream as `last_costs`; calling `op.observe()` folds it back into
the schedule's `CostRefiner`, after which `op.schedule.refine()` re-lowers
under a fresh cache generation. Per-worker sums of the emitted stream
equal the schedule's tile-cost totals exactly — the routing proof in
tests/test_adaptive_properties.py.

Each op makes its one jitted program in `_ObservableOp._run`, under a
stable name (`ich_spmv`, `ich_bfs_step`, `ich_kmeans_assign`, `ich_moe`)
that traces and HLO show, and runs its host work in `repro.obs` spans:
`op.shard`, `op.pack`, `op.upload` at construction, `op.compile` on the
first call, `op.dispatch` on every later one.

jax is imported inside the op constructors: deriving costs and constructing
schedules is numpy-only, and the registry must be listable without paying
the jax import.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

from repro import obs
from repro.core.tiling import gather_width, pack_csr, token_block_width
from repro.kernels import default_interpret

from .api import Schedule
from .costs import (DegreeCosts, ExpertLoadCosts, ExplicitCosts, NnzCosts,
                    RemainingTokensCosts)
from .registry import register


def _flat_slot_cost(schedule: Schedule, n_tiles_padded: int) -> np.ndarray:
    """The (T_pad, R) float32 per-slot scheduled-cost stream the sharded
    SpMV/BFS kernels fetch blockwise (pad tiles carry zeros)."""
    sc = np.zeros((n_tiles_padded, schedule.rows_per_tile), np.float32)
    sc[:schedule.n_tiles] = schedule.slot_cost()
    return sc


def _sharded_slot_cost(schedule: Schedule, shards) -> np.ndarray:
    """The (p*S, R) per-slot cost stream in SHARD layout for kernels with
    no flat-payload indirection (K-Means); padding rows are zero."""
    flat = shards.perm.reshape(-1)
    if schedule.n_tiles == 0:  # 0-tile schedule: all rows are padding
        return np.zeros((flat.size, schedule.rows_per_tile), np.float32)
    sc = schedule.slot_cost()
    out = np.where((flat >= 0)[:, None], sc[np.clip(flat, 0, None)], 0.0)
    return np.ascontiguousarray(out, np.float32)


class _ObservableOp:
    """Shared plumbing of the kernel ops: shard, pack and upload a
    schedule's payload; make and call the op's one jitted program; stash
    the kernel's latest cost stream and route it into the schedule's
    refiner on demand. Each stretch of host work runs in an `obs.span`."""

    program: str  # the jitted program's name, as traces and HLO show it
    schedule: Schedule
    last_costs = None  # (p, S_B) device array from the latest invocation

    def __init__(self, schedule: Schedule):
        self.schedule = schedule
        self._jitted = {}  # interpret mode -> jitted program (compile once)

    def _bind_csr(self, indptr, indices, data):
        """Shard, pack and upload a CSR payload in the FLAT layout the
        SpMV, BFS and MoE kernels fetch blockwise. Sets the shard streams
        `rowid`, `blkid`, `slot_cost` and any of the op's own
        (`_streams`); returns the device (vals, cols)."""
        import jax.numpy as jnp
        schedule = self.schedule
        with obs.span("op.shard"):
            shards = self.shards = schedule.shard()
            run, n_tiles = self._lowering(shards)
            streams = self._streams(run, n_tiles)
        with obs.span("op.pack"):
            vals, cols = pack_csr(np.asarray(indptr), np.asarray(indices),
                                  np.asarray(data), schedule.tiles,
                                  pad_tiles_to=shards.superstep)
            if n_tiles > vals.shape[0]:
                pad = ((0, n_tiles - vals.shape[0]), (0, 0), (0, 0))
                vals, cols = np.pad(vals, pad), np.pad(cols, pad)
        with obs.span("op.upload"):
            vals, cols, self.rowid, self.blkid, self.slot_cost, *more = (
                jnp.asarray(a) for a in (vals, cols, *streams))
            self.more_streams = tuple(more)
        self.p = run.p
        self.superstep = run.superstep
        return vals, cols

    def _lowering(self, shards):
        """The shard layout the kernel runs and the flat tile count of its
        payload: the schedule's own, unless an op pads them."""
        return shards, shards.n_tiles_padded

    def _streams(self, run, n_tiles: int) -> tuple:
        """Host streams to upload: the shard layout's item ids and block
        ids and the flat per-slot costs, then any of the op's own."""
        return (run.shard_item_id(self.schedule.tiles),
                run.kernel_block_ids(),
                _flat_slot_cost(self.schedule, n_tiles))

    def _kernel(self):
        """The sharded kernel with this op's static arguments bound."""
        raise NotImplementedError

    def _program(self, interpret: bool):
        """The kernel as a jitted program named `self.program`."""
        import jax
        fn = functools.partial(self._kernel(), interpret=interpret)
        fn.__name__ = self.program
        return jax.jit(fn)

    def _run(self, interpret: bool | None, *args, **kwargs):
        """Call the op's program. The first call per interpret mode makes
        it (trace, lower, compile or persistent-cache hit) under
        `op.compile`; later calls dispatch under `op.dispatch`."""
        interpret = default_interpret(interpret)
        prog = self._jitted.get(interpret)
        if prog is None:
            with obs.span("op.compile", program=self.program):
                prog = self._jitted[interpret] = self._program(interpret)
                return prog(*args, **kwargs)
        with obs.span("op.dispatch", program=self.program):
            return prog(*args, **kwargs)

    def _empty_costs(self):
        """Zero (p, S_B) cost stream for a 0-tile schedule: an empty
        workload lowers as a no-op — no kernel launch, no payload fetch —
        but the op still reports a well-shaped (all-zero) cost stream."""
        import jax.numpy as jnp
        return jnp.zeros(self.shards.block_perm.shape, jnp.float32)

    def observe(self) -> Schedule:
        """Fold the latest invocation's per-worker, per-superstep cost
        stream into `schedule.refiner`; chain with
        ``op.observe().refine()`` to re-lower from it. The op names its
        own shard lowering explicitly — a (p, S_B) shape alone cannot
        identify one."""
        if self.last_costs is None:
            raise ValueError("no kernel invocation to observe yet; run the "
                             "op first")
        return self.schedule.observe(np.asarray(self.last_costs),
                                     shards=self.shards)


class SpmvOp(_ObservableOp):
    """iCh-scheduled segmented CSR SpMV: pack once, apply many times."""

    program = "ich_spmv"

    def __init__(self, schedule: Schedule, indptr, indices, data):
        super().__init__(schedule)
        self.n_rows = len(indptr) - 1
        self.width = schedule.width
        self.vals, self.cols = self._bind_csr(indptr, indices, data)

    def _kernel(self):
        from repro.kernels.ich_spmv.ich_spmv import ich_spmv_sharded
        return functools.partial(ich_spmv_sharded, n_rows=self.n_rows,
                                 p=self.p, superstep=self.superstep)

    def __call__(self, x, interpret: bool | None = None):
        import jax.numpy as jnp
        if self.schedule.n_tiles == 0:
            self.last_costs = self._empty_costs()
            return jnp.zeros((self.n_rows,), jnp.float32)
        y, self.last_costs = self._run(
            interpret, self.vals, self.cols, self.rowid, self.blkid, x,
            slot_cost=self.slot_cost)
        return y


class BfsOp(_ObservableOp):
    """iCh-scheduled BFS: pack the graph once, expand frontiers many times."""

    program = "ich_bfs_step"

    def __init__(self, schedule: Schedule, indptr, indices):
        super().__init__(schedule)
        self.n = len(indptr) - 1
        self.mask, self.cols = self._bind_csr(
            indptr, indices, np.ones(len(indices), np.float32))

    def _kernel(self):
        from repro.kernels.ich_bfs.ich_bfs import ich_bfs_step_sharded
        return functools.partial(ich_bfs_step_sharded, n_vertices=self.n,
                                 p=self.p, superstep=self.superstep)

    def step(self, frontier, visited, interpret: bool | None = None):
        """One frontier expansion; indicator in, indicator out."""
        import jax.numpy as jnp
        if self.schedule.n_tiles == 0:
            self.last_costs = self._empty_costs()
            return jnp.zeros((self.n,), jnp.float32)
        with obs.span("bfs.send"):
            frontier = jnp.asarray(frontier, jnp.float32)
            visited = jnp.asarray(visited, jnp.float32)
        nxt, self.last_costs = self._run(
            interpret, self.mask, self.cols, self.rowid, self.blkid,
            frontier, visited, slot_cost=self.slot_cost)
        return nxt

    def levels(self, source: int = 0,
               interpret: bool | None = None) -> np.ndarray:
        """Full traversal: level per vertex (-1 = unreached)."""
        with obs.span("bfs.levels", source=source):
            level = np.full(self.n, -1, np.int32)
            level[source] = 0
            frontier = np.zeros(self.n, np.float32)
            frontier[source] = 1.0
            visited = frontier.copy()
            depth = 0
            more = True  # the source is a nonempty first frontier
            while more:
                depth += 1
                with obs.span("bfs.level", depth=depth):
                    nxt = self.step(frontier, visited, interpret)
                    with obs.span("bfs.wait"):
                        nxt = np.asarray(nxt)
                    with obs.span("bfs.update"):
                        level[nxt > 0] = depth
                        visited = np.maximum(visited, nxt)
                        frontier = nxt
                        more = frontier.any()
            return level


class KMeansOp(_ObservableOp):
    """iCh-scheduled K-Means assignment over a predicted per-point cost.

    K-Means re-predicts its costs every round, so its schedules are
    one-shot: build them on a `LoopScheduler(cache_size=0)`, which keeps
    no dead entries."""

    program = "ich_kmeans_assign"

    def __init__(self, schedule: Schedule, costs):
        import jax.numpy as jnp
        super().__init__(schedule)
        self.sizes = schedule.sizes
        self.n = schedule.n_items
        with obs.span("op.shard"):
            shards = self.shards = schedule.shard()
            rowid = shards.shard_item_id(schedule.tiles)
            slot_cost = _sharded_slot_cost(schedule, shards)
        with obs.span("op.upload"):
            self.rowid = jnp.asarray(rowid)
            self.slot_cost = jnp.asarray(slot_cost)
        self.p = shards.p
        self.superstep = shards.superstep

    def _kernel(self):
        from repro.kernels.ich_kmeans.ich_kmeans import \
            ich_kmeans_assign_sharded
        return functools.partial(ich_kmeans_assign_sharded, p=self.p,
                                 superstep=self.superstep)

    def __call__(self, points, centroids, interpret: bool | None = None):
        import jax.numpy as jnp
        if self.schedule.n_tiles == 0:
            self.last_costs = self._empty_costs()
            return jnp.zeros((self.n,), jnp.int32)
        assign, self.last_costs = self._run(
            interpret, jnp.asarray(points, jnp.float32),
            jnp.asarray(centroids, jnp.float32), self.rowid,
            slot_cost=self.slot_cost)
        return assign


def _bucket(n: int) -> int:
    """The least of 0, 1, 2, 3, 4, 6, 8, 12, ... (2^k and 3 * 2^(k-1))
    that is at least n: padded sizes that take few distinct values."""
    n = int(n)
    if n <= 2:
        return max(n, 0)
    k = (n - 1).bit_length() - 1  # 2^k < n <= 2^(k+1)
    return 3 << (k - 1) if n <= 3 << (k - 1) else 2 << k


class MoeDispatchOp(_ObservableOp):
    """iCh-scheduled MoE expert application: pack a dispatch plan once,
    apply the expert FFN stack (DESIGN.md §2.8).

    The plan's expert-major CSR (token ids + combine weights per expert)
    packs through the same `pack_csr` path as SpMV — expert = item, a hot
    expert's tokens split across slot rows like a heavy row — and runs as
    `ich_moe_sharded`: the XLA token gather, then the grouped expert FFN
    kernel with each expert's weights streamed per tile and the combine
    into y as its epilogue.

    Plans of one batch size differ in their expert loads, so the flat
    tile count is padded up to a `_bucket` size and every worker's step
    count to the padded block count: the program's shapes take few
    values, and consecutive plans reuse one compiled program, which all
    MoE ops share. Besides the (p, S_B) superstep cost stream every op
    emits, the program returns (p, E) per-worker per-expert cost totals
    (`last_expert_costs`); `expert_load()` worker-sums them into the
    per-expert load that `repro.sched.moe.refine_cap_scale` folds into
    the next step's capacity scale."""

    program = "ich_moe"
    last_expert_costs = None  # (p, E) from the latest invocation
    _step_costs = None        # (p, padded S_B) from the latest invocation
    _programs: dict = {}      # interpret mode -> the shared jitted program

    @property
    def last_costs(self):
        """(p, S_B) per-worker, per-superstep cost stream of the latest
        invocation, cut from the padded one when read, so that the call
        itself runs no op whose shape follows the partition."""
        c = self._step_costs
        return None if c is None else c[:, :self.shards.n_steps]

    def __init__(self, schedule: Schedule, plan):
        super().__init__(schedule)
        self._jitted = MoeDispatchOp._programs
        self.plan = plan
        self.n_tokens = plan.n_tokens
        self.n_experts = plan.n_experts
        self.vals, self.cols = self._bind_csr(*plan.csr())

    def _lowering(self, shards):
        # every worker gets room for all the blocks, so the shapes follow
        # the padded tile count alone, whatever the partition
        B = shards.superstep
        blocks = max(_bucket(shards.n_tiles_padded // B), shards.n_steps)
        run = dataclasses.replace(shards, block_perm=np.pad(
            shards.block_perm, ((0, 0), (0, blocks - shards.n_steps)),
            constant_values=-1))
        return run, B * blocks

    def _streams(self, run, n_tiles: int) -> tuple:
        from repro.kernels.ich_moe.ich_moe import grid_streams
        rowid, blkid, slot_cost = super()._streams(run, n_tiles)
        return (rowid, blkid, slot_cost,
                *grid_streams(rowid, blkid, run.p, run.superstep,
                              n_tiles * self.schedule.rows_per_tile))

    def _program(self, interpret: bool):
        import jax
        from repro.kernels.ich_moe.ich_moe import ich_moe_sharded
        fn = functools.partial(ich_moe_sharded, interpret=interpret)
        fn.__name__ = self.program
        return jax.jit(fn, static_argnames=("p", "superstep"))

    def __call__(self, x, wi, wg, wo, interpret: bool | None = None, *,
                 w0=None):
        """Apply the planned dispatch: x (n_tokens, D) token activations,
        wi/wg (E, D, F) up and gate weights, wo (E, F, D) down weights.
        Returns y (n_tokens, D) float32, each token's combine-weighted sum
        of its experts' SwiGLU outputs, plus w0[t] x[t] where `w0`
        (n_tokens,), the tokens' weights on zero-compute experts
        (`route(..., zero=)`), is given."""
        import jax.numpy as jnp
        # n_tokens == 0 also short-circuits: a zero-admission plan still
        # carries one tile per (zero-count) expert, but the kernel's token
        # gather has no source rows to read
        if self.schedule.n_tiles == 0 or self.n_tokens == 0:
            self._step_costs = self._empty_costs()
            self.last_expert_costs = jnp.zeros(
                (self.p, self.n_experts), jnp.float32)
            return jnp.zeros((self.n_tokens, x.shape[-1]), jnp.float32)
        y, self._step_costs, self.last_expert_costs = self._run(
            interpret, self.vals, self.cols, self.rowid, self.blkid,
            *self.more_streams, x, wi, wg, wo, p=self.p,
            superstep=self.superstep, slot_cost=self.slot_cost, w0=w0)
        return y

    def expert_load(self) -> np.ndarray:
        """Measured per-expert cost totals of the latest invocation
        (worker-summed (E,) float64) — equals the plan's kept token
        counts exactly; the signal `refine_cap_scale` consumes."""
        if self.last_expert_costs is None:
            raise ValueError("no kernel invocation to read yet; run the "
                             "op first")
        return np.asarray(self.last_expert_costs, np.float64).sum(axis=0)


register(
    "spmv",
    costs=lambda indptr, indices, data: NnzCosts(indptr),
    build=SpmvOp,
    doc="Segmented CSR SpMV; inputs (indptr, indices, data); cost = row nnz.",
    width=gather_width)
register(
    "bfs",
    costs=lambda indptr, indices: DegreeCosts(indptr),
    build=BfsOp,
    doc="Pull-direction BFS; inputs (indptr, indices); cost = in-degree.",
    width=gather_width)
register(
    "kmeans",
    # float64 coercion keeps the provider on its quantizing path (ceil, >= 1
    # unit per point) for integer inputs too — every point must be computed
    costs=lambda costs: ExplicitCosts(np.asarray(costs, np.float64)),
    build=KMeansOp,
    doc="K-Means assignment; input (predicted per-point costs).")
register(
    "moe-dispatch",
    costs=lambda plan: ExpertLoadCosts(plan.counts),
    build=MoeDispatchOp,
    doc="MoE expert FFN over a dispatch plan (sched/moe.py); input "
        "(DispatchPlan); cost = per-expert kept token load.",
    width=token_block_width)
register(
    "serve-prefill",
    costs=lambda remaining: RemainingTokensCosts(
        np.asarray(remaining, np.int64)),
    # there is no kernel here: the "op" IS the schedule — the continuous
    # batcher (serve/batcher.py) consumes its cost estimates and tile
    # order to pick the next prefill target, and routes measured step
    # wall-clock back through Schedule.observe/refine (DESIGN.md §2.10)
    build=lambda schedule, remaining: schedule,
    doc="Continuous-batching prefill scheduling; input (per-request "
        "remaining prompt token counts); cost = remaining tokens.")
