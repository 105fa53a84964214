"""Jitted schedule construction: `core/tiling.py` as an XLA array program.

The numpy construction path (band width -> item splitting -> greedy
packing -> payload pack -> LPT sharding) is already loop-free —
cumsum/repeat/take programs — so it ports to jax nearly term-for-term.
This module is that port: build -> pack -> shard runs as a jitted
pipeline on the accelerator, so per-request scheduling (the serving
path's ich-adaptive policy, every `Schedule.refine()` round) stops
round-tripping arrays through host numpy.

Conformance bar: ELEMENT-IDENTICAL outputs to `core/tiling.py`, not
"close" (tests/test_tiling_jax.py asserts it over the paper-grid
families). The integer streams (item_id/seg_start/seg_len, block
permutations, prefetch streams) are exact by construction — same
index arithmetic, same gathers. The one subtlety is float cost
arithmetic: LPT partitioning compares f64 partial sums, so a one-ulp
difference in `tile_cost` can flip a worker assignment. Two rules keep
it exact:

* all cost arithmetic runs in float64 (`jax.enable_x64`
  scopes the flip to this module's traces — nothing else in the repo
  sees x64);
* reductions replicate numpy's exact association order:
  `_pairwise_rowsum` mirrors numpy's pairwise_sum (8-accumulator
  unrolled block reduction) for the slot-cost row sums, and
  `segment_sum` matches `np.bincount(weights=...)` addition order for
  block/chain folds (both asserted in the test suite).

Shapes must be static under jit, so a tiny host-side `SchedulePlan`
(one numpy pass over sizes: total segment count, tile count, width)
parameterizes the traced program; jax caches one executable per plan
shape. The only device->host sync in the whole pipeline is the
per-worker block count that sizes the (p, S_B) shard layout — and
callers that know S_B (a refine round re-lowering at the same shape,
the serving path's steady state) can pass `n_steps=` and skip even
that. Input buffers are donated to the pipeline where the platform
supports it (no-op on CPU), so a refine loop reuses the previous
generation's device pages instead of growing the live set.

Zero-tile schedules (empty sizes) mirror `build_schedule`'s 0-tile
semantics host-side — there is nothing to launch.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

from repro.sched.defaults import ICH_EPS, SUPERSTEP

from .tiling import TileSchedule, WorkerShards, _check_width, ich_tile_width

# jax import is deliberately eager here: this module IS the jax path.
import jax
import jax.numpy as jnp
from jax import lax


def _i32(x):
    return jnp.asarray(x, jnp.int32)


# ---------------------------------------------------------------------------
# Host-side shape plan: everything jit needs to be static, from one cheap
# numpy pass over sizes.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SchedulePlan:
    """Static shapes of one schedule construction (the jit cache key)."""

    n_items: int        # len(sizes)
    width: int          # W (band width, host-resolved like the numpy path)
    total_segs: int     # real segments before padding
    n_tiles: int        # T = ceil(max(total, 1) / R)
    rows_per_tile: int  # R

    @property
    def capacity(self) -> int:
        return self.n_tiles * self.rows_per_tile


def plan_schedule(sizes: np.ndarray, *, rows_per_tile: int = 8,
                  width: int | None = None, eps: float = ICH_EPS,
                  min_w: int = 8, max_w: int = 512) -> SchedulePlan:
    """Resolve the static shapes `build_schedule` would produce."""
    sizes = np.asarray(sizes)
    width = _check_width(width)
    W = width if width else ich_tile_width(sizes, eps, min_w, max_w)
    R = int(rows_per_tile)
    if sizes.size == 0:
        return SchedulePlan(0, W, 0, 0, R)
    if int(sizes.max()) > np.iinfo(np.int32).max - W:
        raise ValueError("per-item sizes must fit int32; largest item is "
                         f"{int(sizes.max())} work units")
    total = int(np.maximum(-(-sizes.astype(np.int64) // W), 1).sum())
    if total > np.iinfo(np.int32).max:
        raise ValueError(f"schedule would need {total} segments, which "
                         "exceeds the int32 construction bound")
    T = -(-max(total, 1) // R)
    return SchedulePlan(int(sizes.size), W, total, T, R)


# ---------------------------------------------------------------------------
# Device-side containers (jax.Array twins of TileSchedule / WorkerShards)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DeviceSchedule:
    """`TileSchedule` with device-resident arrays."""

    item_id: jax.Array    # (T, R) int32, -1 = padding slot
    seg_start: jax.Array  # (T, R) int32
    seg_len: jax.Array    # (T, R) int32
    width: int
    n_items: int

    @property
    def n_tiles(self) -> int:
        return int(self.item_id.shape[0])

    @property
    def rows_per_tile(self) -> int:
        return int(self.item_id.shape[1])

    def to_host(self) -> TileSchedule:
        return TileSchedule(np.asarray(self.item_id),
                            np.asarray(self.seg_start),
                            np.asarray(self.seg_len),
                            self.width, self.n_items)


@dataclasses.dataclass(frozen=True)
class DeviceLowering:
    """One schedule fully lowered on device: tiles + costs + the (p, S_B)
    shard layout + the exact streams the sharded kernels prefetch.
    What a `backend="jax"` Schedule memoizes per (p, superstep)."""

    schedule: DeviceSchedule
    tile_cost: jax.Array   # (T,) float64, numpy-identical association order
    worker: jax.Array      # (T,) int32
    block_perm: jax.Array  # (p, S_B) int32, -1 = padding step
    rowid: jax.Array       # (p*S, R) int32 shard item-id stream
    blkid: jax.Array       # (p*S_B,) int32 kernel block-id prefetch stream
    slot_cost: jax.Array   # (T_pad, R) float32 flat kernel cost stream
    superstep: int

    @property
    def p(self) -> int:
        return int(self.block_perm.shape[0])

    @property
    def n_steps(self) -> int:
        return int(self.block_perm.shape[1])

    def to_host_shards(self) -> WorkerShards:
        return WorkerShards(worker=np.asarray(self.worker),
                            block_perm=np.asarray(self.block_perm),
                            superstep=self.superstep)


# ---------------------------------------------------------------------------
# Bit-exact float reductions
# ---------------------------------------------------------------------------

def _pairwise_rowsum(x: jax.Array) -> jax.Array:
    """Sum (T, R) over axis 1 in EXACTLY numpy's pairwise_sum association
    order (sequential under 8 columns; 8 accumulators then a fixed
    4-2-1 combine tree up to 128; halved recursion above), so LPT sees
    bit-identical tile costs to the numpy path. R is static, so the
    "loop" unrolls at trace time."""
    R = int(x.shape[1])
    if R == 0:
        return jnp.zeros(x.shape[0], x.dtype)
    if R < 8:
        res = x[:, 0]
        for i in range(1, R):
            res = res + x[:, i]
        return res
    if R <= 128:
        r = [x[:, j] for j in range(8)]
        i = 8
        while i + 8 <= R:
            for j in range(8):
                r[j] = r[j] + x[:, i + j]
            i += 8
        res = ((r[0] + r[1]) + (r[2] + r[3])) + ((r[4] + r[5]) + (r[6] + r[7]))
        while i < R:
            res = res + x[:, i]
            i += 1
        return res
    half = (R // 2) - ((R // 2) % 8)
    return _pairwise_rowsum(x[:, :half]) + _pairwise_rowsum(x[:, half:])


def _segment_sum(values: jax.Array, segment_ids: jax.Array,
                 num_segments: int) -> jax.Array:
    """`np.bincount(segment_ids, weights=values)` twin (sequential
    scatter-add matches bincount's addition order bit-exactly on CPU/TPU
    for the contiguous id streams used here)."""
    return jax.ops.segment_sum(values, segment_ids,
                               num_segments=num_segments)


# ---------------------------------------------------------------------------
# Traced mirrors of the construction stages
# ---------------------------------------------------------------------------

def ich_tile_width_jax(sizes: jax.Array, eps: float = ICH_EPS,
                       min_w: int = 8, max_w: int = 512) -> jax.Array:
    """Traceable twin of `ich_tile_width` (device scalar; the pipeline
    itself resolves W host-side because tile shapes must be static)."""
    with jax.enable_x64():
        sizes = jnp.asarray(sizes)
        mu = (jnp.mean(sizes.astype(jnp.float64)) if sizes.size
              else jnp.float64(0.0))
        upper = mu * (1.0 + eps)
        # integer shift, not exp2: XLA CPU lowers exp2 via exp(x*ln2),
        # which returns 15.999... for exp2(4.0)
        e = jnp.ceil(jnp.log2(jnp.maximum(upper, 1.0))).astype(jnp.int32)
        w = jnp.left_shift(1, jnp.clip(e, 0, 30))
        return jnp.clip(w, min_w, max_w).astype(jnp.int32)


def _split_build(sizes: jax.Array, *, width: int, total: int, n_tiles: int,
                 rows_per_tile: int) -> tuple[jax.Array, jax.Array,
                                              jax.Array]:
    """`_split_segments` + the (T, R) reshape of `build_schedule`."""
    n = sizes.shape[0]
    R, cap = rows_per_tile, n_tiles * rows_per_tile
    s32 = sizes.astype(jnp.int32)
    n_segs = jnp.maximum(lax.div(s32 + jnp.int32(width - 1),
                                 jnp.int32(width)), 1)
    first = jnp.cumsum(n_segs) - n_segs  # exclusive-prefix seg counts
    item = jnp.repeat(jnp.arange(n, dtype=jnp.int32), n_segs,
                      total_repeat_length=cap)
    pos = jnp.arange(cap, dtype=jnp.int32)
    valid = pos < total  # total is static: the tail mask is a constant
    safe = jnp.clip(item, 0, n - 1)
    start = (pos - first[safe]) * jnp.int32(width)
    length = jnp.clip(s32[safe] - start, 0, width)
    item = jnp.where(valid, item, -1)
    start = jnp.where(valid, start, 0)
    length = jnp.where(valid, length, 0)
    return (item.reshape(n_tiles, R), start.reshape(n_tiles, R),
            length.reshape(n_tiles, R))


def _slot_tile_cost(costs: jax.Array, sizes: jax.Array, item_id: jax.Array,
                    seg_len: jax.Array) -> tuple[jax.Array, jax.Array]:
    """`TileSchedule.slot_cost` / `tile_cost` twins (f64, numpy order)."""
    n = costs.shape[0]
    costs = costs.astype(jnp.float64)
    sizes_f = sizes.astype(jnp.float64)
    unit = jnp.where(sizes_f > 0, costs / jnp.where(sizes_f > 0, sizes_f, 1.0),
                     0.0)
    safe = jnp.clip(item_id, 0, max(n - 1, 0))
    per_slot = jnp.where(item_id >= 0, unit[safe], 0.0)
    slot_cost = per_slot * seg_len
    return slot_cost, _pairwise_rowsum(slot_cost)


def _partition(tile_cost: jax.Array, item_id: jax.Array, *, p: int,
               block: int) -> jax.Array:
    """`partition_tiles` twin: item-closed chain merge + LPT assignment.

    `jnp.argmin(loads)` breaks load ties on the smallest worker id —
    exactly the heapq (load, w) tuple order of the numpy original — and
    f64 loads accumulate in the same chain order, so assignments match
    bit-for-bit. Phantom chain slots (the chain count is data-dependent;
    the loop runs over the static n_blocks bound) carry zero cost and
    sort AFTER every real chain (stable argsort, higher ids), so they
    cannot perturb any real assignment."""
    T = int(item_id.shape[0])
    blk = int(block)
    n_blocks = -(-T // blk)
    first = item_id[:, 0]
    last = jnp.max(item_id, axis=1)
    spans = (last[:-1] == first[1:]) & (first[1:] >= 0) & (last[:-1] >= 0)
    merge = spans if blk == 1 else spans[blk - 1:T - 1:blk]
    chain = jnp.concatenate([jnp.zeros(1, jnp.int32),
                             jnp.cumsum((~merge).astype(jnp.int32))])
    bcost = tile_cost
    if blk > 1:
        bcost = _segment_sum(tile_cost,
                             jnp.arange(T, dtype=jnp.int32) // blk, n_blocks)
    ccost = _segment_sum(bcost, chain, n_blocks)
    order = jnp.argsort(-ccost, stable=True)

    def assign(i, carry):
        loads, cw = carry
        c = order[i]
        w = jnp.argmin(loads).astype(jnp.int32)
        return loads.at[w].add(ccost[c]), cw.at[c].set(w)

    loads, chain_worker = lax.fori_loop(
        0, n_blocks, assign,
        (jnp.zeros(p, jnp.float64), jnp.zeros(n_blocks, jnp.int32)))
    block_worker = chain_worker[chain]
    return jnp.repeat(block_worker, blk,
                      total_repeat_length=n_blocks * blk)[:T]


def _shard_layout(worker: jax.Array, item_id: jax.Array, slot_cost: jax.Array,
                  *, p: int, superstep: int,
                  n_steps: int) -> tuple[jax.Array, jax.Array, jax.Array,
                                         jax.Array]:
    """`make_shards` + the kernels' prefetch streams, at static S_B."""
    T = int(worker.shape[0])
    B, S_B = int(superstep), int(n_steps)
    n_blocks = -(-T // B)
    R = int(item_id.shape[1])
    block_worker = worker[::B]
    order = jnp.argsort(block_worker, stable=True)
    w_sorted = block_worker[order]
    pos = jnp.arange(n_blocks) - jnp.searchsorted(w_sorted, w_sorted)
    block_perm = jnp.full((p, S_B), -1, jnp.int32)
    block_perm = block_perm.at[w_sorted, pos].set(order.astype(jnp.int32))
    # tile-granular perm -> shard item-id stream (WorkerShards.shard_item_id)
    tiles = (block_perm[:, :, None] * B
             + jnp.arange(B, dtype=jnp.int32)[None, None, :])
    tiles = jnp.where((block_perm[:, :, None] >= 0) & (tiles < T), tiles, -1)
    flat = tiles.reshape(-1)
    rowid = jnp.where((flat >= 0)[:, None],
                      item_id[jnp.clip(flat, 0, None)], jnp.int32(-1))
    blkid = jnp.maximum(block_perm, 0).reshape(-1)
    # flat (T_pad, R) float32 cost stream (sched/kernels._flat_slot_cost)
    T_pad = n_blocks * B
    flat_cost = jnp.zeros((T_pad, R), jnp.float32)
    flat_cost = flat_cost.at[:T].set(slot_cost.astype(jnp.float32))
    return block_perm, rowid, blkid, flat_cost


def _pack_gather(indptr: jax.Array, indices: jax.Array, data: jax.Array,
                 item_id: jax.Array, seg_start: jax.Array,
                 seg_len: jax.Array, *, width: int,
                 pad_tiles_to: int) -> tuple[jax.Array, jax.Array]:
    """`pack_csr` twin as the rectangular gather (the numpy fast path is a
    masked sequential reshape of the same element stream; tests assert the
    two agree bit-for-bit, as they already do for the numpy fallback)."""
    T, R = item_id.shape
    W = int(width)
    T_pad = -(-T // int(pad_tiles_to)) * int(pad_tiles_to)
    item = item_id.reshape(-1)
    base = (indptr[jnp.clip(item, 0, None)].astype(jnp.int64)
            + seg_start.reshape(-1).astype(jnp.int64))
    lane = jnp.arange(W, dtype=jnp.int64)
    src = jnp.clip(base[:, None] + lane[None, :], 0, data.shape[0] - 1)
    keep = lane[None, :] < seg_len.reshape(-1)[:, None]
    vals = jnp.where(keep, data[src], 0).reshape(T, R, W)
    cols = jnp.where(keep, indices[src], 0).reshape(T, R, W).astype(jnp.int32)
    if T_pad > T:
        vals = jnp.pad(vals, ((0, T_pad - T), (0, 0), (0, 0)))
        cols = jnp.pad(cols, ((0, T_pad - T), (0, 0), (0, 0)))
    return vals, cols


# ---------------------------------------------------------------------------
# Jitted entry points (donation where the platform supports it)
# ---------------------------------------------------------------------------

def _donate(*argnums):
    """Donate argnums on backends with buffer donation; CPU jax donates
    silently or warns depending on version — keep it off there."""
    return argnums if jax.default_backend() != "cpu" else ()


@functools.cache
def _jit_build(width: int, total: int, n_tiles: int, rows_per_tile: int):
    fn = functools.partial(_split_build, width=width, total=total,
                           n_tiles=n_tiles, rows_per_tile=rows_per_tile)
    return jax.jit(fn, donate_argnums=_donate(0))


@functools.cache
def _jit_construct(width: int, total: int, n_tiles: int, rows_per_tile: int,
                   p: int, block: int):
    """build + cost + partition fused into one executable."""

    def construct(sizes, costs):
        item_id, seg_start, seg_len = _split_build(
            sizes, width=width, total=total, n_tiles=n_tiles,
            rows_per_tile=rows_per_tile)
        slot_cost, tile_cost = _slot_tile_cost(costs, sizes, item_id,
                                               seg_len)
        if p == 1:
            worker = jnp.zeros(n_tiles, jnp.int32)
        else:
            worker = _partition(tile_cost, item_id, p=p, block=block)
        n_blocks = -(-n_tiles // block)
        counts = _segment_sum(jnp.ones(n_blocks, jnp.int32), worker[::block],
                              p)
        return (item_id, seg_start, seg_len, slot_cost, tile_cost, worker,
                counts)

    return jax.jit(construct, donate_argnums=_donate(0, 1))


@functools.cache
def _jit_layout(p: int, superstep: int, n_steps: int):
    fn = functools.partial(_shard_layout, p=p, superstep=superstep,
                           n_steps=n_steps)
    return jax.jit(fn)


@functools.cache
def _jit_pack(width: int, pad_tiles_to: int):
    fn = functools.partial(_pack_gather, width=width,
                           pad_tiles_to=pad_tiles_to)
    return jax.jit(fn, donate_argnums=_donate(2))


@functools.cache
def _jit_partition(p: int, block: int):
    return jax.jit(functools.partial(_partition, p=p, block=block))


# ---------------------------------------------------------------------------
# Public mirrors
# ---------------------------------------------------------------------------

def split_items_jax(sizes: np.ndarray,
                    width: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """`split_items` twin: device (item, start, length), real segments only."""
    if int(width) <= 0:
        raise ValueError(f"tile width must be positive, got {width}")
    plan = plan_schedule(sizes, rows_per_tile=1, width=int(width))
    if plan.n_items == 0:
        z = jnp.zeros(0, jnp.int32)
        return z, z, z
    with jax.enable_x64():
        item, start, length = _jit_build(plan.width, plan.total_segs,
                                         plan.n_tiles, 1)(jnp.asarray(sizes))
    t = plan.total_segs
    return item.reshape(-1)[:t], start.reshape(-1)[:t], length.reshape(-1)[:t]


def build_schedule_jax(sizes: np.ndarray, *, rows_per_tile: int = 8,
                       width: int | None = None, eps: float = ICH_EPS,
                       min_w: int = 8, max_w: int = 512) -> DeviceSchedule:
    """`build_schedule` twin with device-resident tiles."""
    plan = plan_schedule(sizes, rows_per_tile=rows_per_tile, width=width,
                         eps=eps, min_w=min_w, max_w=max_w)
    R = plan.rows_per_tile
    if plan.n_items == 0:
        z = jnp.zeros((0, R), jnp.int32)
        return DeviceSchedule(z, z, z, plan.width, 0)
    with jax.enable_x64():
        item_id, seg_start, seg_len = _jit_build(
            plan.width, plan.total_segs, plan.n_tiles, R)(jnp.asarray(sizes))
    return DeviceSchedule(item_id, seg_start, seg_len, plan.width,
                          plan.n_items)


def pack_csr_jax(indptr, indices, data, schedule, *,
                 pad_tiles_to: int = 1) -> tuple[jax.Array, jax.Array]:
    """`pack_csr` twin over a `DeviceSchedule` (or host `TileSchedule`)."""
    if int(pad_tiles_to) < 1:
        raise ValueError(f"pad_tiles_to must be positive, got {pad_tiles_to}")
    T, R, W = schedule.n_tiles, schedule.rows_per_tile, schedule.width
    T_pad = -(-T // int(pad_tiles_to)) * int(pad_tiles_to)
    with jax.enable_x64():  # keeps a float64 payload float64
        data = jnp.asarray(data)
        if data.shape[0] == 0:  # no payload: every slot is padding
            return (jnp.zeros((T_pad, R, W), data.dtype),
                    jnp.zeros((T_pad, R, W), jnp.int32))
        return _jit_pack(W, int(pad_tiles_to))(
            jnp.asarray(np.asarray(indptr)), jnp.asarray(np.asarray(indices)),
            data, jnp.asarray(schedule.item_id),
            jnp.asarray(schedule.seg_start), jnp.asarray(schedule.seg_len))


def partition_tiles_jax(tile_cost, item_id, p: int,
                        block: int = 1) -> jax.Array:
    """`partition_tiles` twin (device (T,) worker map)."""
    p, blk = int(p), int(block)
    if p < 1:
        raise ValueError(f"worker count must be positive, got {p}")
    if blk < 1:
        raise ValueError(f"block must be positive, got {block}")
    T = int(np.asarray(item_id).shape[0] if isinstance(item_id, np.ndarray)
            else item_id.shape[0])
    if T == 0:
        return jnp.zeros(0, jnp.int32)
    if p == 1:
        return jnp.zeros(T, jnp.int32)
    with jax.enable_x64():
        return _jit_partition(p, blk)(
            jnp.asarray(np.asarray(tile_cost, np.float64)),
            jnp.asarray(item_id))


def lower_schedule_jax(sizes: np.ndarray, costs: np.ndarray, *, p: int,
                       superstep: int = SUPERSTEP, rows_per_tile: int = 8,
                       width: int | None = None, eps: float = ICH_EPS,
                       min_w: int = 8, max_w: int = 512,
                       n_steps: int | None = None) -> DeviceLowering:
    """The pipeline: build -> cost -> partition (one executable) -> shard
    layout + prefetch streams (a second, layout-shaped executable).

    `n_steps` (S_B) sizes the (p, S_B) layout; when omitted it is read
    back from the device block counts — the pipeline's single scalar
    sync. Pass the previous generation's `lowering.n_steps` in a refine
    loop to stay fully on device.
    """
    p = int(p)
    if p < 1:
        raise ValueError(f"worker count must be positive, got {p}")
    B = int(superstep)
    if B < 1:
        raise ValueError(f"superstep must be positive, got {superstep}")
    plan = plan_schedule(sizes, rows_per_tile=rows_per_tile, width=width,
                         eps=eps, min_w=min_w, max_w=max_w)
    R = plan.rows_per_tile
    if plan.n_items == 0:
        z2 = jnp.zeros((0, R), jnp.int32)
        dev = DeviceSchedule(z2, z2, z2, plan.width, 0)
        S_B = max(int(n_steps or 0), 1)
        with jax.enable_x64():
            empty_cost = jnp.zeros(0, jnp.float64)
        return DeviceLowering(
            schedule=dev, tile_cost=empty_cost,
            worker=jnp.zeros(0, jnp.int32),
            block_perm=jnp.full((p, S_B), -1, jnp.int32),
            rowid=jnp.full((p * S_B * B, R), -1, jnp.int32),
            blkid=jnp.zeros(p * S_B, jnp.int32),
            slot_cost=jnp.zeros((0, R), jnp.float32), superstep=B)
    with jax.enable_x64():
        (item_id, seg_start, seg_len, slot_cost, tile_cost, worker,
         counts) = _jit_construct(plan.width, plan.total_segs, plan.n_tiles,
                                  R, p, B)(
            jnp.asarray(np.asarray(sizes)),
            jnp.asarray(np.asarray(costs, np.float64)))
        if n_steps is None:
            n_steps = max(int(jnp.max(counts)), 1)  # the one scalar sync
        block_perm, rowid, blkid, flat_cost = _jit_layout(p, B, int(n_steps))(
            worker, item_id, slot_cost)
    dev = DeviceSchedule(item_id, seg_start, seg_len, plan.width,
                         plan.n_items)
    return DeviceLowering(schedule=dev, tile_cost=tile_cost, worker=worker,
                          block_perm=block_perm, rowid=rowid, blkid=blkid,
                          slot_cost=flat_cost, superstep=B)
