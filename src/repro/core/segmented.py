"""Shared segmented-reduction epilogue for the iCh Pallas kernels.

The SpMV, BFS and K-Means kernels end the same way: a tile computed one
value per segment slot and must fold those R values into the output array
at the rows named by the prefetched `item_id` schedule, where several
slots may name the same row (a split item contributes multiple segments,
possibly within one tile). A structural guarantee of
`core.tiling.build_schedule` makes that one windowed vector op: greedy
packing keeps segments in item order and every item owns at least one
segment, so the items of any run of slots form a CONTIGUOUS id range
(consecutive slots step the item id by 0 or +1), and a group's whole
scatter lands in one window of the output, folded under one of three
combine modes: "add" (SpMV partial sums), "max" (BFS frontier OR),
"store" (K-Means idempotent assignment; uncovered rows keep their
previous value).

`worker_reduce` is the epilogue after the worker-sharded 2D kernels
(DESIGN.md §2.6): it folds the (p, n) per-worker accumulators into the
final output with a pairwise tree. The tree order is free because the
shard partition is item-closed (`core.tiling.partition_tiles`): every
output row is accumulated by exactly one worker and all others hold an
exact identity element (0 for add — a worker's accumulated row is never
-0.0, since 0.0 + x only produces -0.0 when x is -0.0, and the accumulate
chain starts at +0.0 — 0 for max over nonnegative values, 0/-1 for
store-as-max), so combining identities in any order is bit-exact.

Lane-dense layout. The TPU compiler cannot gather a vector by an index
array, load a vector of row ids from SMEM, or slice a window at a lane
offset it cannot prove is a multiple of 128, and a (1, n) block of a
(p, n) output breaks its (8, 128) block rule. So:

* every per-slot stream is laid out with its slots on the LANE axis, a
  group of B tiles (one superstep) per block (`slots_on_lanes`): payloads
  become (W, B*R) blocks, row ids and slot costs (1, B*R) rows, all
  streamed as VMEM blocks;
* each worker's accumulator is a lane-dense (rows, 128) block
  (`acc_rows`), output row r at [r // 128, r % 128]; a group's window
  starts at the 128-aligned row holding its first item (`window_starts`,
  prefetched to SMEM as one scalar per group) and spans `window_rows`
  rows, read and written at a dynamic SUBLANE offset, which the compiler
  accepts;
* `fold_tiles` folds the group tile by tile, each tile's slots combined
  per output row by a masked VPU reduction — no MXU, so float32 values are
  never rounded to bfloat16 — into the window in tile order. A row's
  value is therefore (((0 + tile_1) + tile_2) + ...) over the tiles that
  hold its segments, however the tiles are grouped into supersteps and
  workers.

Fold order. That is the one ordering guarantee of every iCh kernel: its
output is bit-identical for every worker count p and superstep B to the
run at p=1, superstep=1, which folds the tiles one by one in schedule
order (tests/test_sharding.py holds the three paper kernels to it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.pipelining import (double_buffer_scratch,
                                   fetch_double_buffered)

COMBINES = ("add", "max", "store")
LANES = 128
# Scoped-VMEM headroom over the resident accumulator blocks, for the
# pipeline's small stream buffers and the compiler's own scratch (its
# default scoped limit on v5e is 16 MiB).
VMEM_HEADROOM = 16 << 20


def worker_reduce(acc: jax.Array, combine: str) -> jax.Array:
    """Fold (p, n) per-worker accumulators into the final (n,) output.

    Pairwise tree over the worker axis. Exact for any order because the
    shard partition is item-closed: each row was accumulated by exactly one
    worker and every other worker holds the combine's identity there ("add"
    folds +0.0s, "max" folds 0s under nonnegative values, "store" is
    lowered to max over init values; see module docstring).
    """
    if combine not in COMBINES:
        raise ValueError(f"combine must be one of {COMBINES}, got {combine!r}")
    op = jnp.add if combine == "add" else jnp.maximum
    parts = [acc[i] for i in range(acc.shape[0])]
    while len(parts) > 1:
        folded = [op(parts[i], parts[i + 1])
                  for i in range(0, len(parts) - 1, 2)]
        if len(parts) % 2:
            folded.append(parts[-1])
        parts = folded
    return parts[0]


# ------------------------------------------------------------ lane-dense
def window_rows(n_slots: int) -> int:
    """Accumulator rows one fold window spans: `n_slots` consecutive slots
    name at most `n_slots` consecutive items, and the window starts at the
    128-aligned row holding the first of them."""
    return -(-(int(n_slots) + LANES - 1) // LANES)


def acc_rows(n_out: int, n_slots: int) -> int:
    """Rows of the lane-dense (rows, 128) accumulator for `n_out` outputs
    folded in windows of `n_slots` slots: the last window may start at the
    final output row, and the count is rounded to whole (8, 128) tiles."""
    rows = -(-int(n_out) // LANES) + window_rows(n_slots) - 1
    return -(-max(rows, 1) // 8) * 8


def cost_rows(n_steps: int) -> int:
    """Rows of a worker's lane-dense (rows, 128) per-step cost block."""
    return -(-max(-(-int(n_steps) // LANES), 1) // 8) * 8


def window_starts(rowid: jax.Array, n_slots: int) -> jax.Array:
    """(G,) int32 first accumulator row of each group of `n_slots`
    consecutive slots of `rowid` (one tile, or one superstep of B tiles):
    the row holding the group's smallest item. All-padding groups start at
    row 0 and fold nothing."""
    rid = jnp.asarray(rowid, jnp.int32).reshape(-1, int(n_slots))
    big = jnp.iinfo(jnp.int32).max
    lo = jnp.min(jnp.where(rid >= 0, rid, big), axis=1)
    return jnp.where(lo == big, 0, lo // LANES).astype(jnp.int32)


def slots_on_lanes(stream: jax.Array, tiles: int, *,
                   whole_lanes: bool = False) -> jax.Array:
    """A per-slot stream, (T, R, W) payload or (T, R) scalars, regrouped so
    each run of `tiles` consecutive tiles is one (W, tiles*R) block — or
    (1, tiles*R) for scalars — with the slots on the lane axis, slot j of
    the run's tile b at lane b*R + j. Returns (T // tiles, W, tiles*R),
    zero-padded to whole 128-lane tiles with `whole_lanes` (a DMA from
    HBM copies whole lane tiles only)."""
    s = stream if stream.ndim == 3 else stream[..., None]
    T, R, W = s.shape
    out = s.reshape(T // int(tiles), int(tiles) * R, W).transpose(0, 2, 1)
    pad = -out.shape[-1] % LANES if whole_lanes else 0
    return jnp.pad(out, ((0, 0), (0, 0), (0, pad))) if pad else out


def unpack_acc(acc: jax.Array, n_out: int) -> jax.Array:
    """(p, rows, 128) lane-dense accumulators -> (p, n_out)."""
    return acc.reshape(acc.shape[0], -1)[:, :n_out]


def fold_tiles(acc_ref, row0, rows: jax.Array, values: jax.Array, *,
               rows_per_tile: int, combine: str) -> None:
    """Fold a group of tiles' slot values into a lane-dense accumulator.

    `rows` and `values` are (1, B*R) lane rows: the item id (-1 padding)
    and value of slot j of tile b at lane b*R + j. `row0` is the group's
    `window_starts` entry and `acc_ref` the (rows, 128) accumulator. The
    window is read once, each tile's slots are combined per output row
    into it in tile order, and it is written back once. `combine` is
    "add", "max" or "store" (module docstring); uncovered rows are left
    unchanged.
    """
    if combine not in COMBINES:
        raise ValueError(f"combine must be one of {COMBINES}, got {combine!r}")
    R = int(rows_per_tile)
    n_win = window_rows(rows.shape[-1])
    win = pl.ds(row0, n_win)
    # slots onto the sublane axis: one (R, 128) masked block per tile and
    # window row, reduced over its sublanes
    offs = jnp.where(rows >= 0, rows - row0 * LANES, -1).T  # (B*R, 1)
    vals = values.T
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, LANES), 1)
    neutral = (-jnp.inf if jnp.issubdtype(values.dtype, jnp.floating)
               else jnp.iinfo(values.dtype).min)
    acc = acc_ref[win, :]
    for b in range(rows.shape[-1] // R):
        o, v = offs[b * R:(b + 1) * R], vals[b * R:(b + 1) * R]
        hits = [o == lane + s * LANES for s in range(n_win)]
        if combine == "add":
            acc = acc + jnp.concatenate(
                [jnp.sum(jnp.where(h, v, 0), axis=0, keepdims=True)
                 for h in hits], axis=0).astype(acc.dtype)
            continue
        val = jnp.concatenate(
            [jnp.max(jnp.where(h, v, neutral), axis=0, keepdims=True)
             for h in hits], axis=0).astype(acc.dtype)
        covered = jnp.concatenate(
            [jnp.max(h.astype(jnp.int32), axis=0, keepdims=True)
             for h in hits], axis=0) > 0
        new = jnp.maximum(acc, val) if combine == "max" else val
        acc = jnp.where(covered, new, acc)
    acc_ref[win, :] = acc


def add_step_cost(cost_ref, rows: jax.Array, slot_cost: jax.Array,
                  j) -> None:
    """Accumulate grid step j's executed cost — the scheduled cost of its
    real (row id >= 0) slots; `rows`/`slot_cost` are (1, B*R) lane rows —
    into lane j % 128 of row j // 128 of the worker's (rows, 128) cost
    block (measured-cost feedback, DESIGN.md §2.7). Padding steps carry
    only -1 rows and add 0."""
    c = jnp.sum(jnp.where(rows >= 0, slot_cost, 0.0), axis=1, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    cost_ref[pl.ds(j // LANES, 1), :] += jnp.where(lane == j % LANES,
                                                    c.astype(cost_ref.dtype),
                                                    0)


def compiler_params(resident_bytes: int):
    """TPU compiler parameters for the iCh kernels: workers "parallel",
    supersteps "arbitrary" (they accumulate in order), and a scoped-VMEM
    limit covering the resident accumulator blocks twice over (the
    pipeline double-buffers them across the worker axis) plus
    `VMEM_HEADROOM`. A v5e core has 128 MiB of VMEM, which bounds the
    outputs one kernel can accumulate whole at about 14M float32 rows."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=int(2 * resident_bytes + VMEM_HEADROOM))


_REDUCE = {"add": jnp.sum, "max": jnp.max}


def _payload_sharded_kernel(starts_ref, blkid_ref, rows_ref, *refs, R: int,
                            combine: str, emit: bool):
    n_hbm = 2 if emit else 1
    hbm, outs = refs[:n_hbm], refs[n_hbm:2 * n_hbm]
    bufs, sems = refs[2 * n_hbm:3 * n_hbm], refs[3 * n_hbm:]
    acc_ref = outs[0]
    w, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        for o in outs:
            o[...] = jnp.zeros_like(o)

    # double-buffered data-dependent fetch (core/pipelining.py): one
    # superstep block per stream, next step's block already in flight
    blocks = fetch_double_buffered(list(zip(hbm, bufs, sems)), blkid_ref,
                                   w, j, B=1)
    rows = rows_ref[...]  # (1, B*R)
    K = rows.shape[-1]
    part = _REDUCE[combine](blocks[0][0][:, :K], axis=0, keepdims=True)
    fold_tiles(acc_ref, starts_ref[w * pl.num_programs(1) + j], rows, part,
               rows_per_tile=R, combine=combine)
    if emit:
        add_step_cost(outs[1], rows, blocks[1][0][:, :K], j)


def segmented_reduce_sharded(payload: jax.Array, rowid: jax.Array,
                             blkid: jax.Array, n_out: int, p: int,
                             superstep: int, *, combine: str,
                             slot_cost=None, interpret: bool = False):
    """Worker-sharded 2D grid (p, S_B) over a FLAT (T_pad, R, W) tile
    payload, T padded to whole supersteps: worker w's grid step j fetches
    superstep block blkid[w*S_B + j] (double-buffered, core/pipelining.py),
    reduces each slot over W and folds the superstep into w's own
    accumulator; `worker_reduce` folds the p accumulators. rowid (p*S, R)
    and blkid (p*S_B,) come from `core.tiling.WorkerShards`. Returns
    (n_out,).

    With `slot_cost` — the (T_pad, R) per-slot scheduled-cost stream — the
    kernel also emits each worker's per-superstep executed cost and
    returns (out, costs (p, S_B)) (DESIGN.md §2.7)."""
    T_pad, R, W = payload.shape
    p, B = int(p), int(superstep)
    n_steps = int(blkid.shape[0]) // p
    if (blkid.shape[0] != p * n_steps or rowid.shape[0] != p * n_steps * B
            or T_pad % B):
        raise ValueError(f"shard layout mismatch: blkid {blkid.shape}, "
                         f"rowid {rowid.shape}, T_pad={T_pad}, p={p}, B={B}")
    K = B * R
    emit = slot_cost is not None
    # payload streams stay whole in HBM as (T_pad // B, W, B*R) superstep
    # blocks, fetched by the prefetched block ids
    with jax.named_scope("ich.relayout"):
        hbm = [slots_on_lanes(payload, B, whole_lanes=True)]
        if emit:
            hbm.append(slots_on_lanes(jnp.asarray(slot_cost, jnp.float32),
                                      B, whole_lanes=True))
        starts, rows = window_starts(rowid, K), slots_on_lanes(rowid, B)
    streams = [(h.shape[1:], h.dtype) for h in hbm]
    n_acc = acc_rows(n_out, K)
    out_specs = [pl.BlockSpec((None, n_acc, LANES),
                              lambda w, j, st, blk: (w, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((p, n_acc, LANES), payload.dtype)]
    resident = n_acc * LANES * payload.dtype.itemsize
    if emit:
        n_cost = cost_rows(n_steps)
        out_specs.append(pl.BlockSpec((None, n_cost, LANES),
                                      lambda w, j, st, blk: (w, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((p, n_cost, LANES),
                                              jnp.float32))
        resident += n_cost * LANES * 4
    call = pl.pallas_call(
        functools.partial(_payload_sharded_kernel, R=R, combine=combine,
                          emit=emit),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # window starts + block ids, to SMEM
            grid=(p, n_steps),
            in_specs=[pl.BlockSpec((None, 1, K),
                                   lambda w, j, st, blk: (w * n_steps + j,
                                                          0, 0))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(hbm),
            out_specs=out_specs,
            scratch_shapes=double_buffer_scratch(1, streams),
        ),
        out_shape=out_shape,
        compiler_params=None if interpret else compiler_params(resident),
        interpret=interpret,
        name=f"ich_reduce_{combine}",
    )
    with jax.named_scope("ich.kernel"):
        outs = call(starts, blkid, rows, *hbm)
    with jax.named_scope("ich.fold"):
        out = worker_reduce(unpack_acc(outs[0], n_out), combine)
        if emit:
            return out, unpack_acc(outs[1], n_steps)
        return out
