"""iCh-scheduled K-Means assignment — the paper's KM application on TPU.

The paper's K-Means loop (§5.1) is near-uniform FLOP-wise but has a
heavy-tailed per-point *cost* (membership flips, cache misses) that is
reshuffled every round. Schedule construction (DESIGN.md §2) consumes that
predicted cost array: each point's cost is quantized to work units, the band
picks the per-slot unit capacity W, and points costlier than W occupy
several slots — possibly in different tiles — so per-tile predicted cost
stays uniform at R*W units, exactly like a split CSR row. A multiply-
scheduled point is recomputed once per slot; the assignment write is
idempotent (same argmin), so correctness is unaffected — redundant compute
is the price a static grid pays where the runtime would have stolen.

`ich_kmeans_assign_sharded` is the one grid (DESIGN.md §2.6; see ich_spmv
for the pattern), checked against `ref.kmeans_assign_ref`: tiles are
cost-partitioned across p workers (item-closed — no point spans workers),
each grid step takes a superstep of B tiles' scheduled points, computes
squared distances to the (K, D) centroids, and writes per-point argmin
through the item-id schedule into the worker's own lane-dense accumulator
("store" mode: uncovered window rows keep their previously written
assignment). A pairwise tree max (`core.segmented.worker_reduce`) folds
the accumulators — exact: assignments are >= 0, each point is stored by
exactly one worker, and every other worker holds the zero-initialized
identity.

The TPU compiler cannot gather rows of a VMEM table by an index vector,
so the scheduled points are gathered in XLA before the kernel, one
(D, B*R) block per superstep with the slots on the lane axis
(`core.segmented.slots_on_lanes`): the distance to each centroid is a
sublane reduction over D, and the argmin a running compare over the K
centroids (first minimum wins, as `jnp.argmin`). Every stream is affine in
the grid step, so Mosaic's automatic pipeliner double-buffers them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.segmented import (LANES, acc_rows, add_step_cost,
                                  compiler_params, cost_rows, fold_tiles,
                                  slots_on_lanes, unpack_acc, window_starts,
                                  worker_reduce)


def _assign(pts, cent):
    """(D, N) points, (D, K) centroids -> (1, N) int32 nearest centroid."""
    best, best_d = None, None
    for k in range(cent.shape[1]):
        d2 = jnp.sum((pts - cent[:, k:k + 1]) ** 2, axis=0, keepdims=True)
        if best is None:
            best, best_d = jnp.zeros(d2.shape, jnp.int32), d2
            continue
        closer = d2 < best_d
        best = jnp.where(closer, k, best)
        best_d = jnp.where(closer, d2, best_d)
    return best


def _gather_points(points, ids, tiles: int):
    """Scheduled points of an (G*tiles, R) id stream, as (G, D, tiles*R)
    superstep blocks (padding slots read point 0 and are never stored)."""
    n = points.shape[0]
    sel = jnp.asarray(points, jnp.float32)[jnp.clip(ids, 0, n - 1)]
    return slots_on_lanes(sel, tiles)


def _kmeans_sharded_kernel(starts_ref, rows_ref, pts_ref, cent_ref, *refs,
                           R: int, emit: bool):
    w, j = pl.program_id(0), pl.program_id(1)
    slotc_ref = refs[0] if emit else None
    outs = refs[1:] if emit else refs

    @pl.when(j == 0)
    def _init():
        for o in outs:
            o[...] = jnp.zeros_like(o)

    rows = rows_ref[...]  # (1, B*R)
    # duplicate slots of a split point carry the same argmin, so the
    # segmented "store" (any-wins within the window) is exact
    fold_tiles(outs[0], starts_ref[w * pl.num_programs(1) + j], rows,
               _assign(pts_ref[...], cent_ref[...]), rows_per_tile=R,
               combine="store")
    if emit:
        add_step_cost(outs[1], rows, slotc_ref[...], j)


def ich_kmeans_assign_sharded(points, centroids, rowid, p: int,
                              superstep: int, *, slot_cost=None,
                              interpret: bool = False):
    """Worker-sharded 2D grid. points (n, D); centroids (K, D); rowid
    (p*S, R) in the shard layout of `core.tiling.WorkerShards`. Returns
    assignments (n,) int32.

    With `slot_cost` — here already in the SHARD layout (p*S, R), matching
    `rowid`, since this kernel has no flat-payload indirection — the
    kernel additionally emits the per-worker, per-superstep cost output
    and returns (assignments, costs) (DESIGN.md §2.7)."""
    n, D = points.shape
    PS, R = rowid.shape
    p, B = int(p), int(superstep)
    S = PS // p
    if PS != p * S or S % B:
        raise ValueError(f"shard layout mismatch: {PS} rows, p={p}, B={B}")
    n_steps = S // B
    K = B * R
    emit = slot_cost is not None

    def step_block(shape):  # this worker's superstep j of a shard stream
        return pl.BlockSpec((None,) + shape,
                            lambda w, j, st: (w * n_steps + j, 0, 0))

    in_specs = [step_block((1, K)), step_block((D, K)),
                pl.BlockSpec((D, centroids.shape[0]),
                             lambda w, j, st: (0, 0))]
    n_acc = acc_rows(n, K)
    out_specs = [pl.BlockSpec((None, n_acc, LANES),
                              lambda w, j, st: (w, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((p, n_acc, LANES), jnp.int32)]
    args = [window_starts(rowid, K), slots_on_lanes(rowid, B),
            _gather_points(points, rowid, B),
            jnp.asarray(centroids, jnp.float32).T]
    resident = n_acc * LANES * 4
    if emit:
        in_specs.append(step_block((1, K)))
        args.append(slots_on_lanes(jnp.asarray(slot_cost, jnp.float32), B))
        n_cost = cost_rows(n_steps)
        out_specs.append(pl.BlockSpec((None, n_cost, LANES),
                                      lambda w, j, st: (w, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((p, n_cost, LANES),
                                              jnp.float32))
        resident += n_cost * LANES * 4
    outs = pl.pallas_call(
        functools.partial(_kmeans_sharded_kernel, R=R, emit=emit),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # window start per superstep, to SMEM
            grid=(p, n_steps),
            in_specs=in_specs,
            out_specs=out_specs,
        ),
        out_shape=out_shape,
        compiler_params=None if interpret else compiler_params(resident),
        interpret=interpret,
        name="ich_kmeans_assign",
    )(*args)
    assign = worker_reduce(unpack_acc(outs[0], n), "store")
    if emit:
        return assign, unpack_acc(outs[1], n_steps)
    return assign
