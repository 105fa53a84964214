"""iCh-scheduled segmented SpMV — the paper's technique at the kernel level.

TPU adaptation (DESIGN.md §2): a TPU grid is static, so iCh's *runtime*
chunk adaptation becomes *schedule construction*. The host packs CSR rows
into fixed-shape work tiles (R rows x W nnz slots), and rows whose nnz
exceeds W are SPLIT across several tiles — the work-stealing analogue: no
tile (chunk) can be overloaded, heavy rows' overflow migrates to later tiles
exactly like stolen iterations. The paper's band classification over the
row-nnz distribution (`core.tiling.ich_tile_width`) bounds W from above;
the registry op (`sched.build("spmv", ...)`) takes the cheapest power of
two under it (`core.tiling.gather_width`), since the gather below walks
every packed slot, padding included.

`ich_spmv_sharded` is the one grid (DESIGN.md §2.6), checked against
`ref.spmv_ref`: the schedule's parallelism p is lowered onto the
accelerator as a worker-major grid (p, S_B). Tiles are cost-partitioned
across p workers at superstep-block granularity
(`core.tiling.partition_tiles`, item-closed so no row spans workers) and
each grid step processes a SUPERSTEP of B tiles, fetched as one block
straight out of the FLAT payload via a prefetched data-dependent block
index (`WorkerShards.kernel_block_ids`) and DOUBLE-BUFFERED
(`core/pipelining.py`): step j+1's block streams into the spare VMEM slot
while step j computes. Every worker accumulates into its own lane-dense
block of a (p, rows, 128) output (no cross-worker races; the worker
dimension is declared "parallel"), and a pairwise tree reduce
(`core.segmented.worker_reduce`) folds the accumulators — exact because
each row is owned by exactly one worker and all others contribute exact
zeros, so the bits do not depend on p or B (`core/segmented.py`, fold
order).

The TPU compiler has no gather of a vector by an index array, so the
products vals * x[cols] are formed in XLA before the kernel and streamed
as the payload; the kernel sums each slot's W products and folds the
partial sums into the output rows. The whole output accumulator stays in
VMEM (`core.segmented.compiler_params` sizes the limit): about 14M rows
fit a v5e core.
"""
from __future__ import annotations

import jax

from repro.core.segmented import segmented_reduce_sharded

__all__ = ["ich_spmv_sharded"]


def ich_spmv_sharded(vals, cols, rowid, blkid, x, n_rows: int, p: int,
                     superstep: int, *, slot_cost=None,
                     interpret: bool = False):
    """Worker-sharded 2D grid. vals/cols (T_pad, R, W): the FLAT packed
    payload with T padded to whole supersteps (`pack_csr(...,
    pad_tiles_to=B)`); rowid (p*S, R) and blkid (p*S_B,) from
    `core.tiling.WorkerShards` (`shard_item_id` / `kernel_block_ids`);
    x (n,). Returns y (n_rows,).

    With `slot_cost` — the (T_pad, R) per-slot scheduled-cost stream
    (`Schedule.slot_cost` padded to T_pad) — the kernel additionally emits
    a per-worker, per-superstep cost output (p, S_B) and returns
    (y, costs): the measured-cost feedback the refiner folds back into
    per-item estimates (DESIGN.md §2.7). Padding steps emit 0, so per-
    worker sums account exactly the schedule's tile costs."""
    with jax.named_scope("ich.gather"):
        xs = x[cols]
    with jax.named_scope("ich.payload"):
        payload = vals * xs
    return segmented_reduce_sharded(payload, rowid, blkid, n_rows, p,
                                    superstep, combine="add",
                                    slot_cost=slot_cost, interpret=interpret)
