"""iCh-scheduled segmented SpMV — the paper's technique at the kernel level.

TPU adaptation (DESIGN.md §2): a TPU grid is static, so iCh's *runtime*
chunk adaptation becomes *schedule construction*. The host packs CSR rows
into fixed-shape work tiles (R rows x W nnz slots), and rows whose nnz
exceeds W are SPLIT across several tiles — the work-stealing analogue: no
tile (chunk) can be overloaded, heavy rows' overflow migrates to later tiles
exactly like stolen iterations. The paper's band classification over the
row-nnz distribution (`ich_tile_width`) bounds W from above; the registry
op (`sched.build("spmv", ...)`) takes the cheapest power of two under it
(`core.tiling.gather_width`), since the gather below walks every packed
slot, padding included. `pack_tiles` packs at the band unless given W.

Two grids run the same segmented reduction (`core/segmented.py`):

* `ich_spmv` — the sequential reference grid: grid = (T,), one tile per
  step, folded into the single output accumulator (grid steps execute in
  order on one TPU core, so the read-modify-write is safe).
* `ich_spmv_sharded` — the production 2D grid (DESIGN.md §2.6): the
  schedule's parallelism p is lowered onto the accelerator as a
  worker-major grid (p, S_B). Tiles are cost-partitioned across p workers
  at superstep-block granularity (`core.tiling.partition_tiles`,
  item-closed so no row spans workers) and each grid step processes a
  SUPERSTEP of B tiles, fetched as one block straight out of the FLAT
  payload via a prefetched data-dependent block index
  (`WorkerShards.kernel_block_ids`) and DOUBLE-BUFFERED
  (`core/pipelining.py`): step j+1's block streams into the spare VMEM
  slot while step j computes. Every worker accumulates into its own
  lane-dense block of a (p, rows, 128) output (no cross-worker races; the
  worker dimension is declared "parallel"), and a pairwise tree reduce
  (`core.segmented.worker_reduce`) folds the accumulators — bit-identical
  to the sequential grid because each row is owned by exactly one worker
  and all others contribute exact zeros.

The TPU compiler has no gather of a vector by an index array, so the
products vals * x[cols] are formed in XLA before the kernel and streamed
as the payload; the kernel sums each slot's W products and folds the
partial sums into the output rows. The whole output accumulator stays in
VMEM (`core.segmented.compiler_params` sizes the limit): about 14M rows
fit a v5e core.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.core.segmented import segmented_reduce, segmented_reduce_sharded
from repro.core.tiling import build_schedule, ich_tile_width, pack_csr
from repro.sched.defaults import ICH_EPS

__all__ = ["ich_tile_width", "pack_tiles", "ich_spmv", "ich_spmv_sharded"]


def pack_tiles(indptr: np.ndarray, indices: np.ndarray, data: np.ndarray,
               *, rows_per_tile: int = 8, width: int = None,
               eps: float = ICH_EPS):
    """CSR -> (values (T,R,W), cols (T,R,W), rowid (T,R)) with row splitting.

    Thin wrapper over the shared schedule-construction layer
    (`core.tiling`): rows are cut into width-W segments; segments are packed
    greedily into tiles of R row-slots each (a segment of a heavy row may
    land in any tile => tile work is uniform at R*W slots).
    """
    row_nnz = np.diff(indptr)
    sched = build_schedule(row_nnz, rows_per_tile=rows_per_tile,
                           width=width, eps=eps)
    vals, cols = pack_csr(indptr, indices, data, sched)
    return vals, cols, sched.item_id, sched.width


def ich_spmv(vals, cols, rowid, x, n_rows: int, *, interpret: bool = False):
    """Sequential reference grid. vals/cols (T,R,W); rowid (T,R); x (n,).
    Returns y (n_rows,)."""
    return segmented_reduce(vals * x[cols], rowid, n_rows, combine="add",
                            interpret=interpret)


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
