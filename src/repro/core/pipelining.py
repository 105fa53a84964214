"""Double-buffered fetch of data-dependent payload blocks (DESIGN.md §2.6).

The worker-sharded iCh kernels read their payload supersteps through a
DATA-DEPENDENT block index (`WorkerShards.kernel_block_ids`): worker w's
j-th grid step needs tiles `[blk*B, blk*B + B)` of the flat packed payload,
where `blk = blkid[w*S_B + j]` is only known from the prefetched schedule.
Mosaic auto-pipelines AFFINE block streams (it can see step s+1's index
while s computes), but an index read out of SMEM defeats that analysis, so
the naive lowering serializes fetch -> compute every step.

This module restores the overlap by hand: each payload stream gets a
two-slot VMEM scratch buffer and a matching two-slot DMA semaphore, and
every grid step

1. (j == 0 only) kicks off the DMA for its OWN first block into slot 0;
2. kicks off the DMA for step j+1's block — readable from the prefetched
   `blkid` stream — into slot (j+1) % 2;
3. waits on slot j % 2 and computes from it.

Step j's compute therefore always overlaps step j+1's fetch, exactly the
schedule Mosaic builds for affine streams. Slot parity guarantees safety:
the slot being written holds step j-1's block, which was fully consumed
before step j began (grid steps on a core run in order). Bit-identity to
the single-buffered kernels is structural — the same block bytes reach the
same jnp compute in the same order; only the copy timing changes.

The K-Means kernel is NOT rewritten onto this path: its block streams
(points, assignment windows) are affine in the grid step, so Mosaic's
automatic pipeliner already double-buffers them.
"""
from __future__ import annotations

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["double_buffer_scratch", "fetch_double_buffered"]


def double_buffer_scratch(B: int, streams) -> list:
    """`scratch_shapes` entries for `fetch_double_buffered`.

    `streams` is a list of `(block_shape, dtype)` pairs, one per payload
    input, where `block_shape` is the per-tile shape — e.g. ``(R, W)`` for
    a (T_pad, R, W) payload. Returns the 2-slot ``(2, B, *block_shape)``
    VMEM buffers for all streams followed by their 2-slot DMA semaphores;
    the kernel receives them as scratch refs in that order.
    """
    bufs = [pltpu.VMEM((2, int(B)) + tuple(shape), dtype)
            for shape, dtype in streams]
    sems = [pltpu.SemaphoreType.DMA((2,)) for _ in streams]
    return bufs + sems


def _block_copy(hbm_ref, buf_ref, sem_ref, slot, blk, B: int):
    return pltpu.make_async_copy(hbm_ref.at[pl.ds(blk * B, B)],
                                 buf_ref.at[slot], sem_ref.at[slot])


def fetch_double_buffered(streams, blkid_ref, w, j, *, B: int) -> list:
    """Return grid step (w, j)'s payload blocks, next step's DMA in flight.

    `streams` is a list of `(hbm_ref, buf_ref, sem_ref)` triples: the
    whole payload left in `pl.ANY` memory space, its ``(2, B, ...)``
    VMEM scratch, and its ``(2,)`` DMA semaphore (`double_buffer_scratch`).
    `blkid_ref` is the prefetched ``(p * S_B,)`` block-id stream; padding
    steps carry a clamped id (block 0) exactly as the single-buffered
    index maps did, and their fetched block is masked out downstream by
    the -1 row ids. Returns one ``(B, ...)`` array per stream.
    """
    n_steps = pl.num_programs(1)
    idx = w * n_steps + j
    blk = blkid_ref[idx]

    @pl.when(j == 0)
    def _warmup():  # this worker's first block has no previous step to
        for hbm, buf, sem in streams:  # have prefetched it
            _block_copy(hbm, buf, sem, 0, blk, B).start()

    @pl.when(j + 1 < n_steps)
    def _prefetch():
        nxt = blkid_ref[idx + 1]
        for hbm, buf, sem in streams:
            _block_copy(hbm, buf, sem, (j + 1) % 2, nxt, B).start()

    cur = j % 2
    out = []
    for hbm, buf, sem in streams:
        _block_copy(hbm, buf, sem, cur, blk, B).wait()
        out.append(buf[cur])
    return out
