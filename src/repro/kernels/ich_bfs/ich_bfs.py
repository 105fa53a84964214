"""iCh-scheduled BFS frontier expansion — the paper's BF application on TPU.

Pull-direction (bottom-up) level step over a CSR graph whose row u lists u's
in-neighbors: vertex u joins the next frontier iff some in-neighbor is on the
current frontier and u is unvisited. Per-vertex cost = degree, the paper's
BFS workload (§5.1): most vertices are trivial, frontier-adjacent ones heavy.

The schedule is constructed once per graph by `core.tiling` (DESIGN.md §2):
a width W under the band of the degree distribution (`gather_width`: the
frontier gather below walks every packed slot, so the registry op takes the
cheapest power of two up to the band's width), heavy adjacency lists split
across W-wide segments, segments greedily packed into (T, R) slots.
`mask` is the all-ones CSR payload from `pack_csr` — 1.0 on real edge slots,
0.0 on padding — so a padded slot can never observe frontier[cols==0].

`ich_bfs_step_sharded` is the one grid (DESIGN.md §2.6; see ich_spmv for
the pattern), checked against `ref.bfs_step_ref`: tiles are
cost-partitioned across p workers at superstep-block granularity
(item-closed — no vertex spans workers), each grid step fetches a
superstep of B tiles as one block straight from the FLAT payload via a
prefetched data-dependent block index, DOUBLE-BUFFERED
(core/pipelining.py), takes the max of each slot's frontier indicators
over W, and max-folds them into the worker's own lane-dense accumulator
(split rows OR together across tiles). A pairwise tree max
(`core.segmented.worker_reduce`) folds the accumulators — exact: each
vertex is owned by one worker and all others contribute exact zeros (the
max identity for the 0/1 frontier indicators).

The gathers the TPU compiler cannot lower run in XLA around the kernel:
mask * frontier[cols] is formed before it and streamed as the payload,
and the visited mask is applied to the folded hits after it (exact: the
indicators are 0/1 and a vertex's mask is the same for all its slots).
"""
from __future__ import annotations

import jax

from repro.core.segmented import segmented_reduce_sharded


def ich_bfs_step_sharded(mask, cols, rowid, blkid, frontier, visited,
                         n_vertices: int, p: int, superstep: int,
                         *, slot_cost=None, interpret: bool = False):
    """One frontier expansion on the worker-sharded 2D grid. mask/cols
    (T_pad, R, W): the FLAT packed payload with T padded to whole
    supersteps; rowid (p*S, R) and blkid (p*S_B,) from
    `core.tiling.WorkerShards`; frontier/visited (n,) float32 indicators.
    Returns the next frontier (n,).

    With `slot_cost` ((T_pad, R) per-slot scheduled costs) the kernel
    additionally emits the per-worker, per-superstep cost output and
    returns (next_frontier, costs) — the measured-cost feedback stream
    (DESIGN.md §2.7)."""
    with jax.named_scope("ich.gather"):
        fs = frontier[cols]
    with jax.named_scope("ich.payload"):
        payload = mask * fs
    out = segmented_reduce_sharded(payload, rowid, blkid, n_vertices, p,
                                   superstep, combine="max",
                                   slot_cost=slot_cost, interpret=interpret)
    with jax.named_scope("ich.fold"):
        if slot_cost is None:
            return out * (1.0 - visited)
        return out[0] * (1.0 - visited), out[1]
