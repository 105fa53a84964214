"""iCh-scheduled MoE expert dispatch — the model running on the scheduler.

The dispatch plan (`repro.sched.moe.plan_dispatch`) resolves token->expert
routing on the host; its kept entries form an expert-major CSR (expert =
item, token ids = column indices, combine weights = values) that packs
into the SAME fixed-shape (T, R, W) work tiles every other iCh kernel
uses (`core.tiling.pack_csr`): row splitting spreads a hot expert's
tokens across tiles exactly like a heavy SpMV row. Every tile row (slot
row, "segment") holds up to W tokens of ONE expert, so the expert FFN of
a segment is a dense (W, D) x (D, F) product: a grouped matmul over the
iCh tiles (DESIGN.md §2.8).

`ich_moe_sharded` runs it in two steps, each under its named scope:

* `ich.gather` — XLA gathers each segment's tokens, x[cols], into a
  slot-major (T_pad*R, W, D) stream;
* `ich.kernel` — the Pallas kernel `expert_ffn`, grid (p, S_B*B*R, F/tf):
  worker w's i-th segment in the shard layout (`WorkerShards`), its
  expert's weights tiled over F. The expert id is prefetched to SMEM and
  picks the weight blocks through the BlockSpec index maps, so Mosaic
  streams exactly one expert's (D, tf) / (tf, D) tiles into VMEM per grid
  step, double-buffered; the segment's (W, D) float32 products
  accumulate in VMEM across the F tiles. Products run on the MXU in the
  weights' dtype (bf16 at the published widths) with float32
  accumulation. The combine is the epilogue of a segment's last F tile:
  the products are weighed row by row by the combine weights and each
  live row is added into y (n_tokens, D) float32, which XLA zeroes and
  the kernel updates in place in HBM, by a DMA read-modify-write of its
  token's row.

The combine's DMAs, in order:

* y is seen as (8, 128) tiles, (n_tokens / 8, D / 128, 8, 128), the
  bytes of the row-major (n_tokens, D) array: token t's row is sublane
  t % 8 of each of its tiles, which one DMA moves (a (1, D) slice of the
  (n_tokens, D) view is refused: it cuts a tile);
* live rows lead a segment and end at its last nonzero weight; the
  padding slots after them (weight 0, token id 0) are never read or
  written, else a padding row and a real entry of token 0 would race;
* a token may hold two slots of one segment (a capacity plan's steal):
  its later rows fold into its first in VMEM, which alone is copied;
* at a segment's first F tile, after its products, the kernel waits for
  the previous segment's writes, then starts this segment's reads, which
  the remaining F tiles' MXU work hides; at its last F tile it waits for
  the reads, adds, and starts the writes, which the next segment's first
  F tile waits for, and the grid's last step drains. A token recurs
  across segments, so no segment reads y before the previous one's
  writes have landed;
* the worker axis is "arbitrary": two TensorCores must never update y at
  once (a v5e has one, so its workers ran in turn already).

Padding grid steps (a worker's steps past its last real segment, and
slot rows with no item) compute and copy nothing: their index maps
repeat the previous step's x and weight blocks (no DMA).

The op also returns the (p, S_B) per-worker, per-superstep cost stream
that `Schedule.observe(shards=...)` folds, and the (p, E) per-worker,
per-expert totals whose worker sum is the plan's kept token counts
exactly (integer counts in float32): every slot row is one expert, so
both are sums of the schedule's slot costs, taken in XLA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grid_streams", "expert_ffn", "ich_moe_sharded"]

# Expert-width tile: the F axis of the up/gate weights and the rows of the
# down weights are streamed in (D, F_TILE) / (F_TILE, D) blocks.
F_TILE = 512
# Scoped VMEM beyond the double-buffered blocks and the kernel's
# intermediates, for the compiler's own scratch.
VMEM_HEADROOM = 16 << 20
SUBLANES, LANES = 8, 128
# the kernel's SMEM counters: rows being read, rows being written
READS, WRITES = 0, 1


def _f_tile(F: int) -> int:
    return F_TILE if F % F_TILE == 0 else F


def grid_streams(rowid: np.ndarray, blkid: np.ndarray, p: int,
                 superstep: int, n_seg: int) -> tuple[np.ndarray, np.ndarray,
                                                      np.ndarray]:
    """The kernel's three scalar-prefetch streams, one entry per grid step
    (w, i) of worker w's i-th segment, from the shard layout's item ids
    rowid (p*S, R) and block ids blkid (p*S_B,) over a flat pack of
    `n_seg` segments (T_pad * R):

    * `src` — the flat segment (tile*R + row) whose tokens the step reads;
    * `dst` — the flat segment it computes and combines, or n_seg on
      a padding step;
    * `expert` — the expert whose weights it streams.

    On a padding step `src` and `expert` repeat the worker's previous real
    step (0 before its first), so its blocks are not fetched again."""
    p, B = int(p), int(superstep)
    R = int(rowid.shape[1])
    n_per = rowid.shape[0] // p * R          # segments per worker, S*R
    item = np.asarray(rowid, np.int32).reshape(p, n_per)
    i = np.arange(n_per, dtype=np.int64)
    seg = (np.asarray(blkid, np.int64).reshape(p, -1)[:, i // (B * R)]
           * (B * R) + i % (B * R))
    live = item >= 0
    # index of each step's latest real step in its worker (-1: none yet)
    last = np.maximum.accumulate(np.where(live, i, -1), axis=1)
    rows = np.arange(p)[:, None]
    src = np.where(last >= 0, seg[rows, np.maximum(last, 0)], 0)
    expert = np.where(last >= 0, item[rows, np.maximum(last, 0)], 0)
    dst = np.where(live, seg, int(n_seg))
    return (src.reshape(-1).astype(np.int32),
            dst.reshape(-1).astype(np.int32),
            expert.reshape(-1).astype(np.int32))


def _ffn_kernel(src_ref, dst_ref, exp_ref, live_ref, to_ref, v_ref, x_ref,
                wi_ref, wg_ref, wo_ref, y_in, y_ref, acc_ref, rows_ref, sems,
                count, *, n_per: int, n_f: int, n_seg: int):
    del y_in  # the same buffer as y_ref
    w, i, f = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    seg = dst_ref[w * n_per + i]
    n = live_ref[jnp.minimum(seg, n_seg - 1)]
    reads, writes = sems.at[0], sems.at[1]

    def row_copy(r, t, sem, to_y):  # rows_ref row r <-> y row t
        # a row is one sublane of each of its (8, L) tiles
        row = rows_ref.at[r // SUBLANES, :, pl.ds(r % SUBLANES, 1)]
        y_row = y_ref.at[t // SUBLANES, :, pl.ds(t % SUBLANES, 1)]
        return pltpu.make_async_copy(*((row, y_row) if to_y else
                                       (y_row, row)), sem)

    def copy_rows(sem, to_y):
        """Start the copy of each live row that is its token's first in
        the segment; returns how many started."""
        def body(r, started):
            t = to_ref[0, 0, r]

            @pl.when(t >= 0)
            def _():
                row_copy(r, t, sem, to_y).start()
            return started + (t >= 0).astype(jnp.int32)
        return jax.lax.fori_loop(0, n, body, 0)

    def wait_rows(k, sem, to_y):  # each copy is one row: wait for k
        def body(r, c):
            row_copy(0, 0, sem, to_y).wait()
            return c
        jax.lax.fori_loop(0, k, body, 0)

    @pl.when((w == 0) & (i == 0) & (f == 0))
    def _start():
        count[WRITES] = 0

    @pl.when(seg != n_seg)  # not a padding step
    def _ffn():
        x = x_ref[0]                                        # (W, D)
        h = jnp.dot(x, wi_ref[0], preferred_element_type=jnp.float32)
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        a = (g / (1.0 + jnp.exp(-g)) * h).astype(wo_ref.dtype)  # SiLU gate
        part = jnp.dot(a, wo_ref[0], preferred_element_type=jnp.float32)

        @pl.when(f == 0)
        def _fetch():
            if n_f > 1:
                acc_ref[...] = part
            # the previous segment's rows land in y before this one's are
            # read: a token recurs across segments
            wait_rows(count[WRITES], writes, True)
            count[READS] = copy_rows(reads, False)

        @pl.when((f > 0) & (f < n_f - 1))
        def _accumulate():
            acc_ref[...] += part

        @pl.when(f == n_f - 1)
        def _combine():
            # weigh each row: the (1, W) weights turned to a (W, 1) column
            v = jnp.transpose(jnp.broadcast_to(v_ref[0],
                                               (LANES, v_ref.shape[-1])))
            acc_ref[...] = (part if n_f == 1 else acc_ref[...] + part) \
                * v[:, :1]

            # a token's later rows in the segment fold into its first
            def fold(r, c):
                t = to_ref[0, 0, r]

                @pl.when(t < 0)
                def _():
                    first = -1 - t
                    acc_ref[pl.ds(first, 1), :] += acc_ref[pl.ds(r, 1), :]
                return c
            jax.lax.fori_loop(0, n, fold, 0)
            wait_rows(count[READS], reads, False)
            n_tiles, n_chunks, _, L = rows_ref.shape
            for c in range(n_chunks):
                rows_ref[:, c] += acc_ref[:, c * L:(c + 1) * L].reshape(
                    n_tiles, SUBLANES, L)
            count[WRITES] = copy_rows(writes, True)

    @pl.when((w == pl.num_programs(0) - 1) & (i == n_per - 1) & (f == n_f - 1))
    def _drain():
        wait_rows(count[WRITES], writes, True)


def expert_ffn(xs, cols, vals, wi, wg, wo, src, dst, expert, *,
               n_tokens: int, p: int, interpret: bool = False):
    """The grouped expert FFN over slot-major token blocks, combined.

    xs (n_seg, W, D): each flat segment's gathered tokens; cols and vals
    (n_seg, W): their token ids and combine weights (padding slots 0, at
    the end of a segment); wi/wg (E, D, F) up and gate weights, wo
    (E, F, D) down weights; src/dst/expert the (p*n_per,) streams of
    `grid_streams`. Returns y (n_tokens, D) float32: y[t] is the sum over
    t's slots of the slot's weight times its expert's FFN of x[t],
    SiLU(x wg) * (x wi) times wo; tokens with no slot read 0."""
    n_seg, W, D = xs.shape
    E, _, F = wi.shape
    tf = _f_tile(F)
    n_f = F // tf
    n_per = int(src.shape[0]) // int(p)
    # live rows lead each segment: they end at its last nonzero weight
    lane = jnp.arange(W, dtype=jnp.int32)
    live = jnp.max(jnp.where(vals != 0, lane + 1, 0), axis=1)
    # where each row goes: its token, or -1 - r for a token's later row
    # that folds into its first row r (a capacity plan's steal can send
    # a token to one expert twice)
    first = jnp.argmax((cols[:, :, None] == cols[:, None, :])
                       & (lane[None, None, :] <= lane[None, :, None]), axis=2)
    to = jnp.where(first == lane, cols, -1 - first).astype(jnp.int32)

    def f_of(k, f, dst):  # a padding step keeps the last F tile
        return jnp.where(dst[k] != n_seg, f, n_f - 1)

    def seg_map(w, i, f, src, dst, exp, live):  # the segment's own blocks
        return src[w * n_per + i], 0, 0

    def up_map(w, i, f, src, dst, exp, live):  # up and gate: (D, tf) tiles
        k = w * n_per + i
        return exp[k], 0, f_of(k, f, dst)

    def down_map(w, i, f, src, dst, exp, live):  # down: (tf, D) tiles
        k = w * n_per + i
        return exp[k], f_of(k, f, dst), 0

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, 1, W), seg_map, memory_space=pltpu.SMEM),
                pl.BlockSpec((1, 1, W), seg_map),
                pl.BlockSpec((1, W, D), seg_map),
                pl.BlockSpec((1, D, tf), up_map),
                pl.BlockSpec((1, D, tf), up_map),
                pl.BlockSpec((1, tf, D), down_map),
                hbm]
    blocks = (W * D * xs.dtype.itemsize + 3 * D * tf * wi.dtype.itemsize
              + 8 * W * 4)
    # y and the segment's rows of it as (8, L) tiles, (rows / 8, D / L, 8,
    # L): the bytes of a row-major (rows, D) array, whose row t is
    # sublane t % 8 of its tiles, so that one DMA moves one row
    L = LANES if D % LANES == 0 else D
    tiled = (lambda rows: (-(-rows // SUBLANES), D // L, SUBLANES, L))
    scratch = 2 * W * D * 4
    temps = 3 * W * tf * 4 + 2 * W * D * 4 + LANES * W * 4
    call = pl.pallas_call(
        functools.partial(_ffn_kernel, n_per=n_per, n_f=n_f,
                          n_seg=n_seg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,  # src, dst, expert per grid step; live
            grid=(int(p), n_per, n_f),
            in_specs=in_specs,
            out_specs=hbm,
            scratch_shapes=[pltpu.VMEM((W, D), jnp.float32),   # products
                            pltpu.VMEM(tiled(W), jnp.float32),  # y's rows
                            pltpu.SemaphoreType.DMA((2,)),     # reads, writes
                            pltpu.SMEM((2,), jnp.int32)],      # READS, WRITES
        ),
        out_shape=jax.ShapeDtypeStruct(tiled(n_tokens), jnp.float32),
        # y, the last operand after the 4 scalar streams and 6 blocked
        # inputs, is updated in place
        input_output_aliases={10: 0},
        compiler_params=None if interpret else pltpu.CompilerParams(
            # workers run in turn: two cores must never update y at once
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=int(2 * blocks + scratch + temps
                                 + VMEM_HEADROOM)),
        interpret=interpret,
        name="ich_moe",
    )
    y = call(src, dst, expert, live, to.reshape(n_seg, 1, W),
             vals.reshape(n_seg, 1, W), xs, wi, wg, wo,
             jnp.zeros(tiled(n_tokens), jnp.float32))
    return y.transpose(0, 2, 1, 3).reshape(-1, D)[:n_tokens]  # a bitcast


def ich_moe_sharded(vals, cols, rowid, blkid, src, dst, expert, x, wi, wg,
                    wo, p: int, superstep: int, *, slot_cost=None,
                    interpret: bool = False):
    """MoE expert application over a packed dispatch plan.

    vals/cols (T_pad, R, W): the plan's combine weights and token ids,
    packed flat (`pack_csr`, padding slots 0); rowid (p*S, R) and blkid
    (p*S_B,) from `WorkerShards`; src/dst/expert from `grid_streams`;
    x (n_tokens, D) token activations; wi/wg (E, D, F), wo (E, F, D).
    Returns y (n_tokens, D) float32: y[t] = sum over t's slots of the
    slot's combine weight times its expert's FFN of x[t].

    With `slot_cost` ((T_pad, R), the schedule's per-slot cost stream)
    returns (y, step_costs (p, S_B), expert_costs (p, E))."""
    T_pad, R, W = vals.shape
    n_tokens, D = x.shape
    p, B = int(p), int(superstep)
    S_B = int(blkid.shape[0]) // p
    if blkid.shape[0] != p * S_B or rowid.shape[0] != p * S_B * B \
            or T_pad % B:
        raise ValueError(f"shard layout mismatch: blkid {blkid.shape}, "
                         f"rowid {rowid.shape}, T_pad={T_pad}, p={p}, B={B}")
    with jax.named_scope("ich.gather"):
        xs = x[cols.reshape(T_pad * R, W)]                  # (T_pad*R, W, D)
    with jax.named_scope("ich.kernel"):
        y = expert_ffn(xs, cols.reshape(T_pad * R, W),
                       vals.reshape(T_pad * R, W), wi, wg, wo, src, dst,
                       expert, n_tokens=n_tokens, p=p, interpret=interpret)
    if slot_cost is None:
        return y
    with jax.named_scope("ich.cost"):
        E = wi.shape[0]
        blk = jnp.asarray(slot_cost, jnp.float32).reshape(-1, B * R)[blkid]
        item = rowid.reshape(p * S_B, B * R)
        blk = jnp.where(item >= 0, blk, 0.0)                # (p*S_B, B*R)
        step_costs = blk.sum(axis=1).reshape(p, S_B)
        worker = jnp.repeat(jnp.arange(p), S_B * B * R)
        expert_costs = jnp.zeros((p, E), jnp.float32).at[
            worker, jnp.maximum(item, 0).reshape(-1)].add(blk.reshape(-1))
    return y, step_costs, expert_costs
