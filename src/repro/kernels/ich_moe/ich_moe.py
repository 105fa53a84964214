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

`ich_moe_sharded` runs it in three steps, each under its named scope:

* `ich.gather` — XLA gathers each segment's tokens, x[cols], into a
  slot-major (T_pad*R, W, D) stream;
* `ich.kernel` — the Pallas kernel `expert_ffn`, grid (p, S_B*B*R, F/tf):
  worker w's i-th segment in the shard layout (`WorkerShards`), its
  expert's weights tiled over F. The expert id is prefetched to SMEM and
  picks the weight blocks through the BlockSpec index maps, so Mosaic
  streams exactly one expert's (D, tf) / (tf, D) tiles into VMEM per grid
  step, double-buffered; the segment's (W, D) float32 output block stays
  in VMEM across the F tiles and is written once, in slot-major order.
  Products run on the MXU in the weights' dtype (bf16 at the published
  widths) with float32 accumulation;
* `ich.fold` — XLA combines: each token's weighted sum over its slots
  (at most its K local entries), in float32.

Padding grid steps (a worker's steps past its last real segment, and
slot rows with no item) compute nothing: their index maps repeat the
previous step's x and weight blocks (no DMA) and point the output at a
trash segment past the real ones, which the combine never reads.

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
    * `dst` — the flat segment it writes, or n_seg (the trash segment) on
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


def _ffn_kernel(src_ref, dst_ref, exp_ref, x_ref, wi_ref, wg_ref, wo_ref,
                out_ref, *, n_per: int, trash: int):
    w, i, f = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    live = dst_ref[w * n_per + i] != trash

    @pl.when(live & (f == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(live)
    def _ffn():
        x = x_ref[0]                                        # (W, D)
        h = jnp.dot(x, wi_ref[0], preferred_element_type=jnp.float32)
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        a = (g / (1.0 + jnp.exp(-g)) * h).astype(wo_ref.dtype)  # SiLU gate
        out_ref[0] += jnp.dot(a, wo_ref[0],
                              preferred_element_type=jnp.float32)


def expert_ffn(xs, wi, wg, wo, src, dst, expert, *, p: int,
               interpret: bool = False):
    """The grouped expert FFN over slot-major token blocks.

    xs (n_seg, W, D): each flat segment's gathered tokens; wi/wg (E, D, F)
    up and gate weights, wo (E, F, D) down weights; src/dst/expert the
    (p*n_per,) streams of `grid_streams`. Returns (n_seg + 1, W, D)
    float32: row s is segment s's W token outputs, SiLU(x wg) * (x wi)
    times wo; rows no step writes (padding segments and the trash row
    n_seg) are left undefined."""
    n_seg, W, D = xs.shape
    E, _, F = wi.shape
    tf = _f_tile(F)
    n_f = F // tf
    n_per = int(src.shape[0]) // int(p)
    trash = n_seg

    def f_of(k, f, dst):  # a padding step keeps the last F tile
        return jnp.where(dst[k] != trash, f, n_f - 1)

    def x_map(w, i, f, src, dst, exp):
        return src[w * n_per + i], 0, 0

    def up_map(w, i, f, src, dst, exp):  # up and gate: (D, tf) tiles
        k = w * n_per + i
        return exp[k], 0, f_of(k, f, dst)

    def down_map(w, i, f, src, dst, exp):  # down: (tf, D) tiles
        k = w * n_per + i
        return exp[k], f_of(k, f, dst), 0

    def out_map(w, i, f, src, dst, exp):
        return dst[w * n_per + i], 0, 0

    in_specs = [pl.BlockSpec((1, W, D), x_map),
                pl.BlockSpec((1, D, tf), up_map),
                pl.BlockSpec((1, D, tf), up_map),
                pl.BlockSpec((1, tf, D), down_map)]
    out_spec = pl.BlockSpec((1, W, D), out_map)
    blocks = (W * D * xs.dtype.itemsize + 3 * D * tf * wi.dtype.itemsize
              + W * D * 4)
    temps = 3 * W * tf * 4 + W * D * 4
    call = pl.pallas_call(
        functools.partial(_ffn_kernel, n_per=n_per, trash=trash),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # src, dst, expert per grid step
            grid=(int(p), n_per, n_f),
            in_specs=in_specs,
            out_specs=out_spec,
        ),
        out_shape=jax.ShapeDtypeStruct((n_seg + 1, W, D), jnp.float32),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=int(2 * blocks + temps + VMEM_HEADROOM)),
        interpret=interpret,
        name="ich_moe",
    )
    return call(src, dst, expert, xs, wi, wg, wo)


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
        out = expert_ffn(xs, wi, wg, wo, src, dst, expert, p=p,
                         interpret=interpret)
    with jax.named_scope("ich.fold"):
        # padding slots carry weight 0, and only they: their outputs (some
        # never written) are dropped, not multiplied
        v = vals.reshape(-1)
        contrib = jnp.where((v != 0)[:, None],
                            out[:T_pad * R].reshape(-1, D) * v[:, None], 0.0)
        y = jnp.zeros((n_tokens, D), jnp.float32).at[
            cols.reshape(-1)].add(contrib)
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
