"""iCh-scheduled MoE expert dispatch — the model running on the scheduler.

The dispatch plan (`repro.sched.moe.plan_dispatch`) resolves token->expert
routing on the host; its kept entries form an expert-major CSR (expert =
item, token ids = column indices, combine weights = values) that packs
into the SAME fixed-shape (T, R, W) work tiles every other iCh kernel
uses (`core.tiling.pack_csr`): row splitting spreads a hot expert's
tokens across tiles exactly like a heavy SpMV row, so no tile — and
after cost partitioning no WORKER — is overloaded by router skew.

`ich_moe_sharded` is the worker-sharded 2D realization (DESIGN.md §2.6
applied to §2.8): grid (p, S_B), each grid step fetches one superstep of
B tiles straight out of the flat payload via the prefetched block-index
stream — double-buffered through 2-slot VMEM scratch so step j+1's
blocks stream in while step j computes (core/pipelining.py) — applies
the gated expert FFN to every (expert-slot, token-slot)
pair of the block, and scatters the weighted outputs into this worker's
private (1, n_tokens, D) accumulator with a one-hot matmul (tokens are
NOT item-closed across workers — a token's K experts may live on
different shards — so the scatter cannot reuse the windowed segmented
epilogue, which is keyed on item ids; the EXPERT-space reductions below
do reuse it). `core.segmented.worker_reduce` folds the p accumulators on
the host; the fold tree is deterministic, so outputs are reproducible
run-to-run even though tokens shared across workers make the sum order
differ from a sequential evaluation (same allclose tolerance class as
any matmul reassociation).

With `slot_cost`, the kernel emits the measured-cost feedback twice over:

* (p, S_B) per-worker per-superstep totals — `emit_step_cost`, the
  stream `Schedule.observe(shards=...)` folds into the `CostRefiner`;
* (p, E) per-worker PER-EXPERT totals — `segmented_apply_batch` into an
  (1, E) window per worker (expert ids ARE the schedule's item ids, so
  the windowed epilogue applies). Worker-summed, these equal the
  schedule's per-item costs EXACTLY (integer token counts carried in
  float32), the §2.7 routing proof extended to expert granularity — and
  the measured per-expert load that `refine_cap_scale` turns into the
  next step's capacity scale.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.pipelining import (double_buffer_scratch,
                                   fetch_double_buffered)
from repro.core.segmented import (emit_step_cost, segmented_apply_batch,
                                  worker_reduce)

__all__ = ["ich_moe_sharded"]


def _moe_sharded_body(rowid_ref, blkid_ref, vals_hbm, cols_hbm, slotc_hbm,
                      x_ref, wi_ref, wg_ref, wo_ref, out_ref, cost_ref,
                      ecost_ref, bufs, sems, *, S: int, B: int):
    w, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        if cost_ref is not None:
            cost_ref[...] = jnp.zeros_like(cost_ref)
            ecost_ref[...] = jnp.zeros_like(ecost_ref)

    # double-buffered data-dependent fetch (core/pipelining.py)
    hbm = (vals_hbm, cols_hbm) if slotc_hbm is None \
        else (vals_hbm, cols_hbm, slotc_hbm)
    blocks = fetch_double_buffered(list(zip(hbm, bufs, sems)),
                                   blkid_ref, w, j, B=B)
    vals = blocks[0]  # (B, R, W): one superstep of combine weights
    cols = blocks[1]  # (B, R, W): token ids (0 on padding, vals 0)
    x = x_ref[...]    # (n_tokens, D)
    rows = rowid_ref[pl.ds(w * S + j * B, B)]  # (B, R) expert ids, -1 pad
    e = jnp.maximum(rows, 0)

    # gated FFN on every slot: tokens enter f32 like the in-graph router
    # path; expert weights are gathered per slot row (whole-E residency)
    xs = x[cols].astype(jnp.float32)                   # (B, R, W, D)
    h = jnp.einsum("brwd,brdf->brwf", xs, wi_ref[...][e],
                   preferred_element_type=jnp.float32)
    g = jnp.einsum("brwd,brdf->brwf", xs, wg_ref[...][e],
                   preferred_element_type=jnp.float32)
    yb = jnp.einsum("brwf,brfd->brwd", jax.nn.silu(g) * h, wo_ref[...][e],
                    preferred_element_type=jnp.float32)
    # combine weight per slot; padding slots carry vals == 0 and padding
    # STEPS fetch a clamped block whose vals are real, so mask on rows too
    contrib = yb * vals[..., None] * (rows >= 0)[..., None, None]

    # token scatter: one-hot matmul over the flattened (B*R*W) slot axis
    # into this worker's private accumulator (tokens are not item-closed
    # across workers, so no windowed RMW — the window is in expert space)
    n_tokens = out_ref.shape[1]
    flat_tok = cols.reshape(-1)                        # (B*R*W,)
    flat_c = contrib.reshape(-1, contrib.shape[-1])    # (B*R*W, D)
    lane = jax.lax.broadcasted_iota(jnp.int32, (n_tokens,
                                                flat_tok.shape[0]), 0)
    onehot = (lane == flat_tok[None, :]).astype(jnp.float32)
    out_ref[...] += jnp.dot(onehot, flat_c,
                            preferred_element_type=jnp.float32)[None]

    if cost_ref is not None:
        slotc = blocks[2]  # (B, R) scheduled per-slot costs
        emit_step_cost(cost_ref, rows, slotc, j)
        # per-expert totals: expert ids are the schedule's item ids, so
        # the windowed segmented epilogue applies directly
        masked = jnp.where(rows >= 0, slotc, 0.0)
        segmented_apply_batch(ecost_ref, rows, masked, combine="add")


def _moe_kernel_sharded(rowid_ref, blkid_ref, vals_hbm, cols_hbm, x_ref,
                        wi_ref, wg_ref, wo_ref, out_ref, vbuf, cbuf, vsem,
                        csem, *, S: int, B: int):
    _moe_sharded_body(rowid_ref, blkid_ref, vals_hbm, cols_hbm, None,
                      x_ref, wi_ref, wg_ref, wo_ref, out_ref, None, None,
                      (vbuf, cbuf), (vsem, csem), S=S, B=B)


def _moe_kernel_sharded_cost(rowid_ref, blkid_ref, vals_hbm, cols_hbm,
                             slotc_hbm, x_ref, wi_ref, wg_ref, wo_ref,
                             out_ref, cost_ref, ecost_ref, vbuf, cbuf,
                             sbuf, vsem, csem, ssem, *, S: int, B: int):
    _moe_sharded_body(rowid_ref, blkid_ref, vals_hbm, cols_hbm, slotc_hbm,
                      x_ref, wi_ref, wg_ref, wo_ref, out_ref, cost_ref,
                      ecost_ref, (vbuf, cbuf, sbuf), (vsem, csem, ssem),
                      S=S, B=B)


def ich_moe_sharded(vals, cols, rowid, blkid, x, wi, wg, wo, p: int,
                    superstep: int, *, slot_cost=None,
                    interpret: bool = False):
    """Worker-sharded MoE expert application over a packed dispatch plan.

    vals/cols (T_pad, R, W): flat packed combine weights + token ids
    (`pack_csr` over the plan's expert-major CSR, padded to whole
    supersteps); rowid (p*S, R) per-slot expert ids and blkid (p*S_B,)
    from `WorkerShards`; x (n_tokens, D) token activations; wi/wg
    (E, D, F) and wo (E, F, D) expert FFN weights. Returns y (n_tokens, D)
    in float32.

    With `slot_cost` ((T_pad, R), the schedule's per-slot cost stream)
    returns (y, step_costs (p, S_B), expert_costs (p, E)); summed over
    workers the expert costs equal the schedule's per-expert totals
    exactly (integer token counts in float32)."""
    T_pad, R, W = vals.shape
    n_tokens, D = x.shape
    E = wi.shape[0]
    p, B = int(p), int(superstep)
    n_steps = int(blkid.shape[0]) // p
    S = n_steps * B
    if blkid.shape[0] != p * n_steps or rowid.shape[0] != p * S or T_pad % B:
        raise ValueError(f"shard layout mismatch: blkid {blkid.shape}, "
                         f"rowid {rowid.shape}, T_pad={T_pad}, p={p}, B={B}")
    emit = slot_cost is not None
    # payloads stay whole in ANY memory; the kernel double-buffers the
    # data-dependent superstep blocks through 2-slot VMEM scratch
    # (core/pipelining.py)
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),  # vals (T_pad, R, W)
        pl.BlockSpec(memory_space=pl.ANY),  # cols (T_pad, R, W)
    ]
    db_streams = [((R, W), vals.dtype), ((R, W), jnp.int32)]
    out_specs = pl.BlockSpec((1, n_tokens, D),
                             lambda w, j, rowid, blk: (w, 0, 0))
    out_shape = jax.ShapeDtypeStruct((p, n_tokens, D), jnp.float32)
    if emit:
        kernel = functools.partial(_moe_kernel_sharded_cost, S=S, B=B)
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))  # slot costs
        db_streams.append(((R,), jnp.float32))
        out_specs = [out_specs,
                     pl.BlockSpec((1, n_steps),
                                  lambda w, j, rowid, blk: (w, 0)),
                     pl.BlockSpec((1, E), lambda w, j, rowid, blk: (w, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((p, n_steps), jnp.float32),
                     jax.ShapeDtypeStruct((p, E), jnp.float32)]
    else:
        kernel = functools.partial(_moe_kernel_sharded, S=S, B=B)
    # token activations + the full expert weight stacks stay whole in VMEM
    in_specs.append(pl.BlockSpec(x.shape, lambda w, j, rowid, blk: (0, 0)))
    in_specs.append(pl.BlockSpec(wi.shape,
                                 lambda w, j, rowid, blk: (0, 0, 0)))
    in_specs.append(pl.BlockSpec(wg.shape,
                                 lambda w, j, rowid, blk: (0, 0, 0)))
    in_specs.append(pl.BlockSpec(wo.shape,
                                 lambda w, j, rowid, blk: (0, 0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # sharded expert ids + block ids to SMEM
        grid=(p, n_steps),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=double_buffer_scratch(B, db_streams),
    )
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        # workers accumulate into private rows; the shard dimension may
        # run concurrently across TPU cores / megacore
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ich_moe",
    )
    if emit:
        acc, costs, ecosts = call(rowid, blkid, vals, cols,
                                  jnp.asarray(slot_cost, jnp.float32),
                                  x, wi, wg, wo)
        return worker_reduce(acc, "add"), costs, ecosts
    acc = call(rowid, blkid, vals, cols, x, wi, wg, wo)
    return worker_reduce(acc, "add")
