"""MoE expert dispatch as a scheduling problem (DESIGN.md §2.8).

The paper's loop-scheduling problem reappears verbatim in MoE routing:
tokens are loop iterations, experts are workers, per-expert *capacity* is
the chunk size, and overflow rerouting is the steal — except that on an
accelerator the steal must happen at SCHEDULE time, not run time. This
module is the host-side half of that mapping:

* `plan_dispatch` mirrors the in-graph sort-based dispatch of
  `models/moe.py` (`dispatch_decisions`) decision-for-decision in numpy —
  stable argsort positions, `pos < cap` capacity cut, one steal round to
  each dropped token's max-slack alternative — and returns a
  `DispatchPlan`. The two paths are BIT-IDENTICAL at equal capacity
  (tests/test_moe_sched.py), which is what lets the model run on the
  scheduler without changing a single routing decision.
* `DispatchPlan.csr()` lays the kept entries out as an expert-major CSR
  (indptr over experts, token ids + combine weights as payload), i.e.
  exactly the shape `LoopScheduler.schedule` consumes through
  `ExpertLoadCosts` and the packed segmented kernels execute
  (`sched/kernels.py:MoeDispatchOp`, `kernels/ich_moe/`).
* `cap_scale_from_costs` / `refine_cap_scale` close the adaptive loop:
  measured per-expert load folds into the schedule's `CostRefiner`
  (`Schedule.observe` / `refine`) and the refined estimates become the
  next step's `cap_scale` — the d_i array of the in-graph balancer
  (`models/moe.py:ich_update_cap_scale`), derived from compounding
  Welford statistics instead of one multiplicative step.

* `route` is the device half in front of it, for expert-parallel layers:
  RMSNorm and a router with a selection bias over every router output,
  top-K. Its scoring is a parameter: a sigmoid with normalised weights
  (DeepSeek-V3's `noaux_tc`, one group) or a softmax whose chosen
  scores are scaled and not normalised (LongCat-Flash). Outputs may
  include zero-compute experts, which pass u on unchanged: their
  weights sum, on the device, to each token's identity weight w0.
  `read_routing` brings the choices to the host, where
  `plan_dispatch(..., experts=(first, count), zero=...)` keeps the
  entries of the experts this chip holds, droplessly, and counts the
  zero-compute choices it leaves out.

Planning is numpy-only and runs on the host between steps, never inside a
traced computation; jax is imported by `route` alone.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

from repro import obs

from .defaults import (MOE_CAP_SCALE_MAX, MOE_CAP_SCALE_MIN,
                       MOE_CAPACITY_FACTOR, MOE_CMAX_FACTOR, MOE_MIN_CAPACITY)

__all__ = ["DispatchPlan", "expert_capacity", "plan_dispatch", "route",
           "read_routing", "cap_scale_from_costs", "refine_cap_scale"]


@functools.lru_cache(maxsize=None)
def _router(top_k: int, eps: float, score: str = "sigmoid",
            renorm: bool = True, scale: float = 1.0, zero=None):
    import jax
    import jax.numpy as jnp

    if score not in ("sigmoid", "softmax"):
        raise ValueError(f"score must be 'sigmoid' or 'softmax', got "
                         f"{score!r}")

    def router(h, w_router, bias):
        hf = h.astype(jnp.float32)
        u = hf * jax.lax.rsqrt(jnp.mean(hf * hf, axis=-1, keepdims=True)
                               + eps)
        u = u.astype(h.dtype)
        logits = jnp.dot(u, w_router.T, preferred_element_type=jnp.float32)
        if score == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        else:
            scores = jax.nn.softmax(logits, axis=-1)
        _, e_topk = jax.lax.top_k(scores + bias, top_k)
        # The chosen scores by one masked sum over the experts per choice:
        # each reads whole rows of scores, where a gather fetches each
        # index on its own. A sum is one score and exact zeros, so w is
        # the gather's bit for bit. The barrier keeps the normaliser a sum
        # of w as picked: without it XLA on a TPU v5e folds the sum over k
        # into the sums over experts and adds the chosen scores in expert
        # order.
        expert = jnp.arange(scores.shape[-1], dtype=e_topk.dtype)
        w = jnp.stack([jnp.sum(jnp.where(e_topk[:, k:k + 1] == expert,
                                         scores, 0.0), axis=-1)
                       for k in range(top_k)], axis=-1)
        w = jax.lax.optimization_barrier(w)
        if renorm:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        if scale != 1.0:
            w = w * scale
        if zero is None:
            return u, e_topk, w
        first, count = zero
        on_zero = (e_topk >= first) & (e_topk < first + count)
        return u, e_topk, w, jnp.sum(jnp.where(on_zero, w, 0.0), axis=-1)

    router.__name__ = "moe_route"
    return jax.jit(router)


def route(h, w_router, bias, *, top_k: int, eps: float = 1e-5,
          score: str = "sigmoid", renorm: bool = True, scale: float = 1.0,
          zero=None):
    """RMSNorm (gain 1) and the router of an MoE layer, jitted.

    h (T, D) token activations; w_router (E, D), the router over ALL E
    router outputs; bias (E,) the selection bias. Scores are float32:
    s = sigmoid(u w_router^T) (`score="sigmoid"`, DeepSeek-V3's
    `noaux_tc`) or its softmax over the E outputs (`score="softmax"`).
    The top `top_k` of s + bias are chosen, and each choice weighs s_e,
    over the chosen s' sum where `renorm`, times `scale` (the
    `routed_scaling_factor`). The chosen s_e are picked by one masked sum
    over the E outputs per choice, not a gather: one score plus exact
    zeros, so the weights are bit for bit those of
    `take_along_axis(s, e_topk)`. Returns (u (T, D) in h's dtype, e_topk
    (T, top_k) int32, weights (T, top_k) float32), all on the device.

    `zero=(first, count)` names the zero-compute (identity) experts
    [first, first + count) among the outputs: a token that picks one
    adds its weight times u to its update, and runs no FFN for it. The
    router then also returns each token's identity weight w0 (T,)
    float32, the sum of its chosen zero experts' weights, on the device,
    for `MoeDispatchOp` to start y at w0 u."""
    zero = None if zero is None else (int(zero[0]), int(zero[1]))
    return _router(int(top_k), float(eps), score, bool(renorm),
                   float(scale), zero)(h, w_router, bias)


def read_routing(e_topk, weights):
    """The router's choices and weights as host arrays (the device-to-host
    read-back in front of `plan_dispatch`). It first waits for the router
    to finish, outside the `moe.readback` span, so that the span times the
    copy alone."""
    for a in (e_topk, weights):
        getattr(a, "block_until_ready", lambda: None)()
    with obs.span("moe.readback"):
        return np.asarray(e_topk), np.asarray(weights)


def expert_capacity(n_tokens: int, n_experts: int, experts_per_token: int,
                    factor: float = MOE_CAPACITY_FACTOR) -> int:
    """Base per-expert capacity for a token pool: ceil(K*T*factor/E),
    floored at MOE_MIN_CAPACITY. The chunk-size analogue."""
    return max(MOE_MIN_CAPACITY,
               int(-(-experts_per_token * n_tokens * factor // n_experts)))


def _dispatch_positions(experts_flat: np.ndarray, n_experts: int):
    """Positions of each (token, choice) entry within its expert segment —
    the numpy mirror of `models/moe.py:_dispatch_positions` (stable
    argsort + searchsorted segment starts, positions scattered back)."""
    order = np.argsort(experts_flat, kind="stable")
    es = experts_flat[order]
    seg_start = np.searchsorted(es, np.arange(n_experts))
    pos_sorted = np.arange(es.shape[0], dtype=np.int64) - seg_start[es]
    pos = np.zeros_like(pos_sorted)
    pos[order] = pos_sorted
    return pos


@dataclasses.dataclass(frozen=True)
class DispatchPlan:
    """A resolved token->expert dispatch: which (token, choice) entries run
    where after the capacity cut and the schedule-time steal round.

    Entry arrays are flat over the (T, K) router choices in token-major
    order (entry t*K + k is token t's k-th choice). `expert`/`pos` are the
    FINAL assignment — a stolen entry points at its steal target, not its
    router choice."""

    n_tokens: int
    n_experts: int
    experts_per_token: int
    expert: np.ndarray      # (T*K,) int32 final expert per entry
    token: np.ndarray       # (T*K,) int32 token id per entry
    weight: np.ndarray      # (T*K,) float32 combine weight per entry
    pos: np.ndarray         # (T*K,) int64 slot within the expert segment
    keep: np.ndarray        # (T*K,) bool — entry survives dispatch
    cap: np.ndarray         # (E,) int32 per-expert capacity used
    counts: np.ndarray      # (E,) int64 kept token load per expert
    router_counts: np.ndarray  # (E,) int64 pre-cut router demand
    stolen: int             # entries rerouted by the steal round
    dropped: int            # entries dropped after the steal round
    n_zero: int = 0         # router choices of zero-compute experts

    def csr(self):
        """Kept entries as an expert-major CSR: (indptr (E+1,), token ids,
        combine weights), tokens of one expert ordered by dispatch slot.

        Kept slots per expert are contiguous [0, counts[e]) — first-round
        keeps occupy [0, used_e) and stolen entries are ranked from
        used_e up — so scattering by `indptr[expert] + pos` is a
        permutation of the kept entries, no gaps."""
        indptr = np.zeros(self.n_experts + 1, np.int64)
        np.cumsum(self.counts, out=indptr[1:])
        tok = np.zeros(int(indptr[-1]), np.int32)
        w = np.zeros(int(indptr[-1]), np.float32)
        k = self.keep
        at = indptr[self.expert[k]] + self.pos[k]
        tok[at] = self.token[k]
        w[at] = self.weight[k]
        return indptr, tok, w


def plan_dispatch(e_topk: np.ndarray, weights: np.ndarray = None, *,
                  cap=None, cap_scale=None,
                  capacity_factor: float = MOE_CAPACITY_FACTOR,
                  cmax_factor: float = MOE_CMAX_FACTOR,
                  steal: bool = True, experts=None,
                  zero=None) -> DispatchPlan:
    """Resolve a dispatch plan from router choices — the scheduler-side
    mirror of the in-graph path.

    e_topk (T, K): the router's top-K expert ids per token, with implied
    expert count E = max id + 1 unless `cap` fixes it. weights (T, K):
    combine weights (defaults to 1/K). Capacity comes either from `cap`
    ((E,) int, used verbatim) or from `cap_scale` ((E,) float, the d_i
    array) through the same clip-to-[MOE_MIN_CAPACITY, C_max] rule the
    model uses; `cap_scale=None` means scale 1 everywhere.

    Decision semantics (bit-identical to `models/moe.py`): entries take
    stable-sort positions inside their expert segment and survive while
    `pos < cap[expert]`; with `steal`, each overflowing entry is rerouted
    to its token's max-slack alternative (first max on ties — the exact
    argmax the in-graph path computes) and ranked after the expert's
    first-round keeps, surviving under the same capacity rule.

    `experts=(first, count)` plans one expert-parallel rank that holds
    experts [first, first + count): entries routed to them are kept,
    renumbered to [0, count), and none is dropped (no capacity, no
    steal); entries routed elsewhere belong to other ranks and are not in
    the plan, whose entry arrays then list the kept entries alone, in
    token-major order. `cap` and `cap_scale` do not apply.

    `zero=(first, count)`, with `experts`, names the zero-compute
    (identity) experts [first, first + count), which every rank applies
    on the device (`route(..., zero=)`): the held range may not overlap
    them, so no entry of the plan is one, and `n_zero` counts the
    router's choices of them over all T tokens.
    """
    with obs.span("moe.plan"):
        e_topk = np.asarray(e_topk)
        if e_topk.ndim != 2:
            raise ValueError(f"e_topk must be (T, K), got {e_topk.shape}")
        if experts is not None:
            if cap is not None or cap_scale is not None:
                raise ValueError("a plan over held experts is dropless: "
                                 "cap and cap_scale do not apply")
            return _plan_held(e_topk, weights, *experts, zero=zero)
        if zero is not None:
            raise ValueError("zero-compute experts are planned over held "
                             "experts: pass experts=(first, count)")
        return _plan(e_topk, weights, cap, cap_scale, capacity_factor,
                     cmax_factor, steal)


def _weights(e_topk: np.ndarray, weights) -> np.ndarray:
    T, K = e_topk.shape
    if weights is None:
        return np.full((T, K), 1.0 / K, np.float32)
    weights = np.asarray(weights, np.float32)
    if weights.shape != (T, K):
        raise ValueError(f"weights {weights.shape} != e_topk {(T, K)}")
    return weights


def _plan_held(e_topk, weights, first: int, count: int,
               zero=None) -> DispatchPlan:
    first, count = int(first), int(count)
    if first < 0 or count < 1:
        raise ValueError(f"held experts must be (first >= 0, count >= 1), "
                         f"got ({first}, {count})")
    n_zero = 0
    if zero is not None:
        z_first, z_count = int(zero[0]), int(zero[1])
        if z_first < 0 or z_count < 1:
            raise ValueError(f"zero-compute experts must be (first >= 0, "
                             f"count >= 1), got ({z_first}, {z_count})")
        if first < z_first + z_count and z_first < first + count:
            raise ValueError(f"held experts [{first}, {first + count}) "
                             f"overlap the zero-compute experts "
                             f"[{z_first}, {z_first + z_count})")
        n_zero = int(np.count_nonzero((e_topk >= z_first)
                                      & (e_topk < z_first + z_count)))
    T, K = e_topk.shape
    weights = _weights(e_topk, weights)
    held = (e_topk >= first) & (e_topk < first + count)
    ef = (e_topk[held] - first).astype(np.int64)
    tf = np.nonzero(held)[0].astype(np.int32)
    counts = np.bincount(ef, minlength=count).astype(np.int64)
    return DispatchPlan(
        n_tokens=T, n_experts=count, experts_per_token=K,
        expert=ef.astype(np.int32), token=tf, weight=weights[held],
        pos=_dispatch_positions(ef, count), keep=np.ones(ef.size, bool),
        cap=counts.astype(np.int32), counts=counts, router_counts=counts,
        stolen=0, dropped=0, n_zero=n_zero)


def _plan(e_topk, weights, cap, cap_scale, capacity_factor, cmax_factor,
          steal) -> DispatchPlan:
    T, K = e_topk.shape
    weights = _weights(e_topk, weights)

    if cap is not None:
        cap_e = np.asarray(cap, np.int32)
        E = cap_e.shape[0]
    else:
        E = int(e_topk.max()) + 1 if e_topk.size else 1
        if cap_scale is None:
            cap_scale = np.ones(E, np.float64)
        cap_scale = np.asarray(cap_scale, np.float64)
        E = cap_scale.shape[0]
        c_base = expert_capacity(T, E, K, capacity_factor)
        c_max = max(c_base, int(round(cmax_factor * c_base)))
        cap_e = np.clip(np.round(c_base * cap_scale),
                        MOE_MIN_CAPACITY, c_max).astype(np.int32)
    if (e_topk < 0).any() or (e_topk >= E).any():
        raise ValueError(f"expert ids out of range [0, {E})")

    ef = e_topk.reshape(-1).astype(np.int64)
    tf = np.repeat(np.arange(T, dtype=np.int32), K)
    wf = weights.reshape(-1)
    router_counts = np.bincount(ef, minlength=E).astype(np.int64)

    pos = _dispatch_positions(ef, E)
    keep = pos < cap_e[ef]

    if steal:
        # float32 slack to match the in-graph argmax bit-for-bit (counts
        # and capacities are exact integers well under 2^24 in float32)
        slack = np.maximum(cap_e.astype(np.float32)
                           - router_counts.astype(np.float32), 0.0)
        alt_slack = slack[e_topk]                                    # (T,K)
        fallback = e_topk[np.arange(T), np.argmax(alt_slack, axis=-1)]
        ef2 = np.where(keep, ef, fallback[tf])
        used = np.bincount(ef[keep], minlength=E).astype(np.int64)
        # rank stolen entries only: kept entries park on sentinel E+1
        pos2 = _dispatch_positions(np.where(keep, E + 1, ef2), E + 2)
        pos2 = pos2 + used[ef2]
        keep2 = (~keep) & (pos2 < cap_e[ef2])
        ef = np.where(keep2, ef2, ef)
        pos = np.where(keep2, pos2, pos)
        stolen = int(keep2.sum())
        keep = keep | keep2
    else:
        stolen = 0

    counts = np.bincount(ef[keep], minlength=E).astype(np.int64)
    return DispatchPlan(
        n_tokens=T, n_experts=E, experts_per_token=K,
        expert=ef.astype(np.int32), token=tf, weight=wf, pos=pos,
        keep=keep, cap=cap_e, counts=counts, router_counts=router_counts,
        stolen=stolen, dropped=int((~keep).sum()))


# ---------------------------------------------------------------------------
# Closing the loop: measured expert load -> next step's cap_scale
# ---------------------------------------------------------------------------

def cap_scale_from_costs(costs: np.ndarray, *,
                         lo: float = MOE_CAP_SCALE_MIN,
                         hi: float = MOE_CAP_SCALE_MAX) -> np.ndarray:
    """Per-expert capacity scale from (refined) per-expert costs: the
    cost-to-mean ratio clipped to the materializable range, renormalized
    only when the total EXCEEDS the budget (sum == E) — the same clip and
    budget rule as the in-graph `ich_update_cap_scale`, but derived from
    absolute load estimates instead of a multiplicative step."""
    costs = np.asarray(costs, np.float64)
    mu = costs.mean() if costs.size else 0.0
    if mu <= 0:
        return np.ones_like(costs)
    scale = np.clip(costs / mu, lo, hi)
    over = scale.sum() / scale.size
    return scale / over if over > 1.0 else scale


def refine_cap_scale(schedule, measured: np.ndarray, *,
                     blend: float = None,
                     lo: float = MOE_CAP_SCALE_MIN,
                     hi: float = MOE_CAP_SCALE_MAX):
    """One closed-loop round: fold measured per-expert load (what the
    sharded MoE kernel's per-expert cost output sums to) into the
    schedule's `CostRefiner`, re-lower, and derive the next step's
    cap_scale from the refined estimates.

    Returns `(refined_schedule, cap_scale)`. Repeated rounds on a
    structural (integer-count) workload reach a fixed point: once the
    Welford means equal the true loads, both the schedule and the scale
    stop moving (tests/test_moe_sched.py)."""
    refined = schedule.observe(np.asarray(measured, np.float64),
                               level="item").refine(blend=blend)
    return refined, cap_scale_from_costs(refined.costs, lo=lo, hi=hi)
