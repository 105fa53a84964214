"""Dispatch-conformance suite for iCh-scheduled MoE expert dispatch
(DESIGN.md §2.8) — the acceptance gate for running the model ON the
scheduler.

Covered contracts:

* token conservation: every (token, choice) entry is kept exactly once or
  dropped; the plan's expert-major CSR is a gap-free permutation of the
  kept entries;
* dispatch bit-identity: the host-side planner (`sched.moe.plan_dispatch`)
  reproduces the in-graph sort-based path (`models/moe.py:
  dispatch_decisions`) decision-for-decision at equal capacity, and the
  scheduled kernel's outputs match `moe_local`'s end to end;
* steal-target optimality: every stolen entry lands on its token's
  max-slack alternative, and only on an expert that actually had slack;
* simulator-vs-kernel cross-checks for p in {1, 2, 4}: the sharded MoE
  kernel's per-expert cost sums equal the schedule's per-item totals
  EXACTLY, its per-worker superstep sums equal the shard partition's
  worker costs exactly, and the zero-overhead sharded replay's makespan
  is the same number;
* hypothesis properties mirroring tests/test_adaptive_properties.py:
  permutation-of-tokens invariance of per-expert loads, overflow landing
  underloaded-or-dropped deterministically, and refined `cap_scale` as a
  monotone fixed point on structural (integer-count) workloads;
* the regression pin for the previously xfail'd decode-vs-prefill gap:
  shared-capacity dispatch depends on the token pool size, dropless
  (serving) dispatch does not (tests/test_arch_smoke.py asserts the
  full-model consequence).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import sched
from repro.configs import get_arch, reduced
from repro.core.simulator import SimParams
from repro.kernels.ich_moe.ref import moe_dispatch_ref
from repro.models import moe as MOE
from repro.sched import get as sched_get
from repro.sched.moe import (cap_scale_from_costs, expert_capacity,
                             plan_dispatch, refine_cap_scale)

_ZERO = SimParams(dispatch_overhead=0.0, local_dispatch_overhead=0.0,
                  speed_jitter=0.0)


def _router(T, E, K, seed=0, skew=1.2):
    """Zipf-skewed synthetic router: distinct top-K expert ids per token
    (gumbel-perturbed popularity) + renormalized combine weights."""
    rng = np.random.default_rng(seed)
    pop = np.arange(1, E + 1, dtype=np.float64) ** -float(skew)
    logits = rng.gumbel(size=(T, E)) + 3.0 * np.log(pop)[None]
    e_topk = np.argsort(-logits, axis=1)[:, :K].astype(np.int32)
    w = rng.random((T, K)).astype(np.float32) + 0.1
    w /= w.sum(1, keepdims=True)
    return e_topk, w


def _ffn(E, D, F, seed=0):
    rng = np.random.default_rng(seed)
    wi = (rng.standard_normal((E, D, F)) * D ** -0.5).astype(np.float32)
    wg = (rng.standard_normal((E, D, F)) * D ** -0.5).astype(np.float32)
    wo = (rng.standard_normal((E, F, D)) * F ** -0.5).astype(np.float32)
    return wi, wg, wo


# ------------------------------------------------------ token conservation
@pytest.mark.parametrize("steal", [False, True])
@pytest.mark.parametrize("seed", [0, 3, 11])
def test_plan_token_conservation_and_csr_layout(seed, steal):
    T, E, K = 200, 16, 2
    e_topk, w = _router(T, E, K, seed=seed)
    plan = plan_dispatch(e_topk, w, cap_scale=np.ones(E), steal=steal)
    assert int(plan.counts.sum()) + plan.dropped == T * K
    assert plan.stolen + int((plan.expert.reshape(T, K)
                              == e_topk).all(axis=None)) >= 0
    np.testing.assert_array_equal(
        plan.counts, np.bincount(plan.expert[plan.keep], minlength=E))
    np.testing.assert_array_equal(
        plan.router_counts, np.bincount(e_topk.reshape(-1), minlength=E))
    # capacity is never exceeded
    assert (plan.counts <= plan.cap.astype(np.int64)).all()
    # CSR: gap-free permutation of the kept entries, segment sizes = loads
    indptr, tok, wcsr = plan.csr()
    np.testing.assert_array_equal(np.diff(indptr), plan.counts)
    at = indptr[plan.expert[plan.keep]] + plan.pos[plan.keep]
    assert np.unique(at).size == at.size  # no slot collisions, no gaps
    assert tok.min() >= 0 and tok.max() < T if tok.size else True
    np.testing.assert_allclose(wcsr.sum(), plan.weight[plan.keep].sum(),
                               rtol=1e-6)
    # without stealing, kept loads are exactly min(demand, capacity)
    if not steal:
        np.testing.assert_array_equal(
            plan.counts, np.minimum(plan.router_counts,
                                    plan.cap.astype(np.int64)))


# -------------------------------------- bit-identity vs the in-graph path
@pytest.mark.parametrize("steal", [False, True])
@pytest.mark.parametrize("seed", [0, 7, 42])
def test_plan_matches_ingraph_decisions_bitwise(seed, steal):
    """The numpy planner and the jnp dispatch pass agree on every entry:
    final expert, dispatch slot, survival, steal count."""
    T, E, K = 160, 8, 2
    e_topk, _ = _router(T, E, K, seed=seed)
    plan = plan_dispatch(e_topk, cap_scale=np.ones(E), steal=steal)
    ef, tf, pos, keep, stolen = MOE.dispatch_decisions(
        jnp.asarray(e_topk), jnp.asarray(plan.cap), steal=steal)
    np.testing.assert_array_equal(np.asarray(ef), plan.expert)
    np.testing.assert_array_equal(np.asarray(tf), plan.token)
    np.testing.assert_array_equal(np.asarray(pos), plan.pos)
    np.testing.assert_array_equal(np.asarray(keep), plan.keep)
    assert int(stolen) == plan.stolen


def test_scheduled_dispatch_matches_moe_local_end_to_end():
    """At equal capacity the scheduled kernel reproduces the sort-based
    layer's output: same router, same capacities, same combine weights —
    the model-on-scheduler bridge, end to end."""
    cfg = reduced(get_arch("olmoe-1b-7b"), n_experts=8, experts_per_token=2,
                  d_model=32, moe_d_ff=32)
    E, K = cfg.n_experts, cfg.experts_per_token
    T = 96
    p = MOE.init_moe(jax.random.PRNGKey(0), cfg)
    p["router"] = p["router"].at[:, 0].add(2.0)  # skew the load
    x = jax.random.normal(jax.random.PRNGKey(1), (T, cfg.d_model),
                          dtype=jnp.float32)
    cap_scale = jnp.ones((E,))
    y_model, aux = MOE.moe_local(cfg, p, x, cap_scale, capacity_factor=1.0)

    # host-side mirror of the router + capacity arithmetic
    probs = jax.nn.softmax((x @ p["router"]).astype(jnp.float32), -1)
    w_topk, e_topk = jax.lax.top_k(probs, K)
    w_topk = w_topk / jnp.maximum(w_topk.sum(-1, keepdims=True), 1e-9)
    c_base = MOE.capacity(cfg, T, 1.0)
    c_max = max(c_base, int(round(getattr(cfg, "moe_cmax_factor", 2.0)
                                  * c_base)))
    cap_e = np.clip(np.round(c_base * np.asarray(cap_scale)), 4,
                    c_max).astype(np.int32)
    plan = plan_dispatch(np.asarray(e_topk), np.asarray(w_topk), cap=cap_e)
    assert plan.dropped == int(aux["dropped"])
    assert plan.stolen == int(aux["stolen"])

    op = sched.LoopScheduler(p=2).build("moe-dispatch", plan)
    y_sched = op(x, p["wi"].astype(jnp.float32),
                 p["wg"].astype(jnp.float32), p["wo"].astype(jnp.float32),
                 interpret=True)
    np.testing.assert_allclose(np.asarray(y_sched), np.asarray(y_model),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_array_equal(op.expert_load(),
                                  plan.counts.astype(np.float64))


# ------------------------------------------------- steal-target optimality
@pytest.mark.parametrize("seed", [0, 5, 19])
def test_steal_targets_are_max_slack_alternatives(seed):
    """Every stolen entry (a) lands on an expert that had positive slack,
    (b) lands on one of its token's own top-K alternatives, and (c) picks
    the FIRST max-slack alternative — the exact argmax the in-graph path
    computes."""
    T, E, K = 300, 16, 4
    e_topk, w = _router(T, E, K, seed=seed, skew=1.6)
    plan = plan_dispatch(e_topk, w, cap_scale=np.ones(E), steal=True)
    orig = e_topk.reshape(-1).astype(np.int32)
    stolen = plan.keep & (plan.expert != orig)
    assert plan.stolen >= int(stolen.sum())  # rerouted-to-same never counts
    if not stolen.any():
        pytest.skip(f"seed {seed} produced no steals at this skew")
    slack = np.maximum(plan.cap.astype(np.int64) - plan.router_counts, 0)
    dests = plan.expert[stolen]
    assert (slack[dests] > 0).all()  # always an underloaded expert
    toks = plan.token[stolen]
    choice_rows = e_topk[toks]  # (n_stolen, K)
    assert (dests[:, None] == choice_rows).any(axis=1).all()
    expected = choice_rows[np.arange(toks.size),
                           np.argmax(slack[choice_rows].astype(np.float32),
                                     axis=1)]
    np.testing.assert_array_equal(dests, expected)


# --------------------------- simulator vs kernel per-expert work (p grid)
@pytest.mark.parametrize("p", [1, 2, 4])
def test_kernel_costs_match_schedule_and_simulator_exactly(p):
    """PR 5's routing proof extended to the MoE kernel at every p: the
    emitted per-expert cost sums equal the plan's kept token counts
    EXACTLY, the per-worker superstep sums equal the shard partition's
    worker costs exactly, and the zero-overhead sharded replay agrees on
    the makespan."""
    T, E, K, D, F = 256, 16, 2, 16, 24
    e_topk, w = _router(T, E, K, seed=p)
    plan = plan_dispatch(e_topk, w, cap_scale=np.ones(E))
    op = sched.LoopScheduler(p=p, cache_size=0).build("moe-dispatch", plan)
    wi, wg, wo = _ffn(E, D, F, seed=p)
    x = np.random.default_rng(p).standard_normal((T, D)).astype(np.float32)
    y = op(jnp.asarray(x), jnp.asarray(wi), jnp.asarray(wg),
           jnp.asarray(wo), interpret=True)

    indptr, tok, wcsr = plan.csr()
    np.testing.assert_allclose(np.asarray(y),
                               moe_dispatch_ref(indptr, tok, wcsr, x,
                                                wi, wg, wo),
                               atol=1e-4, rtol=1e-4)
    # per-expert totals: bit-exact integer token counts in float32
    emitted_e = np.asarray(op.last_expert_costs)
    assert emitted_e.shape == (op.p, E)
    np.testing.assert_array_equal(emitted_e.sum(axis=0),
                                  plan.counts.astype(np.float32))
    np.testing.assert_array_equal(emitted_e.sum(axis=0),
                                  op.schedule.costs.astype(np.float32))
    # per-worker superstep stream: the §2.7 invariant
    emitted_w = np.asarray(op.last_costs)
    shards = op.schedule.shard()
    assert emitted_w.shape == shards.block_perm.shape
    wc = shards.worker_cost(op.schedule.tile_cost())
    np.testing.assert_array_equal(emitted_w.sum(axis=1),
                                  wc.astype(np.float32))
    # simulator cross-check: zero-overhead sharded replay's makespan is
    # the partition's max per-worker cost — the same number the kernel
    # emitted
    rep = op.schedule.replay_sharded(params=_ZERO)
    assert rep.makespan == pytest.approx(float(wc.max()))
    assert rep.makespan == pytest.approx(float(emitted_w.sum(axis=1).max()))


def test_op_observe_refine_roundtrip_keeps_dispatch_semantics():
    """Closing the loop re-partitions but never re-routes: the op rebuilt
    on the refined schedule dispatches the same plan (exact same
    per-expert loads, outputs equal to tolerance — fold order may differ
    because tokens are shared across workers)."""
    T, E, K, D, F = 200, 16, 2, 16, 24
    e_topk, w = _router(T, E, K, seed=2)
    plan = plan_dispatch(e_topk, w, cap_scale=np.ones(E))
    scheduler = sched.LoopScheduler(p=4, cache_size=0)
    op = scheduler.build("moe-dispatch", plan)
    wi, wg, wo = _ffn(E, D, F, seed=2)
    x = np.random.default_rng(2).standard_normal((T, D)).astype(np.float32)
    y0 = np.asarray(op(jnp.asarray(x), jnp.asarray(wi), jnp.asarray(wg),
                       jnp.asarray(wo), interpret=True))
    refined_s = op.observe().refine()
    assert refined_s.generation == 1
    np.testing.assert_array_equal(refined_s.sizes, plan.counts)  # structural
    op2 = sched_get("moe-dispatch").build(refined_s, plan)
    y1 = np.asarray(op2(jnp.asarray(x), jnp.asarray(wi), jnp.asarray(wg),
                        jnp.asarray(wo), interpret=True))
    np.testing.assert_allclose(y1, y0, atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(op2.expert_load(), op.expert_load())


def test_registry_and_provider_validation():
    assert "moe-dispatch" in sched.registered()
    with pytest.raises(TypeError, match="integer"):
        sched.ExpertLoadCosts(np.ones(4, np.float64))
    with pytest.raises(ValueError, match="non-negative"):
        sched.ExpertLoadCosts(np.array([3, -1], np.int64))
    with pytest.raises(ValueError, match="1-D"):
        sched.ExpertLoadCosts(np.ones((2, 2), np.int64))


# ----------------------------------------------------- hypothesis properties
@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 5000), E=st.sampled_from([4, 8, 16]),
       K=st.sampled_from([1, 2, 4]), T=st.integers(16, 200))
def test_per_expert_loads_are_permutation_invariant(seed, E, K, T):
    """Reordering the token pool never changes per-expert loads: without
    stealing the loads are exactly min(demand, capacity) — a function of
    the demand histogram alone — and the steal round's demand/slack
    inputs are permutation-invariant too (WHICH entries overflow is
    order-dependent by design: positions are the dispatch order)."""
    e_topk, w = _router(T, E, K, seed=seed)
    perm = np.random.default_rng(seed + 1).permutation(T)
    a = plan_dispatch(e_topk, w, cap_scale=np.ones(E), steal=False)
    b = plan_dispatch(e_topk[perm], w[perm], cap_scale=np.ones(E),
                      steal=False)
    np.testing.assert_array_equal(a.counts, b.counts)
    np.testing.assert_array_equal(a.router_counts, b.router_counts)
    assert a.dropped == b.dropped
    np.testing.assert_array_equal(
        a.counts, np.minimum(a.router_counts, a.cap.astype(np.int64)))
    # stealing fills from an order-invariant slack pool: kept totals can
    # only improve on the no-steal dispatch, for every ordering
    sa = plan_dispatch(e_topk, w, cap_scale=np.ones(E), steal=True)
    sb = plan_dispatch(e_topk[perm], w[perm], cap_scale=np.ones(E),
                       steal=True)
    assert sa.dropped <= a.dropped and sb.dropped <= b.dropped
    np.testing.assert_array_equal(sa.router_counts, sb.router_counts)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 5000), E=st.sampled_from([8, 16]),
       K=st.sampled_from([2, 4]), T=st.integers(32, 200),
       skew=st.floats(0.5, 2.0))
def test_overflow_lands_underloaded_or_drops_deterministically(seed, E, K,
                                                               T, skew):
    """Every entry that overflows its router choice either lands on an
    alternative that had positive slack or is dropped — and the whole
    resolution is a deterministic function of the inputs (bit-identical
    on re-planning)."""
    e_topk, w = _router(T, E, K, seed=seed, skew=skew)
    plan = plan_dispatch(e_topk, w, cap_scale=np.ones(E), steal=True)
    orig = e_topk.reshape(-1).astype(np.int32)
    stolen = plan.keep & (plan.expert != orig)
    slack = np.maximum(plan.cap.astype(np.int64) - plan.router_counts, 0)
    assert (slack[plan.expert[stolen]] > 0).all()
    # dropped entries still point at a router choice of their own token
    dropped = ~plan.keep
    assert (plan.expert[dropped][:, None]
            == e_topk[plan.token[dropped]]).any(axis=1).all()
    replan = plan_dispatch(e_topk, w, cap_scale=np.ones(E), steal=True)
    np.testing.assert_array_equal(replan.expert, plan.expert)
    np.testing.assert_array_equal(replan.keep, plan.keep)
    np.testing.assert_array_equal(replan.pos, plan.pos)


@pytest.mark.parametrize("seed", [1, 5, 9])
def test_refined_cap_scale_is_monotone_fixed_point(seed):
    """On a structural (integer-count) workload, the closed capacity loop
    mirrors tests/test_adaptive_properties.py's refine-round invariant:
    the sharded makespan on true per-expert costs is non-increasing
    across observe/refine rounds and hits a fixed point once the loads
    are learned; cap_scale orders experts like the measured loads
    (monotone) and stops moving at the fixed point (bit-identical across
    further rounds)."""
    rng = np.random.default_rng(seed)
    E = 256
    counts = np.minimum(rng.zipf(1.6, E), 400).astype(np.int64)
    # heterogeneous per-expert throughput: true cost != token count
    true = counts.astype(np.float64) * rng.uniform(0.5, 2.0, E) + 0.01
    s = sched.LoopScheduler(p=8, cache_size=0).schedule(
        sched.ExpertLoadCosts(counts))
    ms, scales = [], []
    for _ in range(4):
        ms.append(s.replay_refined(true, sharded=True, params=_ZERO)
                  .makespan)
        s, cs = refine_cap_scale(s, true)
        np.testing.assert_array_equal(s.sizes, counts)  # structural
        scales.append(cs)
    assert all(b <= a + 1e-9 for a, b in zip(ms, ms[1:])), ms
    assert ms[2] == pytest.approx(ms[1], rel=1e-12)  # fixed point
    # cap_scale is monotone in measured load (clip preserves order)
    order = np.argsort(true)
    assert (np.diff(scales[0][order]) >= -1e-12).all()
    # and a fixed point: identical once the Welford means equal the loads
    np.testing.assert_array_equal(scales[1], scales[2])
    np.testing.assert_array_equal(scales[2], scales[3])
    # budget rule: never exceeds E, clips to the materializable range
    for cs in scales:
        assert cs.sum() <= E + 1e-9
        assert (cs >= 0.25 - 1e-12).all() and (cs <= 2.0 + 1e-12).all()


def test_cap_scale_from_costs_degenerate_inputs():
    np.testing.assert_array_equal(cap_scale_from_costs(np.zeros(4)),
                                  np.ones(4))
    uniform = cap_scale_from_costs(np.full(6, 7.0))
    np.testing.assert_allclose(uniform, np.ones(6))


# -------------------------------------- decode-vs-prefill regression pin
def test_shared_capacity_depends_on_pool_size_but_dropless_does_not():
    """The mechanism behind the previously xfail'd
    test_decode_matches_prefill[olmoe-1b-7b]: under shared capacity the
    SAME prefix tokens dispatch differently depending on how many tokens
    compete (pool T vs T+1 — exactly prefill-of-S vs fresh
    prefill-of-S+1), while dropless per-request dispatch is pool-size
    independent — which is why serving now uses it
    (models/model.py prefill/decode_step)."""
    T, E, K = 12, 4, 2
    # every token's first choice is expert 0: demand 12 > capacity
    e_topk = np.stack([np.zeros(T + 1, np.int32),
                       1 + (np.arange(T + 1, dtype=np.int32) % (E - 1))],
                      axis=1)
    cap_s = np.full(E, expert_capacity(T, E, K, 1.0), np.int32)      # 6
    cap_s1 = np.full(E, expert_capacity(T + 1, E, K, 1.0), np.int32)  # 7
    assert cap_s[0] != cap_s1[0]
    plan_s = plan_dispatch(e_topk[:T], cap=cap_s, steal=False)
    plan_s1 = plan_dispatch(e_topk, cap=cap_s1, steal=False)
    shared = slice(0, T * K)  # the prefix tokens' entries in both plans
    assert (plan_s.keep != plan_s1.keep[shared]).any(), \
        "pool-size competition must change a shared token's dispatch"
    # dropless: capacity = the whole pool; nothing dropped, assignments
    # of the shared tokens identical across pool sizes
    drop_s = plan_dispatch(e_topk[:T], cap=np.full(E, T, np.int32),
                           steal=False)
    drop_s1 = plan_dispatch(e_topk, cap=np.full(E, T + 1, np.int32),
                            steal=False)
    assert drop_s.keep.all() and drop_s1.keep.all()
    np.testing.assert_array_equal(drop_s.expert, drop_s1.expert[shared])


def test_moe_local_dropless_flag_keeps_everything():
    """dropless=True through the in-graph layer: zero drops, zero steals,
    and the output equals the generous-capacity dispatch exactly."""
    cfg = reduced(get_arch("olmoe-1b-7b"), n_experts=8, experts_per_token=2,
                  d_model=32, moe_d_ff=32)
    p = MOE.init_moe(jax.random.PRNGKey(3), cfg)
    p["router"] = p["router"].at[:, 0].add(3.0)  # heavy skew
    x = jax.random.normal(jax.random.PRNGKey(4), (48, cfg.d_model))
    cap = jnp.ones((cfg.n_experts,))
    y_d, aux_d = MOE.moe_local(cfg, p, x, cap, dropless=True)
    assert float(aux_d["dropped"]) == 0 and float(aux_d["stolen"]) == 0
    y_g, aux_g = MOE.moe_local(cfg, p, x, cap * 100, steal=False,
                               capacity_factor=50.0)
    assert float(aux_g["dropped"]) == 0
    np.testing.assert_allclose(np.asarray(y_d), np.asarray(y_g), atol=1e-5)
    # and the capacity-constrained path under the same skew DOES drop —
    # the two serving/training modes are genuinely different
    _, aux_c = MOE.moe_local(cfg, p, x, cap, capacity_factor=1.0)
    assert float(aux_c["dropped"]) > 0
