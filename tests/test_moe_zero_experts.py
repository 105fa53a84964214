"""MoE layers with zero-compute experts through the scheduler, against the
plain jax.numpy reference (kernels/ich_moe/ref_zero.py), at a small size
in interpret mode: D 256, F 128, 24 FFN experts and 12 zero-compute
(identity) experts behind one softmax router of 36 outputs, top-6, two
ranks of 12 FFN experts, 512 tokens.

The path is the one a rank of LongCat-Flash's MoE layers runs:
`sched.moe.route` with the softmax rule (selection by s + b, weights 6 s
not normalised, the identity weight w0 summed on the device),
`read_routing`, `plan_dispatch(..., experts=(first, count), zero=(24,
12))`, `LoopScheduler.build("moe-dispatch", plan)`, the op started at w0 u,
and the residual add. The share-sum test ties the cut to the model: the
two ranks' parts, the identity part counted once, add up to the whole
layer.
"""
import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro import sched
from repro.kernels.ich_moe.ref_zero import moe_zero_layer_ref
from repro.sched.moe import plan_dispatch, read_routing, route

T, D, F, N, Z, K, HELD = 512, 256, 128, 24, 12, 6, 12
E = N + Z
ZERO = (N, Z)
SCALE = 6.0
RULE = dict(score="softmax", renorm=False, scale=SCALE, zero=ZERO)


def _layer(seed, dtype=np.float32, bias_scale=0.1):
    """Tokens, router, selection bias (±bias_scale of the mean score
    1/E) and all N FFN experts' weights."""
    rng = np.random.default_rng(seed)
    h = rng.standard_normal((T, D)).astype(np.float32)
    w_router = (rng.standard_normal((E, D)) / np.sqrt(D)).astype(np.float32)
    bias = rng.uniform(-bias_scale / E, bias_scale / E, E).astype(np.float32)
    wi = (rng.standard_normal((N, D, F)) / np.sqrt(D)).astype(np.float32)
    wg = (rng.standard_normal((N, D, F)) / np.sqrt(D)).astype(np.float32)
    wo = (rng.standard_normal((N, F, D)) / np.sqrt(F)).astype(np.float32)
    rnd = (lambda a: a.astype(dtype).astype(np.float32))
    return rnd(h), rnd(w_router), bias, rnd(wi), rnd(wg), rnd(wo)


def _reference(layer, experts, **kw):
    """The reference layer over the held experts' weights: (h', e, w0)."""
    first, count = experts
    h, w_router, bias, wi, wg, wo = layer
    held = slice(first, first + count)
    return tuple(np.asarray(a) for a in moe_zero_layer_ref(
        h, w_router, bias, wi[held], wg[held], wo[held], top_k=K,
        experts=experts, zero=ZERO, scale=SCALE, **kw))


def _program(layer, first, count, p, dtype=jnp.float32, identity=True):
    """One rank's layer through the scheduler: (new h, op, plan, e_topk,
    w0)."""
    h, w_router, bias, wi, wg, wo = (jnp.asarray(a) for a in layer)
    cast = (lambda a: a.astype(dtype))
    u, e_topk, w, w0 = route(cast(h), cast(w_router), bias, top_k=K, **RULE)
    e_np, w_np = read_routing(e_topk, w)
    plan = plan_dispatch(e_np, w_np, experts=(first, count), zero=ZERO)
    op = sched.LoopScheduler(p=p, cache_size=0).build("moe-dispatch", plan)
    held = slice(first, first + count)
    y = op(u, cast(wi[held]), cast(wg[held]), cast(wo[held]),
           interpret=True, w0=w0 if identity else None)
    new_h = (cast(h).astype(jnp.float32) + y).astype(dtype)
    return (np.asarray(new_h.astype(jnp.float32)), op, plan, e_np,
            np.asarray(w0))


def _scores(layer):
    """The reference's softmax scores s and selection scores s + b."""
    h, w_router, bias = layer[:3]
    hf = h / np.sqrt(np.mean(h * h, axis=-1, keepdims=True) + 1e-5)
    logits = hf.astype(np.float64) @ w_router.T.astype(np.float64)
    s = np.exp(logits - logits.max(axis=1, keepdims=True))
    s = s / s.sum(axis=1, keepdims=True)
    return s, s + bias


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_softmax_router_matches_reference(seed):
    """Top-6 of s + b wherever the 6th/7th margin clears float32
    rounding; weights 6 s_e, not normalised; w0 the chosen zero-compute
    experts' weights; the bias changes the choices."""
    layer = _layer(seed)
    h, w_router, bias = (jnp.asarray(a) for a in layer[:3])
    u, e_topk, w, w0 = route(h, w_router, bias, top_k=K, **RULE)
    e_np, w_np = read_routing(e_topk, w)
    w0 = np.asarray(w0)
    s, sel = _scores(layer)
    ranked = -np.sort(-sel, axis=1)
    clear = ranked[:, K - 1] - ranked[:, K] > 1e-6
    assert clear.mean() > 0.99
    want = np.argsort(-sel, axis=1, kind="stable")[:, :K]
    np.testing.assert_array_equal(np.sort(e_np[clear], 1),
                                  np.sort(want[clear], 1))
    np.testing.assert_allclose(w_np, SCALE * np.take_along_axis(s, e_np, 1),
                               rtol=1e-5, atol=1e-9)
    # not normalised: the weights sum to 6 times the chosen mass, below 6
    total = w_np.sum(1)
    assert (total < SCALE).all() and np.ptp(total) > 0.1
    on_zero = e_np >= N
    np.testing.assert_allclose(w0, np.where(on_zero, w_np, 0.0).sum(1),
                               rtol=1e-6, atol=1e-9)
    assert 0 < on_zero.sum() < on_zero.size
    _, e_nobias = jax.lax.top_k(jnp.asarray(s, jnp.float32), K)
    assert (np.sort(np.asarray(e_nobias), 1) != np.sort(want, 1)).any()


def test_softmax_scores_exclude_the_bias():
    """A bias that lifts experts 0 and 1 into every token's choice adds
    nothing to their weights."""
    layer = list(_layer(8))
    layer[2] = layer[2].copy()
    layer[2][:2] = 1.0
    h, w_router, bias = (jnp.asarray(a) for a in layer[:3])
    _, e_topk, w, _ = route(h, w_router, bias, top_k=K, **RULE)
    e_np, w_np = read_routing(e_topk, w)
    s, _ = _scores(layer)
    assert (np.sort(e_np[:, :2], 1) == [0, 1]).all()
    np.testing.assert_allclose(
        w_np[:, :2], SCALE * np.take_along_axis(s, e_np[:, :2], 1),
        rtol=1e-5)


@pytest.mark.parametrize("first", [0, HELD])
def test_plan_counts_zero_choices_and_holds_none(first):
    """`n_zero` is the number of (token, choice) entries on zero-compute
    experts over all tokens; the held plan lists only held FFN experts,
    and held plus other FFN plus zero entries make up every choice."""
    rng = np.random.default_rng(first)
    e_topk = np.argsort(rng.random((T, E)), axis=1)[:, :K].astype(np.int32)
    w = rng.random((T, K)).astype(np.float32)
    plan = plan_dispatch(e_topk, w, experts=(first, HELD), zero=ZERO)
    assert plan.n_zero == int((e_topk >= N).sum()) > 0
    assert plan.n_experts == HELD and plan.keep.all()
    assert ((plan.expert >= 0) & (plan.expert < HELD)).all()
    held = (e_topk >= first) & (e_topk < first + HELD)
    assert plan.counts.sum() == held.sum()
    other = plan_dispatch(e_topk, w, experts=(HELD - first, HELD),
                          zero=ZERO)
    assert (plan.counts.sum() + other.counts.sum() + plan.n_zero
            == T * K)
    np.testing.assert_array_equal(plan.weight, w[held])
    assert plan_dispatch(e_topk, w, experts=(first, HELD)).n_zero == 0


@pytest.mark.parametrize("experts,zero", [((0, 25), ZERO), ((24, 1), ZERO),
                                          ((30, 8), ZERO), ((0, 8), (4, 2)),
                                          ((0, 8), (0, 0))])
def test_plan_refuses_overlapping_or_empty_zero_range(experts, zero):
    e_topk = np.zeros((4, 2), np.int32)
    with pytest.raises(ValueError, match="zero-compute"):
        plan_dispatch(e_topk, experts=experts, zero=zero)


def test_plan_refuses_zero_range_without_held_experts():
    with pytest.raises(ValueError, match="held experts"):
        plan_dispatch(np.zeros((4, 2), np.int32), zero=ZERO)


@pytest.mark.parametrize("identity", [True, False])
@pytest.mark.parametrize("p", [1, 4])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_layer_matches_reference(dtype, p, identity):
    """Route, plan, build, apply (with or without w0), add: against the
    reference layer on the program's own choices. bf16 stores u, the
    activation and h in bf16 with float32 sums, as the reference does."""
    np_dt = np.float32 if dtype == "float32" else ml_dtypes.bfloat16
    layer = _layer(3, np_dt)
    got, op, plan, e_np, w0 = _program(layer, 0, HELD, p,
                                       getattr(jnp, dtype), identity)
    want, _, w0_ref = _reference(layer, (0, HELD), e_topk=e_np,
                                 dtype=getattr(jnp, dtype),
                                 identity=identity)
    assert plan.counts.sum() > 0 and plan.n_zero > 0
    np.testing.assert_allclose(w0, w0_ref, rtol=1e-5, atol=1e-8)
    err = np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)
    assert err.max() < (1e-6 if dtype == "float32" else 2e-3)
    if identity:  # w0 u moves every token that chose a zero expert
        without, _, _ = _reference(layer, (0, HELD), e_topk=e_np,
                                   identity=False)
        moved = np.linalg.norm(got - without, axis=1)
        assert (moved[w0 > 0] > 1e-3).all()


@pytest.mark.parametrize("p", [1, 4])
def test_shares_sum_to_the_whole_layer(p):
    """Two ranks of 12 FFN experts: their updates, the identity part
    (which each rank applies) counted once, sum to the uncut layer."""
    layer = _layer(4)
    h = layer[0]
    whole, e_np, w0 = _reference(layer, (0, N))
    ident = np.asarray(moe_zero_layer_ref(
        h, *layer[1:3], *(a[:0] for a in layer[3:]), top_k=K,
        experts=(0, 0), zero=ZERO, scale=SCALE)[0]) - h
    parts = [_program(layer, r * HELD, HELD, p)[0] - h
             for r in range(N // HELD)]
    assert np.abs(ident).max() > 0.1         # the identity part is there
    np.testing.assert_allclose(h + sum(parts) - (len(parts) - 1) * ident,
                               whole, rtol=1e-5, atol=1e-5)


def test_token_of_zero_experts_alone_runs_no_ffn():
    """A token whose every pick is a zero-compute expert does no FFN work:
    the plan is empty and y is exactly w0 u."""
    layer = list(_layer(9))
    layer[2] = np.where(np.arange(E) >= N, 1.0, 0.0).astype(np.float32)
    got, op, plan, e_np, w0 = _program(layer, 0, HELD, 2)
    assert (e_np >= N).all() and plan.counts.sum() == 0
    assert plan.n_zero == T * K
    h = jnp.asarray(layer[0])
    u = route(h, jnp.asarray(layer[1]), jnp.asarray(layer[2]), top_k=K,
              **RULE)[0]
    want = np.asarray(h + w0[:, None] * np.asarray(u))
    np.testing.assert_array_equal(got, want)
