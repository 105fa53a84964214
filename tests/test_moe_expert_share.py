"""Expert-parallel MoE layers through the scheduler, against the plain
reference (kernels/ich_moe/ref.py), at a small size in interpret mode:
D 256, F 128, 32 routed experts with 8 held here, top-8, 512 tokens.

The path is the one a rank runs per layer: `sched.moe.route` (RMSNorm and
the sigmoid router with a selection bias), `read_routing`,
`plan_dispatch(..., experts=(first, count))`, `LoopScheduler.build(
"moe-dispatch", plan)`, the op, and the residual add. The share-sum test
ties the cut to the model: the four ranks' parts add up to the whole
layer.
"""
import functools

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro import sched
from repro.core.tiling import token_block_width
from repro.kernels.ich_moe.ref import moe_layer_ref, rms_norm_ref, route_ref
from repro.sched.kernels import _bucket
from repro.sched.moe import _router, plan_dispatch, read_routing, route

T, D, F, E, K, HELD = 512, 256, 128, 32, 8, 8


def _layer(seed, dtype=np.float32):
    """Tokens, router, selection bias and all E experts' weights."""
    rng = np.random.default_rng(seed)
    h = rng.standard_normal((T, D)).astype(np.float32)
    w_router = (rng.standard_normal((E, D)) / np.sqrt(D)).astype(np.float32)
    bias = rng.uniform(-0.05, 0.05, E).astype(np.float32)
    wi = (rng.standard_normal((E, D, F)) / np.sqrt(D)).astype(np.float32)
    wg = (rng.standard_normal((E, D, F)) / np.sqrt(D)).astype(np.float32)
    wo = (rng.standard_normal((E, F, D)) / np.sqrt(F)).astype(np.float32)
    rnd = (lambda a: a.astype(dtype).astype(np.float32))
    return rnd(h), rnd(w_router), bias, rnd(wi), rnd(wg), rnd(wo)


def _program(layer, first, count, p, dtype=jnp.float32):
    """One rank's layer through the scheduler: (new h, op, plan, e_topk)."""
    h, w_router, bias, wi, wg, wo = (jnp.asarray(a) for a in layer)
    cast = (lambda a: a.astype(dtype))
    u, e_topk, w = route(cast(h), cast(w_router), bias, top_k=K)
    e_np, w_np = read_routing(e_topk, w)
    plan = plan_dispatch(e_np, w_np, experts=(first, count))
    op = sched.LoopScheduler(p=p, cache_size=0).build("moe-dispatch", plan)
    held = slice(first, first + count)
    y = op(u, cast(wi[held]), cast(wg[held]), cast(wo[held]),
           interpret=True)
    new_h = (cast(h).astype(jnp.float32) + y).astype(dtype)
    return np.asarray(new_h.astype(jnp.float32)), op, plan, e_np


def _margin(layer):
    """Each token's gap between its 8th and 9th (s + bias)."""
    h, w_router, bias = layer[:3]
    _, _, scores = route_ref(rms_norm_ref(h), w_router, bias, K)
    sel = -np.sort(-(scores + bias), axis=1)
    return sel[:, K - 1] - sel[:, K]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_router_matches_reference(seed):
    """Top-8 sets equal the reference's wherever the 8th/9th margin
    exceeds float32 rounding; the weights are the normalised sigmoid
    scores, bias excluded; the selection bias changes the choices."""
    layer = _layer(seed)
    h, w_router, bias = layer[:3]
    u, e_topk, w = route(jnp.asarray(h), jnp.asarray(w_router),
                         jnp.asarray(bias), top_k=K)
    e_np, w_np = read_routing(e_topk, w)
    u_ref = rms_norm_ref(h)
    np.testing.assert_allclose(np.asarray(u), u_ref, rtol=1e-5, atol=1e-6)
    e_ref, w_ref, _ = route_ref(u_ref, w_router, bias, K)
    clear = _margin(layer) > 1e-5
    assert clear.mean() > 0.99
    np.testing.assert_array_equal(np.sort(e_np[clear], 1),
                                  np.sort(e_ref[clear], 1))
    order = np.argsort(e_np, 1), np.argsort(e_ref, 1)
    np.testing.assert_allclose(
        np.take_along_axis(w_np, order[0], 1)[clear],
        np.take_along_axis(w_ref, order[1], 1)[clear], rtol=1e-5)
    np.testing.assert_allclose(w_np.sum(1), 1.0, rtol=1e-6)
    e_nobias, _, _ = route_ref(u_ref, w_router, np.zeros(E), K)
    assert (np.sort(e_nobias, 1) != np.sort(e_ref, 1)).any()


# LongCat-Flash's rule: softmax scores, scaled by routed_scaling_factor
# and not normalised, the last third of the outputs zero-compute experts
SOFTMAX = dict(score="softmax", renorm=False, scale=6.0)


def _softmax_rule(n_exp):
    return dict(SOFTMAX, zero=(n_exp - n_exp // 3, n_exp // 3))


@functools.lru_cache(maxsize=None)
def _gather_router(top_k, eps=1e-5, score="sigmoid", renorm=True, scale=1.0,
                   zero=None):
    """`route`'s program with the chosen scores picked by a gather."""
    def router(h, w_router, bias):
        hf = h.astype(jnp.float32)
        u = hf * jax.lax.rsqrt(jnp.mean(hf * hf, axis=-1, keepdims=True)
                               + eps)
        u = u.astype(h.dtype)
        logits = jnp.dot(u, w_router.T, preferred_element_type=jnp.float32)
        scores = (jax.nn.sigmoid(logits) if score == "sigmoid"
                  else jax.nn.softmax(logits, axis=-1))
        _, e_topk = jax.lax.top_k(scores + bias, top_k)
        w = jnp.take_along_axis(scores, e_topk, axis=-1)
        if renorm:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        w = w * scale if scale != 1.0 else w
        if zero is None:
            return u, e_topk, w
        on = (e_topk >= zero[0]) & (e_topk < zero[0] + zero[1])
        return u, e_topk, w, jnp.sum(jnp.where(on, w, 0.0), axis=-1)
    return jax.jit(router)


@pytest.mark.parametrize("case", [(0, E), (1, E), (2, E), (3, 256),
                                  ("tie", E), (0, 36, 6, "softmax"),
                                  (1, 768, 12, "softmax"),
                                  ("tie", 36, 6, "softmax")], ids=str)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_route_weights_bitwise_equal_to_gather(case, dtype):
    """The masked sums give the gather's weights bit for bit, and
    lax.top_k's choices. In the tie case experts 0 and 1 have the same
    router row and bias, lifted so that every token picks both: the tie
    is broken by index, and the two weights are equal. The softmax cases
    (scaled, not normalised, with zero-compute experts) also give the
    identity weight w0 of the gathered weights bit for bit."""
    seed, n_exp = case[:2]
    top_k = K if len(case) == 2 else case[2]
    rule = {} if len(case) == 2 else _softmax_rule(n_exp)
    rng = np.random.default_rng(7 if seed == "tie" else seed)
    h = rng.standard_normal((T, D)).astype(np.float32)
    w_router = (rng.standard_normal((n_exp, D)) / np.sqrt(D)).astype(
        np.float32)
    bias = rng.uniform(-0.05, 0.05, n_exp).astype(np.float32)
    if seed == "tie":
        w_router[1] = w_router[0]
        bias[:2] = 1.0
    args = (jnp.asarray(h, dtype), jnp.asarray(w_router, dtype),
            jnp.asarray(bias))
    u, e_topk, w, *w0 = route(*args, top_k=top_k, **rule)
    u_g, e_g, w_g, *w0_g = _gather_router(top_k, 1e-5, **rule)(*args)
    np.testing.assert_array_equal(np.asarray(e_topk), np.asarray(e_g))
    np.testing.assert_array_equal(np.asarray(w).view(np.int32),
                                  np.asarray(w_g).view(np.int32))
    assert len(w0) == len(w0_g) == (1 if rule else 0)
    for a, b in zip(w0, w0_g):
        np.testing.assert_array_equal(np.asarray(a).view(np.int32),
                                      np.asarray(b).view(np.int32))
    np.testing.assert_array_equal(np.asarray(u.astype(jnp.float32)),
                                  np.asarray(u_g.astype(jnp.float32)))
    logits = jnp.dot(u, args[1].T, preferred_element_type=jnp.float32)
    scores = (jax.nn.softmax(logits, axis=-1) if rule
              else jax.nn.sigmoid(logits))
    _, e_lax = jax.lax.top_k(scores + args[2], top_k)
    np.testing.assert_array_equal(np.asarray(e_topk), np.asarray(e_lax))
    if seed == "tie":
        e_np, w_np = np.asarray(e_topk), np.asarray(w)
        assert (e_np[:, :2] == [0, 1]).all()
        np.testing.assert_array_equal(w_np[:, 0].view(np.int32),
                                      w_np[:, 1].view(np.int32))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n_exp,top_k,score", [
    pytest.param(E, K, "sigmoid", id="32-8"),
    pytest.param(256, 8, "sigmoid", id="256-8"),
    pytest.param(64, 6, "sigmoid", id="64-6"),
    pytest.param(768, 12, "softmax", id="768-12-softmax"),
    pytest.param(36, 6, "softmax", id="36-6-softmax")])
def test_route_lowers_without_gather(n_exp, top_k, score, dtype):
    """The chosen scores are picked by masked sums, so the router's program
    has no gather, at any width and under either scoring rule."""
    h = jax.ShapeDtypeStruct((T, D), getattr(jnp, dtype))
    w_router = jax.ShapeDtypeStruct((n_exp, D), getattr(jnp, dtype))
    bias = jax.ShapeDtypeStruct((n_exp,), jnp.float32)
    rule = () if score == "sigmoid" else tuple(
        _softmax_rule(n_exp).values())
    lowered = _router(top_k, 1e-5, *rule).lower(h, w_router,
                                                bias).as_text()
    assert "moe_route" in lowered
    assert "gather" not in lowered
    assert "gather" in _gather_router(top_k, 1e-5, *rule).lower(
        h, w_router, bias).as_text()


@pytest.mark.parametrize("first,count", [(0, 8), (8, 8), (24, 8), (0, 32)])
def test_held_plan_is_a_filter_of_the_full_plan(first, count):
    """`experts=(first, count)` keeps exactly the full dropless plan's
    entries on the held experts, renumbered, in the same slots."""
    rng = np.random.default_rng(first + count)
    e_topk = np.argsort(rng.random((T, E)), axis=1)[:, :K].astype(np.int32)
    w = rng.random((T, K)).astype(np.float32)
    full = plan_dispatch(e_topk, w, cap=np.full(E, T * K))
    assert full.dropped == 0 and full.stolen == 0
    held = plan_dispatch(e_topk, w, experts=(first, count))
    on = full.keep & (full.expert >= first) & (full.expert < first + count)
    np.testing.assert_array_equal(held.expert, full.expert[on] - first)
    np.testing.assert_array_equal(held.token, full.token[on])
    np.testing.assert_array_equal(held.weight, full.weight[on])
    np.testing.assert_array_equal(held.pos, full.pos[on])
    np.testing.assert_array_equal(held.counts,
                                  full.counts[first:first + count])
    assert held.n_experts == count and held.dropped == 0
    assert held.keep.all()
    indptr, tok, wt = held.csr()
    f_indptr, f_tok, f_wt = full.csr()
    lo, hi = f_indptr[first], f_indptr[first + count]
    np.testing.assert_array_equal(indptr, f_indptr[first:first + count + 1]
                                  - lo)
    np.testing.assert_array_equal(tok, f_tok[lo:hi])
    np.testing.assert_array_equal(wt, f_wt[lo:hi])


def test_held_plan_refuses_capacity():
    e_topk = np.zeros((4, 2), np.int32)
    with pytest.raises(ValueError, match="dropless"):
        plan_dispatch(e_topk, cap=np.ones(4), experts=(0, 2))
    with pytest.raises(ValueError, match="held experts"):
        plan_dispatch(e_topk, experts=(0, 0))


@pytest.mark.parametrize("p", [1, 4])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_layer_matches_reference(p, dtype):
    """Route, plan, build, apply, add: against the reference layer on the
    program's own choices (float32 rounding may swap a near-tie). bf16
    stores u, the activation and h in bf16 with float32 sums, as the
    reference does, so only summation order and its rare roundings
    differ."""
    np_dt = np.float32 if dtype == "float32" else ml_dtypes.bfloat16
    layer = _layer(3, np_dt)
    got, op, plan, e_np = _program(layer, 0, HELD, p, getattr(jnp, dtype))
    want, _ = moe_layer_ref(*layer, top_k=K, experts=(0, HELD),
                            dtype=np_dt, e_topk=e_np)
    assert plan.counts.sum() > 0
    err = np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)
    assert err.max() < (1e-6 if dtype == "float32" else 2e-3)


@pytest.mark.parametrize("p", [1, 4])
def test_shares_sum_to_the_whole_layer(p):
    """Four ranks of 8 experts: their updates sum to the uncut layer."""
    layer = _layer(4)
    h = layer[0]
    whole, e_np = moe_layer_ref(*layer, top_k=K, experts=(0, E))
    parts = [_program(layer, r * HELD, HELD, p)[0] - h
             for r in range(E // HELD)]
    np.testing.assert_allclose(h + sum(parts), whole, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("p", [1, 4])
def test_expert_load_equals_plan_counts(p):
    """Integer counts, exact, per held expert; the superstep stream sums
    to the shard partition's worker costs exactly."""
    _, op, plan, _ = _program(_layer(5), 8, HELD, p)
    np.testing.assert_array_equal(op.expert_load(),
                                  plan.counts.astype(np.float64))
    shards = op.schedule.shard()
    costs = np.asarray(op.last_costs)
    assert costs.shape == shards.block_perm.shape
    np.testing.assert_array_equal(
        costs.sum(axis=1),
        shards.worker_cost(op.schedule.tile_cost()).astype(np.float32))


@pytest.mark.parametrize("p", [1, 4])
def test_grid_streams_visit_each_segment_once(p):
    """Every real slot row is written by exactly one grid step, with its
    own expert; padding steps name no segment (n_seg) and repeat
    their worker's last blocks."""
    _, op, _, _ = _program(_layer(6), 0, HELD, p)
    src, dst, expert = (np.asarray(a) for a in op.more_streams)
    n_seg = op.vals.shape[0] * op.vals.shape[1]
    item = op.schedule.tiles.item_id.reshape(-1)
    real = np.flatnonzero(item >= 0)
    live = dst != n_seg
    np.testing.assert_array_equal(np.sort(dst[live]), real)
    np.testing.assert_array_equal(expert[live], item[dst[live]])
    np.testing.assert_array_equal(src[live], dst[live])
    per = src.reshape(p, -1), expert.reshape(p, -1), live.reshape(p, -1)
    for s, e, lv in zip(*per):
        for i in np.flatnonzero(~lv)[np.flatnonzero(~lv) > 0]:
            assert (s[i], e[i]) == (s[i - 1], e[i - 1])


def test_bucket_sizes():
    assert [_bucket(n) for n in range(10)] == [0, 1, 2, 3, 4, 6, 6, 8, 8, 12]
    assert _bucket(13) == 16 and _bucket(17) == 24 and _bucket(25) == 32


def test_moe_dispatch_uses_token_block_width():
    """Whole 128-token blocks at the band's width or above."""
    e = np.minimum(np.random.default_rng(13).zipf(1.3, (4_000, 2)) - 1, 15)
    plan = plan_dispatch(e, cap=np.full(16, 10_000))
    s = sched.LoopScheduler(p=2).build("moe-dispatch", plan).schedule
    assert s.width_rule is token_block_width
    assert s.width == token_block_width(s.sizes) == 512
    assert token_block_width(np.full(16, 4096)) == 512
    assert token_block_width(np.full(16, 3)) == 128
    assert token_block_width(np.full(16, 150), max_w=1024) == 256
