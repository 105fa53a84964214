"""Property-based tests (hypothesis) for the MoE dispatch invariants."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.configs import get_arch, reduced
from repro.models import moe as MOE


def _setup(E, K, T, seed, skew):
    cfg = reduced(get_arch("olmoe-1b-7b"), n_experts=E, experts_per_token=K,
                  d_model=32, moe_d_ff=32)
    p = MOE.init_moe(jax.random.PRNGKey(seed), cfg)
    if skew:
        p["router"] = p["router"].at[:, 0].add(float(skew))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (T, cfg.d_model))
    return cfg, p, x


@settings(max_examples=12, deadline=None)
@given(E=st.sampled_from([4, 8, 16]), K=st.sampled_from([1, 2, 4]),
       T=st.integers(16, 96), seed=st.integers(0, 50),
       skew=st.floats(0, 4))
def test_dispatch_accounting_invariant(E, K, T, seed, skew):
    """kept + dropped == T*K entries, capacity is never exceeded, and
    stealing never increases drops."""
    cfg, p, x = _setup(E, K, T, seed, skew)
    cap = jnp.ones((E,))
    for steal in (False, True):
        y, aux = MOE.moe_local(cfg, p, x, cap, steal=steal, capacity_factor=1.0)
        assert float(aux["entries"]) == T * K
        assert 0 <= float(aux["dropped"]) <= T * K
        assert bool(jnp.isfinite(y).all())
    _, a_ns = MOE.moe_local(cfg, p, x, cap, steal=False, capacity_factor=1.0)
    _, a_st = MOE.moe_local(cfg, p, x, cap, steal=True, capacity_factor=1.0)
    assert float(a_st["dropped"]) <= float(a_ns["dropped"]) + 1e-6


@settings(max_examples=10, deadline=None)
@given(E=st.sampled_from([4, 8]), T=st.integers(16, 64),
       seed=st.integers(0, 20))
def test_generous_capacity_matches_dropless(E, T, seed):
    """with capacity >> demand and no stealing, output equals the dropless
    top-k mixture exactly."""
    cfg, p, x = _setup(E, 2, T, seed, 0.0)
    y, aux = MOE.moe_local(cfg, p, x, jnp.ones((E,)) * 100, steal=False,
                           capacity_factor=50.0)
    assert float(aux["dropped"]) == 0
    probs = jax.nn.softmax((x @ p["router"]).astype(jnp.float32), -1)
    w, e = jax.lax.top_k(probs, 2)
    w = w / w.sum(-1, keepdims=True)
    y_ref = jnp.zeros_like(x)
    for j in range(2):
        h = jax.nn.silu(jnp.einsum("td,tdf->tf", x, p["wg"][e[:, j]])) * \
            jnp.einsum("td,tdf->tf", x, p["wi"][e[:, j]])
        y_ref = y_ref + w[:, j, None] * jnp.einsum("tf,tfd->td", h, p["wo"][e[:, j]])
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=5e-4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 100), eps=st.floats(0.1, 0.6))
def test_cap_scale_fixed_point_on_balanced_load(seed, eps):
    """uniform router load is a fixed point of the iCh capacity update."""
    counts = jnp.full((16,), 100.0)
    cap = jnp.ones((16,))
    new = MOE.ich_update_cap_scale(counts, cap, eps=eps)
    np.testing.assert_allclose(np.asarray(new), np.asarray(cap), atol=1e-6)
