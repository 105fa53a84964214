"""Plain jax.numpy reference of an MoE layer with zero-compute experts.

LongCat-Flash's MoE layer (arXiv:2509.01322, section 2, "zero-computation
experts"; the model's config.json): N FFN experts and Z zero-compute
experts of the identity type share one router of E = N + Z outputs, the
zero-compute ones last. For token h (D,):

    u = RMSNorm(h)                       gain 1
    s = softmax(u W_r^T)                 (E,) float32
    S = top-K of s + b                   b: the selection bias
    g_e = scale * s_e                    routed_scaling_factor, no
                                         renormalisation over S
    y = sum_{e in S, e < N} g_e SwiGLU-FFN_e(u) + (sum_{e in S, e >= N} g_e) u
    h' = h + y

A token's FFN work is the share of its K picks that lands below N: 0 to K
experts. Everything is float32 under the highest matmul precision, with
no kernel, plan or batching: each held expert's FFN runs on every token
and is weighed by that token's gate, 0 where it did not pick it.

Departures from the report:

* the layer stands alone as h + MoE(RMSNorm(h)): the shortcut-connected
  (ScMoE) block's attention and dense FFNs, which run beside the MoE
  branch, are left out;
* one expert-parallel rank's share: the FFN experts [first, first +
  count) of the N are summed, the identity part on every rank
  (`identity=False` leaves it out);
* `dtype` rounds u, the SwiGLU activation and h' where a program stores
  them (bfloat16 in the benchmark's configuration); the sums stay
  float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def moe_zero_layer_ref(h, w_router, bias, wi, wg, wo, *, top_k: int,
                       experts, zero, scale: float, eps: float = 1e-5,
                       dtype=jnp.float32, e_topk=None,
                       identity: bool = True):
    """One layer for tokens h (T, D): w_router (E, D) over all E outputs,
    bias (E,), wi/wg (count, D, F) and wo (count, F, D) the held FFN
    experts `experts=(first, count)`; `zero=(first, count)` the
    zero-compute experts. `e_topk` (T, top_k), where given, replaces the
    router's choices (the gates stay its scores).

    Returns (h' (T, D), e_topk (T, top_k), w0 (T,)): the new h, the
    choices and each token's identity weight, all float32."""
    first, count = experts
    z_first, z_count = zero

    def rnd(a):
        return a.astype(dtype).astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(h, jnp.float32)
        u = rnd(h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True)
                                  + eps))
        s = jax.nn.softmax(u @ jnp.asarray(w_router, jnp.float32).T, axis=-1)
        if e_topk is None:
            _, e_topk = jax.lax.top_k(s + jnp.asarray(bias, jnp.float32),
                                      top_k)
        e_topk = jnp.asarray(e_topk)
        picked = jnp.zeros_like(s).at[
            jnp.arange(s.shape[0])[:, None], e_topk].set(1.0)
        gate = scale * s * picked                                # (T, E)
        w0 = jnp.sum(gate[:, z_first:z_first + z_count], axis=-1)
        y = w0[:, None] * u if identity else jnp.zeros_like(u)
        for e in range(count):
            up = u @ jnp.asarray(wi[e], jnp.float32)
            g = u @ jnp.asarray(wg[e], jnp.float32)
            a = rnd(g * jax.nn.sigmoid(g) * up)
            y = y + gate[:, first + e, None] * (
                a @ jnp.asarray(wo[e], jnp.float32))
        return rnd(h + y), e_topk, w0
