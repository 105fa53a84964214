"""Pure-numpy oracle for the iCh-scheduled MoE expert-dispatch kernel."""
import numpy as np


def _silu(x):
    return x / (1.0 + np.exp(-x))


def moe_dispatch_ref(indptr, tok, w, x, wi, wg, wo):
    """Expert-major CSR apply: y[t] += w_entry * FFN_e(x[t]) over every
    kept dispatch entry of every expert e. The dispatch-plan analogue of
    spmv_ref: the plan's CSR (sched/moe.py DispatchPlan.csr) is the
    matrix, the gated expert FFN the per-entry work."""
    n_tokens, d = x.shape
    y = np.zeros((n_tokens, d), np.float32)
    E = len(indptr) - 1
    for e in range(E):
        lo, hi = int(indptr[e]), int(indptr[e + 1])
        if hi == lo:
            continue
        xs = x[tok[lo:hi]].astype(np.float32)          # (n_e, D)
        h = xs @ wi[e]
        g = xs @ wg[e]
        ye = (_silu(g) * h) @ wo[e]                    # (n_e, D)
        np.add.at(y, tok[lo:hi], ye * w[lo:hi, None])
    return y


def expert_loads_ref(indptr):
    """Per-expert kept token counts straight off the CSR layout."""
    return np.diff(np.asarray(indptr)).astype(np.int64)


# --------------------------------------------------- one routed-expert layer
def rms_norm_ref(h, eps=1e-5):
    """RMSNorm with gain 1, in float32."""
    h = np.asarray(h, np.float32)
    return h / np.sqrt(np.mean(h * h, axis=-1, keepdims=True) + np.float32(eps))


def route_ref(u, w_router, bias, top_k):
    """Sigmoid router with a selection bias: (e_topk (T, K), weights
    (T, K), scores (T, E)). The top K of s + bias are chosen (the lower id
    first among equals); each weighs s_e over the chosen experts' s."""
    u = np.asarray(u, np.float32)
    scores = 1.0 / (1.0 + np.exp(-(u @ np.asarray(w_router, np.float32).T)))
    sel = scores + np.asarray(bias, np.float32)
    e_topk = np.argsort(-sel, axis=1, kind="stable")[:, :top_k]
    w = np.take_along_axis(scores, e_topk, axis=1)
    return e_topk.astype(np.int32), w / w.sum(axis=1, keepdims=True), scores


def moe_layer_ref(h, w_router, bias, wi, wg, wo, *, top_k, experts,
                  eps=1e-5, dtype=np.float32, e_topk=None):
    """One routed-expert layer of an expert-parallel rank, in float32:
    h + sum over t's chosen experts e in [first, first + count) of
    g_e * wo_e^T (SiLU(wg_e^T u) * (wi_e^T u)), u = RMSNorm(h).

    wi/wg (count, D, F) and wo (count, F, D) are the held experts'
    weights; the router w_router (E, D) spans all E experts. `dtype` is
    the activations' storage type: u, the SwiGLU activation and the new h
    are rounded to it as the program stores them, every sum is float32.
    `e_topk` overrides the router's choices (weights stay the router's).
    Returns (new h, e_topk)."""
    def rnd(a):
        return np.asarray(a, np.float32).astype(dtype).astype(np.float32)

    first, count = experts
    h = np.asarray(h, np.float32)
    u = rnd(rms_norm_ref(h, eps))
    chosen, w, scores = route_ref(u, w_router, bias, top_k)
    if e_topk is not None:
        chosen = np.asarray(e_topk)
        w = np.take_along_axis(scores, chosen, axis=1)
        w = w / w.sum(axis=1, keepdims=True)
    y = np.zeros_like(h)
    for e in range(count):
        tok, k = np.nonzero(chosen == first + e)
        if tok.size == 0:
            continue
        xs = u[tok]
        a = rnd(_silu(xs @ np.asarray(wg[e], np.float32))
                * (xs @ np.asarray(wi[e], np.float32)))
        np.add.at(y, tok, (a @ np.asarray(wo[e], np.float32))
                  * w[tok, k][:, None])
    return rnd(h + y), chosen
