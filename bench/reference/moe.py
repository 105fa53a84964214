"""Plain routed-expert layers of an expert-parallel rank, in numpy float32.

For token h (D,) of layer l, with the rank holding experts
[first, first + count) of E:

    u = RMSNorm(h)                       gain 1, eps from the config
    s = sigmoid(u W_r^T)                 (E,) float32
    S = top-K of s + b                   b: the selection bias
    g_i = s_i / sum_{j in S} s_j
    h <- h + sum_{i in S, held} g_i W_down,i (SiLU(W_gate,i u) * (W_up,i u))

The configuration stores u, the SwiGLU activation and h in bfloat16 and
sums in float32; `dtype` rounds the three where the program stores them.
With `acc="bfloat16"` (the control) each expert product also keeps its
sums in bfloat16: the partial sum of every 128-deep pass of the MXU is
rounded, and the passes are added in bfloat16.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

PASS = 128  # contraction depth of one MXU pass


def f32(a) -> np.ndarray:
    return np.asarray(a).astype(np.float32)


def rnd(a, dtype) -> np.ndarray:
    return np.asarray(a, np.float32).astype(dtype).astype(np.float32)


def rms_norm(h: np.ndarray, eps: float) -> np.ndarray:
    return h / np.sqrt(np.mean(h * h, axis=-1, keepdims=True)
                       + np.float32(eps))


def scores(u: np.ndarray, w_router, bias) -> tuple[np.ndarray, np.ndarray]:
    """(s, s + b): the sigmoid scores and the selection scores."""
    s = 1.0 / (1.0 + np.exp(-(u @ f32(w_router).T)))
    return s, s + f32(bias)


def top_k(sel: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
    """(chosen (T, k), margin (T,)): the k best of `sel`, the lower id
    first among equals, and each row's gap between its k-th and
    (k+1)-th."""
    order = np.argsort(-sel, axis=1, kind="stable")
    ranked = np.take_along_axis(sel, order[:, :k + 1], axis=1)
    return order[:, :k], ranked[:, k - 1] - ranked[:, k]


def matmul(a: np.ndarray, b: np.ndarray, acc: str) -> np.ndarray:
    if acc == "float32":
        return a @ b
    bf16 = ml_dtypes.bfloat16
    out = np.zeros((a.shape[0], b.shape[1]), np.float32)
    for k in range(0, a.shape[1], PASS):
        part = rnd(a[:, k:k + PASS] @ b[k:k + PASS], bf16)
        out = rnd(out + part, bf16)
    return out


def layer(h, lay, *, first: int, count: int, k: int, eps: float,
          dtype=ml_dtypes.bfloat16, acc: str = "float32", u_route=None,
          choices=None, margin: float = 0.0) -> dict:
    """One layer for tokens h (T, D); `lay` holds w_router (E, D), bias
    (E,), wi/wg (count, D, F) and wo (count, F, D).

    `u_route` (T, D), where given, is another run's normalised input: the
    router scores it in place of this u, so that a one-ulp flip of u
    between two runs does not move the scores compared. `choices` (T, k),
    where given, are another run's expert choices: tokens whose
    k-th/(k+1)-th margin is below `margin` take them in place of their
    own (a near-tie may go either way on rounding).

    Returns {u, chosen (T, k), margin (T,), y (T, D) float32, h}: the
    normalised input, the choices, their margin, the layer's update and
    the output h."""
    h = f32(h)
    u = rnd(rms_norm(h, eps), dtype)
    s, sel = scores(u if u_route is None else f32(u_route),
                    lay["w_router"], lay["bias"])
    chosen, gap = top_k(sel, k)
    if choices is not None:
        chosen = np.where((gap < margin)[:, None], choices, chosen)
    g = np.take_along_axis(s, chosen, axis=1)
    g = g / g.sum(axis=1, keepdims=True)
    y = np.zeros_like(h)
    for e in range(count):
        tok, slot = np.nonzero(chosen == first + e)
        if tok.size == 0:
            continue
        x = u[tok]
        gate = matmul(x, f32(lay["wg"][e]), acc)
        up = matmul(x, f32(lay["wi"][e]), acc)
        a = rnd(gate / (1.0 + np.exp(-gate)) * up, dtype)
        out = matmul(a, f32(lay["wo"][e]), acc)
        np.add.at(y, tok, out * g[tok, slot][:, None])
    return {"u": u, "chosen": chosen, "margin": gap, "y": y,
            "h": rnd(h + y, dtype)}


def forward(h, layers, **kw) -> list[dict]:
    """Tokens h (T, D) through `layers` in turn: per layer, `layer`'s
    record with its input h as `h_in`."""
    out = []
    for lay in layers:
        r = layer(h, lay, **kw)
        r["h_in"] = f32(h)
        out.append(r)
        h = r["h"]
    return out


def rel_err(y: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per token: |y - ref| / |ref| (float64 norms); where ref is 0, 0 if
    y is 0 too, else 1."""
    y, ref = np.asarray(y, np.float64), np.asarray(ref, np.float64)
    diff = np.linalg.norm(y - ref, axis=1)
    norm = np.linalg.norm(ref, axis=1)
    return np.where(norm > 0, diff / np.where(norm > 0, norm, 1.0),
                    (diff > 0).astype(np.float64))
