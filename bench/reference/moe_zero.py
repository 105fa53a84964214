"""Plain layers of zero-compute MoE on an expert-parallel rank, in numpy
float32 (LongCat-Flash, arXiv:2509.01322 section 2).

The router has E outputs: FFN experts, then `zero=(first, count)`
zero-compute (identity) experts. For token h (D,) of a layer, with the
rank holding FFN experts [first, first + count):

    u = RMSNorm(h)                       gain 1, eps from the config
    s = softmax(u W_r^T)                 (E,) float32
    S = top-K of s + b                   b: the selection bias
    g_i = scale * s_i                    not normalised over S
    y = sum_{i in S, zero} g_i u + sum_{i in S, held} g_i FFN_i(u)
    h <- h + y

with FFN_i(u) = W_down,i (SiLU(W_gate,i u) * (W_up,i u)).

Every rank applies the identity part; the FFN part is the held experts'.
Rounding and the control's bfloat16 sums follow `bench/reference/moe.py`,
whose helpers this module uses.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

from bench.reference.moe import f32, matmul, rel_err, rms_norm, rnd, top_k

__all__ = ["scores", "layer", "forward", "rel_err"]


def scores(u: np.ndarray, w_router, bias) -> tuple[np.ndarray, np.ndarray]:
    """(s, s + b): the softmax scores over all outputs and the selection
    scores."""
    logits = u @ f32(w_router).T
    s = np.exp(logits - logits.max(axis=1, keepdims=True))
    s = s / s.sum(axis=1, keepdims=True)
    return s, s + f32(bias)


def layer(h, lay, *, first: int, count: int, k: int, eps: float,
          scale: float, zero, dtype=ml_dtypes.bfloat16, acc: str = "float32",
          u_route=None, choices=None, margin: float = 0.0) -> dict:
    """One layer for tokens h (T, D); `lay` holds w_router (E, D), bias
    (E,), wi/wg (count, D, F) and wo (count, F, D). `u_route`, `choices`
    and `margin` as in `bench/reference/moe.py` `layer`.

    Returns {u, s (T, E), chosen (T, k), margin (T,), w0 (T,), y (T, D)
    float32, h}: the normalised input, the scores, the choices, their
    margin, the identity weight, the layer's update and the output h."""
    h = f32(h)
    u = rnd(rms_norm(h, eps), dtype)
    s, sel = scores(u if u_route is None else f32(u_route),
                    lay["w_router"], lay["bias"])
    chosen, gap = top_k(sel, k)
    if choices is not None:
        chosen = np.where((gap < margin)[:, None], choices, chosen)
    g = np.float32(scale) * np.take_along_axis(s, chosen, axis=1)
    z_first, z_count = zero
    on_zero = (chosen >= z_first) & (chosen < z_first + z_count)
    w0 = np.where(on_zero, g, 0.0).sum(axis=1, dtype=np.float32)
    y = w0[:, None] * u
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
    return {"u": u, "s": s, "chosen": chosen, "margin": gap, "w0": w0,
            "y": y, "h": rnd(h + y, dtype)}


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
