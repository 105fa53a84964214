"""The combine epilogue of the MoE expert kernel (`kernels/ich_moe/ich_moe.py`
`expert_ffn`) against `kernels/ich_moe/ref.py:moe_dispatch_ref`, in
interpret mode, on hand-built segments and worker layouts.

Each live segment's weighted rows are added into y by a read-modify-write
of y's rows, so the cases are the ones where an update could be lost or
misplaced: a token in consecutive segments (one worker at p = 1, two
workers above), token 0 as a real entry beside padding slots (whose token
id is 0 too), a token twice in one segment (a capacity plan's steal), an
expert with no entries, tokens no segment holds, a worker with padding
steps only, and a token count that is not a whole number of 8-row tiles.
The interpreter runs each DMA when it starts, so these cases show what is
copied where; whether copies in flight are waited for in the right order
shows only on the chip (the `moe-mimo-v2-flash.prefill` cell's check).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ich_moe.ich_moe import expert_ffn
from repro.kernels.ich_moe.ref import moe_dispatch_ref

N_TOKENS, D, E, W = 61, 128, 4, 128

# (expert, [(token, weight), ...]) per flat segment, in order
SEGMENTS = [
    (0, [(5, 0.5), (9, 0.25), (17, 1.0), (30, 0.125)]),
    (1, [(0, 0.75), (5, 0.5), (9, 2.0), (40, 0.5)]),   # token 0 real
    (2, [(5, 1.5), (33, 0.25), (0, 0.5)]),
    (3, []),                                            # no entries
    (1, [(7, 0.5), (12, 1.0), (7, 0.25)]),             # token 7 twice
    (0, [(60, 1.0), (0, 0.125), (12, 0.5)]),
]


def _pack(segments):
    """cols, vals (n_seg, W): live slots first, padding slots 0."""
    cols = np.zeros((len(segments), W), np.int32)
    vals = np.zeros((len(segments), W), np.float32)
    for s, (_, entries) in enumerate(segments):
        for r, (t, v) in enumerate(entries):
            cols[s, r], vals[s, r] = t, v
    return cols, vals


def _streams(per_worker, n_per, n_seg, experts):
    """src/dst/expert in `grid_streams` form: worker w runs its segments
    in order, then padding steps that repeat its last real step (segment
    0 and expert 0 before any) and name no segment (dst n_seg)."""
    src, dst, exp = [], [], []
    for segs in per_worker:
        last = 0
        for i in range(n_per):
            if i < len(segs):
                last = segs[i]
                dst.append(last)
            else:
                dst.append(n_seg)
            src.append(last)
            exp.append(experts[last])
    return (np.asarray(src, np.int32), np.asarray(dst, np.int32),
            np.asarray(exp, np.int32))


def _ffn(F, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((N_TOKENS, D)).astype(np.float32)
    wi = (rng.standard_normal((E, D, F)) * D ** -0.5).astype(np.float32)
    wg = (rng.standard_normal((E, D, F)) * D ** -0.5).astype(np.float32)
    wo = (rng.standard_normal((E, F, D)) * F ** -0.5).astype(np.float32)
    return x, wi, wg, wo


def _run(segments, per_worker, F):
    cols, vals = _pack(segments)
    experts = [e for e, _ in segments]
    n_per = max(len(s) for s in per_worker) + 1  # every worker pads
    src, dst, exp = _streams(per_worker, n_per, len(segments), experts)
    x, wi, wg, wo = _ffn(F)
    y = expert_ffn(jnp.asarray(x[cols]), jnp.asarray(cols),
                   jnp.asarray(vals), jnp.asarray(wi), jnp.asarray(wg),
                   jnp.asarray(wo), jnp.asarray(src), jnp.asarray(dst),
                   jnp.asarray(exp), n_tokens=N_TOKENS, p=len(per_worker),
                   interpret=True)
    return np.asarray(y), (x, wi, wg, wo)


def _reference(segments, x, wi, wg, wo):
    """The same entries as an expert-major CSR through the oracle."""
    by_expert = [[(t, v) for e2, entries in segments if e2 == e
                  for t, v in entries] for e in range(E)]
    indptr = np.cumsum([0] + [len(b) for b in by_expert])
    tok = np.asarray([t for b in by_expert for t, _ in b], np.int64)
    w = np.asarray([v for b in by_expert for _, v in b], np.float32)
    return moe_dispatch_ref(indptr, tok, w, x, wi, wg, wo)


@pytest.mark.parametrize("F", [256, 1024])  # one F tile, two F tiles
@pytest.mark.parametrize("p", [1, 2, 4])
def test_combine_matches_reference(p, F):
    # segments dealt round-robin over p - 1 workers (all of them at p = 1):
    # segments 0 and 1, which share tokens 5 and 9, run back to back on
    # one worker at p = 1 and on two workers above; the last worker holds
    # padding steps only when p > 1
    busy = max(p - 1, 1)
    per_worker = [list(range(w, len(SEGMENTS), busy)) if w < busy else []
                  for w in range(p)]
    y, weights = _run(SEGMENTS, per_worker, F)
    want = _reference(SEGMENTS, *weights)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    held = {t for _, entries in SEGMENTS for t, _ in entries}
    untouched = [t for t in range(N_TOKENS) if t not in held]
    assert untouched and not np.any(y[untouched])  # exactly 0


@pytest.mark.parametrize("p", [1, 2, 4])
def test_all_padding_grid_leaves_y_zero(p):
    """No worker holds a live segment: no row of y is read or written."""
    y, _ = _run(SEGMENTS[:1], [[] for _ in range(p)], 256)
    assert y.shape == (N_TOKENS, D) and not np.any(y)
