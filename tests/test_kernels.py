"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.ich_bfs.ref import bfs_levels_ref, bfs_step_ref
from repro.kernels.ich_kmeans.ref import kmeans_assign_ref
from repro.kernels.ich_spmv.ref import spmv_ref, tiles_ref
from repro.kernels.mamba_scan.mamba_scan import mamba_scan
from repro.kernels.mamba_scan.ref import ssd_ref
from repro.sched import LoopScheduler

RNG = np.random.default_rng(0)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("B,S,Hq,Hkv,dh", [
    (1, 64, 2, 2, 64),
    (2, 128, 4, 2, 64),
    (1, 256, 8, 8, 128),
    (2, 192, 6, 3, 64),
    (1, 512, 4, 1, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, Hq, Hkv, dh, dtype):
    q = jnp.array(RNG.standard_normal((B, S, Hq, dh)), dtype)
    k = jnp.array(RNG.standard_normal((B, S, Hkv, dh)), dtype)
    v = jnp.array(RNG.standard_normal((B, S, Hkv, dh)), dtype)
    out = flash_attention(q, k, v, causal=True, q_block=64, kv_block=64,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_flash_attention_noncausal():
    q = jnp.array(RNG.standard_normal((2, 128, 4, 64)), jnp.float32)
    k = jnp.array(RNG.standard_normal((2, 128, 4, 64)), jnp.float32)
    v = jnp.array(RNG.standard_normal((2, 128, 4, 64)), jnp.float32)
    out = flash_attention(q, k, v, causal=False, q_block=64, kv_block=64,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_attention_op_pads_ragged_seq():
    q = jnp.array(RNG.standard_normal((1, 100, 4, 64)), jnp.float32)
    k = jnp.array(RNG.standard_normal((1, 100, 2, 64)), jnp.float32)
    v = jnp.array(RNG.standard_normal((1, 100, 2, 64)), jnp.float32)
    out = flash_attention_op(q, k, v, q_block=32, kv_block=32, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)


# ------------------------------------------------------------------ ich_spmv
def _random_csr(n, zipf_a, seed=0, max_nnz=300):
    rng = np.random.default_rng(seed)
    row_nnz = np.minimum(rng.zipf(zipf_a, n), max_nnz)
    indptr = np.concatenate([[0], np.cumsum(row_nnz)]).astype(np.int64)
    nnz = int(indptr[-1])
    indices = rng.integers(0, n, nnz).astype(np.int32)
    data = rng.standard_normal(nnz).astype(np.float32)
    return indptr, indices, data


@pytest.mark.parametrize("n,zipf_a,R", [(100, 1.6, 4), (256, 1.9, 8),
                                        (333, 2.5, 8), (64, 1.3, 16)])
def test_ich_spmv_sweep(n, zipf_a, R):
    indptr, indices, data = _random_csr(n, zipf_a, seed=n)
    x = np.random.default_rng(1).standard_normal(n).astype(np.float32)
    op = LoopScheduler(rows_per_tile=R, cache_size=0).build(
        "spmv", indptr, indices, data)
    y_ref = spmv_ref(indptr, indices, data, x)
    # packing oracle on the op's uploaded payload (isolates
    # schedule-construction bugs)
    Tn = op.schedule.n_tiles
    np.testing.assert_allclose(
        tiles_ref(np.asarray(op.vals)[:Tn], np.asarray(op.cols)[:Tn],
                  op.schedule.item_id, x, n),
        y_ref, atol=1e-4, rtol=1e-4)
    y = op(jnp.asarray(x), interpret=True)
    np.testing.assert_allclose(y, y_ref, atol=1e-4, rtol=1e-4)


def test_ich_spmv_ops_wrapper():
    """The registry op at the facade's defaults."""
    indptr, indices, data = _random_csr(128, 1.8, seed=7)
    op = LoopScheduler().build("spmv", indptr, indices, data)
    x = jnp.array(np.random.default_rng(2).standard_normal(128), jnp.float32)
    np.testing.assert_allclose(op(x, interpret=True),
                               spmv_ref(indptr, indices, data, x),
                               atol=1e-4, rtol=1e-4)


def test_ich_spmv_empty_rows():
    indptr = np.array([0, 0, 3, 3, 5], np.int64)  # rows 0 and 2 empty
    indices = np.array([0, 1, 2, 1, 3], np.int32)
    data = np.ones(5, np.float32)
    x = jnp.arange(4, dtype=jnp.float32) + 1.0
    op = LoopScheduler(rows_per_tile=4, cache_size=0).build(
        "spmv", indptr, indices, data)
    np.testing.assert_allclose(op(x, interpret=True),
                               spmv_ref(indptr, indices, data, x), atol=1e-6)


# ------------------------------------------------------------------- ich_bfs
def _random_graph(n, kind, seed):
    rng = np.random.default_rng(seed)
    if kind == "uniform":
        deg = rng.integers(1, 21, n)
    else:  # scale-free, P(k) ~ k^-2.3 as in workloads.bfs_levels
        deg = np.minimum(rng.zipf(2.3, n), n // 4)
    indptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int64)
    indices = rng.integers(0, n, int(indptr[-1])).astype(np.int32)
    return indptr, indices


@pytest.mark.parametrize("n,kind,R", [(100, "uniform", 4),
                                      (256, "scale_free", 8),
                                      (200, "uniform", 8),
                                      (150, "scale_free", 16)])
def test_ich_bfs_levels_sweep(n, kind, R):
    indptr, indices = _random_graph(n, kind, seed=n)
    g = LoopScheduler(rows_per_tile=R, cache_size=0).build(
        "bfs", indptr, indices)
    np.testing.assert_array_equal(g.levels(0, interpret=True),
                                  bfs_levels_ref(indptr, indices, 0))


def test_ich_bfs_single_step_matches_ref():
    indptr, indices = _random_graph(128, "uniform", seed=3)
    g = LoopScheduler(cache_size=0).build("bfs", indptr, indices)
    rng = np.random.default_rng(4)
    frontier = (rng.random(128) < 0.1).astype(np.float32)
    visited = np.maximum(frontier, (rng.random(128) < 0.3)).astype(np.float32)
    out = g.step(frontier, visited, interpret=True)
    np.testing.assert_allclose(out, bfs_step_ref(indptr, indices, frontier,
                                                 visited), atol=1e-5)


def test_ich_bfs_isolated_source():
    # source with no in-neighbors anywhere pointing out: frontier dies after
    # expansion; unreached vertices stay at -1
    indptr = np.array([0, 0, 1, 2], np.int64)   # v0 no in-nbrs; v1<-0; v2<-1
    indices = np.array([0, 1], np.int32)
    g = LoopScheduler(rows_per_tile=4, cache_size=0).build(
        "bfs", indptr, indices)
    np.testing.assert_array_equal(g.levels(0, interpret=True),
                                  np.array([0, 1, 2], np.int32))
    np.testing.assert_array_equal(g.levels(2, interpret=True),
                                  np.array([-1, -1, 0], np.int32))


# ---------------------------------------------------------------- ich_kmeans
@pytest.mark.parametrize("n,D,K,R", [(100, 4, 3, 4), (256, 8, 16, 8),
                                     (333, 2, 5, 8), (64, 16, 2, 16)])
def test_ich_kmeans_assign_sweep(n, D, K, R):
    rng = np.random.default_rng(n)
    pts = rng.standard_normal((n, D)).astype(np.float32)
    cent = rng.standard_normal((K, D)).astype(np.float32)
    costs = rng.uniform(6.0, 10.0, n)
    costs[rng.choice(n, max(n // 50, 1), replace=False)] += \
        rng.exponential(120.0, max(n // 50, 1))
    km = LoopScheduler(rows_per_tile=R, cache_size=0).build("kmeans", costs)
    out = np.asarray(km(pts, cent, interpret=True))
    np.testing.assert_allclose(out, kmeans_assign_ref(pts, cent), atol=1e-5)


def test_ich_kmeans_heavy_point_split_is_idempotent():
    # a point far heavier than max_w occupies many slots; its assignment is
    # recomputed per slot and must still be written exactly once per value
    costs = np.full(32, 7.0)
    costs[5] = 10_000.0
    km = LoopScheduler(cache_size=0).build("kmeans", costs, width=8)
    assert (km.schedule.item_id == 5).sum() > 1  # genuinely split
    rng = np.random.default_rng(9)
    pts = rng.standard_normal((32, 3)).astype(np.float32)
    cent = rng.standard_normal((4, 3)).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(km(pts, cent, interpret=True)),
                                  kmeans_assign_ref(pts, cent))


# ---------------------------------------------------------------- mamba_scan
@pytest.mark.parametrize("B,S,H,N,Pd,chunk", [
    (1, 128, 2, 16, 32, 64),
    (2, 256, 3, 16, 32, 64),
    (1, 256, 1, 64, 64, 128),
    (2, 128, 4, 8, 16, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mamba_scan_sweep(B, S, H, N, Pd, chunk, dtype):
    q = jnp.array(RNG.standard_normal((B, S, H, N)), dtype)
    k = jnp.array(RNG.standard_normal((B, S, H, N)), dtype)
    v = jnp.array(RNG.standard_normal((B, S, H, Pd)), dtype)
    la = jnp.array(-np.abs(RNG.standard_normal((B, S, H))) * 0.2, jnp.float32)
    y, s = mamba_scan(q, k, v, la, chunk=chunk, interpret=True)
    y_ref, s_ref = ssd_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                           v.astype(jnp.float32), la, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=_tol(dtype) * 10, rtol=_tol(dtype) * 10)
    np.testing.assert_allclose(s, s_ref, atol=_tol(dtype) * 10,
                               rtol=_tol(dtype) * 10)


def test_mamba_scan_matches_sequential():
    """End-to-end: kernel vs the plain sequential recurrence."""
    B, S, H, N, Pd = 1, 64, 2, 8, 16
    q = np.asarray(RNG.standard_normal((B, S, H, N)), np.float32)
    k = np.asarray(RNG.standard_normal((B, S, H, N)), np.float32)
    v = np.asarray(RNG.standard_normal((B, S, H, Pd)), np.float32)
    la = -np.abs(RNG.standard_normal((B, S, H))).astype(np.float32) * 0.3
    St = np.zeros((B, H, Pd, N))
    ys = []
    for t in range(S):
        a = np.exp(la[:, t])[:, :, None, None]
        St = St * a + np.einsum("bhn,bhp->bhpn", k[:, t], v[:, t])
        ys.append(np.einsum("bhn,bhpn->bhp", q[:, t], St))
    y_ref = np.stack(ys, 1)
    y, s = mamba_scan(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                      jnp.asarray(la), chunk=32, interpret=True)
    np.testing.assert_allclose(y, y_ref, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(s, St.swapaxes(-1, -2), atol=1e-4, rtol=1e-4)
