"""Tests for the worker-sharded kernel execution layer (DESIGN.md §2.6):
cost-balanced block-granular tile partitioning, the (p, S_B) zero-copy
shard layout, superstep-padded CSR packing, the simulator cross-check
(`policies.assigned` / `Schedule.replay_sharded`), and the sharded kernels'
fold-order guarantee for all three workloads: the run at p=1, superstep=1
matches `ref.py`, and every other (p, superstep) reproduces its bits."""
import functools

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from conftest import random_csr as _random_csr
from conftest import skewed_csr

from repro.core import policies as P
from repro.core import tiling as T
from repro.core.simulator import SimParams
from repro.sched.api import LoopScheduler

_NO_OVERHEAD = SimParams(dispatch_overhead=0.0, local_dispatch_overhead=0.0,
                         speed_jitter=0.0)

_SIZES = st.lists(st.one_of(st.just(0), st.integers(0, 40),
                            st.integers(200, 3000)),
                  min_size=1, max_size=120)


# ------------------------------------------------------------ partitioning
@settings(max_examples=30, deadline=None)
@given(sizes=_SIZES, R=st.integers(1, 17), p=st.integers(1, 9),
       B=st.integers(1, 8))
def test_partition_item_closed_and_layout_valid(sizes, R, p, B):
    """Every tile is assigned exactly one worker, the assignment is
    constant within each superstep block, no item's tiles span two
    workers, and the (p, S_B) block layout lists each worker's blocks
    exactly once in ascending order."""
    sizes = np.asarray(sizes, np.int64)
    sched = T.build_schedule(sizes, rows_per_tile=R)
    costs = 1.0 + sizes.astype(np.float64)
    tc = sched.tile_cost(costs, sizes)
    worker = T.partition_tiles(tc, sched.item_id, p, block=B)
    assert worker.shape == (sched.n_tiles,)
    assert worker.min() >= 0 and worker.max() < p
    # constant within each B-tile block
    np.testing.assert_array_equal(
        worker, np.repeat(worker[::B], B)[:sched.n_tiles])
    # item-closed: the tiles holding any one item sit on one worker
    for item in range(len(sizes)):
        tiles = np.nonzero((sched.item_id == item).any(axis=1))[0]
        assert len(np.unique(worker[tiles])) == 1
    shards = T.make_shards(worker, p, superstep=B)
    assert shards.p == p and shards.superstep == B
    assert shards.tiles_per_worker == shards.n_steps * B
    n_blocks = -(-sched.n_tiles // B)
    bp = shards.block_perm
    np.testing.assert_array_equal(np.sort(bp[bp >= 0]), np.arange(n_blocks))
    perm = shards.perm
    np.testing.assert_array_equal(np.sort(perm[perm >= 0]),
                                  np.arange(sched.n_tiles))
    assert shards.n_tiles_padded % B == 0
    assert shards.n_tiles_padded >= sched.n_tiles
    for w in range(p):
        row = perm[w][perm[w] >= 0]
        assert (np.diff(row) > 0).all()  # ascending global tile order
        np.testing.assert_array_equal(row, np.nonzero(worker == w)[0])


@settings(max_examples=25, deadline=None)
@given(sizes=_SIZES, R=st.integers(1, 17), p=st.integers(1, 9))
def test_lpt_partition_matches_simulator_per_worker_work(sizes, R, p):
    """The LPT partition's per-worker cost — and its max, the predicted
    sharded makespan — must match a zero-overhead simulator replay that
    dispatches every tile on its assigned worker."""
    sizes = np.asarray(sizes, np.int64)
    if int(sizes.sum()) == 0:
        return  # no work units: nothing for the simulator to dispatch
    costs = 1.0 + sizes.astype(np.float64)
    scheduler = LoopScheduler(p=p, cache_size=0)
    s = scheduler.schedule(np.asarray(costs), rows_per_tile=R)
    shards = s.shard()
    wc = shards.worker_cost(s.tile_cost())
    assert wc.shape == (p,)
    np.testing.assert_allclose(wc.sum(), s.tile_cost().sum(), atol=1e-9)
    rep = s.replay_sharded(params=_NO_OVERHEAD)
    # every tile dispatched on its assigned worker with its predicted work
    assert rep.chunks == s.n_tiles
    sim_wc = np.zeros(p)
    for (b, e, w, work) in rep.chunk_log:
        assert shards.worker[np.searchsorted(
            s.unit_ranges()[:, 1], b, side="right")] == w
        sim_wc[w] += work
    np.testing.assert_allclose(sim_wc, wc, atol=1e-9)
    np.testing.assert_allclose(rep.makespan, wc.max(), atol=1e-9)


@pytest.mark.parametrize("n,p,R", [(60, 1, 8), (250, 3, 8), (400, 8, 4)])
def test_replay_sharded_per_worker_work_deterministic(n, p, R):
    """Deterministic twin of the hypothesis cross-check above: per-worker
    dispatched work equals the partition's worker_cost and the
    zero-overhead makespan equals its max."""
    rng = np.random.default_rng(n + p)
    costs = rng.uniform(0.5, 5.0, n)
    costs[rng.choice(n, 5, replace=False)] += rng.exponential(60.0, 5)
    s = LoopScheduler(p=p, cache_size=0).schedule(costs, rows_per_tile=R)
    shards = s.shard()
    wc = shards.worker_cost(s.tile_cost())
    rep = s.replay_sharded(params=_NO_OVERHEAD)
    assert rep.chunks == s.n_tiles
    sim_wc = np.zeros(p)
    for (b, e, w, work) in rep.chunk_log:
        sim_wc[w] += work
    np.testing.assert_allclose(sim_wc, wc, atol=1e-9)
    np.testing.assert_allclose(rep.makespan, wc.max(), atol=1e-9)
    # and the assignment covers exactly the tile ranges per worker
    ranges = s.unit_ranges()
    log = np.array([(b, e) for (b, e, _, _) in rep.chunk_log])
    np.testing.assert_array_equal(log, ranges)


def test_partition_lpt_balances_heavy_tail():
    """A zipf-heavy 2000-item workload must spread within a few percent of
    perfectly even across 8 workers (block-chains are fine-grained
    there)."""
    rng = np.random.default_rng(3)
    sizes = np.minimum(rng.zipf(1.8, 2000), 500).astype(np.int64)
    sizes[rng.random(2000) < 0.1] = 0
    sched = T.build_schedule(sizes, rows_per_tile=8)
    costs = 1.0 + sizes.astype(np.float64)
    tc = sched.tile_cost(costs, sizes)
    shards = T.shard_schedule(sched, tc, 8)
    wc = shards.worker_cost(tc)
    assert wc.max() <= 1.15 * wc.mean()


def test_make_shards_rejects_block_misaligned_worker_map():
    # superstep blocks must be whole: a worker map that flips mid-block
    # was partitioned at the wrong granularity
    with pytest.raises(ValueError, match="not constant within superstep"):
        T.make_shards(np.array([0, 1, 0, 1], np.int32), 2, superstep=2)
    # out-of-range worker ids (map built for a different p) fail loudly
    with pytest.raises(ValueError, match=r"lie in \[0, 2\)"):
        T.make_shards(np.array([0, 5], np.int32), 2, superstep=1)


def test_assigned_policy_validates_inputs():
    with pytest.raises(ValueError, match="worker assignments"):
        P.assigned([(0, 5), (5, 9)], [0])
    with pytest.raises(ValueError, match="must be >= 0"):
        P.assigned([(0, 5), (5, 9)], [0, -1])
    from repro.core.simulator import simulate
    with pytest.raises(ValueError, match=r"outside \[0, 2\)"):
        simulate(np.ones(9), 2, P.assigned([(0, 5), (5, 9)], [0, 4]))


# ----------------------------------------------------- superstep-padded pack
@settings(max_examples=20, deadline=None)
@given(sizes=_SIZES, R=st.integers(1, 17),
       W=st.one_of(st.none(), st.integers(1, 600)), B=st.integers(1, 8),
       seed=st.integers(0, 99))
def test_pack_csr_pad_tiles_matches_reference(sizes, R, W, B, seed):
    """pack_csr(pad_tiles_to=B) — the payload the sharded kernels fetch
    blocks from — must equal the loop reference oracle on the real tiles
    and be all-zero on the pad tiles."""
    sizes = np.asarray(sizes, np.int64)
    sched = T.build_schedule(sizes, rows_per_tile=R, width=W)
    rng = np.random.default_rng(seed)
    indptr = np.concatenate([[0], np.cumsum(sizes)])
    nnz = int(indptr[-1])
    indices = rng.integers(0, sizes.size, nnz).astype(np.int32)
    data = rng.standard_normal(nnz).astype(np.float32)
    vp, cp = T.pack_csr(indptr, indices, data, sched, pad_tiles_to=B)
    Tn = sched.n_tiles
    T_pad = -(-Tn // B) * B
    assert vp.shape == (T_pad, R, sched.width)
    vr, cr = T._reference_pack_csr(indptr, indices, data, sched)
    np.testing.assert_array_equal(vp[:Tn], vr)
    np.testing.assert_array_equal(cp[:Tn], cr)
    assert (vp[Tn:] == 0).all() and (cp[Tn:] == 0).all()


def test_pack_csr_gather_fallback_matches_reference():
    """Nonzero indptr[0] (CSR slice views) breaks the sequential-stream
    precondition; pack_csr must detect it and still match the oracle."""
    rng = np.random.default_rng(11)
    sizes = np.minimum(rng.zipf(1.7, 150), 300).astype(np.int64)
    sched = T.build_schedule(sizes, rows_per_tile=8)
    indptr = np.concatenate([[0], np.cumsum(sizes)]) + 7
    nnz = int(indptr[-1])
    indices = rng.integers(0, 150, nnz).astype(np.int32)
    data = rng.standard_normal(nnz).astype(np.float32)
    vr, cr = T._reference_pack_csr(indptr, indices, data, sched)
    for B in (1, 8):
        v, c = T.pack_csr(indptr, indices, data, sched, pad_tiles_to=B)
        Tn = sched.n_tiles
        np.testing.assert_array_equal(v[:Tn], vr)
        np.testing.assert_array_equal(c[:Tn], cr)
        assert (v[Tn:] == 0).all() and (c[Tn:] == 0).all()


# ------------------------------------------- sharded kernel bit-identity
HUB = 600  # nonzeros of the hub row: 75 segments at W = 8


def _csr_case(case, n, seed):
    """(indptr, indices, data, width): the zipf matrix at the band's width,
    or a lognormal body (band 32) with one hub row of HUB nonzeros at the
    gather rule's W = 8, where the hub splits over about ten tiles."""
    if case == "zipf_band":
        return (*_random_csr(n, seed=seed), None)
    return (*skewed_csr(n, seed, hubs=[(n // 3, HUB)]), 8)


def _check_hub_split(case, s, n):
    if case == "hub_w8":
        assert s.width == 8
        assert (s.item_id == n // 3).any(axis=1).sum() >= HUB // (8 * 8)


def _shard_args(s, p, B):
    shards = s.shard(p=p, superstep=B)
    return (shards, shards.shard_item_id(s.tiles),
            shards.kernel_block_ids())


# every (p, superstep) but the witness's own (1, 1)
_P_B = [(p, B) for p in (1, 2, 4) for B in (1, 4, 8) if (p, B) != (1, 1)]


def _spmv_sharded(s, indptr, indices, data, x, p, B):
    import jax.numpy as jnp
    from repro.kernels.ich_spmv.ich_spmv import ich_spmv_sharded

    _, rid, blk = _shard_args(s, p, B)
    vp, cp = T.pack_csr(indptr, indices, data, s.tiles, pad_tiles_to=B)
    return np.asarray(ich_spmv_sharded(
        jnp.asarray(vp), jnp.asarray(cp), jnp.asarray(rid), jnp.asarray(blk),
        jnp.asarray(x), len(indptr) - 1, p, B, interpret=True))


@functools.lru_cache(maxsize=None)
def _spmv_case(case):
    """The case's schedule and inputs, and its witness: the sharded grid
    at p=1, superstep=1, which folds tile by tile in schedule order."""
    n = 180
    indptr, indices, data, width = _csr_case(case, n, 1)
    x = np.random.default_rng(1).standard_normal(n).astype(np.float32)
    s = LoopScheduler(cache_size=0).schedule(np.diff(indptr), width=width)
    _check_hub_split(case, s, n)
    csr = (indptr, indices, data)
    return s, csr, x, _spmv_sharded(s, *csr, x, 1, 1)


@pytest.mark.parametrize("case", ["zipf_band", "hub_w8"])
@pytest.mark.parametrize("p,B", _P_B)
def test_sharded_spmv_bits_independent_of_p_and_superstep(p, B, case):
    from repro.kernels.ich_spmv.ref import spmv_ref

    s, csr, x, witness = _spmv_case(case)
    np.testing.assert_allclose(witness, spmv_ref(*csr, x),
                               atol=1e-4, rtol=1e-4)
    y = _spmv_sharded(s, *csr, x, p, B)
    np.testing.assert_array_equal(y, witness)  # bitwise, fp add order


def _bfs_sharded(s, indptr, indices, frontier, visited, p, B):
    import jax.numpy as jnp
    from repro.kernels.ich_bfs.ich_bfs import ich_bfs_step_sharded

    _, rid, blk = _shard_args(s, p, B)
    ones = np.ones(int(indptr[-1]), np.float32)
    mp, cp = T.pack_csr(indptr, indices, ones, s.tiles, pad_tiles_to=B)
    return np.asarray(ich_bfs_step_sharded(
        jnp.asarray(mp), jnp.asarray(cp), jnp.asarray(rid), jnp.asarray(blk),
        jnp.asarray(frontier), jnp.asarray(visited), len(indptr) - 1, p, B,
        interpret=True))


@functools.lru_cache(maxsize=None)
def _bfs_case(case):
    """As `_spmv_case`, for one BFS frontier expansion."""
    n = 160
    indptr, indices, _, width = _csr_case(case, n, 21)
    frontier = (np.random.default_rng(21).random(n) < 0.08).astype(
        np.float32)
    visited = frontier.copy()
    s = LoopScheduler(cache_size=0).schedule(np.diff(indptr), width=width)
    _check_hub_split(case, s, n)
    args = (indptr, indices, frontier, visited)
    return s, args, _bfs_sharded(s, *args, 1, 1)


@pytest.mark.parametrize("case", ["zipf_band", "hub_w8"])
@pytest.mark.parametrize("p,B", _P_B)
def test_sharded_bfs_bits_independent_of_p_and_superstep(p, B, case):
    from repro.kernels.ich_bfs.ref import bfs_step_ref

    s, args, witness = _bfs_case(case)
    np.testing.assert_array_equal(witness, bfs_step_ref(*args))
    np.testing.assert_array_equal(_bfs_sharded(s, *args, p, B), witness)


def _kmeans_sharded(s, pts, cent, p, B):
    import jax.numpy as jnp
    from repro.kernels.ich_kmeans.ich_kmeans import ich_kmeans_assign_sharded

    _, rid, _ = _shard_args(s, p, B)
    return np.asarray(ich_kmeans_assign_sharded(
        jnp.asarray(pts), jnp.asarray(cent), jnp.asarray(rid), p, B,
        interpret=True))


@functools.lru_cache(maxsize=None)
def _kmeans_case():
    """As `_spmv_case`, for one K-Means assignment over heavy-tailed
    per-point costs."""
    rng = np.random.default_rng(41)
    n = 150
    costs = rng.uniform(1.0, 9.0, n)
    costs[rng.choice(n, 4, replace=False)] += rng.exponential(70.0, 4)
    s = LoopScheduler(cache_size=0).schedule(costs)
    pts = rng.standard_normal((n, 6)).astype(np.float32)
    cent = rng.standard_normal((7, 6)).astype(np.float32)
    return s, pts, cent, _kmeans_sharded(s, pts, cent, 1, 1)


@pytest.mark.parametrize("p,B", _P_B)
def test_sharded_kmeans_bits_independent_of_p_and_superstep(p, B):
    from repro.kernels.ich_kmeans.ref import kmeans_assign_ref

    s, pts, cent, witness = _kmeans_case()
    np.testing.assert_array_equal(witness, kmeans_assign_ref(pts, cent))
    np.testing.assert_array_equal(_kmeans_sharded(s, pts, cent, p, B),
                                  witness)


@pytest.mark.parametrize("case", ["zipf_band", "hub_w8"])
def test_registry_ops_run_sharded_and_match_refs(case):
    """The registry ops (the production path) execute the sharded kernels
    at the schedule's p and still match the numpy oracles; on the hub
    matrix the gather rule gives them W = 8."""
    from repro.kernels.ich_bfs.ref import bfs_levels_ref
    from repro.kernels.ich_spmv.ref import spmv_ref

    rng = np.random.default_rng(8)
    n = 140
    indptr, indices, data, _ = _csr_case(case, n, 8)
    scheduler = LoopScheduler(p=4, cache_size=0)
    spmv = scheduler.build("spmv", indptr, indices, data)
    _check_hub_split(case, spmv.schedule, n)
    assert spmv.p == 4
    assert spmv.vals.shape[0] % spmv.superstep == 0  # whole supersteps
    x = rng.standard_normal(n).astype(np.float32)
    np.testing.assert_allclose(np.asarray(spmv(x, interpret=True)),
                               spmv_ref(indptr, indices, data, x),
                               atol=1e-4, rtol=1e-4)
    bfs = scheduler.build("bfs", indptr, indices)
    _check_hub_split(case, bfs.schedule, n)
    np.testing.assert_array_equal(bfs.levels(0, interpret=True),
                                  bfs_levels_ref(indptr, indices, 0))


# --------------------------------------------------- degenerate lowerings
def _bit_identity_spmv(s, indptr, indices, data, p, B):
    """Sharded SpMV on schedule `s` at (p, B) against the witness at
    p=1, superstep=1."""
    n = len(indptr) - 1
    rng = np.random.default_rng(p * 31 + B)
    x = rng.standard_normal(n).astype(np.float32)
    witness = _spmv_sharded(s, indptr, indices, data, x, 1, 1)
    y = _spmv_sharded(s, indptr, indices, data, x, p, B)
    np.testing.assert_array_equal(y, witness)
    return s.shard(p=p, superstep=B)


@pytest.mark.parametrize("case", ["p_exceeds_blocks", "superstep_exceeds_T",
                                  "p_one"])
def test_shard_degenerate_lowerings_bit_identical(case):
    """The degenerate shard shapes — more workers than superstep blocks
    (idle workers), a superstep larger than the whole tile axis (one
    block, p-1 idle workers), and p=1 (everything on one worker) — must
    all produce valid layouts, agree with the simulator's static replay,
    and stay bit-identical to the run at p=1, superstep=1."""
    n = 40
    indptr, indices, data = _random_csr(n, seed=13)
    s = LoopScheduler(cache_size=0).schedule(np.diff(indptr),
                                             rows_per_tile=4)
    Tn = s.n_tiles
    p, B = {"p_exceeds_blocks": (max(Tn, 3) + 2, 2),
            "superstep_exceeds_T": (3, Tn + 5),
            "p_one": (1, 4)}[case]
    shards = _bit_identity_spmv(s, indptr, indices, data, p, B)
    assert shards.p == p and shards.superstep == B
    n_blocks = -(-Tn // B)
    # every block placed exactly once; idle workers hold only -1 padding
    bp = shards.block_perm
    np.testing.assert_array_equal(np.sort(bp[bp >= 0]), np.arange(n_blocks))
    idle = ~(bp >= 0).any(axis=1)
    assert idle.sum() == max(0, p - len(np.unique(shards.worker)))
    # simulator static replay: per-worker dispatched work == partition cost
    wc = shards.worker_cost(s.tile_cost())
    assert wc.shape == (p,)
    assert (wc[idle] == 0).all()
    rep = s.replay_sharded(p=p, superstep=B, params=_NO_OVERHEAD)
    sim_wc = np.zeros(p)
    for (b, e, w, work) in rep.chunk_log:
        sim_wc[w] += work
    np.testing.assert_allclose(sim_wc, wc, atol=1e-9)
    np.testing.assert_allclose(rep.makespan, wc.max(), atol=1e-9)
    if case == "p_one":
        # p=1 static assignment degenerates to the sequential tile order
        np.testing.assert_array_equal(shards.worker, np.zeros(Tn, np.int32))
        np.testing.assert_array_equal(
            np.array([(b, e) for (b, e, _, _) in rep.chunk_log]),
            s.unit_ranges())


def test_shard_memoized_per_p_and_superstep():
    scheduler = LoopScheduler(p=2, cache_size=0)
    s = scheduler.schedule(np.arange(1, 200, dtype=np.int64))
    a = s.shard()
    assert s.shard() is a  # memoized on the Schedule
    b = s.shard(p=4)
    assert b is not a and b.p == 4
    c = s.shard(superstep=2)
    assert c is not a and c.superstep == 2
    assert s.shard() is a  # defaults still hit the original entry
