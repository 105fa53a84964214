"""Tests for the measured-cost feedback loop (DESIGN.md §2.7): the
vectorized Welford recurrence, CostRefiner attribution, the
observe() -> refine() round on the Schedule facade, cache-generation
invalidation, the executor's per-chunk instrumentation and deterministic
replay, and the sharded kernels' per-worker cost output."""
import threading

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from conftest import random_csr as _random_csr

from repro.core import policies as P
from repro.core.executor import parallel_for
from repro.core.simulator import SimParams
from repro.core.welford import Welford, WelfordVec
from repro.sched import LoopScheduler, NnzCosts
from repro.sched import get as sched_get
from repro.sched.api import Schedule

_ZERO = SimParams(dispatch_overhead=0.0, local_dispatch_overhead=0.0,
                  speed_jitter=0.0)

# one observe/refine round must never cost more than this factor of the
# unrefined makespan on the self-balancing central replay (empirically the
# worst over wide sweeps is ~1.25; 1.5 catches systematic attribution bugs
# without flaking on adversarial hypothesis cases)
ROUND_TOL = 1.5

_SIZES = st.lists(st.one_of(st.just(0), st.integers(0, 40),
                            st.integers(200, 3000)),
                  min_size=1, max_size=120)


# ----------------------------------------------------------- WelfordVec
@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 999), n=st.integers(1, 20),
       rounds=st.integers(1, 6))
def test_welford_vec_matches_scalar_oracle(seed, n, rounds):
    """Lane i of WelfordVec after folding its observed samples must equal
    a scalar Welford fed the same samples, including masked-out rounds."""
    rng = np.random.default_rng(seed)
    vec = WelfordVec.zeros(n)
    oracles = [Welford() for _ in range(n)]
    for _ in range(rounds):
        xs = rng.exponential(10.0, n)
        mask = rng.random(n) < 0.7
        vec.update(xs, mask)
        for i in range(n):
            if mask[i]:
                oracles[i].update(xs[i])
    for i in range(n):
        assert vec.count[i] == oracles[i].count
        np.testing.assert_allclose(vec.mean[i], oracles[i].mean, atol=1e-12)
        np.testing.assert_allclose(vec.variance[i], oracles[i].variance,
                                   atol=1e-9)


# ----------------------------------------------- observe/refine properties
def _jittered(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(20, 300))
    est = rng.uniform(0.5, 10.0, n)
    if rng.random() < 0.4:
        heavy = rng.choice(n, max(1, n // 30), replace=False)
        est[heavy] += rng.exponential(100.0, heavy.size)
    true = est * rng.uniform(0.25, 4.0, n)
    return est, true


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), p=st.integers(1, 8),
       R=st.integers(1, 17), level=st.sampled_from(["item", "tile"]))
def test_one_refine_round_never_blows_up_central_makespan(seed, p, R, level):
    """One observe/refine round on a jittered workload keeps the central
    pretiled replay makespan within ROUND_TOL of the unrefined one."""
    est, true = _jittered(seed)
    s = LoopScheduler(p=p, cache_size=0).schedule(est, rows_per_tile=R)
    m0 = s.replay_refined(true, params=_ZERO).makespan
    if level == "item":
        s1 = s.observe(true, level="item").refine()
    else:
        rep = s.replay_refined(true, params=_ZERO, record_chunks=True)
        tile_true = np.array([wk for (*_, wk) in rep.chunk_log])
        s1 = s.observe(tile_true, level="tile").refine()
    assert s1.generation == 1
    m1 = s1.replay_refined(true, params=_ZERO).makespan
    assert m1 <= m0 * ROUND_TOL + 1e-9


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), p=st.integers(1, 8),
       R=st.integers(1, 17))
def test_exact_cost_refinement_converges_to_true_schedule(seed, p, R):
    """Refinement from EXACT per-item observations reproduces scheduling
    on the true costs: the refined schedule's tiles and replayed makespan
    equal a schedule constructed from the true costs directly."""
    est, true = _jittered(seed)
    scheduler = LoopScheduler(p=p, cache_size=0)
    s1 = scheduler.schedule(est, rows_per_tile=R) \
        .observe(true, level="item").refine()
    s_true = scheduler.schedule(true, rows_per_tile=R)
    np.testing.assert_array_equal(s1.costs, s_true.costs)
    np.testing.assert_array_equal(s1.sizes, s_true.sizes)
    np.testing.assert_array_equal(s1.item_id, s_true.item_id)
    m1 = s1.replay_refined(true, sharded=True, params=_ZERO).makespan
    mt = s_true.replay_refined(true, sharded=True, params=_ZERO).makespan
    assert m1 == mt


@pytest.mark.parametrize("seed,p,R", [(0, 4, 8), (7, 2, 8), (23, 8, 4)])
def test_exact_cost_refinement_converges_deterministic(seed, p, R):
    """Deterministic twin of the hypothesis convergence property (runs in
    environments without hypothesis)."""
    est, true = _jittered(seed)
    scheduler = LoopScheduler(p=p, cache_size=0)
    s1 = scheduler.schedule(est, rows_per_tile=R) \
        .observe(true, level="item").refine()
    s_true = scheduler.schedule(true, rows_per_tile=R)
    np.testing.assert_array_equal(s1.item_id, s_true.item_id)
    assert s1.replay_refined(true, sharded=True, params=_ZERO).makespan \
        == s_true.replay_refined(true, sharded=True, params=_ZERO).makespan
    m0 = scheduler.schedule(est, rows_per_tile=R) \
        .replay_refined(true, params=_ZERO).makespan
    m1 = s1.replay_refined(true, params=_ZERO).makespan
    assert m1 <= m0 * ROUND_TOL + 1e-9


@pytest.mark.parametrize("seed", [1, 2, 11])
def test_refine_rounds_monotone_on_structural_workload(seed):
    """With structural sizes (NnzCosts: tiling fixed, only the worker
    partition re-weights) the sharded makespan on true costs is
    monotonically non-increasing across observe/refine rounds and reaches
    a fixed point once the tile costs are learned exactly — the
    refine-loop invariant."""
    rng = np.random.default_rng(seed)
    n = 3000
    sizes = np.minimum(rng.zipf(1.8, n), 800).astype(np.int64)
    sizes[rng.random(n) < 0.1] = 0
    indptr = np.concatenate([[0], np.cumsum(sizes)])
    true = (1.0 + sizes) * rng.uniform(0.3, 3.0, n)
    s = LoopScheduler(p=8).schedule(NnzCosts(indptr))
    ms = []
    for r in range(4):
        rep = s.replay_refined(true, sharded=True, params=_ZERO,
                               record_chunks=True)
        ms.append(rep.makespan)
        tile_true = np.array([wk for (*_, wk) in rep.chunk_log])
        s = s.observe(tile_true, level="tile").refine()
        np.testing.assert_array_equal(s.sizes, np.diff(indptr))  # structural
    assert all(b <= a + 1e-9 for a, b in zip(ms, ms[1:])), ms
    assert ms[1] < ms[0]          # the first round visibly improves
    assert ms[2] == pytest.approx(ms[1], rel=1e-12)  # then a fixed point


# ------------------------------------------------ cache generation keying
def test_refine_reenters_cache_under_fresh_generation():
    sizes = np.arange(1, 200, dtype=np.int64)
    scheduler = LoopScheduler(p=4, cache_size=8)
    s0 = scheduler.schedule(sizes)
    sh0 = s0.shard()
    rep = s0.replay(record_chunks=True)
    s1 = s0.observe(rep).refine()
    assert s1 is not s0 and s1.generation == 1
    assert scheduler.cache_stats.misses == 2  # gen-1 entry is a new build
    # the refined schedule's lowering is its own, never the stale one
    assert s1.shard() is not sh0
    # an identical second refine from the same refiner state is a HIT on
    # the generation-1 entry (same refined content, same generation)
    hits = scheduler.cache_stats.hits
    assert s0.refine() is s1
    assert scheduler.cache_stats.hits == hits + 1
    # chaining advances the generation again
    s2 = s1.observe(s1.replay(record_chunks=True)).refine()
    assert s2.generation == 2 and s2 is not s1


def test_refine_without_scheduler_rebuilds_directly():
    """Hand-assembled Schedules (no facade) still refine."""
    import repro.core.tiling as T

    sizes = np.arange(1, 60, dtype=np.int64)
    costs = sizes.astype(np.float64)
    tiles = T.build_schedule(sizes)
    s = Schedule(sizes=sizes, costs=costs, policy=P.ich(), p=2, tiles=tiles)
    s1 = s.observe(costs * 2.0, level="item").refine()
    assert s1.generation == 1
    np.testing.assert_allclose(s1.costs, costs * 2.0)


# ------------------------------------------- executor instrumentation
def test_deterministic_replay_identical_steal_trace():
    """`parallel_for` with a distributed policy, a fixed seed, and
    deterministic=True must produce identical chunk and steal traces
    across two runs — the accounting guard for the per-chunk
    instrumentation."""
    n = 700
    for policy in (P.ich(), P.stealing(4)):
        logs = []
        for _ in range(2):
            hits = np.zeros(n, np.int64)
            stats = parallel_for(n, lambda i: hits.__setitem__(
                i, hits[i] + 1), 4, policy, seed=9, record_chunks=True,
                deterministic=True)
            assert (hits == 1).all()
            logs.append(([(b, e, w) for (b, e, w, _) in stats.chunk_log],
                         stats.steal_log, stats.chunks, stats.steals))
        assert logs[0] == logs[1]
        chunk_trace, steal_trace, chunks, steals = logs[0]
        assert chunks == len(chunk_trace)
        assert steals == len(steal_trace)
        # the trace covers every iteration exactly once
        seen = np.zeros(n, np.int64)
        for b, e, _ in chunk_trace:
            seen[b:e] += 1
        assert (seen == 1).all()


def test_chunk_timing_recorded_on_both_executor_paths():
    n = 400
    for policy, distributed in ((P.dynamic(16), False), (P.guided(1), False),
                                (P.ich(), True), (P.stealing(8), True)):
        hits = np.zeros(n, np.int64)
        lock = threading.Lock()

        def body(i):
            with lock:
                hits[i] += 1

        stats = parallel_for(n, body, 3, policy, seed=2, record_chunks=True)
        assert (hits == 1).all()
        assert stats.chunk_log is not None
        assert len(stats.chunk_log) == stats.chunks
        seen = np.zeros(n, np.int64)
        for b, e, w, dt in stats.chunk_log:
            assert 0 <= w < 3 and dt >= 0.0
            seen[b:e] += 1
        assert (seen == 1).all()
        assert (stats.steal_log is not None) == distributed


def test_record_chunks_off_keeps_logs_none():
    stats = parallel_for(50, lambda i: None, 2, P.ich(), seed=0)
    assert stats.chunk_log is None and stats.steal_log is None


def test_schedule_observe_from_executor_wall_clock():
    """parallel_for_units chunk timings feed the refiner through the
    normalizing ExecStats path: estimate mass is preserved while relative
    per-item costs move toward the measurements."""
    rng = np.random.default_rng(4)
    costs = rng.uniform(1.0, 9.0, 120)
    s = LoopScheduler(p=2, cache_size=0).schedule(costs)
    stats = s.parallel_for_units(lambda u: None, seed=1)
    with pytest.raises(ValueError, match="no chunk_log"):
        s.observe(stats)
    stats = s.parallel_for_units(lambda u: None, seed=1, record_chunks=True)
    s.observe(stats)
    r = s.refiner
    assert (r.stats.count > 0).any()
    refined = r.refined_costs()
    # wall-clock normalization keeps the total estimate mass (ratio ~1)
    assert refined.sum() == pytest.approx(float(s.costs.sum()), rel=0.2)


def test_observe_simresult_ambiguous_space_requires_flag():
    """sizes [3, 0, 0]: a replay's unit-space ranges must not be silently
    read as item ranges (zero-work items would gain cost)."""
    s = LoopScheduler(p=2, cache_size=0).schedule(
        np.array([3, 0, 0], np.int64))
    rep = s.replay(record_chunks=True)
    with pytest.raises(ValueError, match="non-uniform sizes"):
        s.observe(rep)
    s1 = s.observe(rep, space="units").refine()
    # all measured work stays on item 0; zero-size items stay at zero
    np.testing.assert_allclose(s1.costs, [3.0, 0.0, 0.0])
    with pytest.raises(ValueError, match="items space has 3"):
        # a simulate() run over a different n can't claim item space
        bad = s.simulate(record_chunks=True, policy=P.dynamic(1))
        bad.n = 5
        s.observe(bad, space="items")


def test_observe_execstats_ambiguous_space_requires_flag():
    """sizes [2, 0, 1]: n_items == n_units == 3 but the spaces distribute
    differently — auto inference must refuse, an explicit space works."""
    s = LoopScheduler(p=2, cache_size=0).schedule(
        np.array([2, 0, 1], np.int64))
    stats = s.parallel_for_units(lambda u: None, record_chunks=True)
    with pytest.raises(ValueError, match="non-uniform sizes"):
        s.observe(stats)
    s.observe(stats, space="units")
    assert (s.refiner.stats.count > 0).any()
    with pytest.raises(ValueError, match="'units'"):
        s.observe(stats, space="bogus")


def test_observe_validations():
    s = LoopScheduler(cache_size=0).schedule(np.arange(1, 50,
                                                       dtype=np.int64))
    with pytest.raises(ValueError, match="matches neither"):
        s.observe(np.ones(s.n_items + s.n_tiles + 1))
    with pytest.raises(ValueError, match="no chunk_log"):
        s.observe(s.replay(record_chunks=False))
    with pytest.raises(ValueError, match="unknown observation level"):
        s.observe(np.ones(s.n_items), level="bogus")
    with pytest.raises(ValueError, match="cannot identify a lowering"):
        s.observe(np.ones((13, 17)))


def test_worker_step_observation_names_its_lowering():
    """A (p, S_B) shape alone cannot identify a shard lowering — distinct
    supersteps can share a block grid (12 tiles, p=3: superstep 2 and 3
    both lower to (3, 2)). observe() therefore attributes through the
    DEFAULT lowering unless the caller passes `shards=`, and a
    non-default lowering routed explicitly must update the refiner."""
    sizes = np.full(12 * 8, 4, np.int64)  # uniform -> exactly 12 tiles
    s = LoopScheduler(p=3, cache_size=0).schedule(sizes, width=4)
    assert s.n_tiles == 12
    sh2, sh3 = s.shard(superstep=2), s.shard(superstep=3)
    assert sh2.block_perm.shape == sh3.block_perm.shape == (3, 2)
    measured = np.abs(np.random.default_rng(0).standard_normal((3, 2))) + 1
    before = s.refiner.rounds
    s.observe(measured, shards=sh3)
    assert s.refiner.rounds == before + 1
    # shape mismatch against the NAMED lowering still fails loudly
    with pytest.raises(ValueError, match="cannot identify a lowering"):
        s.observe(np.ones((3, 5)), shards=sh3)


# -------------------------------------------- kernel cost-output routing
def test_sharded_kernel_costs_sum_to_schedule_totals_exactly():
    """The ops' emitted per-worker, per-superstep cost streams must sum to
    the schedule's tile-cost totals: bit-exact for SpMV/BFS (integer nnz
    costs stay exact in float32) and to float tolerance for K-Means."""
    rng = np.random.default_rng(8)
    n = 140
    indptr, indices, data = _random_csr(n, seed=8)
    scheduler = LoopScheduler(p=4, cache_size=0)

    spmv = scheduler.build("spmv", indptr, indices, data)
    spmv(rng.standard_normal(n).astype(np.float32), interpret=True)
    emitted = np.asarray(spmv.last_costs)
    shards = spmv.schedule.shard()
    assert emitted.shape == shards.block_perm.shape
    np.testing.assert_array_equal(
        emitted.sum(axis=1),
        shards.worker_cost(spmv.schedule.tile_cost()).astype(np.float32))

    bfs = scheduler.build("bfs", indptr, indices)
    bfs.step(np.ones(n, np.float32), np.zeros(n, np.float32),
             interpret=True)
    emitted = np.asarray(bfs.last_costs)
    shards = bfs.schedule.shard()
    np.testing.assert_array_equal(
        emitted.sum(axis=1),
        shards.worker_cost(bfs.schedule.tile_cost()).astype(np.float32))

    km = scheduler.build("kmeans", rng.uniform(1.0, 20.0, 64))
    km(rng.standard_normal((64, 4)).astype(np.float32),
       rng.standard_normal((5, 4)).astype(np.float32), interpret=True)
    emitted = np.asarray(km.last_costs)
    shards = km.schedule.shard()
    np.testing.assert_allclose(emitted.sum(axis=1),
                               shards.worker_cost(km.schedule.tile_cost()),
                               rtol=1e-5)


def test_op_observe_refine_roundtrip_keeps_outputs_identical():
    """Closing the loop through the kernels must not change payload
    semantics: ops rebuilt on the refined schedule produce outputs equal
    to the unrefined ops' for the same inputs (bit-identical for SpMV —
    structural sizes keep the tiling, and the sharded grids are
    fold-order-exact for any partition — and exactly equal for BFS levels
    and K-Means assignments)."""
    rng = np.random.default_rng(3)
    n = 120
    indptr, indices, data = _random_csr(n, seed=3)
    scheduler = LoopScheduler(p=4, cache_size=0)

    spmv = scheduler.build("spmv", indptr, indices, data)
    x = rng.standard_normal(n).astype(np.float32)
    y0 = np.asarray(spmv(x, interpret=True))
    refined_s = spmv.observe().refine()
    assert refined_s.generation == 1
    spmv2 = sched_get("spmv").build(refined_s, indptr, indices, data)
    np.testing.assert_array_equal(np.asarray(spmv2(x, interpret=True)), y0)

    bfs = scheduler.build("bfs", indptr, indices)
    lv0 = bfs.levels(0, interpret=True)
    bfs2 = sched_get("bfs").build(bfs.observe().refine(), indptr, indices)
    np.testing.assert_array_equal(bfs2.levels(0, interpret=True), lv0)

    costs = rng.uniform(1.0, 20.0, 64)
    km = scheduler.build("kmeans", costs)
    pts = rng.standard_normal((64, 4)).astype(np.float32)
    cent = rng.standard_normal((5, 4)).astype(np.float32)
    a0 = np.asarray(km(pts, cent, interpret=True))
    km2 = sched_get("kmeans").build(km.observe().refine(), costs)
    np.testing.assert_array_equal(np.asarray(km2(pts, cent,
                                                 interpret=True)), a0)


def test_op_observe_requires_an_invocation():
    indptr, indices, data = _random_csr(60, seed=1)
    op = LoopScheduler(cache_size=0).build("spmv", indptr, indices, data)
    with pytest.raises(ValueError, match="no kernel invocation"):
        op.observe()
