"""Tests for the unified `repro.sched` API: facade, cost providers,
registry, schedule cache (refine generations included), and cross-backend
round-trips."""
import contextlib
import threading

import numpy as np
import pytest
from conftest import random_csr as _random_csr
from conftest import skewed_csr

from repro import sched
from repro.core import policies as P
from repro.core import tiling as T
from repro.sched.api import LoopScheduler, Schedule
from repro.sched.costs import (DegreeCosts, ExplicitCosts, NnzCosts,
                               as_cost_provider, quantize_costs)
from repro.sched.registry import register, unregister


# ------------------------------------------------------------ cost providers
def test_explicit_costs_int_keeps_zeros_float_quantizes():
    ints = ExplicitCosts(np.array([0, 3, 1], np.int64))
    np.testing.assert_array_equal(ints.sizes(), [0, 3, 1])
    floats = ExplicitCosts(np.array([0.2, 3.7, 1.0]))
    np.testing.assert_array_equal(floats.sizes(), [1, 4, 1])  # ceil, >= 1
    np.testing.assert_array_equal(floats.costs(), [0.2, 3.7, 1.0])
    np.testing.assert_array_equal(
        floats.sizes(), quantize_costs(np.array([0.2, 3.7, 1.0])))


def test_cost_provider_fingerprints():
    a = np.array([1, 2, 3], np.int64)
    assert ExplicitCosts(a).fingerprint() == ExplicitCosts(a.copy()).fingerprint()
    assert ExplicitCosts(a).fingerprint() != \
        ExplicitCosts(np.array([1, 2, 4], np.int64)).fingerprint()
    indptr = np.array([0, 2, 5], np.int64)
    # same content, different provider kinds -> different cache identity
    assert NnzCosts(indptr).fingerprint() != DegreeCosts(indptr).fingerprint()
    np.testing.assert_array_equal(NnzCosts(indptr).sizes(), [2, 3])


def test_as_cost_provider_passthrough_and_wrap():
    p = ExplicitCosts(np.arange(1, 4))
    assert as_cost_provider(p) is p
    assert isinstance(as_cost_provider(np.arange(1, 4)), ExplicitCosts)


# ------------------------------------------------------------------- facade
def test_schedule_matches_direct_tiling():
    sizes = np.minimum(np.random.default_rng(0).zipf(1.8, 400), 100)
    s = LoopScheduler().schedule(sizes.astype(np.int64))
    direct = T.build_schedule(sizes.astype(np.int64),
                              rows_per_tile=sched.ROWS_PER_TILE,
                              eps=sched.ICH_EPS)
    np.testing.assert_array_equal(s.item_id, direct.item_id)
    np.testing.assert_array_equal(s.lower().seg_start, direct.seg_start)
    np.testing.assert_array_equal(s.lower().seg_len, direct.seg_len)
    assert s.width == direct.width


def test_cache_hit_returns_same_object_and_skips_construction():
    sizes = np.arange(1, 200, dtype=np.int64)
    scheduler = LoopScheduler(cache_size=4)
    s1 = scheduler.schedule(sizes)
    s2 = scheduler.schedule(sizes)
    assert s2 is s1
    assert scheduler.cache_stats.hits == 1
    assert scheduler.cache_stats.misses == 1
    # different policy / p / construction params are different entries
    scheduler.schedule(sizes, policy=P.ich(0.5))
    scheduler.schedule(sizes, p=2)
    scheduler.schedule(sizes, rows_per_tile=16)
    assert scheduler.cache_stats.misses == 4


def test_cache_keys_worker_partition_params_distinct_p_no_collision():
    """p and superstep are worker-PARTITION parameters now (the Schedule
    lowers to a p-worker shard layout), so distinct values must be
    distinct cache entries — a p=2 schedule's memoized shards must never
    be served to a p=4 caller."""
    sizes = np.arange(1, 300, dtype=np.int64)
    scheduler = LoopScheduler(cache_size=8)
    s2 = scheduler.schedule(sizes, p=2)
    s4 = scheduler.schedule(sizes, p=4)
    assert s2 is not s4
    assert scheduler.cache_stats.misses == 2
    assert scheduler.cache_stats.hits == 0
    # each lowers to its own worker count by default
    assert s2.shard().p == 2 and s4.shard().p == 4
    assert s2.shard().worker.shape == s4.shard().worker.shape
    # repeat calls hit their own entries
    assert scheduler.schedule(sizes, p=2) is s2
    assert scheduler.schedule(sizes, p=4) is s4
    assert scheduler.cache_stats.hits == 2
    # superstep is part of the key too (it shapes the padded layout)
    s2b = scheduler.schedule(sizes, p=2, superstep=2)
    assert s2b is not s2 and s2b.shard().superstep == 2
    assert scheduler.cache_stats.misses == 3


def test_cache_distinguishes_policies_with_lossy_labels():
    # taskloop(4) and taskloop(16) share label() == "taskloop"; the cache
    # keys on the full Policy dataclass so they must NOT alias
    sizes = np.arange(1, 100, dtype=np.int64)
    scheduler = LoopScheduler()
    s4 = scheduler.schedule(sizes, policy=P.taskloop(4))
    s16 = scheduler.schedule(sizes, policy=P.taskloop(16))
    assert s4 is not s16
    assert s16.policy.num_tasks == 16
    assert scheduler.cache_stats.misses == 2
    # same for pretiled policies with equal chunk counts, distinct ranges
    pa = scheduler.schedule(sizes, policy=P.pretiled([(0, 50), (50, 99)]))
    pb = scheduler.schedule(sizes, policy=P.pretiled([(0, 10), (10, 99)]))
    assert pa is not pb and pa.policy.label() == pb.policy.label()


def test_schedule_inherits_scheduler_sim_params():
    from repro.core.simulator import SimParams

    params = SimParams(speed_jitter=0.0, seed=7)
    scheduler = LoopScheduler(p=4, sim_params=params)
    s = scheduler.schedule(np.arange(1, 120, dtype=np.int64))
    assert s.sim_params is params
    # zero jitter => exactly-even worker speeds; replay under the instance
    # params must differ from an explicit default-params run on this seed
    r = s.simulate(policy=P.dynamic(2))
    r_default = s.simulate(policy=P.dynamic(2), params=SimParams())
    assert r.makespan != r_default.makespan


def test_explicit_costs_copy_insulates_cached_schedule():
    sizes = np.arange(1, 80, dtype=np.int64)
    scheduler = LoopScheduler()
    s = scheduler.schedule(sizes)
    total = int(s.sizes.sum())
    sizes[:] = 1  # caller reuses its buffer
    assert int(s.sizes.sum()) == total  # cached Schedule is unaffected


def test_cache_lru_eviction():
    scheduler = LoopScheduler(cache_size=2)
    a = scheduler.schedule(np.arange(1, 50, dtype=np.int64))
    scheduler.schedule(np.arange(1, 60, dtype=np.int64))
    scheduler.schedule(np.arange(1, 70, dtype=np.int64))  # evicts `a`
    assert scheduler.cache_stats.evictions == 1
    a2 = scheduler.schedule(np.arange(1, 50, dtype=np.int64))
    assert a2 is not a  # rebuilt after eviction, equal content
    np.testing.assert_array_equal(a2.item_id, a.item_id)


def test_simulate_and_parallel_for_passthroughs():
    scheduler = LoopScheduler(p=4)
    costs = np.random.default_rng(1).exponential(10.0, 500) + 0.1
    r = scheduler.simulate(costs)
    assert r.policy == P.ich().label() and r.makespan > 0
    hits = np.zeros(300, np.int64)
    lock = threading.Lock()

    def body(i):
        with lock:
            hits[i] += 1

    scheduler.parallel_for(300, body)
    assert (hits == 1).all()


# ------------------------------------------------- cross-backend round-trip
@pytest.mark.parametrize("workload,n", [("spmv", 220), ("bfs", 180),
                                        ("kmeans", 150)])
def test_roundtrip_simulator_executor_tiles_agree(workload, n):
    """schedule -> simulate(replay) -> parallel_for -> lowering must all
    dispatch identical per-tile iteration (work-unit) sets."""
    rng = np.random.default_rng(n)
    if workload == "kmeans":
        costs = rng.uniform(4.0, 9.0, n)
        costs[rng.choice(n, 3, replace=False)] += rng.exponential(80.0, 3)
        inputs = (costs,)
    else:
        indptr, indices, data = _random_csr(n, seed=n)
        inputs = (indptr, indices, data) if workload == "spmv" \
            else (indptr, indices)
    scheduler = LoopScheduler(p=4)
    entry = sched.get(workload)
    provider = entry.costs(*inputs)
    s = scheduler.schedule(provider)
    ranges = s.unit_ranges()
    n_units = int(s.sizes.sum())
    assert ranges[-1, 1] == n_units

    # (a) simulator replay dispatches exactly the tile chunks, in order,
    # with exactly the predicted per-tile work
    rep = s.replay(record_chunks=True)
    log = np.array([(b, e) for (b, e, _, _) in rep.chunk_log])
    np.testing.assert_array_equal(log, ranges)
    work = np.array([w for (*_, w) in rep.chunk_log])
    np.testing.assert_allclose(work, s.tile_cost(), atol=1e-9)

    # (b) threaded executor covers every work unit exactly once in exactly
    # n_tiles chunks (the same pretiled ranges)
    hits = np.zeros(n_units, np.int64)
    lock = threading.Lock()

    def body(u):
        with lock:
            hits[u] += 1

    st = s.parallel_for_units(body)
    assert (hits == 1).all()
    assert st.chunks == s.n_tiles

    # (c) the lowered tiles name the same per-tile item sets as the unit
    # ranges do (via the unit -> item map); padding slots excluded
    unit_item = s.unit_to_item()
    for t in range(s.n_tiles):
        b, e = ranges[t]
        items_from_units = set(unit_item[b:e].tolist())
        ids = s.item_id[t]
        lens = s.lower().seg_len[t]
        items_from_tiles = set(ids[(ids >= 0) & (lens > 0)].tolist())
        assert items_from_tiles == items_from_units


def test_roundtrip_kernel_outputs_match_refs():
    from repro.kernels.ich_bfs.ref import bfs_levels_ref
    from repro.kernels.ich_kmeans.ref import kmeans_assign_ref
    from repro.kernels.ich_spmv.ref import spmv_ref

    rng = np.random.default_rng(5)
    scheduler = LoopScheduler()
    n = 128
    indptr, indices, data = _random_csr(n, seed=5)
    x = rng.standard_normal(n).astype(np.float32)
    spmv = scheduler.build("spmv", indptr, indices, data)
    np.testing.assert_allclose(np.asarray(spmv(x, interpret=True)),
                               spmv_ref(indptr, indices, data, x),
                               atol=1e-4, rtol=1e-4)
    bfs = scheduler.build("bfs", indptr, indices)
    np.testing.assert_array_equal(bfs.levels(0, interpret=True),
                                  bfs_levels_ref(indptr, indices, 0))
    pts = rng.standard_normal((64, 4)).astype(np.float32)
    cent = rng.standard_normal((5, 4)).astype(np.float32)
    km = scheduler.build("kmeans", rng.uniform(1.0, 20.0, 64))
    np.testing.assert_allclose(np.asarray(km(pts, cent, interpret=True)),
                               kmeans_assign_ref(pts, cent), atol=1e-5)


# ----------------------------------------------------------------- registry
def test_registry_builtins_present():
    names = sched.registered()
    assert {"spmv", "bfs", "kmeans"} <= set(names)
    spec = sched.get("spmv")
    assert spec.name == "spmv" and callable(spec.costs) and callable(spec.build)


def test_registry_register_and_duplicate_rejection():
    try:
        spec = register("test_wl", costs=lambda a: ExplicitCosts(a),
                        build=lambda s, a: (s, a), doc="test")
        assert sched.get("test_wl") is spec
        with pytest.raises(ValueError, match="already registered"):
            register("test_wl", costs=spec.costs, build=spec.build)
        register("test_wl", costs=spec.costs, build=spec.build,
                 overwrite=True)  # explicit replacement is allowed
        # facade .build() drives the custom entry end-to-end
        out_s, out_a = LoopScheduler().build(
            "test_wl", np.arange(1, 40, dtype=np.int64))
        assert isinstance(out_s, Schedule) and out_a.shape == (39,)
    finally:
        unregister("test_wl")
    with pytest.raises(KeyError, match="unknown workload"):
        sched.get("test_wl")


# --------------------------------------------------------- width rules
def _skewed_csr():
    return skewed_csr(3_000, 12, hubs=[(5, 2_000), (6, 2_000)])


def _gather_w(sizes):
    return T.gather_width(sizes, sched.ICH_EPS, sched.MIN_WIDTH,
                          sched.MAX_WIDTH, sched.ROWS_PER_TILE)


@pytest.mark.parametrize("workload", ["spmv", "bfs"])
def test_gathered_workloads_take_the_gather_width(workload):
    indptr, indices, data = _skewed_csr()
    inputs = (indptr, indices, data)[:3 if workload == "spmv" else 2]
    s = LoopScheduler(p=2).build(workload, *inputs).schedule
    assert s.width == _gather_w(s.sizes) == 8
    assert T.ich_tile_width(s.sizes) == 32
    assert s.width_rule is T.gather_width
    direct = T.build_schedule(s.sizes, rows_per_tile=sched.ROWS_PER_TILE,
                              width=8)
    np.testing.assert_array_equal(s.item_id, direct.item_id)
    np.testing.assert_array_equal(s.lower().seg_len, direct.seg_len)


def _band_inputs(workload):
    rng = np.random.default_rng(13)
    skewed = np.diff(skewed_csr(500, 13, hubs=[(0, 2_000), (1, 2_000)])[0])
    skewed = skewed + 1.0
    if workload == "serve-prefill":
        return (skewed.astype(np.int64),)
    return (skewed,)  # kmeans costs, and raw schedule() costs


@pytest.mark.parametrize("workload", ["kmeans", "serve-prefill",
                                      "schedule()"])
def test_other_workloads_keep_the_band_width(workload):
    """Element-identical to the band's schedule, on sizes where the
    gather rule would pick another width."""
    inputs = _band_inputs(workload)
    scheduler = LoopScheduler(p=2)
    if workload == "schedule()":
        s = scheduler.schedule(*inputs)
    else:
        out = scheduler.build(workload, *inputs)
        s = out if isinstance(out, Schedule) else out.schedule
    band = T.build_schedule(s.sizes, rows_per_tile=sched.ROWS_PER_TILE,
                            eps=sched.ICH_EPS)
    assert s.width == band.width == T.ich_tile_width(s.sizes)
    assert s.width_rule is None
    assert _gather_w(s.sizes) != band.width
    for a, b in [(s.item_id, band.item_id),
                 (s.lower().seg_start, band.seg_start),
                 (s.lower().seg_len, band.seg_len)]:
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("workload", ["spmv", "bfs"])
@pytest.mark.parametrize("width", [16, 32, 64])
def test_explicit_width_wins_over_the_width_rule(workload, width):
    indptr, indices, data = _skewed_csr()
    inputs = (indptr, indices, data)[:3 if workload == "spmv" else 2]
    scheduler = LoopScheduler(p=2)
    s = scheduler.build(workload, *inputs, width=width).schedule
    assert s.width == width and s.width_arg == width
    # and a pinned width is its own cache entry, apart from the rule's
    assert scheduler.build(workload, *inputs).schedule.width == 8


def test_repeated_build_is_a_cache_hit_that_constructs_nothing(monkeypatch):
    from repro import obs
    names = []

    def span(name, **_):
        names.append(name)
        return contextlib.nullcontext()

    monkeypatch.setattr(obs, "span", span)
    indptr, indices, data = _skewed_csr()
    scheduler = LoopScheduler(p=2)
    first = scheduler.build("spmv", indptr, indices, data).schedule
    assert names.count("sched.construct") == 1
    names.clear()
    again = scheduler.build("spmv", indptr, indices, data * 2.0).schedule
    assert again is first and again.width == 8
    assert "sched.construct" not in names
    assert scheduler.cache_stats.hits == 1


def test_refine_keeps_the_width_rule():
    indptr, indices, data = _skewed_csr()
    scheduler = LoopScheduler(p=2)
    s = scheduler.build("spmv", indptr, indices, data).schedule
    measured = s.costs * np.random.default_rng(14).uniform(0.5, 2.0,
                                                           s.n_items)
    r = s.observe(measured, level="item").refine()
    assert r.generation == 1 and r.width_rule is T.gather_width
    assert r.width == s.width == 8
    np.testing.assert_array_equal(r.item_id, s.item_id)  # structural sizes
    # a schedule with no scheduler behind it rebuilds with the rule too
    import dataclasses
    bare = dataclasses.replace(s, _scheduler=None, _feedback={})
    assert bare.observe(measured, level="item").refine().width == 8


def test_refine_reapplies_the_width_rule_to_refined_sizes():
    """Where sizes are quantized cost estimates, refine() re-derives them,
    and the rule picks the width of the new sizes."""
    try:
        register("test_gathered", costs=lambda c: ExplicitCosts(c),
                 build=lambda s, c: s, width=T.gather_width)
        costs = np.full(400, 16.0)
        s = LoopScheduler(p=2).build("test_gathered", costs)
        assert s.width == 16
        r = s.observe(np.full(400, 64.0), level="item").refine()
        assert r.width_rule is T.gather_width
        assert r.width == _gather_w(r.sizes) == 64
    finally:
        unregister("test_gathered")


def test_schedule_equality_is_identity():
    sizes = np.arange(1, 40, dtype=np.int64)
    scheduler = LoopScheduler(cache_size=0)
    a, b = scheduler.schedule(sizes), scheduler.schedule(sizes)
    # dataclass field-eq over ndarrays would raise; identity semantics don't
    assert a == a and a != b
    assert a in [a, b] and len({id(a), id(b)}) == 2


def test_unregister_builtin_refused():
    with pytest.raises(ValueError, match="cannot unregister built-in"):
        unregister("spmv")
    assert "spmv" in sched.registered()


def test_cache_size_zero_disables_caching():
    scheduler = LoopScheduler(cache_size=0)
    sizes = np.arange(1, 60, dtype=np.int64)
    a = scheduler.schedule(sizes)
    b = scheduler.schedule(sizes)
    assert a is not b  # every call constructs fresh
    np.testing.assert_array_equal(a.item_id, b.item_id)
    assert scheduler.cache_stats.hits == 0
    assert scheduler.cache_stats.misses == 0


def test_register_builtin_name_collides_even_before_any_lookup():
    # register() must load the built-ins first, so claiming "spmv" in a
    # fresh process fails AT the offending call instead of poisoning every
    # later registry lookup
    import os
    import subprocess
    import sys
    code = (
        "from repro.sched.registry import register\n"
        "try:\n"
        "    register('spmv', costs=lambda *a: None, build=lambda *a: None)\n"
        "except ValueError as e:\n"
        "    assert 'already registered' in str(e), e\n"
        "else:\n"
        "    raise SystemExit('collision with built-in spmv not detected')\n"
        "from repro.sched import registered\n"
        "assert {'spmv', 'bfs', 'kmeans'} <= set(registered())\n")
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True)
    assert res.returncode == 0, res.stderr


def test_unknown_workload_raises():
    with pytest.raises(KeyError, match="unknown workload"):
        LoopScheduler().build("no_such_workload")


# ------------------------------------------------ refine cache generations
def _refine_case():
    rng = np.random.default_rng(3)
    sizes = np.minimum(rng.zipf(1.5, 600), 300).astype(np.int64)
    costs = (1.0 + sizes) * np.random.default_rng(1003).uniform(
        0.5, 2.0, sizes.size)
    return LoopScheduler(p=4), ExplicitCosts(costs)


def test_refine_generation_invalidates_lowerings():
    """After observe+refine the new generation must build fresh shard
    lowerings while the old schedule's memo stays untouched."""
    ls, prov = _refine_case()
    s0 = ls.schedule(prov)
    host0 = s0.shard()
    rng = np.random.default_rng(42)
    measured = s0.costs * rng.uniform(0.25, 4.0, s0.n_items)
    s1 = s0.observe(measured, level="item").refine()
    assert s1 is not s0 and s1.generation == s0.generation + 1
    # a fresh memo dict, empty until first use
    assert s1._shards is not s0._shards and not s1._shards
    host1 = s1.shard()
    assert host1 is not host0
    # old entries survive unchanged (no aliasing, no eviction)
    assert s0.shard() is host0
    # the refined lowering partitions the refined costs
    np.testing.assert_array_equal(
        host1.block_perm,
        T.shard_schedule(s1.tiles, s1.tile_cost(), s1.p,
                         superstep=s1.superstep).block_perm)
    assert not np.array_equal(s1.tile_cost(), s0.tile_cost())


def test_same_generation_is_cache_hit():
    """Re-presenting the same provider at the same generation returns
    the SAME schedule object with its shard memo intact; the refined
    generation keys separately (a cache miss, never an overwrite)."""
    ls, prov = _refine_case()
    s0 = ls.schedule(prov)
    low = s0.shard()
    assert ls.schedule(prov) is s0
    assert ls.schedule(prov).shard() is low
    s1 = s0.observe(s0.costs * 2.0, level="item").refine()
    assert ls.schedule(prov) is s0  # gen 0 entry undisturbed
    assert s1._scheduler is ls and s1.generation == 1


# ------------------------------------------------------------- data dispatch
def test_shard_dispatcher_exactly_once_and_weighted_memoized():
    from repro.sched.data_sched import ShardDispatcher

    scheduler = LoopScheduler()
    d = ShardDispatcher(n_hosts=4, scheduler=scheduler)
    n = 500
    hits = np.zeros(n, np.int64)
    lock = threading.Lock()

    def read(i):
        with lock:
            hits[i] += 1

    st = d.dispatch(n, read)
    assert (hits == 1).all() and st.chunks > 0

    costs = np.random.default_rng(2).exponential(5.0, n) + 0.5
    hits[:] = 0
    d.dispatch_weighted(costs, read)
    assert (hits == 1).all()
    before = scheduler.cache_stats.hits
    hits[:] = 0
    d.dispatch_weighted(costs, read)  # chunk list memoized in the LRU
    assert (hits == 1).all()
    assert scheduler.cache_stats.hits == before + 1


# ----------------------------------------------------------- unified epsilon
def test_ich_eps_unified_across_layers():
    import inspect

    from repro.models import moe as MOE

    assert sched.ICH_EPS == 0.33
    assert P.ich().eps == sched.ICH_EPS
    assert P.Policy("x", P.DISTRIBUTED).eps == sched.ICH_EPS
    for fn, name in [(T.ich_tile_width, "eps"), (T.build_schedule, "eps"),
                     (MOE.ich_update_cap_scale, "eps")]:
        assert inspect.signature(fn).parameters[name].default == sched.ICH_EPS
