"""Conformance suite for the jitted schedule pipeline (core/tiling_jax.py).

The bar is ELEMENT-IDENTICAL outputs to the numpy construction path
(core/tiling.py) — integer streams exact by construction, float cost
arithmetic exact because the jax path replicates numpy's f64 association
order (`_pairwise_rowsum`, segment sums). Three layers of evidence:

* hypothesis property tests over arbitrary sizes/R/W/dtypes (skipped
  where hypothesis is absent — the deterministic tests below keep the
  bar in hermetic containers);
* deterministic-twin zipf seeds (the test_tiling.py generator) through
  the FULL lowering pipeline at several (p, superstep) points, plus
  paper-grid workload families;
* `LoopScheduler(backend="jax")` cache-generation tests: device-backed
  entries must invalidate under a new refine generation exactly like
  host-backed ones — a refined schedule can never be served a stale
  device lowering.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import tiling as T
from repro.core import tiling_jax as TJ
from repro.sched.api import LoopScheduler

jnp = pytest.importorskip("jax.numpy")


def _random_sizes(n, zipf_a, seed, max_size=300):
    rng = np.random.default_rng(seed)
    return np.minimum(rng.zipf(zipf_a, n), max_size).astype(np.int64)


def _random_costs(sizes, seed):
    rng = np.random.default_rng(seed + 1000)
    return (1.0 + sizes) * rng.uniform(0.5, 2.0, sizes.size)


def _numpy_lowering(sizes, costs, *, p, superstep, rows_per_tile=8):
    """The host pipeline's arrays, in the exact layout DeviceLowering
    mirrors (shard_item_id / kernel_block_ids / padded slot cost)."""
    sched = T.build_schedule(sizes, rows_per_tile=rows_per_tile)
    tile_cost = sched.tile_cost(costs, sizes)
    shards = T.shard_schedule(sched, tile_cost, p, superstep=superstep)
    slot = np.zeros((shards.n_tiles_padded, sched.rows_per_tile), np.float32)
    slot[:sched.n_tiles] = sched.slot_cost(costs, sizes)
    return sched, tile_cost, shards, slot


def assert_lowering_matches(low, sizes, costs, *, p, superstep):
    sched, tile_cost, shards, slot = _numpy_lowering(
        sizes, costs, p=p, superstep=superstep)
    host = low.schedule.to_host()
    assert host.width == sched.width and host.n_items == sched.n_items
    np.testing.assert_array_equal(host.item_id, sched.item_id)
    np.testing.assert_array_equal(host.seg_start, sched.seg_start)
    np.testing.assert_array_equal(host.seg_len, sched.seg_len)
    # float costs: bit-identical, not merely close
    np.testing.assert_array_equal(np.asarray(low.tile_cost), tile_cost)
    np.testing.assert_array_equal(np.asarray(low.worker), shards.worker)
    np.testing.assert_array_equal(np.asarray(low.block_perm),
                                  shards.block_perm)
    np.testing.assert_array_equal(np.asarray(low.rowid),
                                  shards.shard_item_id(sched))
    np.testing.assert_array_equal(np.asarray(low.blkid),
                                  shards.kernel_block_ids())
    np.testing.assert_array_equal(np.asarray(low.slot_cost), slot)


# --------------------------------------------------------------- hypothesis
# sizes mix zeros, band-sized items, and heavy outliers so splitting,
# padding, and the zero-item slot rule all get exercised (the
# test_tiling_properties.py strategy)
_SIZES = st.lists(st.one_of(st.just(0), st.integers(0, 40),
                            st.integers(200, 3000)),
                  min_size=1, max_size=120)


@settings(max_examples=30, deadline=None)
@given(sizes=_SIZES, R=st.integers(1, 17),
       W=st.one_of(st.none(), st.integers(1, 600)),
       dtype=st.sampled_from([np.int32, np.int64]))
def test_build_matches_numpy(sizes, R, W, dtype):
    sizes = np.asarray(sizes, dtype)
    ref = T.build_schedule(sizes, rows_per_tile=R, width=W)
    dev = TJ.build_schedule_jax(sizes, rows_per_tile=R, width=W).to_host()
    assert dev.width == ref.width and dev.n_items == ref.n_items
    np.testing.assert_array_equal(dev.item_id, ref.item_id)
    np.testing.assert_array_equal(dev.seg_start, ref.seg_start)
    np.testing.assert_array_equal(dev.seg_len, ref.seg_len)
    item, start, length = T.split_items(sizes, ref.width)
    jitem, jstart, jlen = TJ.split_items_jax(sizes, ref.width)
    np.testing.assert_array_equal(np.asarray(jitem), item)
    np.testing.assert_array_equal(np.asarray(jstart), start)
    np.testing.assert_array_equal(np.asarray(jlen), length)
    assert int(TJ.ich_tile_width_jax(sizes)) == T.ich_tile_width(sizes)


@settings(max_examples=20, deadline=None)
@given(sizes=_SIZES, R=st.integers(1, 17), seed=st.integers(0, 99),
       pad=st.integers(1, 5),
       dtype=st.sampled_from([np.float32, np.float64, np.int32]))
def test_pack_matches_numpy(sizes, R, seed, pad, dtype):
    sizes = np.asarray(sizes, np.int64)
    sched = T.build_schedule(sizes, rows_per_tile=R)
    rng = np.random.default_rng(seed)
    indptr = np.concatenate([[0], np.cumsum(sizes)])
    nnz = int(indptr[-1])
    indices = rng.integers(0, sizes.size, nnz).astype(np.int32)
    data = (rng.integers(1, 100, nnz).astype(dtype)
            if np.issubdtype(dtype, np.integer)
            else rng.standard_normal(nnz).astype(dtype))
    ref_v, ref_c = T.pack_csr(indptr, indices, data, sched,
                              pad_tiles_to=pad)
    dev = TJ.build_schedule_jax(sizes, rows_per_tile=R)
    jv, jc = TJ.pack_csr_jax(indptr, indices, data, dev, pad_tiles_to=pad)
    assert np.asarray(jv).dtype == ref_v.dtype
    np.testing.assert_array_equal(np.asarray(jv), ref_v)
    np.testing.assert_array_equal(np.asarray(jc), ref_c)


@settings(max_examples=20, deadline=None)
@given(sizes=_SIZES, p=st.integers(1, 8), B=st.integers(1, 4),
       seed=st.integers(0, 99))
def test_partition_and_lowering_match_numpy(sizes, p, B, seed):
    sizes = np.asarray(sizes, np.int64)
    costs = _random_costs(sizes, seed)
    sched = T.build_schedule(sizes)
    tile_cost = sched.tile_cost(costs, sizes)
    ref = T.partition_tiles(tile_cost, sched.item_id, p, block=B)
    dev = TJ.partition_tiles_jax(tile_cost, sched.item_id, p, block=B)
    np.testing.assert_array_equal(np.asarray(dev), ref)
    low = TJ.lower_schedule_jax(sizes, costs, p=p, superstep=B)
    assert_lowering_matches(low, sizes, costs, p=p, superstep=B)


# ------------------------------------------------- deterministic twin seeds
@pytest.mark.parametrize("n,zipf_a,seed", [
    (500, 1.3, 0), (500, 2.0, 1), (2000, 1.3, 2), (2000, 1.6, 3),
    (97, 1.5, 4), (4096, 2.2, 5),
])
@pytest.mark.parametrize("p", [1, 3, 4, 8])
def test_pipeline_matches_numpy_twin_seeds(n, zipf_a, seed, p):
    sizes = _random_sizes(n, zipf_a, seed)
    costs = _random_costs(sizes, seed)
    low = TJ.lower_schedule_jax(sizes, costs, p=p)
    assert_lowering_matches(low, sizes, costs, p=p, superstep=low.superstep)


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
@pytest.mark.parametrize("cdtype", [np.float32, np.float64])
def test_pipeline_matches_numpy_across_dtypes(dtype, cdtype):
    sizes = _random_sizes(1200, 1.5, 7).astype(dtype)
    costs = _random_costs(sizes.astype(np.int64), 7).astype(cdtype)
    low = TJ.lower_schedule_jax(sizes, costs, p=4)
    assert_lowering_matches(low, sizes, costs, p=4, superstep=low.superstep)


def test_pipeline_no_sync_path_identical():
    """Passing n_steps= (the refine-loop steady state, no device->host
    sync) must produce the identical lowering."""
    sizes = _random_sizes(1500, 1.4, 11)
    costs = _random_costs(sizes, 11)
    low = TJ.lower_schedule_jax(sizes, costs, p=4)
    low2 = TJ.lower_schedule_jax(sizes, costs, p=4, n_steps=low.n_steps)
    assert low2.n_steps == low.n_steps
    np.testing.assert_array_equal(np.asarray(low2.block_perm),
                                  np.asarray(low.block_perm))
    np.testing.assert_array_equal(np.asarray(low2.rowid),
                                  np.asarray(low.rowid))


def test_pipeline_matches_numpy_paper_grid():
    """The lowering equality over real paper-grid cost families (SpMV
    Table-1 matrices, BFS frontier degrees)."""
    from repro.core import workloads as WL

    cases = []
    for name in ("FullChip", "road_usa", "arabic-2005"):
        spec = next(s for s in WL.TABLE1 if s.name == name)
        nnz = WL.matrix_row_nnz(spec, 4000).astype(np.int64)
        cases.append((np.maximum(nnz, 1), 1.0 + nnz))
    levels, _ = WL.bfs_levels("scale_free", 3000)
    deg = np.maximum(np.asarray(levels[0], np.int64), 1)
    cases.append((deg, deg.astype(np.float64)))
    for sizes, costs in cases:
        low = TJ.lower_schedule_jax(sizes, costs, p=8)
        assert_lowering_matches(low, sizes, costs, p=8,
                                superstep=low.superstep)


def test_empty_sizes_zero_tile_lowering():
    low = TJ.lower_schedule_jax(np.zeros(0, np.int64), np.zeros(0), p=4)
    assert low.schedule.n_tiles == 0
    assert (np.asarray(low.block_perm) == -1).all()
    assert (np.asarray(low.rowid) == -1).all()
    host = low.schedule.to_host()
    assert host.n_tiles == 0 and host.n_items == 0


# ------------------------------------------ backend seam cache generations
class TestDeviceCacheGenerations:
    """`LoopScheduler(backend='jax')`: device-backed cache entries must
    invalidate under a new refine generation exactly like host-backed
    ones (sched/cache.py's no-stale-lowering rule)."""

    def _sched(self, backend):
        ls = LoopScheduler(p=4, backend=backend)
        sizes = _random_sizes(600, 1.5, 3)
        from repro.sched.costs import ExplicitCosts
        return ls, ExplicitCosts(_random_costs(sizes, 3))

    def test_backend_tiles_element_identical(self):
        ls_np, prov = self._sched("numpy")
        ls_jx = LoopScheduler(p=4, backend="jax")
        a, b = ls_np.schedule(prov), ls_jx.schedule(prov)
        np.testing.assert_array_equal(a.item_id, b.item_id)
        np.testing.assert_array_equal(a.tiles.seg_len, b.tiles.seg_len)
        assert a.width == b.width

    def test_backend_part_of_cache_key(self):
        ls, prov = self._sched("jax")
        s1 = ls.schedule(prov)
        ls.backend = "numpy"
        s2 = ls.schedule(prov)
        assert s1 is not s2 and s1.backend == "jax" and s2.backend == "numpy"

    def test_device_lowering_memoized_per_key(self):
        ls, prov = self._sched("jax")
        s = ls.schedule(prov)
        low = s.device_lowering()
        assert s.device_lowering() is low
        assert s.device_lowering(p=2) is not low
        assert s.device_lowering(p=2).p == 2
        assert_lowering_matches(low, s.sizes, s.costs, p=s.p,
                                superstep=s.superstep)

    @pytest.mark.parametrize("backend", ["numpy", "jax"])
    def test_refine_generation_invalidates_lowerings(self, backend):
        """After observe+refine the new generation must build fresh
        lowerings while the old schedule's memo stays untouched — for
        the device path exactly as for the host path."""
        ls, prov = self._sched(backend)
        s0 = ls.schedule(prov)
        host0 = s0.shard()
        dev0 = s0.device_lowering() if backend == "jax" else None
        rng = np.random.default_rng(42)
        measured = s0.costs * rng.uniform(0.25, 4.0, s0.n_items)
        s1 = s0.observe(measured, level="item").refine()
        assert s1 is not s0 and s1.generation == s0.generation + 1
        # fresh memo dicts, empty until first use
        assert s1._shards is not s0._shards and not s1._shards
        assert s1._device is not s0._device and not s1._device
        host1 = s1.shard()
        assert host1 is not host0
        # old entries survive unchanged (no aliasing, no eviction)
        assert s0.shard() is host0
        if backend == "jax":
            dev1 = s1.device_lowering()
            assert dev1 is not dev0
            assert s0.device_lowering() is dev0
            # the refined lowering reflects the refined costs, and stays
            # element-identical to ITS OWN generation's host pipeline
            assert_lowering_matches(dev1, s1.sizes, s1.costs, p=s1.p,
                                    superstep=s1.superstep)
            assert not np.array_equal(np.asarray(dev1.tile_cost),
                                      np.asarray(dev0.tile_cost))

    def test_same_generation_is_cache_hit(self):
        """Re-presenting the same provider at the same generation returns
        the SAME schedule object with its device memo intact; the refined
        generation keys separately (a cache miss, never an overwrite)."""
        ls, prov = self._sched("jax")
        s0 = ls.schedule(prov)
        low = s0.device_lowering()
        assert ls.schedule(prov) is s0
        assert ls.schedule(prov).device_lowering() is low
        s1 = s0.observe(s0.costs * 2.0, level="item").refine()
        assert ls.schedule(prov) is s0  # gen 0 entry undisturbed
        assert s1._scheduler is ls and s1.generation == 1
