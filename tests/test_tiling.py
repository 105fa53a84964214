"""Tests for the shared iCh schedule-construction layer (core/tiling.py)."""
import numpy as np
import pytest
from conftest import skewed_csr

from repro.core import policies as P
from repro.core.simulator import SimParams, simulate
from repro.core.tiling import (
    FOLD_SLOTS, _reference_build_schedule, _reference_coverage_counts,
    _reference_pack_csr, _reference_split_items, block_chains,
    build_schedule, coverage_counts, gather_width, ich_tile_width, pack_csr,
    shard_schedule, split_items,
)
from repro.sched.api import Schedule
from repro.sched.defaults import SUPERSTEP
from repro.sched.kernels import _flat_slot_cost


def _random_sizes(n, zipf_a, seed, max_size=300):
    rng = np.random.default_rng(seed)
    sizes = np.minimum(rng.zipf(zipf_a, n), max_size).astype(np.int64)
    sizes[rng.random(n) < 0.1] = 0  # sprinkle empty items
    return sizes


# ------------------------------------------------------------------ coverage
@pytest.mark.parametrize("n,zipf_a,R,seed", [
    (100, 1.6, 4, 0), (256, 1.9, 8, 1), (333, 2.5, 8, 2), (64, 1.3, 16, 3),
])
def test_every_iteration_covered_exactly_once(n, zipf_a, R, seed):
    sizes = _random_sizes(n, zipf_a, seed)
    sched = build_schedule(sizes, rows_per_tile=R)
    counts = coverage_counts(sched, sizes)
    assert counts.shape == (int(sizes.sum()),)
    assert (counts == 1).all()
    # every item owns at least one slot (even empty ones)
    present = np.unique(sched.item_id[sched.item_id >= 0])
    np.testing.assert_array_equal(present, np.arange(n))
    assert int(sched.tile_work().sum()) == int(sizes.sum())


def test_empty_sizes_array_builds_zero_tile_schedule():
    # since the empty-schedule sweep, zero items is a valid degenerate
    # input — the full contract lives in tests/test_empty_schedule.py
    sched = build_schedule(np.array([], dtype=np.int64))
    assert sched.n_tiles == 0 and sched.n_items == 0


def test_int32_overflow_guard_raises_instead_of_corrupting():
    # the vectorized path runs int32 internally; out-of-range items must be
    # rejected loudly, not silently wrapped to empty schedules
    with pytest.raises(ValueError, match="fit int32"):
        build_schedule(np.array([2 ** 31 + 5, 3], dtype=np.int64), width=8)


@pytest.mark.parametrize("bad", [0, -1, -16])
def test_nonpositive_explicit_width_raises(bad):
    # regression: width=0 used to fall through `if width` to the band
    # heuristic instead of being rejected
    with pytest.raises(ValueError, match="width must be positive"):
        build_schedule(np.array([3, 4, 5]), width=bad)
    with pytest.raises(ValueError, match="width must be positive"):
        _reference_build_schedule(np.array([3, 4, 5]), width=bad)
    with pytest.raises(ValueError, match="width must be positive"):
        split_items(np.array([3, 4, 5]), bad)
    with pytest.raises(ValueError, match="width must be positive"):
        _reference_split_items(np.array([3, 4, 5]), bad)


def test_empty_rows_get_one_slot_each():
    sizes = np.zeros(10, np.int64)
    sched = build_schedule(sizes, rows_per_tile=4)
    assert sched.n_tiles == 3  # ceil(10 / 4)
    assert (sched.seg_len == 0).all()
    assert (sched.tile_work() == 0).all()
    assert sorted(sched.item_id[sched.item_id >= 0]) == list(range(10))


def test_single_row_wider_than_max_w_splits():
    sizes = np.array([10_000], np.int64)
    sched = build_schedule(sizes, rows_per_tile=8)
    assert sched.width == 512  # clamped at max_w
    n_segs = -(-10_000 // 512)
    assert (sched.item_id >= 0).sum() == n_segs
    assert (coverage_counts(sched, sizes) == 1).all()
    # all segments belong to item 0 and tile back-to-back
    starts = np.sort(sched.seg_start[sched.item_id >= 0])
    np.testing.assert_array_equal(starts, np.arange(n_segs) * 512)


def test_explicit_width_override():
    sizes = _random_sizes(200, 1.8, 5)
    sched = build_schedule(sizes, width=16)
    assert sched.width == 16
    assert (sched.seg_len <= 16).all()
    assert (coverage_counts(sched, sizes) == 1).all()


def test_width_band_monotone_and_clamped():
    # W = pow2(mu*(1+eps)): uniform-32 rows fit one segment (64 >= 42.6);
    # small-row inputs clamp to min_w; always a power of two in [8, 512]
    assert ich_tile_width(np.full(1000, 32)) == 64
    assert ich_tile_width(np.full(1000, 2)) == 8
    w_hvy = ich_tile_width(
        np.minimum(np.random.default_rng(0).zipf(1.5, 1000), 5000))
    assert w_hvy in {8, 16, 32, 64, 128, 256, 512}
    # monotone in eps (wider band -> wider tiles)
    rows = np.random.default_rng(1).integers(1, 100, 500)
    assert ich_tile_width(rows, eps=0.5) >= ich_tile_width(rows, eps=0.25)


# ------------------------------------------- width of gathered payloads
def _lognormal_hubs():
    """The shape of a web matrix's row lengths, with three hub rows."""
    hubs = [(0, 6_000), (1, 6_000), (2, 6_000)]
    return np.diff(skewed_csr(20_000, 3, hubs=hubs)[0])


def _kronecker_like(seed=4):
    """Zipf degrees with 40% isolated vertices: median 1, mean ~29."""
    rng = np.random.default_rng(seed)
    deg = np.minimum(rng.zipf(1.6, 16_384), 4_000).astype(np.int64)
    deg[rng.random(deg.size) < 0.4] = 0
    return deg


GATHER_PROFILES = {
    "lognormal_hubs": _lognormal_hubs,
    "kronecker_like": _kronecker_like,
    "zipf_empty": lambda: _random_sizes(3_000, 1.7, 8),
    "uniform_small": lambda: np.random.default_rng(9).integers(0, 40, 999),
    "one_wide_item": lambda: np.array([10_000], np.int64),
    "empty": lambda: np.zeros(0, np.int64),
}


@pytest.mark.parametrize("profile", ["lognormal_hubs", "kronecker_like"])
def test_gather_width_is_narrowest_on_skewed_profiles(profile):
    sizes = GATHER_PROFILES[profile]()
    assert ich_tile_width(sizes) >= 32  # the band pads most rows
    assert gather_width(sizes) == 8


@pytest.mark.parametrize("k", [3, 4, 5, 6, 7, 8, 9])
def test_gather_width_uniform_rows_take_their_length(k):
    sizes = np.full(64, 2 ** k, np.int64)
    assert gather_width(sizes) == 2 ** k


@pytest.mark.parametrize("min_w,max_w", [(8, 512), (16, 512), (8, 16),
                                         (4, 64)])
@pytest.mark.parametrize("profile", sorted(GATHER_PROFILES))
def test_gather_width_lies_between_the_floor_and_the_band(profile, min_w,
                                                         max_w):
    sizes = GATHER_PROFILES[profile]()
    w = gather_width(sizes, min_w=min_w, max_w=max_w)
    assert min_w <= w <= ich_tile_width(sizes, min_w=min_w, max_w=max_w)


@pytest.mark.parametrize("R", [4, 8, 16])
@pytest.mark.parametrize("profile", sorted(GATHER_PROFILES))
def test_gather_width_is_the_brute_force_argmin(profile, R):
    """The rule's segment count from distinct sizes equals the tiles that
    `build_schedule` makes: the argmin over built schedules agrees (ties
    to the wider), and every work unit is covered once at the width."""
    sizes = GATHER_PROFILES[profile]()
    band = ich_tile_width(sizes)
    widths = [w for w in (512, 256, 128, 64, 32, 16, 8) if w <= band]

    def cost(w):
        tiles = build_schedule(sizes, rows_per_tile=R, width=w)
        return (w + FOLD_SLOTS) * tiles.n_tiles * R

    best = min(widths, key=cost)
    assert gather_width(sizes, rows_per_tile=R) == best
    sched = build_schedule(sizes, rows_per_tile=R, width=best)
    assert (coverage_counts(sched, sizes) == 1).all()


def test_split_items_orders_segments_by_item():
    item, start, length = split_items(np.array([5, 0, 12]), width=8)
    segs = list(zip(item.tolist(), start.tolist(), length.tolist()))
    assert segs == [(0, 0, 5), (1, 0, 0), (2, 0, 8), (2, 8, 4)]
    assert segs == _reference_split_items(np.array([5, 0, 12]), width=8)


# ------------------------------------------- vectorized vs reference oracles
@pytest.mark.parametrize("n,zipf_a,R,W,seed", [
    (1, 1.5, 8, None, 0), (97, 1.4, 4, None, 1), (256, 2.1, 8, 16, 2),
    (333, 1.7, 16, 1, 3), (64, 1.3, 3, 7, 4), (500, 1.9, 8, None, 5),
])
def test_vectorized_construction_matches_reference(n, zipf_a, R, W, seed):
    sizes = _random_sizes(n, zipf_a, seed)
    vec = build_schedule(sizes, rows_per_tile=R, width=W)
    ref = _reference_build_schedule(sizes, rows_per_tile=R, width=W)
    assert vec.width == ref.width and vec.n_items == ref.n_items
    np.testing.assert_array_equal(vec.item_id, ref.item_id)
    np.testing.assert_array_equal(vec.seg_start, ref.seg_start)
    np.testing.assert_array_equal(vec.seg_len, ref.seg_len)
    item, start, length = split_items(sizes, vec.width)
    assert (list(zip(item.tolist(), start.tolist(), length.tolist()))
            == _reference_split_items(sizes, vec.width))
    rng = np.random.default_rng(seed + 100)
    indptr = np.concatenate([[0], np.cumsum(sizes)])
    nnz = int(indptr[-1])
    indices = rng.integers(0, n, nnz).astype(np.int32)
    data = rng.standard_normal(nnz).astype(np.float32)
    for a, b in zip(pack_csr(indptr, indices, data, vec),
                    _reference_pack_csr(indptr, indices, data, vec)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(coverage_counts(vec, sizes),
                                  _reference_coverage_counts(vec, sizes))


# -------------------------------------------------------------- CSR packing
def test_pack_csr_matches_flat_payload():
    rng = np.random.default_rng(7)
    sizes = _random_sizes(120, 1.7, 7)
    indptr = np.concatenate([[0], np.cumsum(sizes)])
    nnz = int(indptr[-1])
    indices = rng.integers(0, 120, nnz).astype(np.int32)
    data = rng.standard_normal(nnz).astype(np.float32)
    sched = build_schedule(sizes, rows_per_tile=8)
    vals, cols = pack_csr(indptr, indices, data, sched)
    # scatter the tiles back into flat CSR order and compare
    flat_v = np.zeros(nnz, np.float32)
    flat_c = np.zeros(nnz, np.int32)
    for t in range(sched.n_tiles):
        for j in range(sched.rows_per_tile):
            it, s, ln = (int(sched.item_id[t, j]), int(sched.seg_start[t, j]),
                         int(sched.seg_len[t, j]))
            if it >= 0 and ln > 0:
                b = int(indptr[it]) + s
                flat_v[b:b + ln] = vals[t, j, :ln]
                flat_c[b:b + ln] = cols[t, j, :ln]
    np.testing.assert_array_equal(flat_v, data)
    np.testing.assert_array_equal(flat_c, indices)
    # padding slots are zero (kernels reduce over W unmasked)
    mask = np.zeros_like(vals, bool)
    for t in range(sched.n_tiles):
        for j in range(sched.rows_per_tile):
            mask[t, j, :int(sched.seg_len[t, j])] = True
    assert (vals[~mask] == 0).all() and (cols[~mask] == 0).all()


# ------------------------------------------------- simulator cross-check
def test_schedule_replays_in_simulator_chunk_for_chunk():
    """The constructed schedule, handed to the discrete-event simulator as an
    explicit pretiled policy over the same cost array, must be dispatched
    with exactly the per-tile work the schedule predicts."""
    sizes = _random_sizes(300, 1.8, 11)
    costs = 1.0 + sizes.astype(np.float64)  # per-item cost model
    sched = build_schedule(sizes, rows_per_tile=8)
    ranges = sched.slot_ranges()
    # tiles cover the flattened work-unit space contiguously, in order
    assert ranges[0, 0] == 0 and ranges[-1, 1] == int(sizes.sum())
    np.testing.assert_array_equal(ranges[1:, 0], ranges[:-1, 1])
    res = simulate(sched.unit_costs(costs, sizes), 4, P.pretiled(ranges),
                   record_chunks=True)
    sim_work = np.array([w for (_, _, _, w) in res.chunk_log])
    np.testing.assert_allclose(sim_work, sched.tile_cost(costs, sizes),
                               atol=1e-9)
    assert res.chunks == sched.n_tiles


# ------------------------------------------------------- the whole lowering
_ZERO = SimParams(dispatch_overhead=0.0, local_dispatch_overhead=0.0,
                  speed_jitter=0.0)


def _random_costs(sizes, seed):
    rng = np.random.default_rng(seed + 1000)
    return (1.0 + sizes) * rng.uniform(0.5, 2.0, sizes.size)


def assert_lowering_matches_references(sizes, costs, *, p,
                                       superstep=SUPERSTEP, seed=0):
    """The numpy lowering, stage by stage, against its oracles: build and
    pack against the loop references, tile cost against a per-slot loop,
    the LPT partition against the item-closed invariant and LPT's bound,
    the shard layout and its prefetch streams (`shard_item_id`,
    `kernel_block_ids`, the padded slot cost) against the partition, and
    the sharded replay's per-worker work against the partition's."""
    sizes_i = np.asarray(sizes, np.int64)
    costs_f = np.asarray(costs, np.float64)
    B = superstep
    sched = build_schedule(sizes)
    ref = _reference_build_schedule(sizes)
    assert sched.width == ref.width and sched.n_items == ref.n_items
    np.testing.assert_array_equal(sched.item_id, ref.item_id)
    np.testing.assert_array_equal(sched.seg_start, ref.seg_start)
    np.testing.assert_array_equal(sched.seg_len, ref.seg_len)
    Tn, R = sched.item_id.shape

    # tile cost: item i's cost spread evenly over its sizes[i] units
    slot = sched.slot_cost(costs, sizes)
    want = np.zeros((Tn, R))
    for t, j in zip(*np.nonzero(sched.item_id >= 0)):
        i = sched.item_id[t, j]
        if sizes_i[i] > 0:
            want[t, j] = costs_f[i] / sizes_i[i] * sched.seg_len[t, j]
    np.testing.assert_array_equal(slot, want)
    tc = sched.tile_cost(costs, sizes)
    np.testing.assert_allclose(tc, [sum(row) for row in want.tolist()],
                               rtol=1e-12)
    np.testing.assert_allclose(tc.sum(), costs_f[sizes_i > 0].sum(),
                               rtol=1e-9)

    # LPT partition: whole blocks, item-closed, within LPT's bound
    shards = shard_schedule(sched, tc, p, superstep=B)
    worker = shards.worker
    np.testing.assert_array_equal(worker, np.repeat(worker[::B], B)[:Tn])
    live = sched.item_id >= 0
    ids = sched.item_id[live]
    w_slot = np.broadcast_to(worker[:, None], sched.item_id.shape)[live]
    lo = np.full(sched.n_items, p)
    hi = np.full(sched.n_items, -1)
    np.minimum.at(lo, ids, w_slot)
    np.maximum.at(hi, ids, w_slot)
    np.testing.assert_array_equal(lo[ids], hi[ids])
    wc = shards.worker_cost(tc)
    chain = block_chains(sched.item_id, B)
    bcost = np.bincount(np.arange(Tn) // B, weights=tc)
    ccost = np.bincount(chain, weights=bcost)
    assert wc.max() <= wc.sum() / p + ccost.max() + 1e-9 * wc.sum()

    # shard layout and the kernels' prefetch streams
    perm = shards.perm
    np.testing.assert_array_equal(np.sort(perm[perm >= 0]), np.arange(Tn))
    for w in range(p):
        np.testing.assert_array_equal(perm[w][perm[w] >= 0],
                                      np.nonzero(worker == w)[0])
    rowid = shards.shard_item_id(sched)
    want_rowid = np.full((perm.size, R), -1, np.int32)
    for r, t in enumerate(perm.reshape(-1)):
        if t >= 0:
            want_rowid[r] = sched.item_id[t]
    np.testing.assert_array_equal(rowid, want_rowid)
    blkid = shards.kernel_block_ids()
    assert blkid.shape == (p * shards.n_steps,)
    step_live = shards.block_perm.reshape(-1) >= 0
    np.testing.assert_array_equal(np.sort(blkid[step_live]),
                                  np.arange(-(-Tn // B)))
    assert (blkid[~step_live] == 0).all()
    np.testing.assert_array_equal(
        perm.reshape(-1, B)[step_live, 0], blkid[step_live] * B)

    # the Schedule's own lowering: the same layout, the padded slot cost
    s = Schedule(sizes=sizes_i, costs=costs_f, policy=P.ich(), p=p,
                 tiles=sched, superstep=B)
    np.testing.assert_array_equal(s.shard().block_perm, shards.block_perm)
    flat = _flat_slot_cost(s, shards.n_tiles_padded)
    assert flat.shape == (shards.n_tiles_padded, R)
    np.testing.assert_array_equal(flat[:Tn], slot.astype(np.float32))
    assert not flat[Tn:].any()

    # the superstep-padded pack the kernels fetch blocks from
    rng = np.random.default_rng(seed + 100)
    indptr = np.concatenate([[0], np.cumsum(sizes_i)])
    nnz = int(indptr[-1])
    indices = rng.integers(0, sizes_i.size, nnz).astype(np.int32)
    data = rng.standard_normal(nnz).astype(np.float32)
    vp, cp = pack_csr(indptr, indices, data, sched, pad_tiles_to=B)
    vr, cr = _reference_pack_csr(indptr, indices, data, sched)
    assert vp.shape[0] == shards.n_tiles_padded
    np.testing.assert_array_equal(vp[:Tn], vr)
    np.testing.assert_array_equal(cp[:Tn], cr)
    assert not vp[Tn:].any() and not cp[Tn:].any()

    # the simulator's static replay dispatches the partition's work
    rep = s.replay_sharded(params=_ZERO)
    assert rep.chunks == Tn
    sim_wc = np.zeros(p)
    for (_, _, w, work) in rep.chunk_log:
        sim_wc[w] += work
    np.testing.assert_allclose(sim_wc, wc, rtol=1e-9)


@pytest.mark.parametrize("n,zipf_a,seed", [
    (500, 1.3, 0), (500, 2.0, 1), (2000, 1.3, 2), (2000, 1.6, 3),
    (97, 1.5, 4), (4096, 2.2, 5),
])
@pytest.mark.parametrize("p", [1, 3, 4, 8])
def test_pipeline_matches_numpy_twin_seeds(n, zipf_a, seed, p):
    sizes = _random_sizes(n, zipf_a, seed)
    assert_lowering_matches_references(sizes, _random_costs(sizes, seed),
                                       p=p, seed=seed)


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
@pytest.mark.parametrize("cdtype", [np.float32, np.float64])
def test_pipeline_matches_numpy_across_dtypes(dtype, cdtype):
    sizes = _random_sizes(1200, 1.5, 7)
    costs = _random_costs(sizes, 7).astype(cdtype)
    assert_lowering_matches_references(sizes.astype(dtype), costs, p=4,
                                       seed=7)


def test_pipeline_matches_numpy_paper_grid():
    """The lowering against its oracles over real paper-grid cost
    families (SpMV Table-1 matrices, BFS frontier degrees)."""
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
        assert_lowering_matches_references(sizes, costs, p=8)
