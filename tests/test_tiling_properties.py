"""Property-based tests (hypothesis) for the vectorized schedule
construction: the array programs in core/tiling.py must match the
`_reference_*` loop oracles exactly, and every constructed schedule must
still replay chunk-for-chunk through the discrete-event simulator, for
arbitrary sizes / rows_per_tile / width."""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import policies as P
from repro.core.simulator import simulate
from repro.core.tiling import (
    _reference_build_schedule, _reference_coverage_counts,
    _reference_pack_csr, _reference_split_items,
    build_schedule, coverage_counts, pack_csr, split_items,
)

# sizes lists mix zeros, band-sized items, and heavy outliers so splitting,
# padding, and the zero-item slot rule all get exercised
_SIZES = st.lists(st.one_of(st.just(0), st.integers(0, 40),
                            st.integers(200, 3000)),
                  min_size=1, max_size=120)


@settings(max_examples=40, deadline=None)
@given(sizes=_SIZES, R=st.integers(1, 17),
       W=st.one_of(st.none(), st.integers(1, 600)))
def test_vectorized_matches_reference(sizes, R, W):
    sizes = np.asarray(sizes, np.int64)
    vec = build_schedule(sizes, rows_per_tile=R, width=W)
    ref = _reference_build_schedule(sizes, rows_per_tile=R, width=W)
    assert vec.width == ref.width and vec.n_items == ref.n_items
    np.testing.assert_array_equal(vec.item_id, ref.item_id)
    np.testing.assert_array_equal(vec.seg_start, ref.seg_start)
    np.testing.assert_array_equal(vec.seg_len, ref.seg_len)
    item, start, length = split_items(sizes, vec.width)
    assert (list(zip(item.tolist(), start.tolist(), length.tolist()))
            == _reference_split_items(sizes, vec.width))
    np.testing.assert_array_equal(coverage_counts(vec, sizes),
                                  _reference_coverage_counts(vec, sizes))
    counts = coverage_counts(vec, sizes)
    assert counts.shape == (int(sizes.sum()),) and (counts == 1).all()


@settings(max_examples=25, deadline=None)
@given(sizes=_SIZES, R=st.integers(1, 17),
       W=st.one_of(st.none(), st.integers(1, 600)), seed=st.integers(0, 99))
def test_vectorized_pack_csr_matches_reference(sizes, R, W, seed):
    sizes = np.asarray(sizes, np.int64)
    sched = build_schedule(sizes, rows_per_tile=R, width=W)
    rng = np.random.default_rng(seed)
    indptr = np.concatenate([[0], np.cumsum(sizes)])
    nnz = int(indptr[-1])
    indices = rng.integers(0, sizes.size, nnz).astype(np.int32)
    data = rng.standard_normal(nnz).astype(np.float32)
    for a, b in zip(pack_csr(indptr, indices, data, sched),
                    _reference_pack_csr(indptr, indices, data, sched)):
        np.testing.assert_array_equal(a, b)


@settings(max_examples=25, deadline=None)
@given(sizes=_SIZES, R=st.integers(1, 17),
       W=st.one_of(st.none(), st.integers(1, 600)), p=st.integers(1, 8))
def test_schedule_replays_in_simulator(sizes, R, W, p):
    """slot_ranges() of any vectorized-constructed schedule is a valid
    pretiled central-queue chunking: the simulator dispatches exactly the
    per-tile work tile_cost predicts, tile for tile."""
    sizes = np.asarray(sizes, np.int64)
    costs = 1.0 + sizes.astype(np.float64)
    sched = build_schedule(sizes, rows_per_tile=R, width=W)
    ranges = sched.slot_ranges()
    assert ranges[0, 0] == 0 and ranges[-1, 1] == int(sizes.sum())
    np.testing.assert_array_equal(ranges[1:, 0], ranges[:-1, 1])
    if int(sizes.sum()) == 0:  # no work units: nothing for the sim to run
        assert (sched.tile_cost(costs, sizes) == 0).all()
        return
    res = simulate(sched.unit_costs(costs, sizes), p, P.pretiled(ranges),
                   record_chunks=True)
    sim_work = np.array([w for (_, _, _, w) in res.chunk_log])
    np.testing.assert_allclose(sim_work, sched.tile_cost(costs, sizes),
                               atol=1e-9)
    assert res.chunks == sched.n_tiles
