"""Checks of the input generators (python -m pytest bench)."""
import numpy as np
import pytest

from bench.gen import kronecker, rows


@pytest.mark.parametrize("seed", [0, 2**31 + 11])
def test_wikipedia_profile_matches_table1_at_full_size(seed):
    mean, ratio, var = rows.TABLE1["wikipedia"]
    nnz = rows.row_nnz("wikipedia", 3_566_907, seed)
    assert abs(nnz.mean() - mean) <= rows.MEAN_RTOL * mean
    assert abs(nnz.var() - var) <= rows.VAR_RTOL * var
    assert nnz.max() == ratio and nnz.min() >= 1


def test_row_profile_repeats_for_a_seed():
    a = rows.row_nnz("wikipedia", 50_000, 7)
    assert np.array_equal(a, rows.row_nnz("wikipedia", 50_000, 7))
    assert not np.array_equal(a, rows.row_nnz("wikipedia", 50_000, 8))


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_kronecker_graph_is_simple_symmetric_sorted(seed):
    scale = 10
    n = 1 << scale
    indptr, indices = kronecker.graph(scale, 16, 0.57, 0.19, 0.19, seed)
    assert indptr.shape == (n + 1,) and indptr[0] == 0
    assert indptr[-1] == indices.size
    rows_ = np.repeat(np.arange(n), np.diff(indptr))
    key = rows_.astype(np.int64) * n + indices
    assert np.all(rows_ != indices)                 # no self-loops
    assert np.all(np.diff(key) > 0)                 # sorted, no duplicates
    back = np.sort(indices.astype(np.int64) * n + rows_)
    assert np.array_equal(back, key)                # symmetric
    # duplicates and self-loops take some of the 2 * 16 * n directed edges
    assert 0.5 * 32 * n < indices.size < 32 * n


def test_kronecker_seeds_differ_beyond_32_bits():
    a = kronecker.graph(8, 16, 0.57, 0.19, 0.19, 5)
    b = kronecker.graph(8, 16, 0.57, 0.19, 0.19, 5 + 2**32)
    c = kronecker.graph(8, 16, 0.57, 0.19, 0.19, 5)
    assert np.array_equal(a[1], c[1])
    assert not np.array_equal(a[0], b[0])
