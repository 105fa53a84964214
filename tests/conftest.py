"""Shared test helpers (importable as `from conftest import ...` — pytest
puts this directory on sys.path)."""
import numpy as np


def random_csr(n, zipf_a=1.8, seed=0, max_nnz=60):
    """A zipf-heavy CSR matrix with ~10% empty rows (the hard case): the
    canonical irregular workload used across the scheduler suites."""
    rng = np.random.default_rng(seed)
    row_nnz = np.minimum(rng.zipf(zipf_a, n), max_nnz).astype(np.int64)
    row_nnz[rng.random(n) < 0.1] = 0
    indptr = np.concatenate([[0], np.cumsum(row_nnz)]).astype(np.int64)
    nnz = int(indptr[-1])
    indices = rng.integers(0, n, nnz).astype(np.int32)
    data = rng.standard_normal(nnz).astype(np.float32)
    return indptr, indices, data


def skewed_csr(n, seed=0, hubs=()):
    """A web-matrix-like CSR matrix: a lognormal body of row lengths
    (median ~10, mean ~14, so the iCh band gives W = 32) with each
    (row, nnz) of `hubs` set: where the gather width rule takes W = 8."""
    rng = np.random.default_rng(seed)
    row_nnz = np.rint(rng.lognormal(2.3, 0.8, n)).astype(np.int64)
    for row, nnz in hubs:
        row_nnz[row] = nnz
    indptr = np.concatenate([[0], np.cumsum(row_nnz)])
    nnz = int(indptr[-1])
    indices = rng.integers(0, n, nnz).astype(np.int32)
    data = rng.standard_normal(nnz).astype(np.float32)
    return indptr, indices, data
