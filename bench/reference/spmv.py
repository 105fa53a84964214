"""Plain CSR sparse matrix-vector product, in float64 or a lower precision."""
from __future__ import annotations

import numpy as np


def row_ids(indptr: np.ndarray) -> np.ndarray:
    """(nnz,) row of each nonzero."""
    return np.repeat(np.arange(len(indptr) - 1), np.diff(indptr))


def product(rows: np.ndarray, indices: np.ndarray, data: np.ndarray,
            x: np.ndarray, n: int, dtype=np.float64):
    """(y, scale): y = A x summed in float64 over products formed from
    `data` and `x` rounded to `dtype`, and each row's sum of |a_ij x_j|."""
    a = np.asarray(data).astype(dtype).astype(np.float64)
    xs = np.asarray(x).astype(dtype).astype(np.float64)
    prod = a * xs[indices]
    return (np.bincount(rows, weights=prod, minlength=n),
            np.bincount(rows, weights=np.abs(prod), minlength=n))


def rel_err(y: np.ndarray, ref: np.ndarray, scale: np.ndarray) -> float:
    """Largest |y - ref| over a row's sum of |a_ij x_j|, over rows with
    nonzeros (the precision-independent measure of a summation's error)."""
    live = scale > 0
    return float(np.max(np.abs(np.asarray(y, np.float64) - ref)[live]
                        / scale[live]))
