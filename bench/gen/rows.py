"""Row-length sampler for the paper's Table-1 matrices, hubs kept.

Source: the paper's Table 1 gives, per SuiteSparse matrix, the mean
nonzeros per row, the ratio of the longest row to the shortest, and the
variance. The sampler draws a lognormal body moment-matched to a tame
variance (at most mean^2) and puts the rest of the variance into a few hub
rows of degree `ratio` (at most n/10), placed as one contiguous run, as
web crawls order a host's pages together.

This is the program's `core/workloads.matrix_row_nnz` without its hub
caps (`HUB_DEG_CAP`, `HUB_RUN_SHARE`), which exist to keep the simulator's
reduced-n paper grid assertable and at full size cut the `wikipedia`
variance from 6.2e4 to 187. The seed mixing (crc32 of the name) is the
same, so equal seeds give equal bodies.

Tolerance: at the published row count the sample's mean lies within 1%
and its variance within 5% of Table 1 (bench/test_gen.py checks
`wikipedia` at 3,566,907 rows).
"""
from __future__ import annotations

import math
import zlib

import numpy as np

# name -> (mean nnz per row, max/min ratio, variance), paper Table 1
TABLE1 = {
    "wikipedia": (12.6, 1.8e5, 6.2e4),
    "delaunay_n23": (5.9, 7.0, 1.7),
}

MEAN_RTOL = 0.01
VAR_RTOL = 0.05


def row_nnz(name: str, n: int, seed: int) -> np.ndarray:
    """(n,) int64 nonzeros per row with Table 1's statistics for `name`."""
    mean, ratio, sigma2 = TABLE1[name]
    rng = np.random.default_rng(seed + zlib.crc32(name.encode()))
    hub_deg = max(1.0, min(max(ratio, 1.0), n / 10.0))
    body_var = min(sigma2, max(1.0, mean) ** 2)
    hub_var = max(0.0, sigma2 - body_var)
    n_hubs = 0
    if hub_var > 0 and hub_deg > mean:
        by_var = math.ceil(hub_var * n / hub_deg ** 2)
        by_mass = math.floor(0.5 * mean * n / hub_deg)
        n_hubs = int(max(1, min(by_var, by_mass, n // 50)))
    body_mean = max(1.0, mean - n_hubs * hub_deg / n)
    if body_var > 0.05 * body_mean ** 2:
        s2 = math.log(1.0 + body_var / body_mean ** 2)
        body = rng.lognormal(math.log(body_mean) - s2 / 2.0, math.sqrt(s2), n)
    else:
        body = rng.normal(body_mean, math.sqrt(max(body_var, 1e-12)), n)
    nnz = np.maximum(np.round(body), 1.0)
    if n_hubs:
        start = int(rng.integers(0, n - n_hubs + 1))
        nnz[start:start + n_hubs] = hub_deg
    return nnz.astype(np.int64)
