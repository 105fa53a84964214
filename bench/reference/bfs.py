"""Plain BFS: hop distance from a root, level by level over the CSR."""
from __future__ import annotations

import numpy as np


def levels(indptr: np.ndarray, indices: np.ndarray, root: int) -> np.ndarray:
    """(n,) int32 hop distance from `root` along row lists, -1 = unreached."""
    n = len(indptr) - 1
    level = np.full(n, -1, np.int32)
    level[root] = 0
    frontier = np.array([root], np.int64)
    depth = 0
    while frontier.size:
        depth += 1
        starts, ends = indptr[frontier], indptr[frontier + 1]
        lens = ends - starts
        total = int(lens.sum())
        offs = np.repeat(starts - np.cumsum(lens) + lens, lens)
        nbrs = indices[offs + np.arange(total)]
        nbrs = nbrs[level[nbrs] < 0]
        frontier = np.unique(nbrs).astype(np.int64)
        level[frontier] = depth
    return level
