"""Bytes a loop call cannot avoid moving, counted from the input alone.

The counts are of the problem, not of the packed layout, so they stay the
same whatever implements the call, and a share of the HBM roofline built
from them cannot pass 100%.
"""
from __future__ import annotations

F32 = I32 = 4


def spmv_bytes(n: int, nnz: int) -> int:
    """y = A x with A in CSR: each nonzero's value and column index, the row
    pointer, x read once and y written once."""
    return nnz * (F32 + I32) + (n + 1) * I32 + n * F32 + n * F32


def bfs_bytes(n: int, reached_edges: int) -> int:
    """One whole traversal: each edge of the reached vertices read once, the
    row pointer, and a level per vertex written once. Counted per traversal
    and not per level, so a push or direction-optimising search, which reads
    fewer edges per level, still reads at most 100%."""
    return reached_edges * I32 + (n + 1) * I32 + n * I32
