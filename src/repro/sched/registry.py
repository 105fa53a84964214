"""Workload/kernel registry: new applications plug in without a new ops class.

A workload is two functions (DESIGN.md §3):

* ``costs(*inputs) -> CostProvider`` — derive the per-item cost description
  from the workload's raw inputs (numpy-only; runs before any jax import);
* ``build(schedule, *inputs) -> op`` — given the constructed `Schedule`
  and the same raw inputs, return the callable kernel op (this side may
  import jax/Pallas);

and, optionally, the rule its tile width follows: ``width=None`` is the
paper's band (`core.tiling.ich_tile_width`); a workload whose payload is
gathered over every packed slot declares `core.tiling.gather_width`.

Example — registering a custom workload:

    sched.register(
        "histogram",
        costs=lambda values, bins: sched.ExplicitCosts(counts_per_bin),
        build=lambda schedule, values, bins: MyHistogramOp(schedule, ...),
    )
    op = sched.default_scheduler().build("histogram", values, bins)

The three paper applications (``spmv``, ``bfs``, ``kmeans``) are registered
by `sched/kernels.py`, loaded lazily on first lookup so the numpy-only
facade surface never imports jax.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Optional

from .costs import CostProvider


@dataclasses.dataclass(frozen=True)
class WorkloadSpec:
    """A registered workload: name + cost derivation + kernel-op builder,
    and its tile-width rule ``width(sizes, eps, min_w, max_w,
    rows_per_tile) -> W`` (None: the band)."""

    name: str
    costs: Callable[..., CostProvider]
    build: Callable[..., Any]
    doc: str = ""
    width: Optional[Callable[..., int]] = None


_REGISTRY: dict[str, WorkloadSpec] = {}
_LOCK = threading.Lock()
_BUILTINS_LOADED = False
_BUILTINS_LOADING = False


def _load_builtins() -> None:
    # NOT guarded by _LOCK: the kernels module registers its entries at
    # import time, and register() takes _LOCK itself (non-reentrant) —
    # idempotence/races are handled by the import system's own module lock.
    # The _LOADING sentinel keeps the register() calls issued DURING the
    # kernels import from re-entering the import.
    global _BUILTINS_LOADED, _BUILTINS_LOADING
    if _BUILTINS_LOADED or _BUILTINS_LOADING:
        return
    _BUILTINS_LOADING = True
    try:
        from . import kernels  # noqa: F401  (registers spmv/bfs/kmeans)
        _BUILTINS_LOADED = True
    finally:
        _BUILTINS_LOADING = False


def register(name: str, *, costs: Callable[..., CostProvider],
             build: Callable[..., Any], doc: str = "",
             width: Optional[Callable[..., int]] = None,
             overwrite: bool = False) -> WorkloadSpec:
    """Register a workload under `name`; returns the spec.

    Re-registering an existing name raises unless `overwrite=True` — a
    silent replacement of e.g. "spmv" would change what every caller gets.
    """
    if not name or not isinstance(name, str):
        raise ValueError(f"workload name must be a non-empty string: {name!r}")
    # load built-ins first so an early user registration of "spmv"/"bfs"/
    # "kmeans" collides HERE (clear error at the offending call) instead of
    # blowing up the built-in import inside every later get()
    _load_builtins()
    spec = WorkloadSpec(name=name, costs=costs, build=build, doc=doc,
                        width=width)
    with _LOCK:
        if name in _REGISTRY and not overwrite:
            raise ValueError(
                f"workload {name!r} is already registered; pass "
                "overwrite=True to replace it")
        _REGISTRY[name] = spec
    return spec


def get(name: str) -> WorkloadSpec:
    """Look up a registered workload (loads the built-ins on first use)."""
    _load_builtins()
    with _LOCK:
        spec = _REGISTRY.get(name)
    if spec is None:
        raise KeyError(
            f"unknown workload {name!r}; registered: {registered()}")
    return spec


def registered() -> tuple[str, ...]:
    """Names of all registered workloads, sorted."""
    _load_builtins()
    with _LOCK:
        return tuple(sorted(_REGISTRY))


_BUILTIN_NAMES = frozenset({"spmv", "bfs", "kmeans"})


def unregister(name: str) -> None:
    """Remove a workload (primarily for tests tearing down custom entries).

    Built-in names are refused: the kernels module only registers them on
    its first import, so removal would be irreversible for the process.
    Replace a built-in with ``register(..., overwrite=True)`` instead.
    """
    if name in _BUILTIN_NAMES:
        raise ValueError(f"cannot unregister built-in workload {name!r}; "
                         "use register(..., overwrite=True) to replace it")
    with _LOCK:
        _REGISTRY.pop(name, None)
