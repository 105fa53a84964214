"""LRU schedule cache: the serving path reuses schedules across requests.

Schedule construction is O(n) and vectorized (`core/tiling.py`), but at
serving rates even milliseconds per request add up — and most requests
re-present a cost distribution the scheduler has already seen (the same
CSR matrix, the same graph, the same batch shape). The cache keys on
``(cost_fingerprint, policy, p, construction params, superstep,
generation)`` — the full frozen `Policy` dataclass, not its lossy
``label()``, and the worker PARTITION parameters `p`/`superstep`: a
cached `Schedule` memoizes its worker-shard lowering (`Schedule.shard`)
and the kernel ops pack payloads into that layout, so entries built for
different worker counts must never alias (tests/test_sched_api.py proves
distinct `p` values don't collide).
A repeat `LoopScheduler.schedule()` call returns the previously built
`Schedule` object without touching construction at all
(tests/test_sched_api.py asserts it on the hit and miss counters).

Generation invalidation (measured-cost feedback, DESIGN.md §2.7): the key
also carries the refinement GENERATION. `Schedule.refine()` re-enters
this cache with generation g+1 and a `RefinedCosts` fingerprint over the
refreshed (sizes, costs) content, so a refined schedule — and everything
hanging off it: memoized shard layouts, packed kernel payloads — is
always a fresh entry; a stale generation-g lowering can never be served for
generation-g+1 costs, even if an unrelated entry hashed equal on the
non-generation fields. Old generations age out through normal LRU
eviction rather than eager invalidation: in a serving loop the previous
generation often still has in-flight consumers, and evicting it early
would only force rebuilds (`tests/test_adaptive_properties.py` pins the
no-aliasing rule).

Thread-safe; eviction is least-recently-used. Construction runs outside
the cache lock (it serializes internally on the tiling workspace), so a
slow build never blocks concurrent hits. Two threads racing on the same
missing key may both build; the first insert wins and both get a usable
schedule — acceptable for a cache whose values are immutable.
"""
from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from typing import Any, Callable, Hashable


@dataclasses.dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0


class ScheduleCache:
    """LRU map from schedule keys to built `Schedule` objects."""

    def __init__(self, maxsize: int = 128):
        if maxsize < 1:
            raise ValueError(f"cache maxsize must be >= 1, got {maxsize}")
        self.maxsize = int(maxsize)
        self._data: OrderedDict[Hashable, Any] = OrderedDict()
        self._lock = threading.Lock()
        self.stats = CacheStats()

    def __len__(self) -> int:
        return len(self._data)

    def get_or_build(self, key: Hashable, build: Callable[[], Any]) -> Any:
        """Return the cached value for `key`, building it on a miss."""
        with self._lock:
            if key in self._data:
                self._data.move_to_end(key)
                self.stats.hits += 1
                return self._data[key]
            self.stats.misses += 1
        value = build()
        with self._lock:
            if key not in self._data:  # lost races keep the first insert
                self._data[key] = value
                if len(self._data) > self.maxsize:
                    self._data.popitem(last=False)
                    self.stats.evictions += 1
            return self._data[key]

    def clear(self) -> None:
        with self._lock:
            self._data.clear()
            self.stats = CacheStats()
