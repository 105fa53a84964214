"""Host seconds of each named phase of a run's set-up."""
from __future__ import annotations

import contextlib
import time


class Phases(dict):
    """{phase: seconds}, in the order the phases first ran; `with
    phases("name"):` adds the block's host time to that phase."""

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self[name] = self.get(name, 0.0) + time.perf_counter() - t0

    def line(self) -> str:
        return ", ".join(f"{k} {v:.3f} s" for k, v in self.items())
