"""SpMV cells: a CSR matrix with a Table-1 row profile, applied by the op
that `LoopScheduler.build("spmv", ...)` returns.

The row lengths come from the configuration's `structure_seed`, so every
run has the same schedule and so the same compiled programs; the run's
seed draws the columns, the values and the start vector.

Traffic parameters (bench/traffic/<name>.json):

* `rebuild_every` — every k-th call re-assembles: it rebuilds the op
  through `LoopScheduler.build("spmv", indptr, indices, data_v)` with the
  next of `value_sets` value arrays (made in set-up) before applying it;
  0 builds once in set-up;
* `value_sets` — how many value arrays the re-assemblies cycle through;
* `sample` — how many calls of the window the check compares, drawn from
  the seed (the last call is always among them).

Each call is one power-iteration step, `y = op(x)`, then `x = y / |y|`
with |y| read on the host as a solver's convergence check, so every call
depends on the one before and ends in a sync.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.gen import rows as gen_rows
from bench.phases import Phases
from bench.reference import spmv as ref
from bench.roofline import spmv_bytes

# Largest |y - ref| over the row's sum of |a_ij x_j|, per checked call.
# Set from readings on the chip (PERF.md "Limits"): the program's float32
# products and sums read at most LOWER_READING over sound seeds; the
# bfloat16 control reads at least CONTROL_READING.
ERR_LIMIT = 1e-4


class Workload:
    def __init__(self, config: dict, traffic: dict, seed: int, sched):
        self.cfg, self.seed, self.sched = config, seed, sched
        self.rebuild_every = int(traffic.get("rebuild_every", 0))
        self.n_sets = int(traffic.get("value_sets", 1))
        self.n_sample = int(traffic["sample"])
        self.phases = Phases()

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        n = int(self.cfg["n_rows"])
        ph = self.phases
        with ph("inputs"):
            nnz = gen_rows.row_nnz(self.cfg["row_profile"], n,
                                   int(self.cfg["structure_seed"]))
            self.indptr = np.concatenate([[0], np.cumsum(nnz)])
            self.nnz = int(self.indptr[-1])
            rng = np.random.default_rng(self.seed)
            self.indices = rng.integers(0, n, self.nnz, dtype=np.int32)
            self.data = [rng.standard_normal(self.nnz, dtype=np.float32)
                         for _ in range(self.n_sets)]
            x0 = rng.standard_normal(n, dtype=np.float32)
        self.n = n
        with ph("build"):
            self.scheduler = self.sched.LoopScheduler(
                p=int(self.cfg["p"]), cache_size=int(self.cfg["cache_size"]))
            self.variant = 0
            self.op = self.scheduler.build("spmv", self.indptr, self.indices,
                                           self.data[0])
            self.x = jnp.asarray(x0)
        # warm-up: the op's call twice here, and one call of the window's
        # own, which re-assembles, where the cell has re-assemblies
        with ph("warm"):
            self._step()
            self._step()
        self.warm_calls = 1 if self.rebuild_every else 0
        self.open_window()

    def open_window(self) -> None:
        """Forget the warm-up calls."""
        self.rebuild_s = []     # host time of each re-assembly's build
        self.kept = []          # (call index, variant, x, y) of sampled calls
        self.rng = np.random.default_rng([self.seed, 1])
        self.i = 0

    def _rebuild(self) -> None:
        self.variant = (self.variant + 1) % self.n_sets
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.rebuild"):
            self.op = self.scheduler.build("spmv", self.indptr, self.indices,
                                           self.data[self.variant])
        self.rebuild_s.append(time.perf_counter() - t0)

    def _step(self):
        x = self.x
        y = self.op(x)
        norm = float(jnp.linalg.norm(y))
        self.x = y / norm
        return x, y

    # ------------------------------------------------------------ window
    def call(self) -> None:
        i = self.i
        if self.rebuild_every and i % self.rebuild_every == 0:
            self._rebuild()
        x, y = self._step()
        # reservoir sample of the window's calls, drawn from the seed
        rec = (i, self.variant, x, y)
        if len(self.kept) < self.n_sample:
            self.kept.append(rec)
        else:
            j = int(self.rng.integers(0, i + 1))
            if j < self.n_sample:
                self.kept[j] = rec
        self.last = rec
        self.i += 1

    def units(self) -> int:
        """Loop calls the window completed."""
        return self.i

    def counters(self) -> dict:
        sch = self.op.schedule
        slots = (self.op.shards.n_tiles_padded * sch.rows_per_tile
                 * sch.width)
        return {"nnz": self.nnz, "n": self.n, "slots": slots,
                "tiles": sch.n_tiles, "width": sch.width,
                "blocks_per_worker": self.op.shards.n_steps,
                "bytes_per_call": spmv_bytes(self.n, self.nnz),
                "rebuild_s": list(self.rebuild_s)}

    def release(self) -> None:
        """Copy the sampled calls to the host and free the device state."""
        recs = {r[0]: r for r in self.kept}
        recs[self.last[0]] = self.last
        self.checked = [(i, v, np.asarray(x), np.asarray(y))
                        for i, v, x, y in sorted(recs.values(),
                                                 key=lambda r: r[0])]
        del self.op, self.x, self.kept, self.last

    # ------------------------------------------------------------- check
    def check(self, control: bool = False) -> dict:
        """{name: (value, limit)}. With `control`, the reference in
        bfloat16 takes the program's place, on the same inputs."""
        rows = ref.row_ids(self.indptr)
        errs = []
        for _, v, x, y in self.checked:
            want, scale = ref.product(rows, self.indices, self.data[v], x,
                                      self.n)
            if control:
                import ml_dtypes
                y, _ = ref.product(rows, self.indices, self.data[v], x,
                                   self.n, dtype=ml_dtypes.bfloat16)
            errs.append(ref.rel_err(y, want, scale)
                        if np.all(np.isfinite(y)) else np.inf)
        self.n_failed = sum(not e <= ERR_LIMIT for e in errs)
        return {"spmv_err": (max(errs), ERR_LIMIT)}
