"""BFS cells: Graph500 kernel 2 on a Kronecker graph, each call one full
traversal through `LoopScheduler.build("bfs", ...).levels(root)`.

The graph comes from the configuration's `graph_seed`: every run searches
one graph, as kernel 2 does, so every run has the same schedule and so the
same compiled programs. The run's seed draws the search keys.

Traffic parameters (bench/traffic/<name>.json):

* `roots` — how many search keys set-up draws from the seed, as Graph500
  does (64), among vertices of degree >= `min_degree`;
* `eccentricity` — the window searches from those keys, in drawn order,
  whose farthest vertex lies this many hops away, and starts again from
  the first if it runs through them all. A pull traversal takes
  eccentricity + 1 level steps of equal cost, so every seed gets the same
  work: keys of any depth would make the time per call a draw of the
  seed. The counter `key_eccentricity` gives the drawn keys' mix;
* `sample` — how many traversals of the window the check compares, drawn
  from the seed; the deepest traversal is always among them.
"""
from __future__ import annotations

import jax
import numpy as np

from bench.gen import kronecker
from bench.phases import Phases
from bench.reference import bfs as ref
from bench.roofline import bfs_bytes


def eccentricities(indptr: np.ndarray, indices: np.ndarray,
                   roots: np.ndarray) -> np.ndarray:
    """Hops to the farthest reachable vertex from each of up to 64 roots:
    one breadth-first search for all of them, a bit per root."""
    n = len(indptr) - 1
    bits = np.left_shift(np.uint64(1), np.arange(len(roots), dtype=np.uint64))
    seen = np.zeros(n, np.uint64)
    np.bitwise_or.at(seen, roots, bits)
    frontier = seen.copy()
    rows = np.flatnonzero(np.diff(indptr))
    starts = indptr[:-1][rows]
    ecc = np.zeros(len(roots), np.int64)
    depth = 0
    while True:
        depth += 1
        nxt = np.zeros(n, np.uint64)
        nxt[rows] = np.bitwise_or.reduceat(frontier[indices], starts)
        nxt &= ~seen
        grown = np.bitwise_or.reduce(nxt)
        if not grown:
            return ecc
        ecc[(bits & grown) != 0] = depth
        seen |= nxt
        frontier = nxt


class Workload:
    def __init__(self, config: dict, traffic: dict, seed: int, sched):
        self.cfg, self.traffic, self.seed = config, traffic, seed
        self.sched = sched
        self.n_sample = int(traffic["sample"])
        self.phases = Phases()

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        c, ph = self.cfg, self.phases
        with ph("graph"):
            self.indptr, self.indices = kronecker.graph(
                int(c["scale"]), int(c["edgefactor"]), c["A"], c["B"],
                c["C"], int(c["graph_seed"]))
        self.n = len(self.indptr) - 1
        self.deg = np.diff(self.indptr)
        with ph("keys"):
            rng = np.random.default_rng([self.seed, 2])
            cand = np.flatnonzero(
                self.deg >= int(self.traffic["min_degree"]))
            keys = rng.choice(cand, int(self.traffic["roots"]),
                              replace=False)
            ecc = eccentricities(self.indptr, self.indices, keys)
        self.key_ecc = np.bincount(ecc).tolist()
        self.roots = keys[ecc == int(self.traffic["eccentricity"])]
        if not self.roots.size:
            raise RuntimeError(
                f"no search key of eccentricity "
                f"{self.traffic['eccentricity']} among {keys.size}; "
                f"found {self.key_ecc} by eccentricity")
        with ph("build"):
            self.op = self.sched.LoopScheduler(p=int(c["p"])).build(
                "bfs", self.indptr, self.indices)
        # warm-up: one level step, the only program a traversal runs
        with ph("warm"):
            front = np.zeros(self.n, np.float32)
            front[self.roots[-1]] = 1.0
            jax.block_until_ready(self.op.step(front, front))
        self.warm_calls = 0
        self.open_window()

    def open_window(self) -> None:
        self.out = []           # (root, levels) of every traversal
        self.i = 0

    # ------------------------------------------------------------ window
    def call(self) -> None:
        root = int(self.roots[self.i % len(self.roots)])
        self.out.append((root, self.op.levels(root)))
        self.i += 1

    def units(self) -> int:
        """Traversals the window completed."""
        return self.i

    def counters(self) -> dict:
        sch = self.op.schedule
        slots = (self.op.shards.n_tiles_padded * sch.rows_per_tile
                 * sch.width)
        steps = [int(lv.max()) + 1 for _, lv in self.out]
        reached_edges = [int(self.deg[lv >= 0].sum()) for _, lv in self.out]
        return {"nnz": int(self.indptr[-1]), "n": self.n, "slots": slots,
                "tiles": sch.n_tiles, "width": sch.width,
                "blocks_per_worker": self.op.shards.n_steps,
                "key_eccentricity": self.key_ecc,
                "level_steps": steps,
                "bytes_per_call": float(np.mean(
                    [bfs_bytes(self.n, e) for e in reached_edges]))
                if reached_edges else None}

    def release(self) -> None:
        """Pick the traversals to check and free the device state."""
        rng = np.random.default_rng([self.seed, 3])
        idx = set(rng.choice(len(self.out), min(self.n_sample, len(self.out)),
                             replace=False).tolist())
        idx.add(int(np.argmax([lv.max() for _, lv in self.out])))
        self.checked = [self.out[i] for i in sorted(idx)]
        del self.op

    # ------------------------------------------------------------- check
    def check(self, control: bool = False) -> dict:
        """{name: (value, limit)}: vertices whose level differs from the
        reference, over the checked traversals. With `control`, a reference
        over one direction of each edge, which breaks the configuration's
        undirected graph, takes the program's place."""
        if control:
            rows = np.repeat(np.arange(self.n), self.deg)
            keep = rows < self.indices
            half_ptr = np.concatenate([[0], np.cumsum(
                np.bincount(rows[keep], minlength=self.n))])
            half_idx = self.indices[keep]
        bad = []
        for root, lv in self.checked:
            want = ref.levels(self.indptr, self.indices, root)
            got = ref.levels(half_ptr, half_idx, root) if control else lv
            bad.append(int(np.sum(np.asarray(got) != want)))
        self.n_failed = sum(b > 0 for b in bad)
        return {"bfs_wrong_levels": (sum(bad), 0)}
