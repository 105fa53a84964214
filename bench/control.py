"""Readings that the limits of `correct` are set from.

    python3 -m bench.control --workload <cell> --seconds <s> --seeds 1 2 3 ...

For each seed, in one process: the cell's set-up and a short window at
its own size and load, then the numbers that a run compares, once for the
program and once for the control (the plain reference in the precision
below the configuration's, or breaking one of its guarantees, in the
program's place). One JSON line per seed. Needs a TPU, as a run does.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from bench import run


def readings(name: str, seeds, seconds: float, overrides=None):
    """Yield {seed, calls, program, control} for each seed; the last two
    map each number compared to (value, limit)."""
    cell = run.load_cell(name, overrides)
    cfg = cell["config"]
    sys.path.insert(0, str(run.ROOT / "src"))
    from repro import sched
    run.use_checkout_cache()
    kind = run.kind_module(cfg["kind"])
    for seed in seeds:
        wl = kind.Workload(cfg, cell["traffic"], seed, sched)
        wl.setup()
        t0 = time.perf_counter()
        while True:
            wl.call()
            if time.perf_counter() - t0 >= seconds:
                break
        wl.release()
        yield {"seed": seed, "calls": wl.units(),
               "program": wl.check(), "control": wl.check(control=True)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("bench.control: needs a TPU", file=sys.stderr)
        return 2
    for r in readings(args.workload, args.seeds, args.seconds):
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
