"""On-chip benchmark of the iCh loop scheduler: one run of one cell.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. Everything is found by name from
`BENCHMARK.json`: the cell's configuration file, its traffic mix
`bench/traffic/<traffic>.json`, the module of the configuration's kind
`bench/kinds/<kind>.py` and each per-layer metric's reader
`bench/metrics/<name>.py`.

A kind's `Workload` (configuration, traffic, seed, `repro.sched`) has
`setup()`, `warm_calls` (calls of the window's own that warm up before it
opens), `open_window()`, `call()`, `units()`, `counters()`, `release()`
and `check(control=False)`, which returns {number: (value, limit)}.

A run makes its inputs from `--seed`, builds the op through
`repro.sched.LoopScheduler(...).build(...)`, warms up every program the
window will run, then calls the loop back to back for `--seconds` (the
last call started finishes), frees the device state and compares the
sampled outputs with a plain reference. With `--trace 1` the window runs
under the profiler and the per-layer metrics are read from the trace.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
before any work and prints no result. The last line of standard output is
one JSON object: correct, attempted, failed, metrics, device, breakdown
(traced runs) and the numbers compared with their limits.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def log(msg: str) -> None:
    print(msg, flush=True)


def load_module(path: Path):
    """Import a file by path (names may hold '-' and '.')."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, overrides: dict | None = None) -> dict:
    """The cell `name` with its configuration, traffic and metrics."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"have {sorted(cells)}")
    cell = cells[name]
    config = next(c for c in spec["configs"] if c["name"] == cell["config"])

    def applies(m):
        return name in m.get("workloads", [name])

    over = overrides or {}
    return {
        "cell": cell,
        "config": dict(json.loads((ROOT / config["file"]).read_text()),
                       **over.get("config", {})),
        "traffic": dict(json.loads(
            (BENCH / "traffic" / f"{cell['traffic']}.json").read_text()),
            **over.get("traffic", {})),
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
    }


def use_checkout_cache() -> str:
    """Keep JAX's persistent compile cache in the checkout's `.jax_cache`
    (the program's own default), whatever the environment names, so that
    two checkouts share nothing; cache every program, however quick to
    compile, so that only a checkout's first run compiles."""
    import jax
    from repro import compile_cache
    path = str(compile_cache.REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def kind_module(kind: str):
    return load_module(BENCH / "kinds" / f"{kind}.py")


class CompileCounter:
    """Counts JAX's own compile-path events: traces, lowerings, backend
    compiles and persistent-cache hits. JAX times a cache hit as a backend
    compile too, so compiles that ran are `compiles - cache_hits`."""

    DURATIONS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration":
                     "lowerings",
                 "/jax/core/compile/backend_compile_duration": "compiles"}
    EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits"}

    def __init__(self):
        import jax
        self.n = dict.fromkeys([*self.DURATIONS.values(),
                                *self.EVENTS.values()], 0)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event in self.DURATIONS:
            self.n[self.DURATIONS[event]] += 1

    def _on_event(self, event: str, **_) -> None:
        if event in self.EVENTS:
            self.n[self.EVENTS[event]] += 1

    def snapshot(self) -> dict:
        return dict(self.n)


def p95(values) -> float:
    """95th percentile of all values (linear between order statistics)."""
    s = sorted(values)
    k = 0.95 * (len(s) - 1)
    lo = int(k)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (k - lo)


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_start: float, overrides: dict | None = None) -> dict:
    """One run of cell `name`; returns the result object. `overrides`
    ({"config": {...}, "traffic": {...}}) replaces keys of either (the CPU
    rehearsals use tiny sizes)."""
    cell = load_cell(name, overrides)
    cfg = cell["config"]
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro import sched
    cache_dir = use_checkout_cache()
    compiles = CompileCounter()
    dev = jax.devices()[0]
    kind = kind_module(cfg["kind"])
    wl = kind.Workload(cfg, cell["traffic"], seed, sched)
    start_s = time.perf_counter() - t_start
    wl.setup()
    tracer = None
    if trace:
        from bench import trace as T
        tracer = T.Tracer(tempfile.mkdtemp(prefix="bench-trace-"))
    # Warm-up calls go through the window's own `wl.call()` statement: a
    # Pallas kernel's compiled form, and so its persistent-cache key, holds
    # the source locations of the call that first traced it, and an op built
    # inside the window traces anew.
    n_warm = wl.warm_calls
    times = []
    i = 0
    while True:
        if i == n_warm:
            wl.open_window()
            c_setup = compiles.snapshot()
            setup_s = time.perf_counter() - t_start
            if tracer is not None:
                tracer.start()
            t0 = time.perf_counter()
        c0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.call"):
            wl.call()
        c1 = time.perf_counter()
        i += 1
        if i > n_warm:
            times.append(c1 - c0)
            if c1 - t0 >= seconds:
                break
    window_s = c1 - t0
    if tracer is not None:
        tracer.stop()
    log(f"cell {name}: {cfg['kind']} config {cell['cell']['config']}, "
        f"traffic {cell['cell']['traffic']}, seed {seed}; device "
        f"{dev.platform} {dev.device_kind}; compile cache {cache_dir}; "
        f"set-up {setup_s:.3f} s ({n_warm} warm-up calls), {c_setup}; "
        f"start {start_s:.3f} s, {wl.phases.line()}")
    c_end = compiles.snapshot()
    in_window = {k: c_end[k] - c_setup[k] for k in c_end}
    stats = dev.memory_stats() or {}
    memory_peak = stats.get("peak_bytes_in_use")
    units = wl.units()
    counters = wl.counters()
    log(f"window {window_s:.6f} s, {units} calls, per call (s): "
        f"{[round(t, 6) for t in times]}")
    log(f"in the window: {in_window}; compiles that ran: "
        f"{in_window['compiles'] - in_window['cache_hits']} (should be 0)")
    log("counters: " + json.dumps(counters, default=str))

    wl.release()
    gc.collect()
    checks = wl.check()
    correct = all(v <= lim for v, lim in checks.values())

    result = {"correct": bool(correct), "attempted": units,
              "failed": int(getattr(wl, "n_failed", 0)), "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": memory_peak}}
    ms = 1e3 * window_s / units
    if not trace:
        values = {"call_ms": ms, "call_p95_ms": 1e3 * p95(times),
                  "setup_s": setup_s}
        for m in cell["end_to_end"]:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    else:
        from bench import peaks
        try:
            tr = tracer.read()
        finally:
            tracer.cleanup()
        ctx = {"trace": tr, "units": units, "window_s": window_s,
               "call_times": times, "counters": counters,
               "peaks": peaks.peaks(dev.device_kind)}
        for m in cell["per_layer"]:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            v = reader.read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["device"]["busy_s"] = tr.busy_s()
        result["device"]["window_s"] = tr.window_s()
        result["breakdown"] = tr.breakdown()
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v!r} (limit {lim!r})", file=sys.stderr,
              flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    import jax
    devices = jax.devices()
    need = int(cell["cell"]["chips"])
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"bench: cell {args.workload} needs {need} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), T_START)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
