#!/usr/bin/env python3
"""Smoke test of the main path on one TPU chip.

    python chip_smoke.py [--seed N]

Runs in one process and needs a TPU: without one it exits non-zero before
any work and prints no result. Phases, each through the normal entry
points and each checked against a reference independent of the code under
test:

* kernels — `sched.LoopScheduler(p=...).build(...)` at p=1 and p=4 on the
  one chip, then the op: SpMV on the Table-1 `wikipedia` row-length
  profile at its published row count, BFS levels on a 2^21-vertex
  scale-free graph, K-Means assignment at Rodinia's kdd_cup shape;
* serving — `serve.Engine` on olmo-1b at its full published width behind
  `serve.ContinuousBatcher(..., backend=serve.EngineBackend(engine))`,
  each request's final prefill logits checked against a one-shot
  `models.prefill` of the same prompt.

Inputs and weights are random, made from `--seed`. Timings printed are
smoke timings (host clock around calls that end in a device sync), not
metrics. The last line of standard output is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

# SpMV: relative to the row's sum of |a_ij * x_j|. float32 products folded
# in float32 over <= 27 segments of <= 32 products (the longest row has 840
# nonzeros) stay below ~2e-6 of that sum; float32 partial sums rounded to
# bfloat16 anywhere on the path miss it by ~2^-9 = 2e-3.
SPMV_RTOL = 1e-5
# K-Means: an assignment may differ from the reference only where the two
# centroids' squared distances tie within float32 summation-order rounding
# (34 terms, ~1e-6 relative).
KMEANS_TIE_RTOL = 1e-5
# Serving: chunked and one-shot prefill are the same sums split and ordered
# differently, and the TPU runs float32 matmuls as bfloat16 MXU passes, so
# each program rounds its own intermediates; over 16 layers that moves the
# final logits by well under 2% of their largest magnitude. A wrong cache
# slot, position or mask moves them by O(1).
SERVE_RTOL = 2e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def timed(fn):
    """(result, seconds) of fn(), synced on the device."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


class CompileCounter:
    """Counts backend compiles (JAX's own monitoring event)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


# ----------------------------------------------------------------- inputs
def spmv_inputs(n_rows: int, seed: int):
    """CSR with the `wikipedia` row-length profile, random columns/values."""
    from repro.core import workloads as WL
    spec = next(s for s in WL.TABLE1 if s.name == "wikipedia")
    nnz = WL.matrix_row_nnz(spec, n_rows, seed=seed).astype(np.int64)
    indptr = np.concatenate([[0], np.cumsum(nnz)])
    rng = np.random.default_rng(seed)
    indices = rng.integers(0, n_rows, int(indptr[-1]), dtype=np.int32)
    data = rng.standard_normal(int(indptr[-1]), dtype=np.float32)
    x = rng.standard_normal(n_rows, dtype=np.float32)
    return indptr, indices, data, x


def bfs_inputs(n: int, seed: int):
    """The repo's scale-free graph: in-degree ~ zipf(2.3), random sources."""
    from repro.core import workloads as WL
    rng = np.random.default_rng(seed)
    degrees = np.minimum(rng.zipf(2.3, n), n // 10).astype(np.int64)
    return WL._random_graph_csr(degrees, seed + 1)


def kmeans_inputs(n: int, d: int, k: int, seed: int):
    """Points, initial centroids (k of the points) and one round's
    predicted per-point costs."""
    from repro.core import workloads as WL
    rng = np.random.default_rng(seed)
    pts = rng.standard_normal((n, d), dtype=np.float32)
    cent = pts[rng.choice(n, k, replace=False)].copy()
    costs = WL.kmeans_rounds(n, rounds=1, seed=seed)[0][0]
    return pts, cent, costs


# ----------------------------------------------------------------- phases
def phase_spmv(n_rows: int, seed: int, ps=(1, 4)) -> dict:
    from repro import sched
    indptr, indices, data, x = spmv_inputs(n_rows, seed)
    rows = np.repeat(np.arange(n_rows), np.diff(indptr))
    prod = data.astype(np.float64) * x[indices]
    ref = np.bincount(rows, weights=prod, minlength=n_rows)
    scale = np.bincount(rows, weights=np.abs(prod), minlength=n_rows)
    log(f"spmv: wikipedia profile, {n_rows} rows, {indptr[-1]} nonzeros")
    out, ys = {}, {}
    for p in ps:
        op = sched.LoopScheduler(p=p, cache_size=0).build(
            "spmv", indptr, indices, data)
        y, t_first = timed(lambda: op(x))
        y, t_run = timed(lambda: op(x))
        y = np.asarray(y, np.float64)
        live = scale > 0
        err = float(np.max(np.abs(y - ref)[live] / scale[live]))
        log(f"  p={p}: tiles {op.schedule.n_tiles} (R={op.schedule.rows_per_tile}"
            f", W={op.width}, B={op.superstep}), first call {t_first:.3f} s "
            f"(compile + run), second call {t_run:.3f} s [smoke timings]; "
            f"max |y - ref| / sum|a x| = {err:.3e} (tol {SPMV_RTOL})")
        if not err <= SPMV_RTOL:
            raise AssertionError(f"spmv p={p}: error {err} > {SPMV_RTOL}")
        ys[p] = y
        out[f"spmv_p{p}_err"] = err
        del op
        gc.collect()
    if len(ps) > 1:
        d = float(np.max(np.abs(ys[ps[0]] - ys[ps[-1]])))
        log(f"  p={ps[0]} vs p={ps[-1]}: max |diff| = {d:.3e}")
    return out


def phase_bfs(n: int, seed: int, ps=(1, 4)) -> dict:
    from repro import sched
    from repro.kernels.ich_bfs.ref import bfs_levels_ref
    indptr, indices = bfs_inputs(n, seed)
    ref = bfs_levels_ref(indptr, indices, 0)
    log(f"bfs: scale-free graph, {n} vertices, {indptr[-1]} edges, "
        f"{int(ref.max()) + 1} levels from vertex 0, "
        f"{int((ref >= 0).sum())} reached")
    out = {}
    for p in ps:
        op = sched.LoopScheduler(p=p, cache_size=0).build(
            "bfs", indptr, indices)
        lv, t_first = timed(lambda: op.levels(0))
        lv, t_run = timed(lambda: op.levels(0))
        bad = int(np.sum(lv != ref))
        log(f"  p={p}: tiles {op.schedule.n_tiles}, first traversal "
            f"{t_first:.3f} s (compile + run), second {t_run:.3f} s "
            f"[smoke timings]; levels differing from reference: {bad}")
        if bad:
            raise AssertionError(f"bfs p={p}: {bad} levels differ")
        out[f"bfs_p{p}_mismatch"] = bad
        del op
        gc.collect()
    return out


def phase_kmeans(n: int, d: int, k: int, seed: int, ps=(1, 4)) -> dict:
    from repro import sched
    from repro.kernels.ich_kmeans.ref import kmeans_assign_ref
    pts, cent, costs = kmeans_inputs(n, d, k, seed)
    ref = kmeans_assign_ref(pts, cent)
    d2 = ((pts[:, None, :] - cent[None]) ** 2).sum(-1)
    log(f"kmeans: {n} points x {d} features, {k} centroids")
    out = {}
    for p in ps:
        op = sched.LoopScheduler(p=p, cache_size=0).build("kmeans", costs)
        a, t_first = timed(lambda: op(pts, cent))
        a, t_run = timed(lambda: op(pts, cent))
        a = np.asarray(a)
        rows = np.arange(n)
        diff = a != ref
        gap = (d2[rows, a] - d2[rows, ref]) / np.maximum(d2[rows, ref], 1e-30)
        wrong = int(np.sum(diff & (gap > KMEANS_TIE_RTOL)))
        log(f"  p={p}: tiles {op.schedule.n_tiles}, first call {t_first:.3f} s"
            f" (compile + run), second {t_run:.3f} s [smoke timings]; "
            f"{int(diff.sum())} assignments differ, {wrong} outside the tie "
            f"band {KMEANS_TIE_RTOL}")
        if wrong:
            raise AssertionError(f"kmeans p={p}: {wrong} wrong assignments")
        out[f"kmeans_p{p}_wrong"] = wrong
        del op
        gc.collect()
    return out


def phase_serve(arch: str, n_req: int, prompt_len: int, n_new: int,
                chunk: int, seed: int, compiles: CompileCounter) -> dict:
    import jax
    import jax.numpy as jnp
    from repro import serve
    from repro.configs import get_arch
    from repro.models import model as M
    from repro.serve.loadgen import Arrival
    cfg = get_arch(arch)
    max_seq = prompt_len + n_new
    c0 = compiles.n
    params, t_init = timed(lambda: jax.jit(
        lambda key: M.init_params(cfg, key, max_seq))(
            jax.random.PRNGKey(seed)))
    n_params = sum(int(a.size) for a in jax.tree_util.tree_leaves(params))
    log(f"serve: {cfg.name}, {cfg.n_layers} layers, d={cfg.d_model}, "
        f"ff={cfg.d_ff}, vocab {cfg.vocab_size}, {n_params} parameters "
        f"(float32, random from seed {seed}; init {t_init:.1f} s)")
    eng = serve.Engine(cfg, params, serve.EngineConfig(max_seq=max_seq,
                                                       min_chunk=chunk))
    # record each request's logits when its prefill completes (decode
    # steps overwrite RequestState.last_logits afterwards)
    prefill_logits = {}
    step = eng.prefill_chunk_step

    def recording_step(st, n):
        step(st, n)
        if st.remaining_prefill == 0 and st.request.req_id not in \
                prefill_logits:
            prefill_logits[st.request.req_id] = np.asarray(
                st.last_logits[0], np.float32)

    eng.prefill_chunk_step = recording_step
    rng = np.random.default_rng(seed)
    prompts = {i: rng.integers(1, cfg.vocab_size, (1, prompt_len),
                               dtype=np.int32) for i in range(n_req)}
    batcher = serve.ContinuousBatcher(
        serve.FCFSStatic(chunk=chunk, min_chunk=chunk),
        queue=serve.AdmissionQueue(max_running=n_req),
        backend=serve.EngineBackend(eng))
    arrivals = [Arrival(req_id=i, t=0.0, prompt_len=prompt_len, n_new=n_new)
                for i in range(n_req)]
    c1 = compiles.n
    metrics, t_run = timed(lambda: batcher.run(
        arrivals, make_request=lambda a: serve.Request(
            req_id=a.req_id, tokens=prompts[a.req_id], n_new=a.n_new,
            t_arrival=a.t)))
    n_serve_compiles = compiles.n - c1
    done = {st.request.req_id: st for st in batcher.queue.done}
    answered = sum(len(st.out_tokens) == n_new for st in done.values())
    log(f"  {n_req} requests x {prompt_len} prompt tokens, {n_new} new each,"
        f" fixed chunk {chunk}: {answered} answered in full, "
        f"{metrics.n_tokens_out} tokens out, {t_run:.2f} s incl. compile "
        f"[smoke timing]; {n_serve_compiles} compiles in the serving run "
        f"({c1 - c0} for parameter init)")
    if answered != n_req:
        raise AssertionError(f"serve: {answered}/{n_req} requests answered")
    one_shot = jax.jit(lambda p, t: M.prefill(
        cfg, p, {"tokens": t}, None, dtype=jnp.float32)[0])
    worst = 0.0
    agree = 0
    for i, toks in prompts.items():
        ref = np.asarray(one_shot(params, jnp.asarray(toks))[0], np.float32)
        got = prefill_logits[i]
        err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
        worst = max(worst, err)
        agree += int(np.argmax(got) == np.argmax(ref) == done[i].out_tokens[0])
    log(f"  final prefill logits vs one-shot prefill: max |diff| / max|ref| "
        f"= {worst:.3e} (tol {SERVE_RTOL}); first token agrees on "
        f"{agree}/{n_req}")
    if not worst <= SERVE_RTOL:
        raise AssertionError(f"serve: logits error {worst} > {SERVE_RTOL}")
    return {"serve_err": worst, "serve_compiles": n_serve_compiles}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro import compile_cache
    cache = compile_cache.configure()
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache}")
    compiles = CompileCounter()
    t0 = time.perf_counter()
    phase_spmv(3_566_907, args.seed)
    phase_bfs(1 << 21, args.seed)
    phase_kmeans(494_020, 34, 5, args.seed)
    phase_serve("olmo-1b", n_req=4, prompt_len=512, n_new=8, chunk=128,
                seed=args.seed, compiles=compiles)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s; "
        f"{compiles.n} backend compiles")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
