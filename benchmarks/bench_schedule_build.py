"""Schedule-construction performance benchmark (the repo's perf trajectory).

Measures, across item counts (default 10k / 100k / 1M):

  * `build_schedule` wall time — vectorized array program vs the
    `_reference_*` loop oracle (the seed implementation);
  * `pack_csr` wall time PER LAYOUT: the flat (T, R, W) layout and the
    worker-sharded (p*S, R, W) layout the 2D kernels consume (partition +
    shard layout time reported separately). Outputs are asserted identical
    to the loop oracle on BOTH layouts before any timing is reported, so
    the speedup numbers can't drift away from correctness;
  * the `repro.sched` schedule cache: a repeated `LoopScheduler.schedule()`
    call with identical inputs must be an LRU hit that returns the
    previously built `Schedule` object and skips construction entirely
    (asserted on the cache counters and on object identity); warm-path
    cost is the fingerprint hash;
  * interpret-mode step cost of the three ich_* kernels at the smallest
    size (interpret mode is Python-per-grid-step, so larger sizes measure
    the interpreter, not the kernel), on the sequential (T,) reference
    grid AND the worker-sharded superstepped 2D grid at p in {1, 4} —
    sharded outputs are asserted bit-identical to the sequential grid, so
    this section doubles as the CI sharded-kernel smoke;
  * MoE expert dispatch on the scheduler (DESIGN.md §2.8) at the smallest
    size: the sort-based dispatch resolution alone vs the full scheduled
    build (plan + schedule + shard + pack), and the closed capacity loop —
    the sharded-replay TRUE-cost imbalance is asserted non-increasing
    across three `refine_cap_scale` rounds;
  * fault-injection degradation (DESIGN.md §2.9) at the smallest size:
    makespan inflation of the iCh simulator run vs number of killed
    workers (seeded `FaultPlan` deaths, queues reclaimed by survivors) —
    asserted monotone in the kill count, bounded by 1.5x the fault-free
    run on the surviving worker count, and bit-identical across replays;
  * the measured-cost refine loop (DESIGN.md §2.7) at the smallest size:
    a jittered workload is scheduled from a-priori estimates, per-tile
    true costs are observed from a sharded replay, and
    `Schedule.observe(...).refine()` re-lowers — the simulated sharded
    makespan on the TRUE costs is asserted monotonically non-increasing
    across the rounds and reported against the perfect-balance bound;
  * the COMPILED trajectory (DESIGN.md §2.12) at the smallest size: the
    jitted on-device schedule pipeline (`core/tiling_jax.py` — build ->
    cost -> partition -> shard layout as one XLA executable) asserted
    element-identical to the numpy construction and timed cold
    (trace+compile) and warm, the jitted device `pack_csr` twin asserted
    equal to the host pack, and the sharded SpMV kernel step at p in
    {1, 4} consuming the device pipeline's own prefetch streams,
    asserted bit-identical to the sequential grid. On a real TPU the
    kernel compiles (interpret=False); on CPU the Pallas TPU lowering is
    unavailable, so the step falls back to jit-wrapped interpret mode
    and the record carries `interpret_fallback: true` — an honestly
    labeled stand-in, not a compiled number. `--compiled-smoke` runs
    ONLY this section and merges it into an existing BENCH_schedule.json
    (the CI compiled-smoke step); `--no-compiled` skips it.

Writes `BENCH_schedule.json` at the repo root so future PRs have a recorded
trajectory to regress against, and prints one CSV line per measurement.
Run standalone:

  PYTHONPATH=src python -m benchmarks.bench_schedule_build
  PYTHONPATH=src python -m benchmarks.bench_schedule_build --sizes 10000

or through the driver: PYTHONPATH=src python -m benchmarks.run --bench schedule
"""
from __future__ import annotations

import argparse
import json
import platform
import time
from pathlib import Path

import numpy as np

from repro import compile_cache
from repro.core import tiling as T
from repro.sched.defaults import SUPERSTEP

ROOT = Path(__file__).resolve().parent.parent
DEFAULT_SIZES = (10_000, 100_000, 1_000_000)
ROWS_PER_TILE = 8
SHARD_P = 8  # worker count for the sharded-layout pack measurements


def workload(n: int, seed: int = 1) -> np.ndarray:
    """Heavy-tailed per-item work: zipf(1.8) capped at 2000, 10% zero items
    (the empty-CSR-row / isolated-vertex case)."""
    rng = np.random.default_rng(seed)
    sizes = np.minimum(rng.zipf(1.8, n), 2000).astype(np.int64)
    sizes[rng.random(n) < 0.1] = 0
    return sizes


def _best(fn, repeats: int):
    best, out = float("inf"), None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def _csr(sizes: np.ndarray, seed: int = 2):
    rng = np.random.default_rng(seed)
    indptr = np.concatenate([[0], np.cumsum(sizes)])
    nnz = int(indptr[-1])
    indices = rng.integers(0, sizes.size, nnz).astype(np.int32)
    data = rng.standard_normal(nnz).astype(np.float32)
    return indptr, indices, data


def bench_build(n: int, repeats: int) -> dict:
    """Vectorized vs reference construction at n items, plus pack_csr per
    layout (outputs asserted equal before any timing is reported)."""
    sizes = workload(n)
    ref_repeats = repeats if n <= 100_000 else 1  # ref at 1M is seconds/run
    t_vec, sched = _best(lambda: T.build_schedule(
        sizes, rows_per_tile=ROWS_PER_TILE), repeats)
    t_ref, ref = _best(lambda: T._reference_build_schedule(
        sizes, rows_per_tile=ROWS_PER_TILE), ref_repeats)
    np.testing.assert_array_equal(sched.item_id, ref.item_id)
    np.testing.assert_array_equal(sched.seg_start, ref.seg_start)
    np.testing.assert_array_equal(sched.seg_len, ref.seg_len)

    indptr, indices, data = _csr(sizes)
    costs = 1.0 + sizes.astype(np.float64)
    t_shard, shards = _best(lambda: T.shard_schedule(
        sched, sched.tile_cost(costs, sizes), SHARD_P), repeats)

    t_pvec, packed = _best(
        lambda: T.pack_csr(indptr, indices, data, sched), repeats)
    # the sharded layout is zero-copy (kernels fetch blocks straight from
    # the flat payload): its pack = the superstep-padded flat pack plus
    # the prefetch-stream build (block ids + sharded item ids)
    B = shards.superstep

    def pack_sharded():
        vp, cp = T.pack_csr(indptr, indices, data, sched, pad_tiles_to=B)
        return vp, cp, shards.kernel_block_ids(), shards.shard_item_id(sched)

    t_psh, (pv, pc, blkid, rowid_sh) = _best(pack_sharded, repeats)
    t_pref, packed_ref = _best(
        lambda: T._reference_pack_csr(indptr, indices, data, sched), 1)
    # vec == reference on the flat layout...
    np.testing.assert_array_equal(packed[0], packed_ref[0])
    np.testing.assert_array_equal(packed[1], packed_ref[1])
    # ...and on the sharded layout: the padded payload matches reference on
    # real tiles (zeros beyond), and the block/item prefetch streams name
    # every tile exactly once
    Tn = sched.n_tiles
    np.testing.assert_array_equal(pv[:Tn], packed_ref[0])
    np.testing.assert_array_equal(pc[:Tn], packed_ref[1])
    assert (pv[Tn:] == 0).all() and (pc[Tn:] == 0).all()
    perm = shards.perm
    np.testing.assert_array_equal(np.sort(perm[perm >= 0]), np.arange(Tn))
    assert blkid.shape == (SHARD_P * shards.n_steps,)
    assert rowid_sh.shape == (SHARD_P * shards.tiles_per_worker,
                              ROWS_PER_TILE)
    return {
        "n_items": n,
        "nnz": int(sizes.sum()),
        "width": sched.width,
        "n_tiles": sched.n_tiles,
        "build_vec_s": t_vec,
        "build_ref_s": t_ref,
        "build_speedup": t_ref / t_vec,
        "pack": {
            "ref_s": t_pref,
            "flat": {"vec_s": t_pvec, "speedup": t_pref / t_pvec},
            "sharded": {"vec_s": t_psh, "speedup": t_pref / t_psh,
                        "p": SHARD_P, "superstep": B,
                        "partition_s": t_shard,
                        "tiles_per_worker": shards.tiles_per_worker},
        },
    }


def bench_cache(n: int, repeats: int) -> dict:
    """Schedule-cache behavior at n items (the serving path's reuse story).

    The second `schedule()` call with identical inputs MUST be a cache hit
    that skips construction entirely: asserted on the LRU counters (one
    miss total) and on object identity (the very same `Schedule` comes
    back). The warm path pays only the cost-fingerprint hash.
    """
    from repro.sched import LoopScheduler

    sizes = workload(n)
    sched = LoopScheduler()
    t0 = time.perf_counter()
    first = sched.schedule(sizes)
    t_cold = time.perf_counter() - t0
    assert sched.cache_stats.misses == 1 and sched.cache_stats.hits == 0
    t_warm, again = _best(lambda: sched.schedule(sizes), repeats)
    assert again is first, "cache hit must return the cached Schedule object"
    assert sched.cache_stats.misses == 1, \
        "cache hit must not re-run schedule construction"
    assert sched.cache_stats.hits == repeats
    return {
        "n_items": n,
        "cold_s": t_cold,
        "warm_hit_s": t_warm,
        "hit_speedup": t_cold / max(t_warm, 1e-12),
        "hits": sched.cache_stats.hits,
        "misses": sched.cache_stats.misses,
    }


def bench_refine_loop(n: int, p: int = 8, rounds: int = None,
                      jitter_seed: int = 5) -> dict:
    """The closed feedback loop, demonstrated end to end (DESIGN.md §2.7).

    A zipf workload's payload structure (row sizes) is known exactly, but
    its TRUE per-item costs carry a hidden multiplicative jitter the
    a-priori estimate (cost ~ size) misses — the paper's DVFS/cache-miss
    heterogeneity (§3.2) at item granularity. Each round replays the
    current schedule's worker-sharded lowering on the true costs, observes
    the exact per-tile measured costs from the replay's chunk log, and
    `observe(...).refine()` re-lowers under the refreshed estimates. The
    simulated sharded makespan (zero overhead/jitter: the partition's max
    per-worker true cost) must be monotonically non-increasing across the
    rounds — asserted here, so CI catches any refinement regression — and
    converges onto the perfect-balance bound (busy/p).
    """
    from repro.core.simulator import SimParams
    from repro.sched import LoopScheduler, NnzCosts
    from repro.sched.defaults import REFINE_ROUNDS

    rounds = REFINE_ROUNDS if rounds is None else int(rounds)
    rng = np.random.default_rng(jitter_seed)
    sizes = workload(n)
    indptr = np.concatenate([[0], np.cumsum(sizes)])
    true = (1.0 + sizes) * rng.uniform(0.3, 3.0, n)
    zero = SimParams(dispatch_overhead=0.0, local_dispatch_overhead=0.0,
                     speed_jitter=0.0)
    s = LoopScheduler(p=p).schedule(NnzCosts(indptr))
    makespans, balance = [], None
    t0 = time.perf_counter()
    for r in range(rounds + 1):
        rep = s.replay_refined(true, sharded=True, params=zero,
                               record_chunks=True)
        makespans.append(rep.makespan)
        balance = rep.busy / p  # perfect-balance lower bound on this work
        if r == rounds:
            break
        tile_true = np.array([wk for (*_, wk) in rep.chunk_log])
        s = s.observe(tile_true, level="tile").refine()
    elapsed = time.perf_counter() - t0
    for a, b in zip(makespans, makespans[1:]):
        assert b <= a + 1e-9, (
            f"refine round increased sharded makespan: {makespans}")
    assert s.generation == rounds
    return {
        "n_items": n, "p": p, "rounds": rounds,
        "makespans": makespans,
        "balance_bound": balance,
        "improvement": 1.0 - makespans[-1] / makespans[0],
        "imbalance_final": makespans[-1] / balance,
        "loop_s": elapsed,
    }


def bench_moe_dispatch(n_tokens: int, repeats: int, n_experts: int = 512,
                       k: int = 2, p: int = 8, rounds: int = 3,
                       seed: int = 7) -> dict:
    """MoE expert dispatch on the scheduler (DESIGN.md §2.8).

    Two measurements over a zipf-skewed router at n_tokens:

    * build cost — the sort-based dispatch resolution alone
      (`plan_dispatch`, what the in-graph path also computes) vs the FULL
      scheduled build: plan + iCh schedule over the per-expert loads +
      worker-shard partition + packed (T, R, W) payload. The difference
      is the price of running the model on the scheduler.
    * the closed capacity loop — per-expert TRUE costs carry hidden
      multiplicative heterogeneity the token-count estimate misses;
      each round folds them in through `refine_cap_scale`
      (observe/refine + next cap_scale) and the sharded-replay TRUE-cost
      imbalance (makespan over the perfect-balance bound) is asserted
      non-increasing across the rounds, so CI catches any regression of
      the §2.8 feedback path.
    """
    from repro.core.simulator import SimParams
    from repro.sched import ExpertLoadCosts, LoopScheduler
    from repro.sched.moe import plan_dispatch, refine_cap_scale

    rng = np.random.default_rng(seed)
    # moderate zipf popularity: every expert sees traffic, hot experts see
    # several times the mean (heavier skew starves most experts and the
    # capacity cut flattens what's left — nothing to schedule)
    pop = np.arange(1, n_experts + 1, dtype=np.float64) ** -1.0
    logits = rng.gumbel(size=(n_tokens, n_experts)) + np.log(pop)[None]
    e_topk = np.argsort(-logits, axis=1)[:, :k].astype(np.int32)
    w = (rng.random((n_tokens, k)) + 0.1).astype(np.float32)
    w /= w.sum(1, keepdims=True)

    # cap_scale pins E: heavy skew can leave high-id experts unrouted
    ones = np.ones(n_experts)
    t_plan, plan = _best(lambda: plan_dispatch(e_topk, w, cap_scale=ones),
                         repeats)
    # time real rebuilds (cache off); 2-row tiles because the shard
    # partition's unit is the superstep BLOCK — 8-row tiles over 512
    # capped experts yield exactly p blocks, leaving the partition no
    # freedom to act on refined costs
    scheduler = LoopScheduler(p=p, cache_size=0, rows_per_tile=2)

    def scheduled_build():
        pl = plan_dispatch(e_topk, w, cap_scale=ones)
        s = scheduler.schedule(ExpertLoadCosts(pl.counts))
        sh = s.shard()
        indptr, tok, wcsr = pl.csr()
        T.pack_csr(indptr, tok, wcsr, s.tiles, pad_tiles_to=sh.superstep)
        return s

    t_sched, s = _best(scheduled_build, repeats)

    zero = SimParams(dispatch_overhead=0.0, local_dispatch_overhead=0.0,
                     speed_jitter=0.0)
    true = (plan.counts.astype(np.float64)
            * rng.uniform(0.5, 2.0, n_experts) + 0.01)
    imb_true, imb_pred, cap_scale = [], [], None
    for r in range(rounds + 1):
        rep = s.replay_refined(true, sharded=True, params=zero)
        imb_true.append(rep.makespan / (rep.busy / p))
        imb_pred.append(s.imbalance())
        if r == rounds:
            break
        s, cap_scale = refine_cap_scale(s, true)
    for a, b in zip(imb_true, imb_true[1:]):
        assert b <= a + 1e-9, (
            f"refine round increased dispatch imbalance: {imb_true}")
    assert s.generation == rounds
    return {
        "n_tokens": n_tokens, "n_experts": n_experts, "k": k, "p": p,
        "kept": int(plan.counts.sum()), "stolen": plan.stolen,
        "dropped": plan.dropped,
        "plan_s": t_plan,
        "scheduled_build_s": t_sched,
        "schedule_overhead": t_sched / t_plan,
        "rounds": rounds,
        "imbalance_true": imb_true,
        "imbalance_predicted": imb_pred,
        "cap_scale_min": float(cap_scale.min()),
        "cap_scale_max": float(cap_scale.max()),
    }


def bench_degradation(n: int, p: int = 4, seed: int = 100) -> dict:
    """Graceful degradation under injected worker deaths (DESIGN.md §2.9):
    makespan inflation vs number of killed workers, asserted monotone.

    Near-uniform per-item costs and EARLY deaths (after each victim's
    first chunk), so the lost capacity dominates the measurement — on
    heavy-tailed workloads steal-path luck can mask a single death (a
    different chunk/steal pattern occasionally beats the fault-free run).
    Asserted, so CI catches any reclaim regression:

      * inflation(k) > 1 and strictly increasing in k for k = 1..p-1
        (each additional dead worker costs more);
      * bounded factor: the k-death run stays within 1.5x of a fault-free
        run on the p-k survivors (recovery never costs more than simply
        having started with the smaller machine, modulo steal luck);
      * every plan replays bit-identically (same makespan + fault trace).
    """
    from repro.core.policies import ich
    from repro.core.simulator import simulate
    from repro.robust import FaultPlan

    rng = np.random.default_rng(seed)
    costs = rng.uniform(8.0, 12.0, n)
    clean = simulate(costs, p, ich())
    rows = []
    prev = 1.0
    for k in range(1, p):
        plan = FaultPlan(seed=seed,
                         deaths=tuple((w, 1) for w in range(k)))
        faulty = simulate(costs, p, ich(), faults=plan)
        again = simulate(costs, p, ich(), faults=plan)
        assert faulty.makespan == again.makespan, \
            f"chaos replay diverged at k={k}"
        assert faulty.fault_log == again.fault_log
        inflation = faulty.makespan / clean.makespan
        assert inflation > prev, (
            f"inflation must increase monotonically in killed workers: "
            f"k={k} gave {inflation:.4f} after {prev:.4f}")
        survivors = simulate(costs, p - k, ich())
        assert faulty.makespan <= 1.5 * survivors.makespan, (
            f"k={k}: faulty makespan {faulty.makespan:.1f} exceeds 1.5x "
            f"the fault-free p-{k} run {survivors.makespan:.1f}")
        rows.append({
            "killed": k,
            "makespan": faulty.makespan,
            "inflation": inflation,
            "vs_survivor_machine": faulty.makespan / survivors.makespan,
            "deaths": faulty.deaths,
            "reclaims": faulty.reclaims,
        })
        prev = inflation
    return {
        "n_items": n, "p": p, "policy": "ich",
        "workload": f"uniform(8, 12), seed {seed}, deaths after 1 chunk",
        "clean_makespan": clean.makespan,
        "rows": rows,
    }


def bench_recovery(n: int, p: int = 4, seed: int = 100) -> dict:
    """Checkpoint-based reshard recovery vs steal-only reclaim
    (DESIGN.md §2.11): kill k of p workers early, then finish the run
    two ways from the SAME amount of completed work — PR 7's dynamic
    steal-path reclaim (pays per-chunk steal/dispatch overheads for
    every reclaimed item) vs re-lowering the incomplete chains onto the
    p-k survivors from the checkpoint at the last superstep barrier
    before the first death (barrier-time model: completed prefix +
    re-execution, no per-chunk overheads). Asserted per row: reshard
    inflation must not exceed steal inflation beyond the superstep
    QUANTIZATION allowance — the checkpoint rounds each worker's credit
    down to a completed block, losing at most one block of progress per
    worker — so CI catches any reshard regression."""
    from repro.core.policies import ich
    from repro.core.simulator import simulate
    from repro.robust import CheckpointLog, FaultPlan
    from repro.sched import LoopScheduler

    rng = np.random.default_rng(seed)
    sizes = rng.integers(8, 13, n)
    s = LoopScheduler(p=p, cache_size=0).schedule(sizes)
    shards = s.shard()
    tc = s.tile_cost()
    B = s.superstep
    clean_static = float(shards.worker_cost(tc).max())
    clean_steal = simulate(s.costs, p, ich())
    # per-worker cumulative cost at each superstep barrier
    perm = shards.perm
    step_cost = np.zeros((shards.p, shards.n_steps))
    for w in range(shards.p):
        for t in range(shards.n_steps):
            tiles = perm[w, t * B:(t + 1) * B]
            step_cost[w, t] = tc[tiles[tiles >= 0]].sum()
    cum = np.cumsum(step_cost, axis=1)
    quantum = float(step_cost.max()) / clean_static  # one block of credit
    rows = []
    for k in range(1, p):
        plan_f = FaultPlan(seed=seed,
                           deaths=tuple((w, 1) for w in range(k)))
        faulty = simulate(s.costs, p, ich(), faults=plan_f,
                          record_assignment=True)
        steal_inflation = faulty.makespan / clean_steal.makespan
        # the last consistent barrier before the first death: every dead
        # worker had completed exactly its first chunk
        t_c = min(float(s.costs[faulty.assignment == w].sum())
                  for w in range(k))
        log = CheckpointLog()
        for w in range(p):
            log.mark_through(w, int(np.searchsorted(cum[w], t_c,
                                                    side="right")))
        plan = s.reshard_survivors(dead=range(k), checkpoint=log)
        again = s.reshard_survivors(
            dead=range(k),
            checkpoint=CheckpointLog.from_json(log.to_json()))
        assert np.array_equal(plan.redo_blocks, again.redo_blocks), \
            f"recovery replan diverged at k={k}"
        mm = plan.makespan_model(tc)
        inflation = mm["makespan"] / clean_static
        assert inflation <= steal_inflation + quantum, (
            f"k={k}: reshard inflation {inflation:.4f} exceeds the "
            f"steal-only reclaim inflation {steal_inflation:.4f} beyond "
            f"the one-block quantization allowance {quantum:.4f}")
        rows.append({
            "killed": k,
            "blocks_redone": int(plan.redo_blocks.size),
            "blocks_kept": int(plan.keep_blocks.size),
            "t_done": mm["t_done"],
            "t_redo": mm["t_redo"],
            "makespan": mm["makespan"],
            "inflation": inflation,
            "steal_inflation": steal_inflation,
        })
    return {
        "n_items": n, "p": p,
        "workload": f"integers(8, 13), seed {seed}, deaths after 1 chunk, "
                    f"checkpoint at the barrier before the first death",
        "clean_static_makespan": clean_static,
        "clean_steal_makespan": clean_steal.makespan,
        "quantization_allowance": quantum,
        "rows": rows,
    }


def _timed(fn, repeats: int = 3):
    import jax
    out = jax.block_until_ready(fn())  # trace + compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best, out


def bench_kernel_step(n: int, shard_ps=(1, 4)) -> dict:
    """Steady-state interpret-mode cost of one full schedule sweep for each
    ich_* kernel (first call = trace/compile, second call timed): the
    sequential (T,) reference grid vs the worker-sharded superstepped 2D
    grid at p in `shard_ps`. Sharded outputs are asserted bit-identical to
    the sequential grid — this is the CI sharded-kernel smoke."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ich_bfs.ich_bfs import (ich_bfs_step,
                                               ich_bfs_step_sharded)
    from repro.kernels.ich_kmeans.ich_kmeans import (
        ich_kmeans_assign, ich_kmeans_assign_sharded)
    from repro.kernels.ich_spmv.ich_spmv import ich_spmv, ich_spmv_sharded
    from repro.sched import LoopScheduler

    rng = np.random.default_rng(3)
    sizes = workload(n)
    indptr, indices, data = _csr(sizes)
    scheduler = LoopScheduler(rows_per_tile=ROWS_PER_TILE)
    s = scheduler.schedule(np.diff(indptr))
    n_tiles, B = s.n_tiles, SUPERSTEP
    out = {"n_items": n, "n_tiles": n_tiles, "superstep": B}

    def record(name, seq_fn, sharded_fn, k_tiles):
        """Time the sequential grid, then each sharded p; assert bitwise
        equality; return {seq: {...}, sharded: {p: {...}}}. `k_tiles` is
        the tile count of the schedule THIS kernel runs (kmeans builds its
        own schedule, which need not match spmv/bfs's)."""
        dt, ref_out = _timed(seq_fn)
        rec = {"seq": {"total_s": dt, "per_tile_us": 1e6 * dt / k_tiles}}
        rec["sharded"] = {}
        for p, fn in sharded_fn.items():
            dt_p, out_p = _timed(fn)
            np.testing.assert_array_equal(
                np.asarray(out_p), np.asarray(ref_out),
                err_msg=f"{name} sharded p={p} != sequential grid")
            rec["sharded"][str(p)] = {
                "total_s": dt_p, "per_tile_us": 1e6 * dt_p / k_tiles,
                "per_tile_speedup": dt / dt_p}
        return rec

    # --- spmv ---------------------------------------------------------
    x = jnp.asarray(rng.standard_normal(sizes.size).astype(np.float32))
    vals, cols = T.pack_csr(indptr, indices, data, s.tiles)
    va, ca, ra = jnp.asarray(vals), jnp.asarray(cols), jnp.asarray(s.item_id)
    vp, cp = T.pack_csr(indptr, indices, data, s.tiles, pad_tiles_to=B)
    vpa, cpa = jnp.asarray(vp), jnp.asarray(cp)
    seq = jax.jit(lambda: ich_spmv(va, ca, ra, x, sizes.size,
                                   interpret=True))
    sharded = {}
    for p in shard_ps:
        sh = s.shard(p=p)
        args = (jnp.asarray(sh.shard_item_id(s.tiles)),
                jnp.asarray(sh.kernel_block_ids()))
        sharded[p] = jax.jit(lambda a=args, p=p: ich_spmv_sharded(
            vpa, cpa, *a, x, sizes.size, p, B, interpret=True))
    out["ich_spmv"] = record("ich_spmv", seq, sharded, s.n_tiles)

    # --- bfs ----------------------------------------------------------
    frontier = jnp.asarray((rng.random(sizes.size) < 0.05)
                           .astype(np.float32))
    ones = np.ones(len(indices), np.float32)
    mask, mcols = T.pack_csr(indptr, indices, ones, s.tiles)
    ma, mc = jnp.asarray(mask), jnp.asarray(mcols)
    mp, mcp = T.pack_csr(indptr, indices, ones, s.tiles, pad_tiles_to=B)
    mpa, mcpa = jnp.asarray(mp), jnp.asarray(mcp)
    seq = jax.jit(lambda: ich_bfs_step(ma, mc, ra, frontier, frontier,
                                       sizes.size, interpret=True))
    sharded = {}
    for p in shard_ps:
        sh = s.shard(p=p)
        args = (jnp.asarray(sh.shard_item_id(s.tiles)),
                jnp.asarray(sh.kernel_block_ids()))
        sharded[p] = jax.jit(lambda a=args, p=p: ich_bfs_step_sharded(
            mpa, mcpa, *a, frontier, frontier, sizes.size, p, B,
            interpret=True))
    out["ich_bfs"] = record("ich_bfs", seq, sharded, s.n_tiles)

    # --- kmeans -------------------------------------------------------
    km_s = scheduler.schedule(np.maximum(sizes.astype(np.float64), 1.0))
    pts = jnp.asarray(rng.standard_normal((sizes.size, 8))
                      .astype(np.float32))
    cent = jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32))
    kra = jnp.asarray(km_s.item_id)
    seq = jax.jit(lambda: ich_kmeans_assign(pts, cent, kra, interpret=True))
    sharded = {}
    for p in shard_ps:
        sh = km_s.shard(p=p)
        rid = jnp.asarray(sh.shard_item_id(km_s.tiles))
        sharded[p] = jax.jit(lambda r=rid, p=p: ich_kmeans_assign_sharded(
            pts, cent, r, p, B, interpret=True))
    out["ich_kmeans"] = record("ich_kmeans", seq, sharded, km_s.n_tiles)
    return out


def bench_compiled(n: int, repeats: int, shard_ps=(1, 4)) -> dict:
    """The compiled-mode trajectory (ISSUE 10 / DESIGN.md §2.12).

    Three measurements, each gated on an exactness assertion so the
    recorded numbers can never drift away from correctness:

    * the jitted on-device pipeline (`tiling_jax.lower_schedule_jax`:
      build -> cost -> partition -> shard layout) vs the numpy
      construction chain at each p — every output (tiles, f64 tile
      costs, LPT worker map, (p, S_B) layout, prefetch streams) asserted
      ELEMENT-IDENTICAL before timing; cold includes trace+compile, warm
      is the steady-state re-dispatch;
    * the jitted device `pack_csr` twin vs the host pack (superstep-
      padded layout), asserted equal;
    * one sharded SpMV sweep at each p consuming the device pipeline's
      own rowid/blkid streams, asserted bit-identical to the sequential
      reference grid. It runs compiled (interpret=False) on a TPU and is
      not measured anywhere else: an interpreter timing is no kernel
      time, so without a TPU `kernel_step` is recorded as None.

    The pipeline and pack timings are host-clock times on `backend`.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import tiling_jax as TJ
    from repro.kernels.ich_spmv.ich_spmv import ich_spmv, ich_spmv_sharded

    sizes = workload(n)
    indptr, indices, data = _csr(sizes)
    costs = 1.0 + sizes.astype(np.float64)
    B = SUPERSTEP
    backend = jax.default_backend()
    out = {"n_items": n, "backend": backend, "superstep": B}

    # --- jitted pipeline vs numpy construction ------------------------
    def np_pipeline(p):
        sched = T.build_schedule(sizes, rows_per_tile=ROWS_PER_TILE)
        tc = sched.tile_cost(costs, sizes)
        shards = T.shard_schedule(sched, tc, p)
        return (sched, tc, shards, shards.shard_item_id(sched),
                shards.kernel_block_ids())

    lowerings, rows = {}, {}
    for p in shard_ps:
        t_np, (sched, tc, shards, rowid, blkid) = _best(
            lambda p=p: np_pipeline(p), repeats)
        t0 = time.perf_counter()
        low = TJ.lower_schedule_jax(sizes, costs, p=p,
                                    rows_per_tile=ROWS_PER_TILE)
        jax.block_until_ready(low.block_perm)
        t_cold = time.perf_counter() - t0

        def jax_pipeline(p=p):
            lw = TJ.lower_schedule_jax(sizes, costs, p=p,
                                       rows_per_tile=ROWS_PER_TILE)
            jax.block_until_ready(lw.block_perm)
            return lw

        t_warm, low = _best(jax_pipeline, repeats)
        np.testing.assert_array_equal(np.asarray(low.schedule.item_id),
                                      sched.item_id)
        np.testing.assert_array_equal(np.asarray(low.schedule.seg_start),
                                      sched.seg_start)
        np.testing.assert_array_equal(np.asarray(low.schedule.seg_len),
                                      sched.seg_len)
        np.testing.assert_array_equal(np.asarray(low.tile_cost), tc)
        np.testing.assert_array_equal(np.asarray(low.worker), shards.worker)
        np.testing.assert_array_equal(np.asarray(low.block_perm),
                                      shards.block_perm)
        np.testing.assert_array_equal(np.asarray(low.rowid), rowid)
        np.testing.assert_array_equal(np.asarray(low.blkid), blkid)
        lowerings[p] = (sched, low)
        rows[str(p)] = {"numpy_s": t_np, "jax_cold_s": t_cold,
                        "jax_warm_s": t_warm,
                        "warm_speedup": t_np / max(t_warm, 1e-12)}
    out["pipeline"] = {
        "asserted": "element-identical to numpy build/cost/partition/shard",
        "p": rows}

    # --- jitted device pack vs host pack ------------------------------
    sched, low = lowerings[shard_ps[0]]
    vp_np, cp_np = T.pack_csr(indptr, indices, data, sched, pad_tiles_to=B)

    def jax_pack():
        vp, cp = TJ.pack_csr_jax(indptr, indices, data, low.schedule,
                                 pad_tiles_to=B)
        jax.block_until_ready(vp)
        return vp, cp

    t0 = time.perf_counter()
    vp, cp = jax_pack()
    t_pcold = time.perf_counter() - t0
    t_pwarm, (vp, cp) = _best(jax_pack, repeats)
    t_pnp, _ = _best(lambda: T.pack_csr(indptr, indices, data, sched,
                                        pad_tiles_to=B), repeats)
    np.testing.assert_array_equal(np.asarray(vp), vp_np)
    np.testing.assert_array_equal(np.asarray(cp), cp_np)
    out["pack"] = {"asserted": "equal to host pack_csr (padded layout)",
                   "numpy_s": t_pnp, "jax_cold_s": t_pcold,
                   "jax_warm_s": t_pwarm}

    # --- sharded kernel step on the device pipeline's streams ---------
    out["kernel_step"] = None  # not measured: needs a TPU
    if backend != "tpu":
        return out
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal(sizes.size).astype(np.float32))
    vals, cols = T.pack_csr(indptr, indices, data, sched)
    seq = jax.jit(lambda: ich_spmv(jnp.asarray(vals), jnp.asarray(cols),
                                   jnp.asarray(sched.item_id), x,
                                   sizes.size, interpret=False))
    dt_seq, ref_out = _timed(seq)
    krows = {}
    for p in shard_ps:
        _, low = lowerings[p]
        vpp, cpp = TJ.pack_csr_jax(indptr, indices, data, low.schedule,
                                   pad_tiles_to=B)
        fn = jax.jit(lambda v=vpp, c=cpp, lw=low, p=p: ich_spmv_sharded(
            v, c, lw.rowid, lw.blkid, x, sizes.size, p, B,
            interpret=False))
        dt, out_p = _timed(fn)
        np.testing.assert_array_equal(
            np.asarray(out_p), np.asarray(ref_out),
            err_msg=f"compiled sharded p={p} != sequential grid")
        krows[str(p)] = {"total_s": dt,
                         "per_tile_us": 1e6 * dt / sched.n_tiles,
                         "vs_seq": dt_seq / dt}
    out["kernel_step"] = {
        "kernel": "ich_spmv_sharded",
        "mode": "compiled",
        "n_tiles": sched.n_tiles,
        "seq": {"total_s": dt_seq,
                "per_tile_us": 1e6 * dt_seq / sched.n_tiles},
        "sharded": krows}
    return out


def _print_compiled(cm: dict) -> None:
    for p, r in cm["pipeline"]["p"].items():
        print(f"compiled_pipeline,n={cm['n_items']},p={p},"
              f"numpy_s={r['numpy_s']:.5f},jax_cold_s={r['jax_cold_s']:.3f},"
              f"jax_warm_s={r['jax_warm_s']:.5f},"
              f"warm_speedup={r['warm_speedup']:.2f}")
    pk = cm["pack"]
    print(f"compiled_pack,numpy_s={pk['numpy_s']:.5f},"
          f"jax_warm_s={pk['jax_warm_s']:.5f}")
    ks = cm["kernel_step"]
    if ks is None:
        print(f"compiled_kernel,not measured (backend {cm['backend']}, "
              "needs a TPU)")
        return
    line = (f"compiled_kernel,{ks['kernel']},mode={ks['mode']},"
            f"seq_per_tile_us={ks['seq']['per_tile_us']:.1f}")
    for p, rec in ks["sharded"].items():
        line += f",p{p}_per_tile_us={rec['per_tile_us']:.1f}"
    print(line)


def main(sizes=DEFAULT_SIZES, repeats: int = 7, out_path: Path | None = None,
         kernel_step: bool = True, compiled: bool = True,
         compiled_only: bool = False) -> dict:
    if repeats < 1:
        raise ValueError(f"repeats must be >= 1, got {repeats}")
    sizes = sorted(int(s) for s in sizes)
    out_path = Path(out_path) if out_path else ROOT / "BENCH_schedule.json"
    if compiled_only:
        # the CI compiled-smoke step: run ONLY the compiled section and
        # merge it into the existing report so the uploaded
        # BENCH_schedule.json carries both trajectories
        report = (json.loads(out_path.read_text()) if out_path.exists()
                  else {"benchmark": "schedule_build"})
        cm = bench_compiled(sizes[0], repeats)
        report["compiled"] = cm
        _print_compiled(cm)
        out_path.write_text(json.dumps(report, indent=2) + "\n")
        print(f"# wrote {out_path}")
        return report
    report = {
        "benchmark": "schedule_build",
        "workload": "zipf(a=1.8) capped at 2000, 10% zero items, seed 1",
        "rows_per_tile": ROWS_PER_TILE,
        "repeats": repeats,
        "env": {"python": platform.python_version(),
                "numpy": np.__version__,
                "machine": platform.machine()},
        "builds": [],
    }
    print("n_items,width,n_tiles,build_vec_s,build_ref_s,build_speedup,"
          "pack_ref_s,pack_flat_s,pack_flat_speedup,pack_sharded_s,"
          "pack_sharded_speedup")
    for n in sizes:
        row = bench_build(n, repeats)
        report["builds"].append(row)
        pk = row["pack"]
        print(f"{row['n_items']},{row['width']},{row['n_tiles']},"
              f"{row['build_vec_s']:.5f},{row['build_ref_s']:.5f},"
              f"{row['build_speedup']:.1f},{pk['ref_s']:.5f},"
              f"{pk['flat']['vec_s']:.5f},{pk['flat']['speedup']:.1f},"
              f"{pk['sharded']['vec_s']:.5f},"
              f"{pk['sharded']['speedup']:.1f}")
    report["schedule_cache"] = []
    for n in sizes:
        row = bench_cache(n, repeats)
        report["schedule_cache"].append(row)
        print(f"cache,n={row['n_items']},cold_s={row['cold_s']:.5f},"
              f"warm_hit_s={row['warm_hit_s']:.6f},"
              f"hit_speedup={row['hit_speedup']:.1f}")
    rf = bench_refine_loop(sizes[0])
    report["refine_loop"] = rf
    print(f"refine_loop,n={rf['n_items']},p={rf['p']},"
          + ",".join(f"round{i}_makespan={m:.1f}"
                     for i, m in enumerate(rf["makespans"]))
          + f",improvement={100 * rf['improvement']:.1f}%"
          + f",imbalance_final={rf['imbalance_final']:.4f}")
    md = bench_moe_dispatch(sizes[0], repeats)
    report["moe_dispatch"] = md
    print(f"moe_dispatch,T={md['n_tokens']},E={md['n_experts']},"
          f"p={md['p']},plan_s={md['plan_s']:.5f},"
          f"scheduled_build_s={md['scheduled_build_s']:.5f},"
          f"schedule_overhead={md['schedule_overhead']:.2f}x,"
          + ",".join(f"round{i}_imbalance={v:.4f}"
                     for i, v in enumerate(md["imbalance_true"])))
    dg = bench_degradation(sizes[0])
    report["degradation"] = dg
    print(f"degradation,n={dg['n_items']},p={dg['p']},"
          f"clean_makespan={dg['clean_makespan']:.1f},"
          + ",".join(f"k{r['killed']}_inflation={r['inflation']:.3f}"
                     for r in dg["rows"]))
    rc = bench_recovery(sizes[0])
    report["recovery"] = rc
    print(f"recovery,n={rc['n_items']},p={rc['p']},"
          f"clean_static_makespan={rc['clean_static_makespan']:.1f},"
          + ",".join(f"k{r['killed']}_inflation={r['inflation']:.3f}"
                     f"(steal={r['steal_inflation']:.3f})"
                     for r in rc["rows"]))
    if kernel_step:
        ks = bench_kernel_step(sizes[0])
        report["kernel_step_interpret"] = ks
        for k in ("ich_spmv", "ich_bfs", "ich_kmeans"):
            line = (f"kernel_step,{k},n={ks['n_items']},"
                    f"seq_per_tile_us={ks[k]['seq']['per_tile_us']:.1f}")
            for p, rec in ks[k]["sharded"].items():
                line += (f",p{p}_per_tile_us={rec['per_tile_us']:.1f}"
                         f",p{p}_speedup={rec['per_tile_speedup']:.1f}")
            print(line)
    if compiled:
        cm = bench_compiled(sizes[0], repeats)
        report["compiled"] = cm
        _print_compiled(cm)
    out_path.write_text(json.dumps(report, indent=2) + "\n")
    print(f"# wrote {out_path}")
    return report


if __name__ == "__main__":
    compile_cache.configure()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sizes", default=",".join(map(str, DEFAULT_SIZES)),
                    help="comma-separated item counts")
    ap.add_argument("--repeats", type=int, default=7,
                    help="best-of repeats for the vectorized path")
    ap.add_argument("--out", default=None,
                    help="output JSON path (default: repo-root "
                         "BENCH_schedule.json)")
    ap.add_argument("--no-kernel-step", action="store_true",
                    help="skip the interpret-mode kernel step measurement")
    ap.add_argument("--no-compiled", action="store_true",
                    help="skip the compiled-mode section")
    ap.add_argument("--compiled-smoke", action="store_true",
                    help="run ONLY the compiled-mode section and merge it "
                         "into an existing BENCH_schedule.json")
    args = ap.parse_args()
    main(sizes=[int(s) for s in args.sizes.split(",")],
         repeats=args.repeats, out_path=args.out,
         kernel_step=not args.no_kernel_step,
         compiled=not args.no_compiled,
         compiled_only=args.compiled_smoke)
