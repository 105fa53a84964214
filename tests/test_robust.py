"""Fault injection & recovery across the simulator and the threaded
executor (DESIGN.md §2.9, repro.robust).

The acceptance contract this suite pins: with k of p workers killed
mid-run under a seeded `FaultPlan`, both layers still complete — SpMV
output bit-identical to the sequential reference, every iteration executed
exactly once, and the same plan replayed twice yields identical
chunk/steal/fault traces.
"""
import dataclasses
import json
import os
import threading
import time

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from conftest import random_csr

from repro.core import executor as E
from repro.core import policies as P
from repro.core import simulator as S
from repro.robust import (Death, FaultError, FaultPlan, InjectedFault,
                          Stall, simulate_faulty)
from repro.sched import LoopScheduler


def zipf_costs(n=400, seed=0):
    rng = np.random.default_rng(seed)
    return rng.zipf(1.8, n).clip(1, 60).astype(np.float64)


# --------------------------------------------------------------- FaultPlan

class TestFaultPlan:
    def test_bare_tuples_coerced(self):
        plan = FaultPlan(deaths=((1, 2),), stalls=((0, 1, 0.5),))
        assert plan.deaths == (Death(1, 2),)
        assert plan.stalls == (Stall(0, 1, 0.5),)

    def test_validation(self):
        with pytest.raises(ValueError):
            FaultPlan(flaky_frac=1.5)
        with pytest.raises(ValueError):
            FaultPlan(flaky_failures=0)
        with pytest.raises(ValueError):
            FaultPlan(cost_noise=-1.0)
        with pytest.raises(ValueError):
            Death(worker=-1)
        with pytest.raises(ValueError):
            Stall(worker=0, duration=-1.0)

    def test_worker_out_of_range_rejected_everywhere(self):
        plan = FaultPlan(deaths=((5, 0),))
        with pytest.raises(ValueError, match="worker 5"):
            plan.validate_workers(2)
        with pytest.raises(ValueError, match="worker 5"):
            S.simulate(zipf_costs(50), 2, P.ich(), faults=plan)
        with pytest.raises(ValueError, match="worker 5"):
            E.parallel_for(50, lambda i: None, 2, P.ich(), faults=plan)

    def test_derived_streams_are_seed_deterministic(self):
        a = FaultPlan(seed=9, flaky_frac=0.2, cost_noise=0.3)
        b = FaultPlan(seed=9, flaky_frac=0.2, cost_noise=0.3)
        costs = zipf_costs(200)
        np.testing.assert_array_equal(a.flaky_items(200), b.flaky_items(200))
        np.testing.assert_array_equal(a.corrupt_costs(costs),
                                      b.corrupt_costs(costs))
        c = FaultPlan(seed=10, flaky_frac=0.2, cost_noise=0.3)
        assert not np.array_equal(a.corrupt_costs(costs),
                                  c.corrupt_costs(costs))

    def test_corrupt_costs_identity_without_noise(self):
        costs = zipf_costs(64)
        out = FaultPlan(seed=1).corrupt_costs(costs)
        np.testing.assert_array_equal(out, costs)
        assert out is not costs  # always a copy

    def test_wrap_body_passthrough_when_no_body_faults(self):
        body = lambda i: None  # noqa: E731
        assert FaultPlan(deaths=((0, 0),)).wrap_body(body, 10) is body
        assert FaultPlan(poison=(3,)).wrap_body(body, 10) is not body


# -------------------------------------------------------- simulator faults

class TestSimulatorFaults:
    def test_single_death_completes_with_full_coverage(self):
        costs = zipf_costs()
        plan = FaultPlan(seed=3, deaths=((2, 2),))
        res = S.simulate(costs, 4, P.ich(), faults=plan,
                         record_assignment=True)
        assert res.deaths == 1
        assert res.reclaims >= 1
        assert (res.assignment >= 0).all()  # every item dispatched
        assert res.assignment.size == costs.size
        kinds = [ev[0] for ev in res.fault_log]
        assert "death" in kinds and "reclaim" in kinds

    def test_fault_replay_is_deterministic(self):
        costs = zipf_costs(seed=5)
        plan = FaultPlan(seed=7, deaths=((1, 3),), stalls=((0, 2, 25.0),))
        runs = [S.simulate(costs, 4, P.ich(), faults=plan,
                           record_chunks=True) for _ in range(2)]
        assert runs[0].makespan == runs[1].makespan
        assert runs[0].chunk_log == runs[1].chunk_log
        assert runs[0].fault_log == runs[1].fault_log

    def test_stall_inflates_makespan(self):
        costs = np.full(200, 5.0)
        plan = FaultPlan(stalls=((0, 1, 500.0),))
        rep = simulate_faulty(costs, 4, P.ich(), plan)
        assert rep.faulty.stall_events == 1
        assert rep.inflation > 1.0

    def test_central_policy_death_survivors_drain(self):
        costs = zipf_costs()
        plan = FaultPlan(deaths=((0, 1),))
        res = S.simulate(costs, 4, P.dynamic(8), faults=plan,
                         record_assignment=True)
        assert res.deaths == 1
        assert (res.assignment >= 0).all()
        assert not (res.assignment == 0).any() or \
            (res.assignment == 0).sum() <= 8  # at most its one chunk

    def test_all_workers_dead_raises(self):
        plan = FaultPlan(deaths=tuple((w, 1) for w in range(4)))
        with pytest.raises(FaultError):
            S.simulate(zipf_costs(), 4, P.ich(), faults=plan)
        with pytest.raises(FaultError):
            S.simulate(zipf_costs(), 4, P.dynamic(4), faults=plan)

    def test_static_assignment_policies_reject_faults(self):
        costs = zipf_costs(64)
        tiles = [(i * 8, (i + 1) * 8) for i in range(8)]
        workers = np.arange(8) % 4
        plan = FaultPlan(deaths=((0, 0),))
        with pytest.raises(ValueError, match="statically"):
            S.simulate(costs, 4, P.assigned(tiles, workers), faults=plan)
        with pytest.raises(ValueError, match="statically"):
            S.simulate(costs, 4, P.binlpt(32), faults=plan)

    def test_bounded_factor_vs_faultfree_smaller_machine(self):
        """Headline invariant: killing k of p workers early costs at most
        a small constant factor over running fault-free on p-k workers
        (measured spread across seeds is ~[0.88, 1.25])."""
        for seed in range(3):
            costs = zipf_costs(seed=seed)
            for k in (1, 2):
                plan = FaultPlan(seed=seed,
                                 deaths=tuple((w, 1) for w in range(k)))
                faulty = S.simulate(costs, 4, P.ich(), faults=plan)
                clean = S.simulate(costs, 4 - k, P.ich())
                assert faulty.makespan <= 1.5 * clean.makespan

    def test_inflation_increases_with_each_killed_worker(self):
        """Graceful degradation: on near-uniform costs with early deaths
        (after each victim's first chunk), so that lost capacity dominates,
        each further dead worker inflates the makespan more, every plan
        replays bit-identically, and k deaths stay within 1.5x of a
        fault-free run on the p-k survivors."""
        p, seed = 4, 100
        costs = np.random.default_rng(seed).uniform(8.0, 12.0, 10_000)
        clean = S.simulate(costs, p, P.ich())
        prev = 1.0
        for k in range(1, p):
            plan = FaultPlan(seed=seed,
                             deaths=tuple((w, 1) for w in range(k)))
            faulty = S.simulate(costs, p, P.ich(), faults=plan)
            again = S.simulate(costs, p, P.ich(), faults=plan)
            assert faulty.makespan == again.makespan
            assert faulty.fault_log == again.fault_log
            inflation = faulty.makespan / clean.makespan
            assert inflation > prev, (k, inflation, prev)
            survivors = S.simulate(costs, p - k, P.ich())
            assert faulty.makespan <= 1.5 * survivors.makespan
            prev = inflation

    def test_simulate_faulty_report(self):
        costs = zipf_costs()
        plan = FaultPlan(seed=3, deaths=((1, 2),))
        rep = simulate_faulty(costs, 4, P.ich(), plan)
        assert rep.clean.deaths == 0 and rep.faulty.deaths == 1
        assert rep.plan is plan
        assert rep.inflation == pytest.approx(
            rep.faulty.makespan / rep.clean.makespan)


# ----------------------------------------------- executor: supervision bug

class TestExecutorSupervision:
    """Satellite 1: `_run_threads` used to swallow worker exceptions — a
    raising body returned partial results as if complete."""

    @pytest.mark.parametrize("policy", [P.dynamic(8), P.guided(4),
                                        P.stealing(4), P.ich()],
                             ids=["dynamic", "guided", "stealing", "ich"])
    def test_worker_exception_reraised_in_caller(self, policy):
        def boom(i):
            if i == 37:
                raise ZeroDivisionError("worker blew up")
        with pytest.raises(ZeroDivisionError, match="worker blew up"):
            E.parallel_for(200, boom, 4, policy, seed=1)

    def test_exception_aborts_siblings_promptly(self):
        """Survivors drain out via the abort event instead of spinning
        against the failed worker's nonempty deque (the old hang mode)."""
        ran = []
        lock = threading.Lock()

        def boom(i):
            if i == 0:
                raise RuntimeError("early")
            with lock:
                ran.append(i)
        with pytest.raises(RuntimeError):
            E.parallel_for(5000, boom, 4, P.ich(), seed=2)
        assert len(ran) < 5000

    def test_first_error_by_worker_id_wins(self):
        def boom(i):
            raise ValueError(f"item {i}")
        with pytest.raises(ValueError):
            E.parallel_for(100, boom, 4, P.dynamic(1), seed=0,
                           deterministic=True)


# ------------------------------------------------- executor: fault plans

def spmv_fixture(n=300, seed=0):
    """CSR SpMV closure over a shared output — the bit-identity workload:
    y[i] depends only on row i, so ANY exactly-once execution order must
    reproduce the sequential reference bit-for-bit."""
    indptr, indices, data = random_csr(n, seed=seed)
    x = np.random.default_rng(seed + 1).standard_normal(n).astype(np.float32)
    y_ref = np.zeros(n, np.float32)
    for i in range(n):
        y_ref[i] = data[indptr[i]:indptr[i + 1]] @ x[indices[indptr[i]:indptr[i + 1]]]
    y = np.zeros(n, np.float32)
    hits = np.zeros(n, np.int64)
    lock = threading.Lock()

    def body(i):
        v = data[indptr[i]:indptr[i + 1]] @ x[indices[indptr[i]:indptr[i + 1]]]
        with lock:
            y[i] = v
            hits[i] += 1
    return body, y, y_ref, hits


class TestExecutorFaultRecovery:
    def test_one_of_four_killed_bit_identical_spmv(self):
        """THE acceptance criterion: 1 of p=4 workers killed mid-run,
        threaded executor completes with SpMV output bit-identical to the
        sequential reference and every row computed exactly once."""
        body, y, y_ref, hits = spmv_fixture()
        plan = FaultPlan(seed=7, deaths=((2, 1),))
        stats = E.parallel_for(300, body, 4, P.ich(), seed=3, faults=plan)
        np.testing.assert_array_equal(y, y_ref)  # bit-identical
        assert (hits == 1).all()                 # exactly once
        assert stats.fault_log is not None

    def test_death_fires_and_reclaims_under_load(self):
        """With a body that takes real time, all four threads participate
        and the planned death actually triggers + its deque is drained."""
        import time
        n = 200
        hits = np.zeros(n, np.int64)
        lock = threading.Lock()

        def body(i):
            time.sleep(0.0003)
            with lock:
                hits[i] += 1
        plan = FaultPlan(seed=7, deaths=((2, 1),))
        stats = E.parallel_for(n, body, 4, P.ich(), seed=3, faults=plan)
        assert (hits == 1).all()
        assert stats.deaths == 1
        assert stats.reclaims >= 1

    def test_deterministic_chaos_replay_identical_traces(self):
        """Same plan replayed twice -> identical chunk/steal/fault traces
        (acceptance criterion, deterministic driver)."""
        plan = FaultPlan(seed=7, deaths=((2, 3),), stalls=((0, 2, 0.1),))
        runs = []
        for _ in range(2):
            st_ = E.parallel_for(400, lambda i: None, 4, P.ich(), seed=3,
                                 faults=plan, record_chunks=True,
                                 deterministic=True)
            runs.append(st_)
        strip = [[(b, e, w) for (b, e, w, _) in r.chunk_log] for r in runs]
        assert strip[0] == strip[1]
        assert runs[0].steal_log == runs[1].steal_log
        assert runs[0].fault_log == runs[1].fault_log
        assert runs[0].deaths == runs[1].deaths == 1

    def test_flaky_items_recovered_by_retry_budget(self):
        n = 300
        hits = np.zeros(n, np.int64)
        lock = threading.Lock()

        def body(i):
            with lock:
                hits[i] += 1
        plan = FaultPlan(seed=11, flaky_frac=0.1, flaky_failures=2)
        stats = E.parallel_for(n, body, 4, P.ich(), seed=3, faults=plan,
                               retries=2)
        assert (hits == 1).all()  # retries never duplicate a completed item
        assert stats.retries > 0
        assert stats.faults_recovered > 0
        assert stats.faults_observed >= stats.retries

    def test_flaky_without_retry_budget_raises(self):
        plan = FaultPlan(seed=11, flaky_frac=0.1)
        with pytest.raises(InjectedFault):
            E.parallel_for(300, lambda i: None, 4, P.ich(), faults=plan)

    def test_poison_propagates_through_retries(self):
        plan = FaultPlan(poison=(150,))
        with pytest.raises(InjectedFault, match="poisoned item 150"):
            E.parallel_for(300, lambda i: None, 4, P.ich(), faults=plan,
                           retries=5)

    def test_all_workers_dead_raises(self):
        plan = FaultPlan(deaths=tuple((w, 1) for w in range(4)))
        for det in (False, True):
            with pytest.raises(FaultError):
                E.parallel_for(400, lambda i: None, 4, P.ich(), seed=3,
                               faults=plan, deterministic=det)
        with pytest.raises(FaultError):
            E.parallel_for(400, lambda i: None, 4, P.dynamic(8),
                           faults=plan, deterministic=True)

    def test_central_policy_death_survivors_drain(self):
        body, y, y_ref, hits = spmv_fixture(seed=4)
        plan = FaultPlan(deaths=((0, 1),))
        stats = E.parallel_for(300, body, 4, P.dynamic(16), seed=3,
                               faults=plan, deterministic=True)
        np.testing.assert_array_equal(y, y_ref)
        assert (hits == 1).all()
        assert stats.deaths == 1

    def test_watchdog_reclaims_stalled_worker(self):
        """A worker that stalls past the heartbeat budget is declared dead
        by the watchdog; survivors drain its deque and the run completes
        exactly-once."""
        import time
        n = 200
        hits = np.zeros(n, np.int64)
        lock = threading.Lock()

        def body(i):
            time.sleep(0.0003)
            with lock:
                hits[i] += 1
        plan = FaultPlan(seed=5, stalls=((1, 0, 0.6),))
        stats = E.parallel_for(n, body, 4, P.ich(), seed=3, faults=plan,
                               watchdog_s=0.15)
        assert (hits == 1).all()
        assert stats.stall_events == 1
        assert stats.deaths == 1  # the watchdog kill
        assert any(ev[0] == "watchdog_kill" for ev in stats.fault_log)


# ------------------------------------------------------- Schedule facade

class TestScheduleFaultApi:
    def test_replay_faulty_deterministic_and_counted(self):
        sch = LoopScheduler(p=4, cache_size=0)
        s = sch.schedule(zipf_costs())
        plan = FaultPlan(seed=3, deaths=((1, 2),))
        a = s.replay_faulty(plan)
        b = s.replay_faulty(plan)
        assert a.faulty.deaths == 1 and a.faulty.reclaims >= 1
        assert a.faulty.makespan == b.faulty.makespan
        assert a.faulty.fault_log == b.faulty.fault_log
        assert a.clean.makespan == b.clean.makespan

    def test_parallel_for_faults_passthrough(self):
        sch = LoopScheduler(p=4, cache_size=0)
        s = sch.schedule(zipf_costs(200))
        hits = np.zeros(s.n_items, np.int64)
        lock = threading.Lock()

        def body(i):
            with lock:
                hits[i] += 1
        stats = s.parallel_for(body, faults=FaultPlan(seed=1,
                                                      deaths=((0, 1),)),
                               deterministic=True)
        assert (hits == 1).all()
        assert stats.deaths == 1

    def test_parallel_for_units_faults_passthrough(self):
        sch = LoopScheduler(p=4, cache_size=0)
        s = sch.schedule(zipf_costs(100))
        n_units = int(s.sizes.sum())
        hits = np.zeros(n_units, np.int64)
        lock = threading.Lock()

        def body(u):
            with lock:
                hits[u] += 1
        stats = s.parallel_for_units(body, faults=FaultPlan(
            seed=1, deaths=((2, 0),)), deterministic=True)
        assert (hits == 1).all()
        assert stats.deaths == 1


# ------------------------------------------------------ CI chaos smoke

# CI's chaos step widens this via CHAOS_SEEDS=0,1,2,... (ci.yml); a plain
# pytest run exercises one seed so the test stays cheap locally.
CHAOS_SEEDS = [int(s) for s in
               os.environ.get("CHAOS_SEEDS", "0").split(",")]


@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_chaos_smoke_matrix(seed):
    """One full chaos scenario per seed — a death, a stall, and flaky
    items together — through BOTH layers: the executor must finish
    exactly-once with bit-identical SpMV output, the simulator must
    dispatch every item and replay deterministically."""
    plan = FaultPlan(seed=seed, deaths=((seed % 4, 1 + seed % 3),),
                     stalls=(((seed + 1) % 4, seed % 2, 10.0),),
                     flaky_frac=0.05)
    body, y, y_ref, hits = spmv_fixture(seed=seed)
    stats = E.parallel_for(300, body, 4, P.ich(), seed=seed, faults=plan,
                           retries=2, deterministic=True)
    np.testing.assert_array_equal(y, y_ref)
    assert (hits == 1).all()
    assert stats.deaths == 1 and stats.stall_events == 1

    costs = zipf_costs(seed=seed)
    sim_plan = FaultPlan(seed=seed, deaths=((seed % 4, 1 + seed % 3),),
                         stalls=(((seed + 1) % 4, seed % 2, 10.0),))
    a = S.simulate(costs, 4, P.ich(), faults=sim_plan,
                   record_assignment=True)
    b = S.simulate(costs, 4, P.ich(), faults=sim_plan)
    assert (a.assignment >= 0).all()
    assert a.makespan == b.makespan and a.fault_log == b.fault_log


# ------------------------------------------------ hypothesis properties

class TestRecoveryProperties:
    """Satellite 3: recovery invariants over random workloads + plans."""

    @settings(max_examples=30, deadline=None)
    @given(n=st.integers(8, 300), p=st.integers(2, 6),
           victim=st.integers(0, 5), after=st.integers(0, 4),
           seed=st.integers(0, 2**16))
    def test_single_death_exactly_once(self, n, p, victim, after, seed):
        victim %= p
        plan = FaultPlan(seed=seed, deaths=((victim, after),))
        hits = np.zeros(n, np.int64)
        stats = E.parallel_for(n, lambda i: hits.__setitem__(
            i, hits[i] + 1), p, P.ich(), seed=seed, faults=plan,
            deterministic=True)
        assert (hits == 1).all()
        assert stats.chunks > 0

    @settings(max_examples=20, deadline=None)
    @given(n=st.integers(8, 200), p=st.integers(2, 6),
           victim=st.integers(0, 5), after=st.integers(0, 4),
           seed=st.integers(0, 2**16))
    def test_simulator_fault_replay_deterministic(self, n, p, victim,
                                                  after, seed):
        victim %= p
        rng = np.random.default_rng(seed)
        costs = rng.uniform(0.5, 20.0, n)
        plan = FaultPlan(seed=seed, deaths=((victim, after),))
        a = S.simulate(costs, p, P.ich(), faults=plan,
                       record_assignment=True)
        b = S.simulate(costs, p, P.ich(), faults=plan,
                       record_assignment=True)
        assert a.makespan == b.makespan
        assert a.fault_log == b.fault_log
        np.testing.assert_array_equal(a.assignment, b.assignment)
        assert (a.assignment >= 0).all()


# ------------------------------------- fault-plan serialization (PR 9)

class TestFaultPlanSerialization:
    def test_roundtrip_and_fingerprint(self):
        plan = FaultPlan(seed=3, deaths=((1, 2),), stalls=((0, 4, 0.5),),
                         flaky_frac=0.1, flaky_failures=2, poison=(7,),
                         cost_noise=0.2)
        assert FaultPlan.from_json(plan.to_json()) == plan
        assert FaultPlan.from_json(json.loads(plan.to_json())) == plan
        assert plan.fingerprint() == FaultPlan.from_json(
            plan.to_json()).fingerprint()

    def test_fingerprint_sensitive_to_every_field(self):
        base = FaultPlan(seed=3, deaths=((1, 2),), stalls=((0, 4, 0.5),),
                         flaky_frac=0.1, flaky_failures=2, poison=(7,),
                         cost_noise=0.2)
        variants = [
            dataclasses.replace(base, seed=4),
            dataclasses.replace(base, deaths=((1, 3),)),
            dataclasses.replace(base, stalls=((0, 4, 0.6),)),
            dataclasses.replace(base, flaky_frac=0.2),
            dataclasses.replace(base, flaky_failures=3),
            dataclasses.replace(base, poison=(8,)),
            dataclasses.replace(base, cost_noise=0.3),
        ]
        fps = {v.fingerprint() for v in variants}
        assert len(fps) == len(variants)
        assert base.fingerprint() not in fps

    def test_invalid_serialized_plan_rejected(self):
        blob = FaultPlan(flaky_frac=0.1).to_json()
        bad = json.loads(blob)
        bad["flaky_frac"] = 1.5
        with pytest.raises(ValueError):
            FaultPlan.from_json(bad)


class TestFaultPlanJsonProperties:
    """Satellite (PR 9): to_json/from_json is the identity over the full
    plan space, and the fingerprint is a function of plan VALUE only."""

    plans = st.builds(
        FaultPlan,
        seed=st.integers(0, 2**31 - 1),
        deaths=st.lists(st.tuples(st.integers(0, 7), st.integers(0, 50)),
                        max_size=4).map(tuple),
        stalls=st.lists(st.tuples(st.integers(0, 7), st.integers(0, 50),
                                  st.floats(0.0, 10.0)),
                        max_size=4).map(tuple),
        flaky_frac=st.floats(0.0, 1.0),
        flaky_failures=st.integers(1, 5),
        poison=st.lists(st.integers(0, 1000), max_size=4).map(tuple),
        cost_noise=st.floats(0.0, 3.0),
    )

    @settings(max_examples=60, deadline=None)
    @given(plan=plans)
    def test_json_roundtrip_identity(self, plan):
        back = FaultPlan.from_json(plan.to_json())
        assert back == plan
        assert back.to_json() == plan.to_json()
        assert back.fingerprint() == plan.fingerprint()

    @settings(max_examples=30, deadline=None)
    @given(plan=plans, seed2=st.integers(0, 2**31 - 1))
    def test_fingerprint_is_value_identity(self, plan, seed2):
        same = FaultPlan.from_json(json.loads(plan.to_json()))
        assert same.fingerprint() == plan.fingerprint()
        other = dataclasses.replace(plan, seed=seed2)
        assert (other.fingerprint() == plan.fingerprint()) == \
            (other == plan)


# --------------------------------------- injectable backoff sleep (PR 9)

class TestSleepFnHook:
    def test_retry_backoff_routed_through_sleep_fn(self):
        """A flaky run with a real backoff costs zero wall-clock when
        `sleep_fn` is injected, and the recorded delays follow the
        bounded-exponential contract."""
        n = 300
        hits = np.zeros(n, np.int64)
        lock = threading.Lock()

        def body(i):
            with lock:
                hits[i] += 1
        sleeps = []
        plan = FaultPlan(seed=11, flaky_frac=0.1, flaky_failures=2)
        t0 = time.monotonic()
        stats = E.parallel_for(n, body, 4, P.ich(), seed=3, faults=plan,
                               retries=2, retry_backoff_s=0.5,
                               sleep_fn=sleeps.append)
        assert time.monotonic() - t0 < 2.0   # nobody actually slept
        assert (hits == 1).all()
        assert stats.retries > 0
        assert len(sleeps) == stats.retries
        assert all(0.0 < s <= E.RETRY_BACKOFF_CAP_S for s in sleeps)

    def test_injected_stalls_routed_through_sleep_fn(self):
        sleeps = []
        plan = FaultPlan(stalls=((0, 2, 5.0),))
        t0 = time.monotonic()
        E.parallel_for(200, lambda i: None, 2, P.ich(), seed=0,
                       faults=plan, sleep_fn=sleeps.append)
        assert time.monotonic() - t0 < 2.0
        assert 5.0 in sleeps
