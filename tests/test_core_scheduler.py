"""Unit + property tests for the iCh scheduler core (paper §3)."""
import threading

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    HIGH, LOW, NORMAL, SimParams, Welford, adapt_d, classify, dynamic,
    guided, ich, ich_band, ich_chunk, ich_initial_d, parallel_for,
    paper_policy_grid, simulate, static, steal_merge, stealing, taskloop,
    binlpt,
)
from repro.core import workloads as WL

PARAMS = SimParams()


# ---------------------------------------------------------------- welford
def test_welford_matches_numpy():
    rng = np.random.default_rng(0)
    xs = rng.exponential(10.0, size=500)
    w = Welford()
    w.update_many(xs)
    assert np.isclose(w.mean, xs.mean())
    assert np.isclose(w.variance, xs.var())


def test_ich_band_and_classification():
    ks = np.array([10.0, 10.0, 10.0, 50.0])
    mu, delta = ich_band(ks, 0.25)
    assert np.isclose(mu, 20.0) and np.isclose(delta, 5.0)
    assert classify(10.0, mu, delta) == LOW
    assert classify(20.0, mu, delta) == NORMAL
    assert classify(50.0, mu, delta) == HIGH


def test_adapt_d_direction_is_inverted_on_purpose():
    # paper §3.2: LOW (slow) -> bigger chunk (smaller d); HIGH -> smaller chunk
    assert adapt_d(8.0, LOW) == 4.0
    assert adapt_d(8.0, HIGH) == 16.0
    assert adapt_d(8.0, NORMAL) == 8.0
    assert adapt_d(1.0, LOW) == 1.0  # clamped
    assert adapt_d(4096.0, HIGH) == 4096.0  # clamped


def test_steal_merge_averages():
    k, d = steal_merge(10.0, 4.0, 30.0, 8.0)
    assert k == 20.0 and d == 6.0


def test_ich_chunk_law():
    p = 4
    assert ich_initial_d(p) == 4.0
    assert ich_chunk(16, 4.0) == 4  # n/p^2 with |q|=n/p
    assert ich_chunk(3, 8.0) == 1  # never below 1
    assert ich_chunk(0, 8.0) == 0


# ---------------------------------------------------------------- simulator
@pytest.mark.parametrize("pol", [
    dynamic(1), dynamic(3), guided(1), taskloop(8), binlpt(64),
    stealing(2), stealing(64), ich(0.25), ich(0.5), static(),
])
def test_simulator_executes_every_iteration_exactly_once(pol):
    costs = WL.synth_exp(2000, increasing=False, seed=3)
    r = simulate(costs, 8, pol, PARAMS, record_assignment=True)
    assert (r.assignment >= 0).all()
    assert r.makespan > 0


@pytest.mark.parametrize("pol", [dynamic(2), guided(1), stealing(2), ich(0.25)])
def test_simulator_makespan_lower_bound(pol):
    """makespan >= total_work / (p * fastest speed) and >= max single cost."""
    costs = WL.synth_exp(3000, increasing=True, seed=1)
    p = 8
    r = simulate(costs, p, pol, PARAMS)
    fastest = 1.0 + 5 * PARAMS.speed_jitter
    assert r.makespan >= costs.sum() / (p * fastest)
    assert r.makespan >= costs.max() / fastest


def test_single_worker_reduces_to_serial():
    costs = np.ones(100) * 5.0
    r = simulate(costs, 1, guided(1), PARAMS)
    # serial work/speed + one dispatch; speed jitter is a few percent
    assert r.makespan == pytest.approx(500.0, rel=0.25)
    assert r.steals == 0


def test_central_queue_contention_limits_throughput():
    """dynamic(1) on tiny iterations must saturate at the lock rate --
    the mechanism behind the paper's K-Means plateau (§6.1)."""
    costs = np.full(20000, 2.0)  # iteration cost ~ dispatch overhead
    r1 = simulate(costs, 1, dynamic(1), PARAMS)
    r28 = simulate(costs, 28, dynamic(1), PARAMS)
    speedup = r1.makespan / r28.makespan
    assert speedup < 5.0  # heavily serialized
    rs = simulate(costs, 28, stealing(64), PARAMS)
    assert r1.makespan / rs.makespan > 15.0  # distributed queues scale


def test_ich_adapts_d_and_steals_on_imbalance():
    costs = WL.synth_exp(4000, increasing=False, seed=0)
    r = simulate(costs, 8, ich(0.25), PARAMS)
    assert r.steals > 0
    assert r.ds is not None and (r.ds != ich_initial_d(8)).any()
    # NOTE: sum(k_i) != n under iCh -- the paper's steal rule AVERAGES the
    # thief's and victim's k (Listing 1), so k is an estimate after steals.
    assert (r.ks > 0).all()
    rs = simulate(costs, 8, stealing(2), PARAMS)
    assert rs.ks.sum() == len(costs)  # plain stealing: k is an exact count


def test_guided_fails_on_exp_decreasing_but_ich_does_not():
    """Paper Fig. 4 (Exp-Decreasing): guided collapses, iCh stays close to
    the best method."""
    costs = WL.synth_exp(20000, increasing=False, seed=0)
    p = 28
    t = {m: min(simulate(costs, p, pol, PARAMS).makespan
                for pol in paper_policy_grid(p) if pol.name == m)
         for m in ("guided", "dynamic", "stealing", "ich")}
    assert t["guided"] > 2.0 * t["dynamic"]
    best = min(t.values())
    assert t["ich"] <= 1.15 * best


# ------------------------------------------------------------- hypothesis
@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(min_value=1, max_value=400),
    p=st.integers(min_value=1, max_value=16),
    pol_idx=st.integers(min_value=0, max_value=4),
    seed=st.integers(min_value=0, max_value=10_000),
)
def test_property_all_policies_schedule_everything(n, p, pol_idx, seed):
    rng = np.random.default_rng(seed)
    costs = rng.exponential(10.0, size=n) + 0.1
    pol = [dynamic(2), guided(1), taskloop(p), stealing(3), ich(0.33)][pol_idx]
    r = simulate(costs, p, pol, PARAMS, record_assignment=True)
    assert (r.assignment >= 0).all()
    assert (r.assignment < p).all()
    fastest = 1.0 + 5 * PARAMS.speed_jitter
    assert r.makespan >= costs.sum() / (p * fastest) - 1e-9


@settings(max_examples=15, deadline=None)
@given(eps=st.floats(min_value=0.05, max_value=0.9),
       seed=st.integers(min_value=0, max_value=100))
def test_property_ich_d_stays_bounded(eps, seed):
    rng = np.random.default_rng(seed)
    costs = rng.exponential(50.0, size=500) + 1.0
    r = simulate(costs, 8, ich(eps), PARAMS)
    assert (r.ds >= 1.0).all() and (r.ds <= 4096.0).all()


# ---------------------------------------------------------------- executor
@pytest.mark.parametrize("pol", [dynamic(3), guided(1), taskloop(4),
                                 stealing(2), ich(0.25)])
def test_threaded_executor_exactly_once(pol):
    n = 3000
    hits = np.zeros(n, dtype=np.int64)
    lock = threading.Lock()

    def body(i):
        with lock:
            hits[i] += 1

    parallel_for(n, body, 6, pol)
    assert (hits == 1).all()


def test_threaded_executor_steals_under_imbalance():
    # worker 0's range is artificially slow -> others must steal
    n = 800
    hits = np.zeros(n, dtype=np.int64)
    lock = threading.Lock()

    def body(i):
        if i < n // 8:
            x = 0.0
            for k in range(2000):
                x += k * 0.5
        with lock:
            hits[i] += 1

    st_ = parallel_for(n, body, 8, ich(0.25))
    assert (hits == 1).all()
    assert st_.chunks > 8
