"""A tiny CPU rehearsal of the MoE cell's check (Pallas in interpret mode):
the program reads correct, and the faults and the control read not
correct. `test_rehearsal.py` covers the SpMV and BFS kinds."""
import time

import pytest

from bench import control, run
from repro import compile_cache
from repro.sched import kernels as K

CELL = "moe-mimo-v2-flash.prefill"
TINY = {"config": {"hidden_size": 256, "moe_intermediate_size": 128,
                   "router_outputs": 32, "n_routed_experts": 8},
        "traffic": {"pool_sequences": 6, "batch_sequences": 4,
                    "sequence_tokens": 128, "sample_tokens": 64,
                    "warm_calls": 1}}


@pytest.fixture(autouse=True, scope="module")
def cache_outside_the_checkout(tmp_path_factory):
    """CPU entries in the checkout's cache would travel to the chip."""
    mp = pytest.MonkeyPatch()
    mp.setattr(compile_cache, "REPO_CACHE_DIR",
               tmp_path_factory.mktemp("jax_cache"))
    yield
    mp.undo()


def rehearse(seed):
    return run.run_cell(CELL, seed, 0.3, False, time.perf_counter(),
                        overrides=TINY)


def test_cell_runs_correct_on_cpu():
    r = rehearse(2**31 + 3)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["checks"]) == {"y_err", "route_mismatch",
                                "route_near_ties", "residual_mismatch"}


def _fault(kind):
    real = K.MoeDispatchOp.__call__

    def call(self, *args, **kwargs):
        y = real(self, *args, **kwargs)
        if kind == "state_unchanged":
            return y * 0.0
        if kind == "half_left_out":
            return y.at[y.shape[0] // 2:].set(0.0)
        return y.at[:, 0].add(1.0)           # answer altered
    return call


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out",
                                   "answer_altered"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    monkeypatch.setattr(K.MoeDispatchOp, "__call__", _fault(fault))
    r = rehearse(11)
    assert not r["correct"] and r["failed"] > 0
    assert r["checks"]["y_err"]["value"] > r["checks"]["y_err"]["limit"]


def test_broken_residual_is_not_correct(monkeypatch):
    from bench import run as R
    real = R.kind_module

    def kind_module(name):
        mod = real(name)
        setup = mod.Workload.setup

        def broken(self):
            setup(self)
            self.residual = lambda h, y: h          # the update is dropped
        mod.Workload.setup = broken
        return mod
    monkeypatch.setattr(R, "kind_module", kind_module)
    r = rehearse(12)
    assert not r["correct"] and r["failed"] > 0
    assert r["checks"]["residual_mismatch"]["value"] > 0


def test_control_is_not_correct():
    for r in control.readings(CELL, [1, 2**33 + 1], 0.2, overrides=TINY):
        assert all(v <= lim for v, lim in r["program"].values())
        ctl, lim = r["control"]["y_err"]
        assert ctl > lim
