"""A tiny CPU rehearsal of the zero-compute MoE cell's check (Pallas in
interpret mode): the program reads correct, and each fault of the
LongCat-Flash layer's rules, and the control, reads not correct."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import control, run
from repro import compile_cache, sched
from repro.sched import kernels as K

CELL = "moe-longcat-flash.prefill2k"
N, Z = 24, 12       # FFN and zero-compute experts at this size
TINY = {"config": {"hidden_size": 256, "expert_ffn_hidden_size": 128,
                   "router_outputs": N + Z, "zero_expert_num": Z,
                   "n_routed_experts": 8},
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
    r = rehearse(2**31 + 5)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["checks"]) == {"y_err", "route_mismatch",
                                "route_near_ties", "residual_mismatch"}


def _identity_dropped(monkeypatch):
    real = K.MoeDispatchOp.__call__

    def call(self, *args, w0=None, **kwargs):
        return real(self, *args, **kwargs)
    monkeypatch.setattr(K.MoeDispatchOp, "__call__", call)


def _router_fault(kind):
    """`sched.route` with one of the layer's rules broken."""
    real = sched.route

    def route(h, w_router, bias, **kw):
        if kind == "renormalised":
            return real(h, w_router, bias, **dict(kw, renorm=True))
        u, e, w, w0 = real(h, w_router, bias, **kw)
        scale = kw["scale"]
        if kind == "zero_to_ffn":          # zero picks run FFN e mod N
            return u, jnp.where(e >= N, e % N, e), w, jnp.zeros_like(w0)
        if kind == "unscaled":
            return u, e, w / scale, w0 / scale
        # softmax over the FFN logits alone: every score over their mass
        logits = jnp.dot(u, w_router.T, preferred_element_type=jnp.float32)
        mass = jax.nn.softmax(logits, axis=-1)[:, :N].sum(-1)
        return u, e, w / mass[:, None], w0 / mass
    return route


@pytest.mark.parametrize("fault", ["identity_dropped", "zero_to_ffn",
                                   "unscaled", "ffn_softmax",
                                   "renormalised"])
def test_broken_layer_rule_is_not_correct(fault, monkeypatch):
    if fault == "identity_dropped":
        _identity_dropped(monkeypatch)
    else:
        monkeypatch.setattr(sched, "route", _router_fault(fault))
    r = rehearse(21)
    assert not r["correct"] and r["failed"] > 0
    assert r["checks"]["y_err"]["value"] > r["checks"]["y_err"]["limit"]


def test_control_is_not_correct():
    for r in control.readings(CELL, [1, 2**33 + 1], 0.2, overrides=TINY):
        assert all(v <= lim for v, lim in r["program"].values())
        ctl, lim = r["control"]["y_err"]
        assert ctl > lim
