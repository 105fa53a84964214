"""MoE prefill cells whose router includes zero-compute experts
(LongCat-Flash): the `moe` kind's calls, set-up and traffic, with the
softmax router, the identity part and their check.

Per layer a call runs `sched.route` with the configuration's rule
(softmax scores, weights scaled by `routed_scaling_factor` and not
normalised, zero-compute experts last among the `router_outputs`), which
also returns each token's identity weight w0 on the device;
`sched.read_routing`; `plan_dispatch(..., experts=..., zero=...)`, which
keeps the held FFN experts' entries and counts the zero-compute choices;
`LoopScheduler.build("moe-dispatch", plan)`; the op with w0, which starts
y at w0 u; and the residual add. A token runs 0 to `moe_topk` FFN
experts: as many of its picks as land on FFN experts.

The configuration names its widths as LongCat-Flash's config.json does
(`expert_ffn_hidden_size`, `moe_topk`, `rms_norm_eps`, `num_layers`);
`Workload` maps them onto the `moe` kind's. The selection bias is drawn
as the `moe` kind draws it and scaled to ±`selection_bias_share` of the
mean score 1/E. Traffic parameters are the `moe` kind's
(`bench/kinds/moe.py`).
"""
from __future__ import annotations

import sys

import jax.numpy as jnp
import ml_dtypes
import numpy as np

from bench.kinds import moe as base
from bench.reference import moe_zero as ref

# Largest relative error, per sampled token and layer, of the layer's
# update y (the identity part included) against the reference's, the
# reference fed the program's own input to the layer. On a TPU v5e the
# program reads 0.55e-3 to 1.16e-3 over 10 seeds and the reference with
# bf16 sums in the expert products 1.54e-2 (PERF.md §6).
ERR_LIMIT = 5e-3
# A token whose 12th and 13th selection scores lie closer than this may
# be routed either way by rounding: the check gives it the program's
# choice; elsewhere the top-12 sets must be equal. The reference scores
# the program's own normalised input; on a TPU v5e the program's chosen
# softmax scores (its weights over the scale) differ from numpy's by at
# most 2.24e-7 over 10 seeds, which the check logs: the margin is 4.5
# times that.
MARGIN = 1e-6
# Share of sampled (token, layer) pairs under MARGIN the check allows
# (0.35% to 0.57% on a TPU v5e).
NEAR_TIE_LIMIT = 0.01
# Local loads, as multiples of the deployment's mean (T K held / E
# entries), that set-up runs the op at: the op pads a plan to whole
# supersteps of 8 tiles of 8 slot rows of 512 tokens, and an even load of
# f times the mean fills 2 f rows an expert, so these four reach 1, 2, 3
# and 4 supersteps. On a TPU v5e the window's plans padded to 1 or 2
# (an expert's load reached 12.5 times the mean in the last layer) and
# nothing compiled in the window (PERF.md §6).
WARM_LOADS = (1.0, 2.5, 4.5, 6.5)
# the `moe` kind's names for LongCat-Flash's keys
NAMES = {"moe_intermediate_size": "expert_ffn_hidden_size",
         "num_experts_per_tok": "moe_topk",
         "layernorm_epsilon": "rms_norm_eps",
         "num_hidden_layers": "num_layers"}
# the `moe` kind's selection bias is U[-BASE_BIAS, BASE_BIAS]
BASE_BIAS = 0.05


class Workload(base.Workload):
    def __init__(self, config: dict, traffic: dict, seed: int, sched):
        config = dict(config, **{k: config[v] for k, v in NAMES.items()})
        super().__init__(config, traffic, seed, sched)
        E = int(config["router_outputs"])
        self.zero = (E - int(config["zero_expert_num"]),
                     int(config["zero_expert_num"]))
        self.scale = float(config["routed_scaling_factor"])
        self.rule = dict(score=config["scoring_func"],
                         renorm=bool(config["norm_topk_prob"]),
                         scale=self.scale, zero=self.zero)
        self.bias_range = float(config["selection_bias_share"]) / E

    # ------------------------------------------------------------ set-up
    def _warm_loads(self) -> None:
        """Scale the selection bias (set-up's last step before the warm
        loads), then run the op, with w0, once at each of `WARM_LOADS`
        times the deployment's mean local load. The choices are
        synthetic: local entries spread evenly over the held experts, the
        rest over the other outputs, zero-compute ones included."""
        shrink = np.float32(self.bias_range / BASE_BIAS)
        for lay in self.layers:
            lay["bias"] = lay["bias"] * shrink
        s, lay = self.sched, self.layers[0]
        h = self.batch(self.pool, jnp.arange(self.n_batch))
        u, _, _, w0 = s.route(h, lay["w_router"], lay["bias"], top_k=self.k,
                              eps=self.eps, **self.rule)
        slot = np.arange(self.T * self.k)
        mean = self.T * self.k * self.held / self.E
        for frac in WARM_LOADS:
            local = slot < frac * mean
            e = np.where(local, self.first + slot % self.held,
                         (self.first + self.held + slot % (self.E - self.held))
                         % self.E)
            plan = s.plan_dispatch(e.reshape(self.T, self.k),
                                   experts=(self.first, self.held),
                                   zero=self.zero)
            op = self.scheduler.build("moe-dispatch", plan)
            op(u, lay["wi"], lay["wg"], lay["wo"],
               w0=w0).block_until_ready()

    def open_window(self) -> None:
        super().open_window()
        self.c.update(zero_entries=0, routed_entries=0)

    # ------------------------------------------------------------ window
    def call(self) -> None:
        s = self.sched
        idx = np.sort(self.rng.choice(self.pool.shape[0], self.n_batch,
                                      replace=False))
        h = self.batch(self.pool, jnp.asarray(idx))
        layers = []
        for l, lay in enumerate(self.layers):
            u, e_topk, w, w0 = s.route(h, lay["w_router"], lay["bias"],
                                       top_k=self.k, eps=self.eps,
                                       **self.rule)
            e_np, w_np = s.read_routing(e_topk, w)
            plan = s.plan_dispatch(e_np, w_np,
                                   experts=(self.first, self.held),
                                   zero=self.zero)
            op = self.scheduler.build("moe-dispatch", plan)
            y = op(u, lay["wi"], lay["wg"], lay["wo"], w0=w0)
            h = self.residual(h, y)
            layers.append(((e_np[self.sample_pos], w_np[self.sample_pos]),
                           self.pick(u, y, h, self.sample_dev)))
            del u, y, w0
            self._count(l, plan, op)
            self.c["zero_entries"] += plan.n_zero
            self.c["routed_entries"] += e_np.size
            # as the `moe` kind: free this layer's buffers before the next
            # router allocates
            h.block_until_ready()
        rec = (idx, layers)
        if len(self.kept_calls) < self.n_keep:
            self.kept_calls.append(rec)
        else:
            j = int(self.keep_rng.integers(0, self.i + 1))
            if j < self.n_keep:
                self.kept_calls[j] = rec
        self.last = rec
        self.i += 1

    # ------------------------------------------------------------- check
    def check(self, control: bool = False) -> dict:
        """{name: (value, limit)}, as the `moe` kind's check, against
        `bench/reference/moe_zero.py`. It also logs the largest gap
        between the program's chosen scores (its weights over the scale)
        and the reference's scores of the same experts, which MARGIN must
        exceed twice over."""
        kw = dict(first=self.first, count=self.held, k=self.k, eps=self.eps,
                  scale=self.scale, zero=self.zero,
                  dtype=ml_dtypes.bfloat16)
        h_in = np.concatenate([r[0] for r in self.checked])
        if control:
            got = [(r["h_in"], r["chosen"], None, r["u"], r["y"], r["h"])
                   for r in ref.forward(h_in, self.host_layers,
                                        acc="bfloat16", **kw)]
        else:
            got = []
            for l in range(len(self.host_layers)):
                e, w = (np.concatenate([r[1][l][0][j] for r in self.checked])
                        for j in range(2))
                u, y, h = (np.concatenate([r[1][l][j] for r in self.checked])
                           for j in range(1, 4))
                got.append((h_in, e, w, u, y, h))
                h_in = h
        errs, route, near, residual, gap = [], [], [], [], 0.0
        for lay, (h_in, e, w, u, y, h) in zip(self.host_layers, got):
            want = ref.layer(h_in, lay, u_route=u, choices=e, margin=MARGIN,
                             **kw)
            clear = want["margin"] >= MARGIN
            differ = (np.sort(want["chosen"], 1) != np.sort(e, 1)).any(1)
            route.append(clear & differ)
            near.append(~clear)
            errs.append(ref.rel_err(y, want["y"]))
            residual.append(
                (h != ref.rnd(h_in + y, ml_dtypes.bfloat16)).any(1))
            if w is not None:
                s = np.take_along_axis(want["s"], e, axis=1)
                gap = max(gap, float(np.abs(w / self.scale - s).max()))
        errs, route = np.stack(errs), np.stack(route)
        residual = np.stack(residual)
        self.n_failed = int(((errs > ERR_LIMIT) | route | residual)
                            .any(0).sum())
        if not control:
            print(f"moe_zero: largest gap of the chosen scores from the "
                  f"reference's {gap!r} (margin {MARGIN!r})",
                  file=sys.stderr, flush=True)
        return {"y_err": (float(errs.max()), ERR_LIMIT),
                "route_mismatch": (int(route.sum()), 0),
                "route_near_ties": (float(np.mean(near)), NEAR_TIE_LIMIT),
                "residual_mismatch": (int(residual.sum()), 0)}
