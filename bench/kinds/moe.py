"""MoE prefill cells: the routed-expert layers of an expert-parallel rank,
each call one batch through every layer the configuration keeps.

Per layer the call runs the program's normal path: the router
(`sched.route`), the read-back of its choices (`sched.read_routing`), the
dispatch plan of the experts this chip holds (`plan_dispatch(...,
experts=...)`), `LoopScheduler.build("moe-dispatch", plan)`, the op on the
layer's expert weights, and the residual add. A call ends when the last
layer's h is ready. Every batch routes anew, so the schedule cache misses
as it does in a deployment.

Set-up makes the weights from the configuration's `weight_seed` and a
pool of token sequences on the device: each sequence has one topic,
topics are Zipf-popular, and a token is sqrt(share) times its topic's
centroid plus sqrt(1 - share) times N(0, I), so tokens of a topic prefer
the same experts and the routing is uneven, as topical batches are. The
pool's topics and their centroids come from the traffic's
`structure_seed`, so every run has the same mix of loads (as the SpMV
cells have one matrix structure); the run's seed draws the tokens' own
part, each call's batch and the checked samples.

Traffic parameters (bench/traffic/<name>.json):

* `pool_sequences`, `batch_sequences`, `sequence_tokens` — the pool, and
  how many of its sequences (drawn from the seed, without replacement)
  each call's batch holds;
* `topics`, `zipf`, `topic_share`, `structure_seed` — the topic mix
  above;
* `warm_calls` — calls of the window's own before it opens, after
  set-up has run the op at `WARM_LOADS` (each padded shape compiles
  there);
* `sample_calls`, `sample_tokens` — the check compares this many calls of
  the window (the last always among them) on this many tokens each, both
  drawn from the seed, layer by layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from bench.phases import Phases
from bench.reference import moe as ref
from bench.roofline_moe import expert_flops, router_flops

# Largest relative error, per sampled token and layer, of the layer's
# update y against the reference's (float32 sums over the configuration's
# bf16 storage), the reference fed the program's own input to the layer.
# On a TPU v5e the program reads 0.62e-3 to 2.21e-3 (one-ulp flips of the
# bf16 SwiGLU activation; the median token 3e-7), the reference with bf16
# sums in the expert products 1.49e-2 to 1.61e-2 (PERF.md §6).
ERR_LIMIT = 5e-3
# A token whose 8th and 9th selection scores lie closer than this may be
# routed either way by rounding: the check gives it the program's choice.
# Elsewhere the program's top-8 set must equal the reference's. The
# reference scores the program's own normalised input; a TPU v5e's scores
# of it differ from numpy's by at most 1.37e-6.
MARGIN = 1e-5
# Share of sampled (token, layer) pairs under MARGIN the check allows
# (0.09% to 0.23% on a TPU v5e).
NEAR_TIE_LIMIT = 0.01
# Local loads, as multiples of the deployment's mean, that set-up runs the
# op at so that every padded shape the window meets is compiled: the
# layers' mean loads read 0.94 to 1.11 times it on a TPU v5e, and no plan
# of a window needed more than 1.9 times (PERF.md §6).
WARM_LOADS = (0.5, 0.75, 1.0, 1.25, 1.5, 1.75, 2.0)


def _key(*words) -> int:
    """A 31-bit JAX seed from any integers (the run's seed may not fit
    32 bits)."""
    return int(np.random.default_rng(list(words)).integers(2 ** 31))


class Workload:
    def __init__(self, config: dict, traffic: dict, seed: int, sched):
        self.cfg, self.tr, self.seed, self.sched = config, traffic, seed, sched
        self.warm_calls = int(traffic["warm_calls"])
        self.phases = Phases()

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        c, t, ph = self.cfg, self.tr, self.phases
        D, F = int(c["hidden_size"]), int(c["moe_intermediate_size"])
        E, held = int(c["router_outputs"]), int(c["n_routed_experts"])
        self.first = int(c["experts_held"][0])
        self.D, self.F, self.E, self.held = D, F, E, held
        self.k = int(c["num_experts_per_tok"])
        self.eps = float(c["layernorm_epsilon"])
        n_layers = int(c["num_hidden_layers"])
        dt = jnp.dtype(c["dtype"])
        with ph("weights"):
            @jax.jit
            def layer(key):
                k = jax.random.split(key, 5)

                def normal(k, shape, fan_in):
                    return (jax.random.normal(k, shape, jnp.float32)
                            / np.sqrt(fan_in)).astype(dt)
                return {"w_router": normal(k[0], (E, D), D),
                        "bias": jax.random.uniform(k[1], (E,), jnp.float32,
                                                   -0.05, 0.05),
                        "wi": normal(k[2], (held, D, F), D),
                        "wg": normal(k[3], (held, D, F), D),
                        "wo": normal(k[4], (held, F, D), F)}

            base = jax.random.key(int(c["weight_seed"]), impl="rbg")
            self.layers = [layer(jax.random.fold_in(base, i))
                           for i in c["moe_layers"][:n_layers]]
            jax.block_until_ready(self.layers)
        n_seq, L = int(t["pool_sequences"]), int(t["sequence_tokens"])
        with ph("pool"):
            structure = int(t["structure_seed"])
            rng = np.random.default_rng([structure, 1])
            pop = 1.0 / np.arange(1, int(t["topics"]) + 1) ** float(t["zipf"])
            self.topic = rng.choice(pop.size, n_seq, p=pop / pop.sum())
            share = float(t["topic_share"])
            cent = jax.random.normal(
                jax.random.key(_key(structure, 2), impl="rbg"),
                (pop.size, D), jnp.float32)
            key = jax.random.key(_key(self.seed, 2), impl="rbg")

            @jax.jit
            def sequence(i, c):
                noise = jax.random.normal(jax.random.fold_in(key, i),
                                          (L, D), jnp.float32)
                return (np.sqrt(share) * c
                        + np.sqrt(1.0 - share) * noise).astype(dt)

            self.pool = jnp.stack([sequence(i, cent[self.topic[i]])
                                   for i in range(n_seq)])
            self.pool.block_until_ready()
        self.n_batch = int(t["batch_sequences"])
        self.T = self.n_batch * L
        self.scheduler = self.sched.LoopScheduler(
            p=int(c["p"]), cache_size=int(c["cache_size"]))
        self.batch = jax.jit(
            lambda pool, idx: pool[idx].reshape(-1, pool.shape[-1]))
        self.residual = jax.jit(
            lambda h, y: (h.astype(jnp.float32) + y).astype(h.dtype))
        # a layer's u, y and output h at the sampled tokens
        self.pick = jax.jit(lambda u, y, h, i: (u[i], y[i], h[i]))
        with ph("warm"):
            self._warm_loads()
        self.rng = np.random.default_rng([self.seed, 3])
        self.sample_pos = np.sort(np.random.default_rng([self.seed, 4])
                                  .choice(self.T, int(t["sample_tokens"]),
                                          replace=False))
        self.sample_dev = jnp.asarray(self.sample_pos)
        self.n_keep = int(t["sample_calls"]) - 1
        self.open_window()

    def _warm_loads(self) -> None:
        """Run the op once at each of `WARM_LOADS` times the deployment's
        mean local load (T K held / E entries): the op pads plans to a
        few tile counts, and each of them compiles here, not in the
        window. The choices are synthetic, spread evenly over the held
        experts; the tokens and weights are the first layer's."""
        s, lay = self.sched, self.layers[0]
        h = self.batch(self.pool, jnp.arange(self.n_batch))
        u = s.route(h, lay["w_router"], lay["bias"], top_k=self.k,
                    eps=self.eps)[0]
        slot = np.arange(self.T * self.k)
        mean = self.T * self.k * self.held / self.E
        for frac in WARM_LOADS:
            local = slot < frac * mean
            e = np.where(local, self.first + slot % self.held,
                         (self.first + self.held + slot % (self.E - self.held))
                         % self.E)
            plan = s.plan_dispatch(e.reshape(self.T, self.k),
                                   experts=(self.first, self.held))
            op = self.scheduler.build("moe-dispatch", plan)
            op(u, lay["wi"], lay["wg"], lay["wo"]).block_until_ready()

    def open_window(self) -> None:
        """Forget the warm-up calls."""
        self.i = 0
        self.kept_calls = []    # reservoir of sampled calls
        self.last = None
        self.keep_rng = np.random.default_rng([self.seed, 5])
        n = len(self.layers)
        self.c = {"kept": 0, "moe_slots": 0, "expert_flops": 0,
                  "flops": 0, "kept_max": [0] * n, "kept_mean": [0.0] * n,
                  "tiles_padded": [0] * n, "width": 0}

    # ------------------------------------------------------------ window
    def call(self) -> None:
        s = self.sched
        idx = np.sort(self.rng.choice(self.pool.shape[0], self.n_batch,
                                      replace=False))
        h = self.batch(self.pool, jnp.asarray(idx))
        layers = []
        for l, lay in enumerate(self.layers):
            u, e_topk, w = s.route(h, lay["w_router"], lay["bias"],
                                   top_k=self.k, eps=self.eps)
            e_np, w_np = s.read_routing(e_topk, w)
            plan = s.plan_dispatch(e_np, w_np,
                                   experts=(self.first, self.held))
            op = self.scheduler.build("moe-dispatch", plan)
            y = op(u, lay["wi"], lay["wg"], lay["wo"])
            h = self.residual(h, y)
            layers.append((e_np[self.sample_pos],
                           self.pick(u, y, h, self.sample_dev)))
            del u, y
            self._count(l, plan, op)
            # the next layer's read-back waits for h anyway; waiting here
            # frees this layer's u, y and the op's buffers before the next
            # router allocates (unsynchronised on a TPU v5e, the peak rose
            # 1.1 GB and 4 of 13 runs stalled 2.4-3.2 s in one call,
            # PERF.md §6)
            h.block_until_ready()
        rec = (idx, layers)
        # reservoir of n_keep calls, drawn from the seed, and the last
        if len(self.kept_calls) < self.n_keep:
            self.kept_calls.append(rec)
        else:
            j = int(self.keep_rng.integers(0, self.i + 1))
            if j < self.n_keep:
                self.kept_calls[j] = rec
        self.last = rec
        self.i += 1

    def _count(self, l: int, plan, op) -> None:
        c, kept = self.c, int(plan.counts.sum())
        c["kept"] += kept
        c["moe_slots"] += int(op.vals.size)
        c["expert_flops"] += expert_flops(kept, self.D, self.F)
        c["flops"] += (expert_flops(kept, self.D, self.F)
                       + router_flops(self.T, self.D, self.E))
        c["kept_max"][l] = max(c["kept_max"][l], int(plan.counts.max()))
        c["kept_mean"][l] += float(plan.counts.mean())
        c["tiles_padded"][l] = max(c["tiles_padded"][l], op.vals.shape[0])
        c["width"] = op.schedule.width

    def units(self) -> int:
        """Loop calls the window completed."""
        return self.i

    def counters(self) -> dict:
        c = dict(self.c)
        c["kept_mean"] = [v / max(self.i, 1) for v in c["kept_mean"]]
        c["calls"] = self.i
        return c

    def release(self) -> None:
        """Copy the sampled calls, their first h and the weights to the
        host, and free the device state."""
        recs = list(self.kept_calls)
        if self.last is not None and all(r is not self.last for r in recs):
            recs.append(self.last)
        L = int(self.tr["sequence_tokens"])
        flat = self.pool.reshape(-1, self.D)
        self.checked = []
        for idx, layers in recs:
            rows = idx[self.sample_pos // L] * L + self.sample_pos % L
            self.checked.append((
                ref.f32(flat[jnp.asarray(rows)]),
                [(e, *(ref.f32(a) for a in picked))
                 for e, picked in layers]))
        self.host_layers = [{k: np.asarray(v) for k, v in lay.items()}
                            for lay in self.layers]
        del self.layers, self.pool, self.kept_calls, self.last, flat

    # ------------------------------------------------------------- check
    def check(self, control: bool = False) -> dict:
        """{name: (value, limit)}. Each layer is compared with the reference
        fed the program's own input to that layer, so that rounding
        differences do not carry from layer to layer. With `control`, the
        reference with bfloat16 sums in the expert products takes the
        program's place, on the same first inputs."""
        kw = dict(first=self.first, count=self.held, k=self.k, eps=self.eps,
                  dtype=ml_dtypes.bfloat16)
        h_in = np.concatenate([r[0] for r in self.checked])
        if control:
            got = [(r["h_in"], r["chosen"], r["u"], r["y"], r["h"])
                   for r in ref.forward(h_in, self.host_layers,
                                        acc="bfloat16", **kw)]
        else:
            got = []
            for l in range(len(self.host_layers)):
                e, u, y, h = (np.concatenate([r[1][l][j]
                                              for r in self.checked])
                              for j in range(4))
                got.append((h_in, e, u, y, h))
                h_in = h
        errs, route, near, residual = [], [], [], []
        for lay, (h_in, e, u, y, h) in zip(self.host_layers, got):
            want = ref.layer(h_in, lay, u_route=u, choices=e, margin=MARGIN,
                             **kw)
            clear = want["margin"] >= MARGIN
            differ = (np.sort(want["chosen"], 1) != np.sort(e, 1)).any(1)
            route.append(clear & differ)
            near.append(~clear)
            errs.append(ref.rel_err(y, want["y"]))
            residual.append(
                (h != ref.rnd(h_in + y, ml_dtypes.bfloat16)).any(1))
        errs, route = np.stack(errs), np.stack(route)
        residual = np.stack(residual)
        self.n_failed = int(((errs > ERR_LIMIT) | route | residual)
                            .any(0).sum())
        return {"y_err": (float(errs.max()), ERR_LIMIT),
                "route_mismatch": (int(route.sum()), 0),
                "route_near_ties": (float(np.mean(near)), NEAR_TIE_LIMIT),
                "residual_mismatch": (int(residual.sum()), 0)}
