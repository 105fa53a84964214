import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable (e)).

For every (architecture x input shape) cell, lower + compile the step the
shape dictates (train_step / prefill / decode) against the production mesh
(single-pod 16x16 and multi-pod 2x16x16), print memory_analysis (proves it
fits) and cost_analysis (FLOPs/bytes for the roofline), parse collective
traffic from the optimized HLO, and dump a JSON record consumed by
launch/roofline.py and EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]
"""
import argparse
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCHS, SHAPES, get_arch
from ..models import model as M
from ..models.moe import DistContext
from ..train import train_step as TS
from . import hlo_stats, specs
from .mesh import batch_axes_of, make_production_mesh


def _ns_tree(mesh, pspec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def build_step(cfg, shape, mesh, *, attn_impl: str = "blockwise",
               decode_params_fsdp: bool = True, serve_bf16: bool = False,
               train_opt: bool = False, ssm_chunk: int = 0):
    """Returns (fn, arg_specs, in_shardings, out_shardings, donate)."""
    if ssm_chunk:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, ssm_chunk=ssm_chunk)
    baxes = batch_axes_of(mesh)
    tp = mesh.shape["model"]
    dist = DistContext(mesh, batch_axes=baxes)
    sp = specs.input_specs(cfg, shape)

    if shape.kind == "train":
        if train_opt:
            import dataclasses as _dc
            cfg = _dc.replace(cfg, moe_cmax_factor=1.25, remat_policy="dots")
        mb = max(1, cfg.train_microbatch) * (2 if train_opt else 1)
        tcfg = TS.TrainConfig(microbatch=mb, bf16_params=train_opt)
        sp["state"] = specs.state_specs(cfg, shape.seq_len, tcfg)
        step = TS.make_train_step(cfg, tcfg, dist)
        state_ns = _ns_tree(mesh, TS.train_state_pspecs(cfg, tp, shape.seq_len, tcfg))
        batch_ns = _ns_tree(mesh, TS.batch_pspec(cfg, baxes))
        return (step, (sp["state"], sp["batch"]), (state_ns, batch_ns),
                (state_ns, None), (0,))

    pp = M.param_pspecs(cfg, tp, shape.seq_len)
    if not decode_params_fsdp:
        # TP-only serving weights: drop the FSDP axis; weights that relied on
        # FSDP for sharding (head-count not divisible by tp) get "model" on
        # their largest tp-divisible dim instead — every rank then runs full
        # heads over its seq shard (the flash-decode layout), with only a
        # tiny activation regather.
        def _serve_spec(spec, leaf):
            names = tuple(a if a != "data" else None for a in spec)
            if "model" in names or not hasattr(leaf, "shape") or leaf.ndim == 0:
                return P(*names)
            # replicate small weights: sharding them buys KBs of HBM but
            # costs a per-layer activation psum (measured on xlstm: 554 MiB
            # of wire for a 350M model — worse than replication)
            if leaf.size * 4 < 32 * 2**20:
                return P(*names)
            names = list(names) + [None] * (leaf.ndim - len(names))
            dims = sorted(range(leaf.ndim), key=lambda d: -leaf.shape[d])
            for d in dims:
                if leaf.shape[d] % tp == 0:
                    names[d] = "model"
                    break
            return P(*names)
        pp = jax.tree.map(_serve_spec, pp, sp["params"],
                          is_leaf=lambda x: isinstance(x, P))
    params_ns = _ns_tree(mesh, pp)
    if serve_bf16:
        sp["params"] = jax.tree.map(
            lambda t: jax.ShapeDtypeStruct(t.shape, jnp.bfloat16)
            if t.dtype == jnp.float32 else t, sp["params"])
    caps = jnp.ones((M.n_moe_layers(cfg), max(cfg.n_experts, 1)), jnp.float32) \
        if cfg.moe else None

    if shape.kind == "prefill":
        def fn(params, batch):
            return M.prefill(cfg, params, batch, caps, dist=dist)
        batch_ns = _ns_tree(mesh, {k: P(baxes, *([None] * (len(v.shape) - 1)))
                                   for k, v in sp["batch"].items()})
        return (fn, (sp["params"], sp["batch"]), (params_ns, batch_ns), None, ())

    # decode
    def fn(params, tokens, cache, pos):
        return M.decode_step(cfg, params, tokens, cache, pos, caps, dist=dist)

    cache_ns = _ns_tree(mesh, M.cache_pspecs(cfg, shape.global_batch, mesh, baxes))
    tok_b = baxes if shape.global_batch % _prod(mesh, baxes) == 0 else None
    tok_ns = NamedSharding(mesh, P(tok_b, None))
    pos_ns = NamedSharding(mesh, P())
    logits_ns = NamedSharding(mesh, P(tok_b, "model"))
    return (fn, (sp["params"], sp["tokens"], sp["cache"], sp["pos"]),
            (params_ns, tok_ns, cache_ns, pos_ns),
            (logits_ns, cache_ns), (2,))


def _prod(mesh, axes):
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def run_cell(arch_name: str, shape_name: str, multi_pod: bool = False,
             out_dir: str = "results/dryrun", save_hlo: bool = False,
             **step_kwargs) -> dict:
    cfg = get_arch(arch_name)
    shape = SHAPES[shape_name]
    rec = {"arch": arch_name, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "params": cfg.param_count(),
           "active_params": cfg.active_param_count()}
    if not cfg.supports(shape):
        rec["status"] = "SKIP"
        rec["reason"] = "full-attention arch: long_500k needs sub-quadratic attention (DESIGN.md §5)"
        return _save(rec, out_dir)
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        fn, args, in_sh, out_sh, donate = build_step(cfg, shape, mesh, **step_kwargs)
        t0 = time.time()
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        rec["lower_s"] = round(time.time() - t0, 1)
        t0 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 1)
        mem = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
        }
        cost = compiled.cost_analysis()
        rec["cost"] = {k: float(v) for k, v in cost.items()
                       if isinstance(v, (int, float)) and k in
                       ("flops", "bytes accessed", "transcendentals",
                        "optimal_seconds", "utilization")}
        hlo = compiled.as_text()
        st = hlo_stats.parse_collectives(hlo)
        rec["collectives"] = {k: {"n": v[0], "result_bytes": v[1],
                                  "operand_bytes": v[2], "wire_bytes": v[3]}
                              for k, v in st.by_kind.items()}
        rec["collective_operand_bytes"] = st.total_operand_bytes
        rec["collective_wire_bytes"] = st.total_wire_bytes
        rec["status"] = "OK"
        if save_hlo:
            p = pathlib.Path(out_dir) / f"{arch_name}_{shape_name}_{rec['mesh']}.hlo"
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(hlo)
        print(f"[dryrun] {arch_name} x {shape_name} ({rec['mesh']}): OK "
              f"flops/dev={rec['cost'].get('flops', 0):.3e} "
              f"args={rec['memory']['argument_bytes']/2**30:.2f}GiB "
              f"temp={rec['memory']['temp_bytes']/2**30:.2f}GiB "
              f"coll={rec['collective_wire_bytes']/2**20:.1f}MiB/dev "
              f"(lower {rec['lower_s']}s compile {rec['compile_s']}s)")
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec["status"] = "FAIL"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        print(f"[dryrun] {arch_name} x {shape_name}: FAIL {rec['error'][:200]}")
    return _save(rec, out_dir)


def _save(rec: dict, out_dir: str) -> dict:
    p = pathlib.Path(out_dir)
    p.mkdir(parents=True, exist_ok=True)
    slim = {k: v for k, v in rec.items() if k != "traceback"}
    name = f"{rec['arch']}_{rec['shape']}_{rec['mesh']}.json"
    (p / name).write_text(json.dumps(slim, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--serve-opt", action="store_true",
                    help="optimized serving: TP-only bf16 weights (§Perf)")
    ap.add_argument("--train-opt", action="store_true",
                    help="optimized training: bf16-cast-once + MoE C_max 1.25 (§Perf)")
    ap.add_argument("--ssm-chunk", type=int, default=0)
    args = ap.parse_args()

    cells = []
    archs = list(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))
    n_ok = n_fail = n_skip = 0
    for a, s, mp in cells:
        kw = dict(decode_params_fsdp=False, serve_bf16=True) if args.serve_opt else {}
        if args.train_opt:
            kw["train_opt"] = True
        if args.ssm_chunk:
            kw["ssm_chunk"] = args.ssm_chunk
        rec = run_cell(a, s, multi_pod=mp, out_dir=args.out,
                       save_hlo=args.save_hlo, **kw)
        n_ok += rec["status"] == "OK"
        n_fail += rec["status"] == "FAIL"
        n_skip += rec["status"] == "SKIP"
    print(f"[dryrun] done: {n_ok} OK, {n_skip} SKIP, {n_fail} FAIL")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
