"""Ahead-of-time compiles of the iCh kernels for a v5e chip.

The TPU compiler installed with jax compiles for a chip that is described
and not attached, so these tests catch what interpret mode cannot — an
unsupported gather, a vector load from SMEM, an unaligned slice, a block
that breaks the (8, 128) rule, a VMEM budget overrun — at no chip time.
Each case compiles a registry op's jitted kernel call (`sched/kernels.py`)
at the sizes `chip_smoke.py` runs with `--seed 0`, and MoE dispatch at one
layer of the `moe-mimo-v2-flash.prefill` cell and, with the identity
part of zero-compute experts, of the `moe-longcat-flash.prefill2k` cell,
at p=1 and p=4.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and test workers import every
test file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ich_bfs.ich_bfs import ich_bfs_step_sharded
from repro.kernels.ich_kmeans.ich_kmeans import ich_kmeans_assign_sharded
from repro.kernels.ich_moe.ich_moe import ich_moe_sharded
from repro.kernels.ich_spmv.ich_spmv import ich_spmv_sharded

HBM_BYTES = 16 * 10 ** 9  # one v5e chip
B = 8  # sched.defaults.SUPERSTEP
R = 8  # sched.defaults.ROWS_PER_TILE

# chip_smoke.py's schedules at --seed 0: (width W, padded tiles T_pad, and
# supersteps per worker S_B at p=1 and p=4)
SPMV = dict(n=3_566_907, W=8, T_pad=902_120, n_steps={1: 112_765, 4: 28_198})
BFS = dict(n=1 << 21, W=8, T_pad=296_232, n_steps={1: 37_029, 4: 9_290})
KMEANS = dict(n=494_020, D=34, K=5, n_steps={1: 8_871, 4: 2_218})
# one MiMo-V2-Flash layer of the prefill cell: 131,072 tokens, 16 experts
# held, widths as published; about 136 slot rows of 512 tokens, padded to
# 3 supersteps, each worker's steps to all 3
MOE = dict(n=131_072, D=4096, F=2048, E=16, W=512, T_pad=24)
# one LongCat-Flash layer of the prefill2k cell: 65,536 tokens, 16 FFN
# experts held, widths as published (hidden 6144, the kernel's largest),
# padded to 2 supersteps, and each token's identity weight w0
MOE_ZERO = dict(n=65_536, D=6144, F=2048, E=16, W=512, T_pad=16)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spmv(p, S):
    c = SPMV
    fn = functools.partial(ich_spmv_sharded, n_rows=c["n"], p=p,
                           superstep=B, interpret=False)
    n_steps = c["n_steps"][p]
    return (lambda v, cols, r, b, x, sc: fn(v, cols, r, b, x, slot_cost=sc),
            (S((c["T_pad"], R, c["W"])), S((c["T_pad"], R, c["W"]), jnp.int32),
             S((p * n_steps * B, R), jnp.int32), S((p * n_steps,), jnp.int32),
             S((c["n"],)), S((c["T_pad"], R))))


def _bfs(p, S):
    c = BFS
    fn = functools.partial(ich_bfs_step_sharded, n_vertices=c["n"], p=p,
                           superstep=B, interpret=False)
    n_steps = c["n_steps"][p]
    return (lambda m, cols, r, b, f, v, sc: fn(m, cols, r, b, f, v,
                                               slot_cost=sc),
            (S((c["T_pad"], R, c["W"])), S((c["T_pad"], R, c["W"]), jnp.int32),
             S((p * n_steps * B, R), jnp.int32), S((p * n_steps,), jnp.int32),
             S((c["n"],)), S((c["n"],)), S((c["T_pad"], R))))


def _kmeans(p, S):
    c = KMEANS
    fn = functools.partial(ich_kmeans_assign_sharded, p=p, superstep=B,
                           interpret=False)
    rows = p * c["n_steps"][p] * B
    return (lambda pts, cent, r, sc: fn(pts, cent, r, slot_cost=sc),
            (S((c["n"], c["D"])), S((c["K"], c["D"])),
             S((rows, R), jnp.int32), S((rows, R))))


def _moe(p, S, c=MOE):
    fn = functools.partial(ich_moe_sharded, p=p, superstep=B,
                           interpret=False)
    steps = p * c["T_pad"] // B
    grid = S((steps * B * R,), jnp.int32)
    bf16 = jnp.bfloat16
    return (lambda v, cols, r, b, s, d, e, x, wi, wg, wo, sc, w0=None: fn(
                v, cols, r, b, s, d, e, x, wi, wg, wo, slot_cost=sc, w0=w0),
            (S((c["T_pad"], R, c["W"])),
             S((c["T_pad"], R, c["W"]), jnp.int32),
             S((steps * B, R), jnp.int32), S((steps,), jnp.int32),
             grid, grid, grid, S((c["n"], c["D"]), bf16),
             S((c["E"], c["D"], c["F"]), bf16),
             S((c["E"], c["D"], c["F"]), bf16),
             S((c["E"], c["F"], c["D"]), bf16), S((c["T_pad"], R))))


def _moe_zero(p, S):
    fn, args = _moe(p, S, MOE_ZERO)
    return fn, args + (S((MOE_ZERO["n"],)),)


@pytest.mark.parametrize("p", [1, 4])
@pytest.mark.parametrize("kernel", ["spmv", "bfs", "kmeans", "moe",
                                    "moe_zero"])
def test_kernel_compiles_for_v5e(one_chip, kernel, p):
    def S(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = {"spmv": _spmv, "bfs": _bfs, "kmeans": _kmeans,
                "moe": _moe, "moe_zero": _moe_zero}[kernel](p, S)
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{kernel} p={p} needs {used} bytes of HBM"
