"""Graph500 Kronecker graph generator, run on the device.

Source: the Graph500 specification, section "Graph Generation" (reference
code `kronecker_generator.m`): M = edgefactor * 2^scale edges, each edge's
endpoint bits drawn level by level with initiator probabilities A, B, C
(D = 1 - A - B - C), then the vertex labels and the edge order permuted.

Departures, each of which gives the same distribution: each level draws one
float32 uniform per edge and reads the quadrant from it (the reference draws
two, the row bit and then the column bit given the row bit); the edge-order
permutation is skipped, because the edges are sorted into rows right after.

The result is the graph that Graph500's kernel 2 searches: both directions
of every edge, self-loops dropped, duplicates merged, each row's neighbours
in ascending order. The endpoint bits, 2 * scale * M draws, come from one
jitted call on the device (numpy takes ~5 s for them at scale 20 on one
host core); the relabelling and the sort into rows run in numpy (~2 s),
since a sort of 2M keys takes the TPU compiler over a minute to build.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A threefry key that uses all the bits of a seed of any size."""
    state = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(state, impl="threefry2x32")


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _endpoints(key, scale: int, edgefactor: int, a: float, b: float,
               c: float):
    """(src, dst) of edgefactor * 2^scale edges, before relabelling."""
    m = edgefactor << scale

    def level(bit, sd):
        s, d = sd
        u = jax.random.uniform(jax.random.fold_in(key, bit), (m,))
        row = u >= a + b
        col = ((u >= a) & ~row) | (u >= a + b + c)
        return (s | (row.astype(jnp.int32) << bit),
                d | (col.astype(jnp.int32) << bit))

    zeros = jnp.zeros(m, jnp.int32)
    return jax.lax.fori_loop(0, scale, level, (zeros, zeros))


def graph(scale: int, edgefactor: int, a: float, b: float, c: float,
          seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(indptr int64 (n+1,), indices int32) of the symmetric simple graph."""
    n = 1 << scale
    src, dst = _endpoints(seed_key(seed), scale, edgefactor, float(a),
                          float(b), float(c))
    perm = np.random.default_rng([seed, 1]).permutation(n)
    s, d = perm[np.asarray(src)], perm[np.asarray(dst)]
    keep = s != d
    s, d = s[keep], d[keep]
    key = np.concatenate([(s << scale) | d, (d << scale) | s])
    key.sort()
    key = key[np.concatenate([[True], key[1:] != key[:-1]])]
    indptr = np.searchsorted(key >> scale, np.arange(n + 1))
    return indptr.astype(np.int64), (key & (n - 1)).astype(np.int32)
