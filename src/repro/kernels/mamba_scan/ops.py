"""Jitted wrapper: pads S to the chunk multiple and dispatches."""
import functools

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from .mamba_scan import mamba_scan


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba_scan_op(q, k, v, log_a, *, chunk: int = 128, interpret: bool | None = None):
    interpret = default_interpret(interpret)
    S = q.shape[1]
    pad = (-S) % chunk
    if pad:
        zf = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q, k, v = zf(q), zf(k), zf(v)
        log_a = jnp.pad(log_a, ((0, 0), (0, pad), (0, 0)))
    y, s = mamba_scan(q, k, v, log_a, chunk=chunk, interpret=interpret)
    return y[:, :S], s
