"""Host spans on the profiler's clock.

`span(name, **args)` marks a stretch of host work for `jax.profiler`: it is
a `jax.profiler.TraceAnnotation`, which a trace records on the calling
thread, nested by time inside whatever span encloses it, so nesting on one
thread is the parent link. With the profiler off a span costs about a
microsecond; without jax imported it is a shared no-op, so the numpy-only
paths (schedule construction, the registry) never import jax for it.

Arguments must be cheap scalars already at hand (a workload name, a depth,
a program name): a span records what the host does and must never add an
array reduction or a device sync of its own.

The spans of the loop-call path (PERF.md §3, "Tracing"):

    moe.readback                 the router's choices to the host
    moe.plan                     plan_dispatch
    sched.build (workload)       LoopScheduler.build
      sched.schedule             cost provider, fingerprint, cache lookup
        sched.construct          a cache miss: tiles and Schedule
      op.shard                   shard layout and per-slot streams
      op.pack                    pack_csr
      op.upload                  device puts of the payload and streams
    op.compile (program)         an op's first call: jit, trace, compile
    op.dispatch (program)        every later call: host dispatch
    bfs.levels (source)          one BfsOp.levels traversal
      bfs.level (depth)          one level step
        bfs.send                 frontier and visited to the device
        op.dispatch
        bfs.wait                 waiting for the next frontier's read-back
        bfs.update               the host's level and frontier update
"""
from __future__ import annotations

import contextlib
import sys

_NO_SPAN = contextlib.nullcontext()


def span(name: str, **args):
    """A context that records `name` (with `args`) as a host span."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NO_SPAN
    return jax.profiler.TraceAnnotation(name, **args)
