"""Host time per re-assembly of `LoopScheduler.schedule` inside
`LoopScheduler.build`: the cost provider and its fingerprint of the
inputs, the cache key and the lookup, and construction on a miss (the
program's `sched.schedule` span)."""
from bench import spans


def read(ctx):
    return spans.per_build_ms(ctx, "sched.schedule")
