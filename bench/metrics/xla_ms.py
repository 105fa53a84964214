"""Device time per loop call of every device op that is not a Pallas
kernel: XLA's gather x[cols], the slots_on_lanes relayout, worker_reduce,
the caller's own normalisation."""


def read(ctx):
    s = ctx["trace"].xla_s()
    return None if s is None else 1e3 * s / ctx["units"]
