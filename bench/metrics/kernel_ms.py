"""Device time of the Pallas (Mosaic) kernels per loop call, found by the
op's type in the trace, not by its name."""


def read(ctx):
    s = ctx["trace"].kernel_s()
    return None if s is None else 1e3 * s / ctx["units"]
