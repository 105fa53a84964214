"""Useful slots over packed slots of the built schedule: nonzeros (edges)
over T_pad * R * W. A count: it repeats exactly for one input."""


def read(ctx):
    c = ctx["counters"]
    return 100.0 * c["nnz"] / c["slots"] if c.get("slots") else None
