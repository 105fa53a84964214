"""The share of the router's choices that went to zero-compute experts,
over the window: `zero_entries` (the plans' `n_zero`) over
`routed_entries` (tokens x top-k), the picks the identity takes off the
expert kernel. None where the program counts no zero-compute choices."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("routed_entries"):
        return None
    return 100.0 * c["zero_entries"] / c["routed_entries"]
