"""Host time per re-assembly of `pack_csr`, which lays the values and
column indices out in the schedule's padded tiles (the program's `op.pack`
span)."""
from bench import spans


def read(ctx):
    return spans.per_build_ms(ctx, "op.pack")
