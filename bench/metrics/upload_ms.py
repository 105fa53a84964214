"""Host time per re-assembly of the device puts of the packed payload and
the shard streams (the program's `op.upload` span): as long as the put
holds the host, whether or not the copy has finished."""
from bench import spans


def read(ctx):
    return spans.per_build_ms(ctx, "op.upload")
