"""Host time per re-assembly of the op's shard layout: `Schedule.shard()`,
`shard_item_id`, `kernel_block_ids` and the per-slot cost stream (the
program's `op.shard` span)."""
from bench import spans


def read(ctx):
    return spans.per_build_ms(ctx, "op.shard")
