"""Host time per loop call of dispatching its MoE layers, summed over the
layers: the routing's read-back (`moe.readback`), the plan
(`moe.plan`), the schedule (`sched.schedule`) and the op's shard layout,
pack and upload (`op.shard`, `op.pack`, `op.upload`). None where the
program has no `moe.plan` span."""
from bench import spans

NAMES = ("moe.readback", "moe.plan", "sched.schedule", "op.shard",
         "op.pack", "op.upload")


def read(ctx):
    tr = ctx["trace"]
    if not spans.in_window(tr, "moe.plan"):
        return None
    return sum(spans.total_ms(spans.in_window(tr, n))
               for n in NAMES) / ctx["units"]
