"""Device time per loop call of the Pallas (Mosaic) kernels, found by the
op's type in the trace: in the MoE cells the one kernel is `ich_moe`, the
grouped expert FFN of every layer."""


def read(ctx):
    if "expert_flops" not in ctx["counters"]:
        return None
    s = ctx["trace"].kernel_s()
    return None if not s else 1e3 * s / ctx["units"]
