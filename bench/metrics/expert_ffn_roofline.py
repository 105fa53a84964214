"""The expert FFN kernel's share of the chip's bf16 peak: the least time
the window's necessary expert operations (kept entries x 6 D F,
`bench/roofline_moe.py`, counter `expert_flops`) take at peak, over the
Pallas kernels' device time in the window. Compute bounds it: each
expert's weights are read once per slot row of up to 512 tokens, 512
operations a byte, above the v5e's 240."""


def read(ctx):
    flops = ctx["counters"].get("expert_flops")
    s = ctx["trace"].kernel_s()
    if not flops or not s:
        return None
    return 100.0 * flops / ctx["peaks"]["bf16_flops"] / s
