"""The whole loop call's share of the chip's bf16 peak: the window's
router and expert operations (`bench/roofline_moe.py`, counter `flops`)
at peak, over the window's host time."""


def read(ctx):
    flops = ctx["counters"].get("flops")
    if not flops:
        return None
    return 100.0 * flops / ctx["peaks"]["bf16_flops"] / ctx["window_s"]
