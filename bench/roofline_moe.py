"""Operations an MoE prefill call cannot avoid, counted from the routing
alone, so a share of the chip's peak built from them cannot pass 100%.

Padding is not counted: the slot rows' empty token slots, padded tiles and
padding steps are the program's overhead, not work the layer needs.
"""
from __future__ import annotations


def expert_flops(kept: int, hidden: int, expert_width: int) -> int:
    """A layer's routed experts: each kept (token, expert) entry runs the
    SwiGLU FFN, three (hidden x expert_width) matrix products of 2
    operations a multiply-add."""
    return 6 * int(kept) * int(hidden) * int(expert_width)


def router_flops(tokens: int, hidden: int, experts: int) -> int:
    """A layer's router: tokens x hidden x experts multiply-adds."""
    return 2 * int(tokens) * int(hidden) * int(experts)
