"""Kept (token, expert) entries over the packed slots the expert kernel's
token gather walks (T_pad * R * W, padding included), over the window."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("moe_slots"):
        return None
    return 100.0 * c["kept"] / c["moe_slots"]
