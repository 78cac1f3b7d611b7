"""Claim rounds per cascade wave: the delta of the engine's ``rounds``
counter (the claim rounds each wave ran, at most the top-K width)
over that of its ``waves`` counter, across the window."""


def read(ctx):
    c = ctx.get("counters", {})
    if c.get("rounds") is None or not c.get("waves"):
        return None
    return c["rounds"] / c["waves"]
