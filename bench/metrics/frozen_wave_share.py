"""Share of cascade waves cut short for a full re-clear (a truncated
slate exhausted, or a reclaim): the delta of the engine's ``frozen``
counter over that of its ``waves`` counter, across the window.  The
cascade's waste ratio."""


def read(ctx):
    c = ctx.get("counters", {})
    if c.get("frozen") is None or not c.get("waves"):
        return None
    return c["frozen"] / c["waves"]
